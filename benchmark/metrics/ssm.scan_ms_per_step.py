"""Device time per step of the Mamba-2 mixers' chunked state-space scan, in
ms: what `models/nemotron_h.ssd_scan` runs between the convolution and the
gate — the decay arrays and their cumulative sums, C B^T inside a chunk and
its product with the steps' inputs, a state a chunk, the carry over the
chunks and the carried state's read-out, forward and backward (class
`ssm_scan` of op_classes/076-nemotron-h.json).  Part of
model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("ssm_scan")
