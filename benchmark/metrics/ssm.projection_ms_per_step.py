"""Device time per step of the Mamba-2 mixers around their scan, in ms:
W_in's and W_out's products, the causal convolution with its silu, the
gate y * silu(z) and the grouped norm, forward and backward (class `ssm` of
op_classes/076-nemotron-h.json).  The scan itself is ssm.scan_ms_per_step.
Part of model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("ssm")
