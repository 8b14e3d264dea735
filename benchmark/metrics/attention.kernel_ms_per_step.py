"""Device time of attention proper per step, in ms: scores, softmax and the
weighted sum of values, forward and backward — the flash kernels where the
program runs them, the XLA fusions of the same work where the configuration
pins `attn_impl: xla`.  The projections are matmuls of the model step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("attention")
