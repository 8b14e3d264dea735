"""Device time per step of the gated short-convolution mixers, in ms: every
operation that touches an array whose last dimension is three times the
hidden size (W_in, the [B | C | x~] array it makes) or a float32
[4, 8192, 2048] array (the gated input and the 3-tap causal filter's work),
forward and backward (class `conv` of op_classes/075-lfm2-moe.json).
W_out's product is in it where XLA fused the gate C * c into it, and not
where it stands alone: then it has the shapes of any 2048 x 2048 product.
Part of model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("conv")
