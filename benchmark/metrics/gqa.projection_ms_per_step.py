"""Device time per step of grouped-query attention's fused q | k | v
projection, in ms: the operations that touch W_qkv [2048, 3072] or the
[tokens, 3072] array it makes, forward, backward and weight gradient, with
the split into heads (class `gqa` of op_classes/075-lfm2-moe.json).  The
head norms, RoPE, the repeated keys and attention proper are
attention.kernel_ms_per_step; the output projection has the shapes of any
2048 x 2048 product and reads as the model step's.  Part of
model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("gqa")
