"""Device time per step of latent attention's projections, in ms: the
operations that touch the weights of q_a, q_b, kv_a, kv_b and o, forward,
backward and weight gradients (class `mla` of op_classes/08-glm-moe.json).
Attention proper is attention.kernel_ms_per_step.  Part of
model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("mla")
