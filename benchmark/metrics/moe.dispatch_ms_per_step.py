"""Device time per step of the dropless dispatch around the grouped
products, in ms: the row gathers and their transposes, the masks, SwiGLU's
elementwise part and the gate-weighted sum, on every assignment row of the
step (tokens x experts per token) whether a held expert takes it or not
(class `dispatch` of op_classes/08-glm-moe.json).  The products themselves
are moe.expert_ms_per_step.  Part of model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("dispatch")
