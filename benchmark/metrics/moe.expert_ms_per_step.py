"""Device time per step of the routed experts held, in ms: the grouped
products (`lax.ragged_dot`, forward and backward, with the bookkeeping the
compiler puts beside them) and every operation that touches the held
experts' stacked weights (class `moe` of op_classes/08-glm-moe.json).  The
router, the sort and the shared expert are not in it.  Part of
model.xla_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("moe")
