"""Device time of the ring's kernels (reduce-scatter+update and the gather
segments) per step, in ms: the union of their intervals in the traced
window over the steps in it, median of the devices.  They are synchronous
custom calls after the backward pass, so nothing hides them; a kernel
waiting for its neighbour counts."""


def read(run):
    if not run.trace or run.trainer.n == 1:
        return None
    return run.trace.class_ms_per_step("ring")
