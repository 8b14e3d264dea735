"""The expert layer's named kernels launched per step on one device: events
whose `ainic_kernel` starts with `moe.` (`moe.gmm`, `moe.tgmm`:
ops/grouped_pallas.py) in the traced window over the steps in it.  One
launch is one grouped product: per expert layer a step runs its products
forward, again in the backward's recompute, and twice more backward (the
rows' and the weights' cotangents), so three SwiGLU matrices give 12 and
two relu2 matrices 8.  None for a program without such kernels (one that
multiplies with `lax.ragged_dot`)."""

from benchmark import kernel_events


def read(run):
    if not run.trace:
        return None
    return kernel_events.launches_per_step(run.trace, "moe.")
