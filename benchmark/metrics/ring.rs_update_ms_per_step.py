"""Device time per step of the ring's reduce-scatter kernel, in ms — with
the optimizer in its last hop where the preset fuses it (`ring.rs_update*`)
— read by the program's own name for it (`ring.rs*`; kernel_events.py).
With ring.gather_ms_per_step it adds up to ring.kernel_ms_per_step: the
ring's kernels do not overlap."""

from benchmark import kernel_events


def read(run):
    if not run.trace or run.trainer.n == 1:
        return None
    return kernel_events.ms_per_step(run.trace, "ring.rs")
