"""Gather kernels launched per step on one device: events named `ring.ag*`
in the traced window over the steps in it.  The streaming gather is cut
into segments by the chip's semaphore memory (8 for the 10x2048^2 MLP at
dp=4, PR 22), and each segment is a launch."""

from benchmark import kernel_events


def read(run):
    if not run.trace or run.trainer.n == 1:
        return None
    return kernel_events.launches_per_step(run.trace, "ring.ag")
