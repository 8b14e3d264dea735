"""Device time per step of the ring's gather launches together, in ms, read
by the program's own name for them (`ring.ag*`; kernel_events.py)."""

from benchmark import kernel_events


def read(run):
    if not run.trace or run.trainer.n == 1:
        return None
    return kernel_events.ms_per_step(run.trace, "ring.ag")
