"""Seconds in set-up's step calls that jax spent reading the compiled step
from the persistent compile cache and loading it: the `jax.compile`
children of the set-up `trainer.step` spans with `cache` hit.  0 on a cold
run (benchmark/setup_spans.py)."""

from benchmark import setup_spans


def read(run):
    phases = setup_spans.step_phases(run)
    return None if phases is None else phases["cache_read"]
