"""Seconds in set-up's step calls that XLA spent compiling: the `jax.compile`
children of the set-up `trainer.step` spans that read nothing from the
persistent compile cache (`cache` miss; none where it was off).  0 on a
warm run (benchmark/setup_spans.py)."""

from benchmark import setup_spans


def read(run):
    phases = setup_spans.step_phases(run)
    return None if phases is None else phases["compile"]
