"""Seconds in set-up's step calls that jax spent lowering the step's jaxpr
to an MLIR module: the union of the `jax.lower` children of the set-up
`trainer.step` spans, less what compiling covers
(benchmark/setup_spans.py)."""

from benchmark import setup_spans


def read(run):
    phases = setup_spans.step_phases(run)
    return None if phases is None else phases["lower"]
