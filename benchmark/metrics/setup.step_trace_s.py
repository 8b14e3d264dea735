"""Seconds in set-up's step calls that jax spent tracing the step to a
jaxpr: the union of the `jax.trace` children of the set-up `trainer.step`
spans, less what lowering and compiling cover (benchmark/setup_spans.py)."""

from benchmark import setup_spans


def read(run):
    phases = setup_spans.step_phases(run)
    return None if phases is None else phases["trace"]
