"""Seconds of `DPTrainer.init_state` in set-up, host side: the program's own
`trainer.init_state` span(s) (benchmark/setup_spans.py), the master shards'
layout with its trace, lowering, and compile or cache read."""

from benchmark import setup_spans


def read(run):
    evs = setup_spans.events(run)
    if evs is None:
        return None
    spans = setup_spans.spans(evs, setup_spans.INIT)
    if not spans:
        return None
    return sum(e["dur_ns"] for e in spans) / 1e9
