"""Programs compiled (not read from the persistent compile cache) under the
trainer's spans in set-up: the `jax.compile` spans with `cache` miss whose
parent is a `trainer.*` span (benchmark/setup_spans.py).  0 says set-up was
warm."""

from benchmark import setup_spans


def read(run):
    evs = setup_spans.events(run)
    if evs is None:
        return None
    if not (setup_spans.spans(evs, setup_spans.INIT)
            or setup_spans.spans(evs, setup_spans.STEP)):
        return None
    return sum(1 for e in setup_spans.spans(evs, "jax.compile")
               if e["attrs"]["cache"] == "miss"
               and e["attrs"]["parent"].startswith("trainer."))
