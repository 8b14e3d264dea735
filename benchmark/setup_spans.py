"""Set-up as the program's own event stream records it: the trainer's spans
(`trainer.init_state`, `trainer.step`) and their children from jax's compile
events (`jax.trace`, `jax.lower`, `jax.compile` with its `cache` outcome;
`fpga_ai_nic_tpu/obs/names.py` SPANS), for the `setup.*` readers under
metrics/.

Set-up is every event that ENDS before the window's start, `run.window[0]`,
a `perf_counter` second; the stream stamps events in unix ns from its own
anchor pair, so the start is converted through that pair.  The check's
`init_state` and `step` after the window are left out that way.

The step's four phases are read as disjoint shares of the set-up
`trainer.step` spans, the later phase taking what two cover at once:
a `jax.compile` that read nothing from the cache (`miss`, or `none` where
the cache was off) over one that read (`hit`), over `jax.lower`, over
`jax.trace` — so their sum cannot pass the spans' own.  Nested traces
(jax traces each jitted function a step calls inside the step's) count
once, as their union.
"""

STEP = "trainer.step"
INIT = "trainer.init_state"


def events(run):
    """The set-up events of the run's trainer, or None where there is
    nothing sound to read: a rehearsal (a CPU time is no device metric),
    a program without the trainer's stream, or a stream that dropped
    events — the ring drops the oldest first, and set-up's are the
    oldest."""
    if run.peaks is None:
        return None
    profiler = getattr(run.trainer, "profiler", None)
    if profiler is None:
        return None
    stream = profiler.events
    if stream.events_dropped:
        return None
    start_ns = stream.to_unix_ns(round(run.window[0] * 1e9))
    return [e for e in stream.snapshot()
            if e["t_unix_ns"] + e.get("dur_ns", 0) <= start_ns]


def spans(evs, name):
    return [e for e in evs if e["kind"] == "span" and e["name"] == name]


def interval(e):
    return e["t_unix_ns"], e["t_unix_ns"] + e["dur_ns"]


def union(intervals):
    """Sorted, disjoint [start, end) intervals covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [tuple(i) for i in out]


def intersect(a, b):
    """Both arguments unions; the time they share, as a union."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """Both unions; the time of a that b does not cover."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if e > s:
            out.append((s, e))
    return out


def seconds(u):
    return sum(e - s for s, e in u) / 1e9


def step_phases(run):
    """{"trace", "lower", "compile", "cache_read"} seconds of set-up's step
    calls, or None where set-up holds no `trainer.step` span."""
    evs = events(run)
    if evs is None:
        return None
    steps = union(interval(e) for e in spans(evs, STEP))
    if not steps:
        return None

    def child(name, keep=lambda e: True):
        return intersect(union(interval(e) for e in spans(evs, name)
                               if (e.get("attrs") or {}).get("parent")
                               == STEP and keep(e)), steps)

    compile_ = child("jax.compile", lambda e: e["attrs"].get("cache") != "hit")
    cache_read = subtract(
        child("jax.compile", lambda e: e["attrs"].get("cache") == "hit"),
        compile_)
    compiles = union(compile_ + cache_read)
    lower = subtract(child("jax.lower"), compiles)
    trace = subtract(child("jax.trace"), union(compiles + lower))
    return {"trace": seconds(trace), "lower": seconds(lower),
            "compile": seconds(compile_), "cache_read": seconds(cache_read)}
