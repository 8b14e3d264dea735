"""Structured telemetry event stream — spans + counters on one timebase.

The reference NIC is observable *by construction*: per-collective active
cycles (`lpbk_latency`, hw/all_reduce.sv:92), stall attribution by cause
(`stall_host_in/out`, `stall_eth_in/out`, hw/all_reduce.sv:94-97), flit
counters (hw/bfp_adapter.sv:705-729), and a DETAILED_PROFILE wall-clock
breakdown in the driver (sw/mlp_mpi_example_f32.cpp:236-244).  Our port's
`utils.observability.Profiler` mirrored only the *aggregates*; this module
is the stream underneath them — every span, counter and instant event,
individually timestamped, so per-phase accounting (what EQuARX-style
compressed-collective evaluation needs) and the Perfetto timeline
(`obs.timeline`) both read from one source of truth.

Contract:

  - **Schema-versioned**: every JSONL dump leads with a header line
    carrying ``SCHEMA_VERSION`` plus the stream's timebase anchors;
    consumers reject versions they don't know.
  - **O(1) hot path**: ``emit`` appends one fixed-shape tuple under a
    plain lock into a bounded ring.  No string formatting, no dict
    merging, no IO on the hot path; rendering happens at dump time.
  - **Bounded, with honest overflow**: the ring keeps the newest
    ``capacity`` events; every evicted event increments
    ``events_dropped``, which rides the summary and the JSONL header so
    a truncated stream can never read as "covered everything"
    (the same rule as RecoveryStats.events_dropped).
  - **Single timebase**: event timestamps are ``time.perf_counter_ns()``
    (monotonic, cheap); the stream records a paired
    (``time.time_ns``, ``perf_counter_ns``) anchor at construction so
    any event converts to absolute unix-epoch ns (``to_unix_ns``) —
    the common axis host spans, queue tickets and device-plane trace
    intervals are merged on.
  - Thread-safe: the elastic watchdog worker, XLA callback threads and
    the trainer thread all emit into one stream.
  - **Compiles as child spans**: while a ``Profiler.bucket`` is open on a
    thread, jax's own compile events on that thread land in the bucket's
    stream as its children: ``jax.trace``, ``jax.lower`` and
    ``jax.compile`` spans, the last with the persistent compile cache's
    outcome as ``cache`` (``forward_compile_events``; names in
    ``obs.names.SPANS``).
"""

from __future__ import annotations

import contextvars
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

SCHEMA_VERSION = 1

# event kinds (the "ph" analogue of the chrome trace format)
SPAN = "span"          # has dur_ns
INSTANT = "instant"    # point event
COUNTER = "counter"    # has value

_EVENT_KINDS = (SPAN, INSTANT, COUNTER)


class EventStream:
    """Bounded ring of structured telemetry events (see module docstring).

    One instance per Profiler (trainers and queues share their profiler's
    stream); capacity defaults generous enough for ~10k steps of span +
    ticket traffic while bounding memory for million-step runs.
    """

    def __init__(self, capacity: int = 1 << 16) -> None:
        assert capacity > 0
        self.capacity = int(capacity)
        # ring slots: (t_ns, dur_ns, kind, name, value, attrs, tid)
        self._buf: Deque[Tuple] = deque()
        self._lock = threading.Lock()
        self.events_dropped = 0
        self._emitted = 0
        # single-timebase anchor pair (see module docstring)
        self.t0_unix_ns = time.time_ns()
        self.t0_perf_ns = time.perf_counter_ns()

    # -- timebase -----------------------------------------------------------

    @staticmethod
    def now_ns() -> int:
        return time.perf_counter_ns()

    def to_unix_ns(self, t_perf_ns: float) -> int:
        """perf_counter timestamp -> absolute unix-epoch ns (the merge
        axis shared with device-plane trace intervals)."""
        return int(self.t0_unix_ns + (t_perf_ns - self.t0_perf_ns))

    # -- hot path -----------------------------------------------------------

    def emit(self, kind: str, name: str, t_ns: Optional[int] = None,
             dur_ns: Optional[int] = None, value: Optional[float] = None,
             attrs: Optional[Dict[str, Any]] = None) -> None:
        """Append one event.  O(1): a tuple append (plus one eviction when
        the ring is full) under a plain lock."""
        if t_ns is None:
            t_ns = time.perf_counter_ns()
        tid = threading.get_ident()
        with self._lock:
            self._emitted += 1
            if len(self._buf) >= self.capacity:
                self._buf.popleft()
                self.events_dropped += 1
            self._buf.append((t_ns, dur_ns, kind, name, value, attrs, tid))

    def instant(self, name: str, **attrs: Any) -> None:
        self.emit(INSTANT, name, attrs=attrs or None)

    def counter(self, name: str, value: float,
                **attrs: Any) -> None:
        self.emit(COUNTER, name, value=float(value), attrs=attrs or None)

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[None]:
        """Timed span; records on exit (exceptions still record — a span
        that died is exactly the span the timeline must show)."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            self.emit(SPAN, name, t_ns=t0, dur_ns=t1 - t0,
                      attrs=attrs or None)

    # -- rendering (cold path) ----------------------------------------------

    def snapshot(self) -> List[Dict[str, Any]]:
        """Events as dicts, oldest first, timestamps in absolute unix ns
        (the JSONL / timeline shape)."""
        with self._lock:
            raw = list(self._buf)
        out = []
        for t_ns, dur_ns, kind, name, value, attrs, tid in raw:
            ev: Dict[str, Any] = {"t_unix_ns": self.to_unix_ns(t_ns),
                                  "kind": kind, "name": name, "tid": tid}
            if dur_ns is not None:
                ev["dur_ns"] = int(dur_ns)
            if value is not None:
                ev["value"] = value
            if attrs:
                ev["attrs"] = attrs
            out.append(ev)
        return out

    def summary(self) -> Dict[str, Any]:
        """Aggregate view: per-span-name wall-clock totals (the
        DETAILED_PROFILE breakdown), latest counter values, and the
        recorded/dropped accounting.  Cheap enough to embed in every
        bench artifact."""
        with self._lock:
            raw = list(self._buf)
            emitted, dropped = self._emitted, self.events_dropped
        spans: Dict[str, Dict[str, float]] = {}
        counters: Dict[str, float] = {}
        kinds: Dict[str, int] = {}
        for t_ns, dur_ns, kind, name, value, attrs, tid in raw:
            kinds[kind] = kinds.get(kind, 0) + 1
            if kind == SPAN and dur_ns is not None:
                agg = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                              "max_s": 0.0})
                agg["count"] += 1
                agg["total_s"] += dur_ns / 1e9
                agg["max_s"] = max(agg["max_s"], dur_ns / 1e9)
            elif kind == COUNTER and value is not None:
                counters[name] = value       # latest wins (time-ordered)
        for agg in spans.values():
            agg["total_s"] = round(agg["total_s"], 6)
            agg["max_s"] = round(agg["max_s"], 6)
        return {"schema_version": SCHEMA_VERSION,
                "emitted": emitted, "recorded": len(raw),
                "events_dropped": dropped,
                "by_kind": kinds, "spans": spans, "counters": counters}

    # -- JSONL sink ---------------------------------------------------------

    def header(self) -> Dict[str, Any]:
        with self._lock:
            emitted, dropped = self._emitted, self.events_dropped
        return {"schema_version": SCHEMA_VERSION,
                "t0_unix_ns": self.t0_unix_ns,
                "emitted": emitted, "events_dropped": dropped,
                "capacity": self.capacity}

    def dump_jsonl(self, path: str) -> str:
        """Write header line + one JSON line per event (absolute unix-ns
        timestamps — streams from different processes merge directly)."""
        events = self.snapshot()       # render before opening (no IO races)
        with open(path, "w") as f:
            f.write(json.dumps(self.header()) + "\n")
            for ev in events:
                f.write(json.dumps(ev) + "\n")
        return path


# -- jax's compile events as children of the innermost open bucket ----------

# jax 0.9's event -> the child span it becomes
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    # wraps compile_or_get_cached: the compile on a miss, the cache read
    # and load on a hit
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# jax's cache-outcome event -> the `cache` attribute of the jax.compile
# span it fires inside
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _OpenBucket:
    """What the forwarder needs of the innermost open bucket: its stream,
    its name, and the cache outcome of the compile now running in it."""

    __slots__ = ("stream", "name", "cache")

    def __init__(self, stream: "EventStream", name: str) -> None:
        self.stream, self.name, self.cache = stream, name, "none"


# set by utils.observability.Profiler.bucket; jax compiles synchronously on
# the calling thread, so the innermost bucket of that context is the parent
_OPEN_BUCKET: contextvars.ContextVar[Optional[_OpenBucket]] = \
    contextvars.ContextVar("obs_open_bucket", default=None)
_forwarding = False
_forwarding_lock = threading.Lock()


def _on_compile_duration(event: str, duration_secs: float,
                         **kwargs: Any) -> None:
    top = _OPEN_BUCKET.get()
    if top is None:
        return
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    # jax times on its own clock: end now on the stream's, start = end - dur
    t1 = time.perf_counter_ns()
    dur = int(duration_secs * 1e9)
    attrs = {"fun_name": kwargs.get("fun_name"), "parent": top.name}
    if name == "jax.compile":
        attrs["cache"], top.cache = top.cache, "none"
    top.stream.emit(SPAN, name, t_ns=t1 - dur, dur_ns=dur, attrs=attrs)


def _on_cache_event(event: str, **kwargs: Any) -> None:
    top = _OPEN_BUCKET.get()
    if top is None:
        return
    top.cache = _CACHE_OUTCOMES.get(event, top.cache)


def forward_compile_events() -> None:
    """Register the process's one forwarder of jax's compile events (once;
    later calls return at once).  Outside any open bucket the forwarder
    does one ``ContextVar.get`` and nothing else."""
    global _forwarding
    if _forwarding:
        return
    with _forwarding_lock:
        if _forwarding:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_cache_event)
        _forwarding = True


def open_bucket(stream: "EventStream", name: str) -> contextvars.Token:
    """Make ``name`` the parent of the compile events of this context until
    ``close_bucket(token)``."""
    forward_compile_events()
    return _OPEN_BUCKET.set(_OpenBucket(stream, name))


def close_bucket(token: contextvars.Token) -> None:
    _OPEN_BUCKET.reset(token)


def read_jsonl(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """(header, events) from a dump_jsonl file.  Rejects unknown schema
    versions — the versioning contract that lets the timeline/gate tools
    evolve without silently misreading old dumps."""
    with open(path) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    if not lines:
        raise ValueError(f"{path}: empty event stream")
    header, events = lines[0], lines[1:]
    ver = header.get("schema_version")
    if ver != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: event schema v{ver!r} != supported v{SCHEMA_VERSION}")
    return header, events
