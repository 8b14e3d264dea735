"""Profiler-trace overlap analysis — stall attribution the TPU way.

The reference attributes stalls with hardware counters (stall_host_in/out,
stall_eth_in/out, hw/all_reduce.sv:94-97) because it owns every queue.  On
TPU the runtime hides queues, so SURVEY.md §5 concludes stall attribution
"must come from profiler trace analysis".  This module is that analysis:
it reads a JAX profiler trace (jax.profiler.trace / --trace-dir), walks the
device plane's sync ("XLA Ops") and async ("Async XLA Ops") lines, and
reports for every async op — collectives (all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all) and DMAs (copy/slice
starts) — how much of its wall time was *overlapped* by synchronous device
compute vs *exposed* (device otherwise idle: the TPU analogue of
stall_eth_in, wire time nothing hid).

Pure-python interval math over jax.profiler.ProfileData; no tensorboard /
xprof dependency.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# hyphenated HLO collective op names — the device-plane classifier matches
# these only.  The short jax-primitive names ("psum", ...) must NOT live
# here: _is_collective substring-matches, and on real-chip traces any
# fusion merely NAMED after a psum consumer (e.g. "psum_invariant_fusion")
# would be banked as async collective time, skewing overlap attribution.
_COLLECTIVE_MARKERS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)

# jax-level instruction names, CPU thunk executor only (XLA names HLO
# collectives there after the primitive that built them, e.g. "psum.7").
# Matched as the WHOLE base name plus an optional ".uid" suffix — never as
# a substring — so "psum.7" classifies but "my_psum_like_fusion" does not.
_CPU_PRIMITIVE_MARKERS = (
    "psum", "ppermute", "all_gather", "all_to_all", "psum_scatter",
    "reduce_scatter", "pmax", "pmin",
)
_CPU_PRIMITIVE_RE = re.compile(
    r"(?:%s)(?:\.\d+)?" % "|".join(_CPU_PRIMITIVE_MARKERS))

Interval = Tuple[float, float]          # (start_ns, end_ns)


# ---------------------------------------------------------------------------
# interval arithmetic (pure, unit-tested)
# ---------------------------------------------------------------------------

def merge_intervals(ivs: Iterable[Interval]) -> List[Interval]:
    """Union of possibly-overlapping intervals, sorted, coalesced."""
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total_len(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def overlap_len(iv: Interval, merged: Sequence[Interval]) -> float:
    """Length of iv covered by a *merged* (sorted, disjoint) interval set.
    Bisects to the first candidate: real traces have ~1e5+ sync ops and a
    linear scan per async event would be O(A*S)."""
    import bisect
    s, e = iv
    cov = 0.0
    i = bisect.bisect_right(merged, (s, float("inf"))) - 1
    if i >= 0 and merged[i][1] <= s:
        i += 1
    i = max(i, 0)
    while i < len(merged) and merged[i][0] < e:
        ms, me = merged[i]
        cov += min(e, me) - max(s, ms)
        i += 1
    return cov


# ---------------------------------------------------------------------------
# trace loading
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    """Newest .xplane.pb under a jax.profiler.trace output directory."""
    cands = []
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                p = os.path.join(root, f)
                cands.append((os.path.getmtime(p), p))
    if not cands:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(cands)[1]


def _load_trace(trace_dir: str, data=None):
    """(xplane path, parsed profile data), reusing an already-parsed
    ``data`` when the caller has one (a multi-MB xplane is worth parsing
    once per CLI run, not once per analysis pass)."""
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    return path, (data if data is not None
                  else ProfileData.from_file(path))


def _is_collective(name: str) -> bool:
    """Device-plane classifier: hyphenated HLO collective names only."""
    n = name.lower()
    return any(m in n for m in _COLLECTIVE_MARKERS)


def _is_cpu_collective(base: str) -> bool:
    """CPU thunk classifier: HLO collective names, plus bare jax-primitive
    instruction names ("psum.7") matched on the full base name."""
    return (_is_collective(base)
            or _CPU_PRIMITIVE_RE.fullmatch(base.lower()) is not None)


def _attribution_report(sync_ivs: List[Interval],
                        async_evs: List[Tuple[str, Interval]],
                        classify=None) -> Dict:
    """Shared overlapped/exposed accounting for one device plane (TPU) or
    one thunk mesh (CPU): async op wall time split into the part covered
    by merged sync compute and the exposed remainder, ranked per op.
    `classify(name)` returns the async bucket key (default: collective vs
    dma by name)."""
    if classify is None:
        def classify(name):
            return ("async_collective_s" if _is_collective(name)
                    else "async_dma_s")
    merged = merge_intervals(sync_ivs)
    rep = {"sync_busy_s": total_len(merged) / 1e9,
           "async_s": 0.0, "async_collective_s": 0.0,
           "async_dma_s": 0.0, "overlapped_s": 0.0, "exposed_s": 0.0}
    exposed_by_op: Dict[str, float] = {}
    for name, iv in async_evs:
        dur = (iv[1] - iv[0]) / 1e9
        cov = overlap_len(iv, merged) / 1e9
        rep["async_s"] += dur
        rep[classify(name)] += dur
        rep["overlapped_s"] += cov
        exposed = dur - cov
        rep["exposed_s"] += exposed
        if exposed > 0:
            exposed_by_op[name] = exposed_by_op.get(name, 0.0) + exposed
    rep["overlap_frac"] = (rep["overlapped_s"] / rep["async_s"]
                           if rep["async_s"] else 1.0)
    rep["exposed_by_op"] = exposed_by_op
    rep["top_exposed"] = sorted(exposed_by_op.items(),
                                key=lambda kv: -kv[1])[:5]
    return rep


def analyze_trace(trace_dir: str, *,
                  plane_substr: str = "/device:", data=None) -> Dict:
    """Overlap/stall report for every device plane in the trace.

    Returns {"devices": {plane_name: report}, "xplane": path}; each report:
      sync_busy_s      — total synchronous device compute ("XLA Ops")
      async{,_collective,_dma}_s — async op wall time by class
      overlapped_s     — async time hidden under sync compute
      exposed_s        — async time with the device otherwise idle (stall)
      top_exposed      — worst offenders [(op, exposed_s)], most first
    """
    path, data = _load_trace(trace_dir, data)
    devices: Dict[str, Dict] = {}
    for plane in data.planes:
        if plane_substr not in plane.name:
            continue
        sync_ivs: List[Interval] = []
        async_evs: List[Tuple[str, Interval]] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    sync_ivs.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            elif line.name == "Async XLA Ops":
                for ev in line.events:
                    async_evs.append((ev.name.split(" = ")[0],
                                      (ev.start_ns,
                                       ev.start_ns + ev.duration_ns)))
        if not sync_ivs and not async_evs:
            continue
        # full exposed_by_op map kept so cross-device aggregation never
        # drops an op that is small per device but large fleet-wide
        devices[plane.name] = _attribution_report(sync_ivs, async_evs)
    if not devices:
        raise ValueError(
            f"{path} has no '{plane_substr}' plane with XLA Ops lines "
            "(CPU traces carry host thunk lines only; capture on TPU)")
    return {"devices": devices, "xplane": path}


# thunks execute on the per-shard executor threads AND the shared Eigen
# intra-op pool threads; both carry leaf op events.  The executor line's
# prefix follows the CPU client's name across jaxlibs: TfrtCpuClient
# before the PjRt rename (jax <= 0.4.x), PjRtCpuClient after.
_CPU_LINE_PREFIXES = ("tf_XLAPjRtCpuClient", "tf_XLATfrtCpuClient",
                      "tf_XLAEigen")
# leaf thunk events are bare HLO instruction names ("wrapped_tanh",
# "psum.7", "broadcast_add_fusion"); executor infrastructure events mostly
# carry spaces or "::" ("ThunkExecutor::Execute (...)", "end: X",
# "Wait: pending_threads=2/8") — the bare-word exceptions are listed
_CPU_OP_RE = re.compile(r"[\w.\-]+")
_CPU_INFRA = frozenset({"Rendezvous"})   # collective-internal wait event,
# already inside the enclosing psum/ppermute thunk interval
# control-flow thunks ENCLOSE their body's thunk events — counting a
# while-loop's full span as sync compute would blanket every collective
# inside it
_CPU_CONTAINER_RE = re.compile(r"(while|call|conditional)(\.\d+)?")


def analyze_cpu_thunk_trace(trace_dir: str, *,
                            data=None) -> Dict:
    """Overlap attribution from a CPU thunk-executor trace — the virtual
    8-device mesh's substitute for TPU device planes (which a CPU trace
    does not carry; capture with ``ProfileOptions.host_tracer_level=3`` so
    per-op thunk events appear).

    Semantics differ from the device-plane analysis and are labeled in
    the report: each ``tf_XLAPjRtCpuClient/*`` line is one shard's
    executor thread; a collective thunk's interval INCLUDES its
    rendezvous wait (the wire-time analogue), and its *overlapped* share
    is the part hidden under compute thunks running concurrently on the
    other shards' threads — the mesh-level "was anything useful happening
    while shards sat in the collective" question the reference answers
    with stall_eth counters (hw/all_reduce.sv:94-97).  Exposed = no shard
    computed: true mesh-wide stall."""
    path, data = _load_trace(trace_dir, data)
    sync_ivs: List[Interval] = []
    async_evs: List[Tuple[str, Interval]] = []
    n_lines = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith(_CPU_LINE_PREFIXES):
                continue
            n_lines += 1
            for ev in line.events:
                if (not _CPU_OP_RE.fullmatch(ev.name)
                        or not ev.duration_ns
                        or ev.name in _CPU_INFRA
                        or _CPU_CONTAINER_RE.fullmatch(ev.name)):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                base = ev.name.removeprefix("wrapped_")
                if _is_cpu_collective(base):
                    async_evs.append((ev.name, iv))
                else:
                    sync_ivs.append(iv)
    if not async_evs and not sync_ivs:
        raise ValueError(
            f"{path} carries no leaf thunk events on "
            f"{'/'.join(_CPU_LINE_PREFIXES)} lines — capture with "
            "ProfileOptions.host_tracer_level=3")
    # every async event here IS a collective (that's how it was classified)
    rep = _attribution_report(sync_ivs, async_evs,
                              classify=lambda name: "async_collective_s")
    rep["mode"] = ("cpu-thunks: per-shard collective wall time (incl. "
                   "rendezvous wait) vs compute concurrently live on any "
                   "shard's executor thread")
    rep["n_executor_lines"] = n_lines
    return {"devices": {"cpu-thunk-mesh": rep}, "xplane": path}


def analyze_any(trace_dir: str, *, data=None) -> Dict:
    """Device-plane analysis when the trace has one (TPU), CPU thunk-mode
    otherwise — so the same tooling attributes collectives on the real
    chip and on the virtual mesh."""
    _, data = _load_trace(trace_dir, data)
    try:
        return analyze_trace(trace_dir, data=data)
    except ValueError:
        return analyze_cpu_thunk_trace(trace_dir, data=data)


def device_intervals(trace_dir: str, *,
                     data=None) -> List[Dict]:
    """Raw per-op intervals for the telemetry timeline (obs.timeline):
    every device-plane sync/async event as
    ``{"plane", "line", "name", "start_ns", "end_ns", "cls"}`` — TPU
    device planes when the trace has them, the CPU thunk-executor lines
    otherwise (classified with the same word-scoped rules the aggregate
    reports use, so the timeline and the attribution numbers can never
    disagree about what counts as a collective)."""
    path, data = _load_trace(trace_dir, data)
    out: List[Dict] = []
    for plane in data.planes:
        if "/device:" not in plane.name:
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            is_async = line.name == "Async XLA Ops"
            for ev in line.events:
                if not ev.duration_ns:
                    continue
                name = ev.name.split(" = ")[0]
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": ev.start_ns,
                            "end_ns": ev.start_ns + ev.duration_ns,
                            "cls": "async" if is_async else "sync"})
    if out:
        return out
    # CPU thunk fallback (virtual-mesh traces): same event filtering as
    # analyze_cpu_thunk_trace, emitted as intervals instead of aggregates
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith(_CPU_LINE_PREFIXES):
                continue
            for ev in line.events:
                if (not _CPU_OP_RE.fullmatch(ev.name)
                        or not ev.duration_ns
                        or ev.name in _CPU_INFRA
                        or _CPU_CONTAINER_RE.fullmatch(ev.name)):
                    continue
                base = ev.name.removeprefix("wrapped_")
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "end_ns": ev.start_ns + ev.duration_ns,
                            "cls": ("async" if _is_cpu_collective(base)
                                    else "sync")})
    return out


def summarize(report: Dict) -> Dict:
    """Single flattened summary across device planes (the JSON-line shape
    examples embed), keeping the ranked worst stall offenders so the
    attribution names the op, not just the seconds."""
    devs = report["devices"].values()
    agg = {k: sum(d[k] for d in devs)
           for k in ("sync_busy_s", "async_s", "async_collective_s",
                     "async_dma_s", "overlapped_s", "exposed_s")}
    agg["overlap_frac"] = (agg["overlapped_s"] / agg["async_s"]
                           if agg["async_s"] else 1.0)
    agg["n_devices"] = len(report["devices"])
    by_op: Dict[str, float] = {}
    for d in devs:
        # aggregate the FULL per-op maps (falling back to the truncated
        # display list for hand-built reports) — a per-device top-5 merge
        # would drop ops that are small everywhere but large in total
        for name, s in (d.get("exposed_by_op") or
                        dict(d.get("top_exposed", ()))).items():
            by_op[name] = by_op.get(name, 0.0) + s
    agg["top_exposed"] = sorted(by_op.items(), key=lambda kv: -kv[1])[:5]
    return agg


# ---------------------------------------------------------------------------
# CLI: device-plane stall attribution without writing code
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m fpga_ai_nic_tpu.utils.trace_analysis <trace-dir>`` —
    the stall-attribution report as one JSON object on stdout (the same
    numbers a driver embeds when run with ``--trace-dir``)."""
    ap = argparse.ArgumentParser(
        prog="python -m fpga_ai_nic_tpu.utils.trace_analysis",
        description="Overlap/stall attribution from a jax.profiler trace "
                    "directory: async collective/DMA wall time split into "
                    "compute-overlapped vs exposed (device idle).")
    ap.add_argument("trace_dir", help="jax.profiler.trace output directory")
    ap.add_argument("--mode", choices=("auto", "device", "cpu"),
                    default="auto",
                    help="device = TPU device planes only, cpu = thunk-"
                         "executor lines only, auto = device with cpu "
                         "fallback (default)")
    ap.add_argument("--per-plane", action="store_true",
                    help="full per-plane reports instead of the flattened "
                         "summary")
    ap.add_argument("--intervals", metavar="FILE", default=None,
                    help="also dump raw per-op intervals (obs.timeline "
                         "input shape) to FILE")
    args = ap.parse_args(argv)
    analyze = {"auto": analyze_any, "device": analyze_trace,
               "cpu": analyze_cpu_thunk_trace}[args.mode]
    try:
        # one parse serves the report AND the interval dump
        _, data = _load_trace(args.trace_dir)
        report = analyze(args.trace_dir, data=data)
    except (FileNotFoundError, ValueError) as e:
        # the JSON error contract of a missing xplane, never a raw
        # traceback
        print(json.dumps({"error": str(e)}))
        return 1
    if args.intervals:
        with open(args.intervals, "w") as f:
            json.dump(device_intervals(args.trace_dir, data=data), f)
    out = dict(report if args.per_plane else summarize(report),
               xplane=report["xplane"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
