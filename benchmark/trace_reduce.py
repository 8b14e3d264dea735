"""From a profiler trace to the numbers the per-layer metrics read.

Three steps, each a function, so that every PR computes the same number in
the same way and a reviewer can read how:

1. `reduce_xplane` reads the profiler's `.xplane.pb` with nothing but jax
   and keeps what the metrics need: for each device plane the events of its
   "XLA Ops" and "XLA Modules" lines, and from the host plane the spans the
   benchmark itself wrote (`bench.*`, jax.profiler.TraceAnnotation).  The
   result is plain JSON; `tests/data/` holds one recorded on the chip.
2. `classify` gives every device operation a class by the rules under
   `op_classes/` (data: a later PR that names a kernel adds a file there).
3. `Trace` holds the interval arithmetic: the steady window (first to last
   whole step on a device), busy and idle time, time per step and class,
   the operations that took most time and the idle gaps by what the host
   was doing.

What was seen in the first traces read by hand (v5e, PR 23) is in PERF.md
section 3.  The interval arithmetic is a copy of
fpga_ai_nic_tpu/utils/trace_analysis.py's, whose classifier reads async
collective lines and is blind to the fused ring (a synchronous custom call).
"""

import bisect
import json
import os
import re
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
Interval = Tuple[float, float]          # (start_ns, end_ns)
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE_RE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
STAT_CHARS = 400


# -- interval arithmetic -----------------------------------------------------

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


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of `window` that a merged interval set leaves uncovered."""
    out, at = [], window[0]
    for s, e in merged:
        if e <= window[0] or s >= window[1]:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


# -- step 1: the profiler's file -> plain JSON -------------------------------

def find_xplane(trace_dir: str) -> str:
    """Newest .xplane.pb under a jax.profiler trace directory."""
    cands = []
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                p = os.path.join(root, f)
                cands.append((os.path.getmtime(p), p))
    if not cands:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(cands)[1]


def _stats(ev) -> dict:
    out = {}
    for key, val in ev.stats:
        if isinstance(val, (int, float)):
            out[key] = val
        else:
            out[key] = str(val)[:STAT_CHARS]
    return out


def reduce_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [[name index, start_ns, dur_ns]...],
    "modules": [...]}}, "names": [...], "host": [[span name, start_ns,
    dur_ns]...]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    names: List[str] = []
    index: Dict[str, int] = {}

    def ident(ev) -> int:
        if ev.name not in index:
            index[ev.name] = len(names)
            names.append(ev.name)
        return index[ev.name]

    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE_RE.fullmatch(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [ident(ev), ev.start_ns, ev.duration_ns]
                        for ev in line.events]
            if lines.get(OPS_LINE):
                devices[plane.name] = {
                    "ops": lines[OPS_LINE],
                    "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "names": names,
            "host": sorted(host, key=lambda h: h[1])}


def describe(path: str, top: int = 40) -> str:
    """What a trace holds, for reading by hand: every plane and line with
    its event count and extent, and the names that took most time on each
    device line with the stats of one event."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = [f"trace {path}"]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                out.append(f"  LINE {line.name!r}: empty")
                continue
            s0 = min(e.start_ns for e in evs)
            s1 = max(e.start_ns + e.duration_ns for e in evs)
            out.append(f"  LINE {line.name!r}: {len(evs)} events, "
                       f"{s0 / 1e6:.3f} .. {s1 / 1e6:.3f} ms")
            device = DEVICE_PLANE_RE.fullmatch(plane.name)
            spans = [e for e in evs if e.name.startswith(HOST_SPAN_PREFIX)]
            if not device and not spans:
                continue
            by = {}
            for e in (evs if device else spans):
                tot, n, first = by.get(e.name, (0.0, 0, e))
                by[e.name] = (tot + e.duration_ns, n + 1, first)
            ranked = sorted(by.items(), key=lambda kv: -kv[1][0])
            # one device tells all: full detail on the first only
            depth = top if plane.name.endswith(":0") or not device else 5
            for name, (tot, n, first) in ranked[:depth]:
                out.append(f"    {tot / 1e6:10.3f} ms {n:6d}x  {name[:100]}")
                if device:
                    out.append(f"        stats {_stats(first)}")
    return "\n".join(out)


# -- step 2: classes ---------------------------------------------------------

def load_rules(rules_dir: Optional[str] = None) -> List[dict]:
    """Every op_classes/*.json, in file-name order: lists of {"class",
    "regex"}.  The regex is searched in the event's name, which on the TPU
    is the whole HLO instruction; the first rule that matches decides."""
    rules_dir = rules_dir or os.path.join(HERE, "op_classes")
    rules = []
    for f in sorted(os.listdir(rules_dir)):
        if f.endswith(".json"):
            with open(os.path.join(rules_dir, f)) as fh:
                for rule in json.load(fh)["rules"]:
                    rules.append(dict(rule, regex=re.compile(rule["regex"])))
    return rules


DEFAULT_CLASS = "model"


def classify(name: str, rules: List[dict]) -> str:
    for rule in rules:
        if rule["regex"].search(name):
            return rule["class"]
    return DEFAULT_CLASS


# -- step 3: the numbers -----------------------------------------------------

class Trace:
    """A reduced trace with its operations classified.  Per device: the
    steady window runs from the start of the first whole step to the end of
    the last, a step being one run of the module that took most time."""

    def __init__(self, reduced: dict, rules: Optional[List[dict]] = None):
        rules = load_rules() if rules is None else rules
        names = reduced["names"]
        cls = [classify(n, rules) for n in names]
        self.host = [(n, s, s + d) for n, s, d in reduced["host"]]
        self.devices = []
        for plane in sorted(reduced["devices"]):
            dev = reduced["devices"][plane]
            mods = [(names[i], s, s + d) for i, s, d in dev["modules"]]
            by_mod: Dict[str, float] = {}
            for n, s, e in mods:
                by_mod[n] = by_mod.get(n, 0.0) + e - s
            if not by_mod:
                continue
            step_mod = max(by_mod, key=by_mod.get)
            steps = sorted((s, e) for n, s, e in mods if n == step_mod)
            # the first and last may be cut by the trace's own ends
            steps = steps[1:-1]
            if not steps:
                continue
            window = (steps[0][0], steps[-1][1])
            ops = [(names[i], cls[i], s, s + d) for i, s, d in dev["ops"]
                   if s >= window[0] and s + d <= window[1]]
            self.devices.append({"plane": plane, "module": step_mod,
                                 "steps": len(steps), "window": window,
                                 "ops": ops})

    def __bool__(self) -> bool:
        return bool(self.devices)

    # the devices differ only by who waits for whom: seconds are their
    # mean (the contract's busy_s), times per step their median

    def window_s(self) -> float:
        return statistics.mean((d["window"][1] - d["window"][0]) / 1e9
                               for d in self.devices)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the devices."""
        return statistics.mean(
            total_len(merge_intervals((s, e) for _, _, s, e in d["ops"]))
            / 1e9 for d in self.devices)

    def steps(self) -> int:
        return min(d["steps"] for d in self.devices)

    def ms_per_step(self, keep) -> Optional[float]:
        """Busy time per step, in ms, of the operations whose class `keep`
        accepts; None where none ran."""
        vals = []
        for d in self.devices:
            ivs = [(s, e) for _, c, s, e in d["ops"] if keep(c)]
            if ivs:
                vals.append(total_len(merge_intervals(ivs)) / 1e6
                            / d["steps"])
        return statistics.median(vals) if vals else None

    def class_ms_per_step(self, want: str) -> Optional[float]:
        return self.ms_per_step(lambda c: c == want)

    def device_ops(self, top: int = 10) -> List[list]:
        """[class:name, seconds] of the operations that took most time in
        the window, on the first device.  An operation that encloses others
        (a while loop) is listed with all it encloses."""
        by: Dict[str, float] = {}
        for name, c, s, e in self.devices[0]["ops"]:
            key = f"{c}:{name.split(' = ')[0].lstrip('%')}"
            by[key] = by.get(key, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """[what the host was doing, seconds] of idle time on the first
        device, by the benchmark's host span that covers the middle of each
        gap, most first.  Host and device share the profiler's clock."""
        d = self.devices[0]
        busy = merge_intervals((s, e) for _, _, s, e in d["ops"])
        starts = [s for _, s, _ in self.host]
        by: Dict[str, float] = {}
        for s, e in gaps(busy, d["window"]):
            mid, doing = (s + e) / 2, "host:no_span"
            i = bisect.bisect_right(starts, mid) - 1
            # spans nest (a dispatch inside a chunk): innermost first
            while i >= 0:
                n, hs, he = self.host[i]
                if hs <= mid < he:
                    doing = "host:" + n
                    break
                i -= 1
            by[doing] = by.get(doing, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def main(argv=None) -> int:
    """`trace_reduce.py describe <trace dir>` prints what a trace holds;
    `trace_reduce.py reduce <trace dir> <out.json>` writes the JSON."""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) >= 2 and argv[0] == "describe":
        print(describe(find_xplane(argv[1])))
        return 0
    if len(argv) == 3 and argv[0] == "reduce":
        with open(argv[2], "w") as f:
            json.dump(reduce_xplane(find_xplane(argv[1])), f)
        return 0
    print(main.__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
