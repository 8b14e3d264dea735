"""BENCHMARK.json and the files it names, read and refused as the driver
would refuse them.

Everything that belongs to one configuration, one job (the traffic mix of a
training system: placement, batch, sequence length, collective preset) or
one per-layer metric sits in a file of its own, found by the name in
BENCHMARK.json:

    configs/<config>.json        widths, source, reduced, assumed, optimizer
    jobs/<traffic>.json          dp, batch per chip, sequence length, preset
    families/<family>.py         program entry, batch, operations, reference
    metrics/<per-layer metric>.py    one reader, `read(ctx) -> number | None`

so a later PR adds a cell with new files and new entries and edits nothing
that is here.  `--rehearse` adds the entries of rehearse.json, which are
written exactly that way and are not in BENCHMARK.json's `workloads`.
"""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH_RE = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
MAX_BOUND = 0.1


class SpecError(ValueError):
    """BENCHMARK.json (or a file it names) is outside the contract."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise SpecError(f"{what} {name!r}: a name starts with a letter, a "
                        "digit or _ and has at most 64 of A-Za-z0-9_.-")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise SpecError(f"{what}: unit {unit!r} needs 1 to 16 of "
                        "A-Za-z0-9_/%.- and no space")
    return unit


def check_line(text, what: str) -> str:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text or "\r" in text):
        raise SpecError(f"{what}: 1 to 200 characters on one line, no tab")
    return text


def _keys(entry: dict, need: set, what: str, optional=()) -> None:
    got = set(entry)
    if got - need - set(optional) or need - got:
        raise SpecError(f"{what}: keys {sorted(got)} are not {sorted(need)}"
                        + (f" (+ {sorted(optional)})" if optional else ""))


def _unique(entries, what: str) -> None:
    names = [e["name"] for e in entries]
    twice = {n for n in names if names.count(n) > 1}
    if twice:
        raise SpecError(f"{what}: name(s) used twice: {sorted(twice)}")


def _under_paths(path: str, paths) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in paths)


def validate(spec: dict) -> dict:
    """Raise SpecError where the driver would refuse the file before any
    run; returns `spec`."""
    if set(spec) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(spec)} are not "
                        f"{sorted(TOP_KEYS)}")
    paths, command = spec["paths"], spec["command"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        raise SpecError("paths: 1 to 16 directories")
    for p in paths:
        if (not isinstance(p, str) or not PATH_RE.fullmatch(p)
                or p.startswith("/") or ".." in p.split("/")):
            raise SpecError(f"paths: {p!r} is not a relative path of the "
                            "permitted characters")
    if not isinstance(command, list) or not 1 <= len(command) <= 32:
        raise SpecError("command: a list of 1 to 32 strings")
    for word in command:
        check_line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise SpecError(f"command: {word!r} leaves the repo")
        if "/" in word and not _under_paths(word, paths):
            raise SpecError(f"command: {word!r} is outside paths")
    secs = spec["run_seconds"]
    if isinstance(secs, bool) or not isinstance(secs, int) \
            or not 1 <= secs <= 51:
        raise SpecError("run_seconds: a whole number from 1 to 51")

    configs, cells = spec["configs"], spec["workloads"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("configs: 1 to 24")
    if not 2 <= len(cells) <= 24:
        raise SpecError("workloads: 2 to 24")
    files = set()
    for c in configs:
        _keys(c, CONFIG_KEYS, f"config {c.get('name')!r}")
        check_name(c["name"], "config")
        check_line(c["source"], f"config {c['name']}: source")
        check_line(c["why"], f"config {c['name']}: why")
        if not PATH_RE.fullmatch(c["file"]) \
                or not _under_paths(c["file"], paths):
            raise SpecError(f"config {c['name']}: file {c['file']!r} is "
                            "not under paths")
        if c["file"] in files:
            raise SpecError(f"config {c['name']}: file {c['file']!r} is "
                            "another configuration's too")
        files.add(c["file"])
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            raise SpecError(f"config {c['name']}: reduced: at most 16 keys")
        for key in c["reduced"]:
            check_name(key, f"config {c['name']}: reduced key")
    _unique(configs, "configs")
    config_names = {c["name"] for c in configs}
    pairs = set()
    for w in cells:
        _keys(w, WORKLOAD_KEYS, f"workload {w.get('name')!r}")
        check_name(w["name"], "workload")
        check_name(w["traffic"], f"workload {w['name']}: traffic")
        check_line(w["why"], f"workload {w['name']}: why")
        if w["config"] not in config_names:
            raise SpecError(f"workload {w['name']}: no config "
                            f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"workload {w['name']}: the pair of config and "
                            "traffic is another cell's")
        pairs.add((w["config"], w["traffic"]))
    _unique(cells, "workloads")
    unused = config_names - {w["config"] for w in cells}
    if unused:
        raise SpecError(f"configs no cell uses: {sorted(unused)}")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        raise SpecError(f"{four} of {len(cells)} cells ask for 4 chips: at "
                        "most a quarter, rounded down, and always one")

    e2e, layer = spec["end_to_end"], spec["per_layer"]
    if not 1 <= len(e2e) <= 16:
        raise SpecError("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        raise SpecError("per_layer: 1 to 128 metrics")
    cell_names = {w["name"] for w in cells}
    for m, need in [(m, E2E_KEYS) for m in e2e] \
            + [(m, LAYER_KEYS) for m in layer]:
        what = f"metric {m.get('name')!r}"
        _keys(m, need, what, optional=("workloads",))
        check_name(m["name"], "metric")
        check_unit(m["unit"], what)
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{what}: better is lower or higher")
        if m["source"] not in SOURCES:
            raise SpecError(f"{what}: source is one of {SOURCES}")
        for w in m.get("workloads", ()):
            if w not in cell_names:
                raise SpecError(f"{what}: no workload {w!r}")
    _unique(e2e + layer, "metrics")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"metric {m['name']}: an end-to-end metric is "
                            "taken by the benchmark itself: host_clock or "
                            "device_trace")
        b = m["bound"]
        if isinstance(b, bool) or not isinstance(b, (int, float)) \
                or not 0 < b <= MAX_BOUND:
            raise SpecError(f"metric {m['name']}: bound {b!r} is not in "
                            f"(0, {MAX_BOUND}]")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise SpecError("end_to_end has no setup_s")
    for m in layer:
        check_line(m["layer"], f"metric {m['name']}: layer")
        if m["moves"] not in e2e_names:
            raise SpecError(f"metric {m['name']}: moves {m['moves']!r}, "
                            "which is no end-to-end metric")
    for w in cells:
        mine = metrics_of(spec, w["name"])
        if "setup_s" not in mine["end_to_end"] \
                or len(mine["end_to_end"]) < 2 or not mine["per_layer"]:
            raise SpecError(f"workload {w['name']}: needs setup_s, one more "
                            "end-to-end metric and a per-layer metric")
    if len(json.dumps(spec)) > 64 << 10:
        raise SpecError("BENCHMARK.json is over 64 KiB")
    return spec


def metrics_of(spec: dict, cell: str) -> dict:
    """{"end_to_end": {name: entry}, "per_layer": {name: entry}} of one
    cell.  A per-layer metric is reported only where the metric it moves
    is."""
    def mine(entries):
        return {m["name"]: m for m in entries
                if "workloads" not in m or cell in m["workloads"]}
    e2e = mine(spec["end_to_end"])
    return {"end_to_end": e2e,
            "per_layer": {k: m for k, m in mine(spec["per_layer"]).items()
                          if m["moves"] in e2e}}


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec(rehearse: bool = False) -> dict:
    """BENCHMARK.json, validated.  With `rehearse`, the tiny cells of
    rehearse.json are added: each names a cell of BENCHMARK.json whose
    metrics it reports (`like`)."""
    spec = validate(read_json(os.path.join(ROOT, "BENCHMARK.json")))
    if not rehearse:
        return spec
    extra = read_json(os.path.join(HERE, "rehearse.json"))
    spec = json.loads(json.dumps(spec))
    spec["configs"] += extra["configs"]
    for w in extra["workloads"]:
        like = w.pop("like")
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(w["name"])
        spec["workloads"].append(w)
    return spec


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module; names may hold dots and
    dashes, so it is found by path and not imported by name."""
    check_name(name, kind)
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {os.path.relpath(path, ROOT)}")
    mod_name = "benchmark_%s_%s" % (kind, re.sub(r"\W", "_", name))
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_cell(spec: dict, name: str) -> dict:
    """One cell with everything its name leads to: the workload entry, the
    configuration's file, the job's file, the family's module and the
    metric entries it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = read_json(os.path.join(ROOT, entry["file"]))
    job_path = os.path.join(HERE, "jobs", w["traffic"] + ".json")
    if not os.path.exists(job_path):
        raise SpecError(f"no {os.path.relpath(job_path, ROOT)}")
    job = read_json(job_path)
    if job["chips"] != w["chips"]:
        raise SpecError(f"workload {name}: chips {w['chips']} but job "
                        f"{w['traffic']} places {job['chips']}")
    return {"name": name, "workload": w, "config": config, "job": job,
            "family": load_module("families", config["family"]),
            "metrics": metrics_of(spec, name)}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by jax's `device_kind`.  A device
    that is not in the table is an error, not a default."""
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no published peak for device_kind {device_kind!r} "
                       f"in benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]
