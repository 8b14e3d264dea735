#!/usr/bin/env python
"""Telemetry regression gate: diff a run's telemetry summary against the
banked benchmark artifacts, with per-metric thresholds.

The observability plane's closing loop: artifacts (BENCH_r*.json,
COLLECTIVE_r*.json, CODEC_BENCH_r*.json and their artifacts/ twins) bank
what the stack measured; this gate turns them from documentation into a
*contract* — a new run whose telemetry summary regresses a banked metric
beyond its threshold exits nonzero, in CI (`make obs-gate`, wired into
`make ci`).

    python tools/obs_gate.py                      # gate-on-self: extract
                                                  # the banked summary and
                                                  # diff it against itself
                                                  # (must pass trivially)
    python tools/obs_gate.py --summary run.json   # diff a run's summary
    python tools/obs_gate.py --write-summary f.json --save-artifact

Summary schema (v1): ``{"schema_version": 1, "metrics": {name:
{"value", "higher_is_better", "rel_tol", "source"}}}``; a candidate file
may also be a flat ``{name: value}`` mapping — direction/threshold then
come from the banked side.  Only metrics present on BOTH sides are
compared (a run that measures a subset gates that subset); the verdict
lists compared/missing counts so a trivially-green gate that compared
nothing is visible, never silent.

No jax import — the gate must run (and fail meaningfully) on a machine
with no chip.
"""

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

SCHEMA_VERSION = 1

# default relative tolerances per metric family: slope-timed rates jitter
# run to run (shared CI machines), so the gate trips on real regressions,
# not scheduler noise
TOL_RATE = 0.25          # GB/s codec / ring rates
TOL_THROUGHPUT = 0.30    # samples/s (the banked record is a CPU fallback)
TOL_LOOPBACK = 0.25      # fused-kernel loopback GB/s

# THE metric-name contract, shared with producers of fresh-run summaries
# (bench_collective.py imports these): gate() compares only names present
# on both sides, so a name that drifted between producer and extractor
# would silently gate nothing for that family
COLLECTIVE_GATE_KEYS = ("codec_roundtrip_gbps", "codec_encode_gbps",
                        "codec_decode_gbps", "fused_ring_loopback_gbps")
SWEEP_GATE_ARMS = ("psum_bf16", "ring_f32", "ring_bfp")
# fused-optimizer bench rows (FUSED_OPT_BENCH_r*.json): step/update-stage
# times gate lower-is-better, the speedup higher-is-better, and the
# moment-state byte accounting is exact (a change means the state layout
# changed — tiny tolerance, not timing noise)
FUSED_OPT_GATE_KEYS = ("fused_ms", "ring_then_opt_ms",
                       "opt_standalone_ms", "speedup_vs_ring_then_opt",
                       "moment_state_bytes")
# dryrun (cpu-mesh) fused-opt artifacts gate ONLY the exact accounting:
# their timings carry oversubscription noise of the effect's own order
FUSED_OPT_BYTE_KEYS = ("moment_state_bytes", "standalone_hbm_bytes")
TOL_FUSED_OPT_TIME = 0.35
TOL_EXACT = 0.01

# reshard-vs-restore MTTR rows (RESHARD_BENCH_r*.json): recovery times
# gate lower-is-better, the speedup higher-is-better; the plan's
# wire-byte accounting is exact (a change means the intersection table
# or the state layout changed — J8 territory, not timing noise).  Dryrun
# (CPU-mesh) artifacts gate ONLY the bytes, same honesty rule as the
# fused-opt rows.
RESHARD_GATE_KEYS = ("mttr_reshard_s", "mttr_restore_s", "mttr_speedup")
RESHARD_BYTE_KEYS = ("reshard_wire_bytes",)
TOL_RESHARD_TIME = 0.40

# autotune matrix rows (TUNE_BENCH_r*.json): the tuned plan's DECLARED
# per-device wire bytes gate exactly (a drift means the plan, the codec
# accounting, or the topology terms changed — J9 territory, not noise);
# measured collective times gate only on non-dryrun artifacts, the
# fused-opt honesty rule.  tuned_vs_best_fixed (modeled ratio, <= 1 by
# argmin construction) gates two-sided-exact too: it moving at all means
# the scoring model or the candidate grid changed.
TUNE_GATE_KEYS = ("tuned_measured_ms", "flat_fixed_measured_ms")
TUNE_BYTE_KEYS = ("tuned_wire_bytes", "tuned_vs_best_fixed")
TOL_TUNE_TIME = 0.40

# serving rows (SERVE_BENCH_r*.json, one per concurrency): latencies
# gate lower-is-better, throughput higher; the byte accounting is exact
# two-sided (pool / page-table / contiguous-equivalent bytes — a drift
# means the pool layout or ServeConfig changed, J10/paged territory,
# not noise) and ``recompiles_steady`` is exact against a banked 0, so
# ANY steady-state recompile fails the gate.  Dryrun (CPU-mesh)
# artifacts gate only the exact keys — the fused-opt honesty rule.
SERVE_GATE_KEYS = ("throughput_tok_s", "ttft_mean_s", "ttft_p95_s",
                   "tpot_mean_s", "pages_in_use_peak")
SERVE_BYTE_KEYS = ("pool_bytes", "page_table_bytes",
                   "contiguous_cache_bytes", "recompiles_steady")
TOL_SERVE_TIME = 0.40

# the serve bench's kernel axis (artifact ``attend`` block): the modeled
# decode roofline of the gathered-view reference vs the Pallas paged
# kernel at the curve's top concurrency.  All MODELED numbers —
# deterministic functions of the workload + ServeConfig + model shape —
# so they gate exact two-sided like the byte accounting: any drift
# means the roofline model, the workload or the pool geometry changed,
# never noise.  Rows carry ``attend_impl``; non-reference rows gate
# under ``serve.c{n}.{impl}.{key}`` so the kernel axis never collides
# with the reference curve's baseline names.
SERVE_ATTEND_KEYS = ("reference_bytes_per_token",
                     "pallas_bytes_per_token",
                     "bytes_per_token_reduction",
                     "reference_hbm_bound_frac",
                     "pallas_hbm_bound_frac",
                     "kv_bytes_per_step_reduction")

# fleet rows (FLEET_BENCH_r*.json, one per scenario): the handoff wire
# accounting and the recovery-tier facts are exact two-sided — the
# banked zeros for fleet_replays / serve_recoveries mean ANY replay or
# replay-tier firing where the handoff tier should have moved the
# request fails CI, and handoff_wire_bytes drifting means the plan or
# the migration set changed (J11 territory, not noise).  MTTR / TTFT /
# throughput gate on non-dryrun artifacts only, the fused-opt honesty
# rule.
FLEET_GATE_KEYS = ("fleet_mttr_s", "ttft_p95_s", "throughput_tok_s")
FLEET_BYTE_KEYS = ("handoff_wire_bytes", "handoffs", "fleet_replays",
                   "serve_recoveries", "recompiles_steady")
TOL_FLEET_TIME = 0.40

# wire-integrity rows (INTEGRITY_BENCH_r*.json).  Route rows: the
# checksum must be INVISIBLE (wire_bytes_delta banked 0 — any nonzero
# means a checksum started riding the wire, J12 territory), must never
# false-trip on a clean run (trips banked 0) and must leave the result
# bit-identical (bit_identical banked 1); ms_on/ms_off/overhead gate on
# non-dryrun artifacts only (CPU timings are oversubscription noise).
# MTTR rows: the trip/recovery COUNTERS are exact two-sided — a drifted
# counter means the recovery routing changed (e.g. the logit guard
# started winning the race the page ledger must win) — while mttr_s
# gates non-dryrun only.
INTEGRITY_GATE_KEYS = ("ms_on", "ms_off", "overhead_ratio")
INTEGRITY_BYTE_KEYS = ("wire_bytes", "wire_bytes_delta", "trips",
                       "bit_identical")
INTEGRITY_MTTR_EXACT = ("wire_corruption_faults", "checkpoint_restores",
                        "reshards", "page_trips", "logit_trips",
                        "token_exact", "bit_exact",
                        "handoff_integrity_trips", "fleet_replays",
                        "serve_recoveries", "recompiles_steady")
TOL_INTEGRITY_TIME = 0.40

# adaptive-tuning rows (ADAPT_BENCH_r*.json, one per scenario): the
# switch/trace counters are exact two-sided — `switches` banked 1 on
# the forced-shift row means detection AND the step-boundary switch
# both happened (0 would be a dead detector, 2+ flapping), banked 0 on
# the steady row means zero false positives, and
# `recompiles_across_switch` banked 0 is the graftlint J13 contract as
# an artifact fact (ANY trace appearing across a switch fails CI).
# detection_latency_steps is a measured quantity: non-dryrun artifacts
# only, lower is better.
ADAPT_GATE_KEYS = ("detection_latency_steps",)
ADAPT_EXACT_KEYS = ("detected", "switches", "false_switches",
                    "recompiles_across_switch", "n_candidates")
TOL_ADAPT_TIME = 0.40

# durable-state rows (CKPT_BENCH_r*.json, one per scenario): the
# storage accounting and audit/repair facts are exact two-sided —
# bytes_written / shard / mirror file counts drifting means the stored
# layout changed (a silent shrink is a lost mirror, i.e. a lost repair
# source), encode_in_background banked 1 is the async-stall satellite
# as an artifact fact (0 = the GB-scale encode moved back into the
# caller's save stall), trips banked 0 means a clean save never
# false-trips its own audit, repaired/bit_exact/healed banked 1 +
# repair_wire_bytes == declared_shard_bytes is the peer-repair contract
# (J14 as an artifact), steps_lost == 1 pins the walk-back landing on
# the PREVIOUS step, and refused == 1 pins the no-clean-source refusal.
# Stall/audit/MTTR timings gate on non-dryrun artifacts only, the
# fused-opt honesty rule.
CKPT_GATE_KEYS = ("save_stall_sync_ms", "save_stall_async_ms",
                  "commit_wall_ms", "audit_ms", "restore_ms",
                  "mttr_repair_ms", "mttr_walkback_ms")
CKPT_EXACT_KEYS = ("bytes_written", "n_leaf_files", "n_shard_files",
                   "mirror_files", "encode_in_background",
                   "audit_leaves", "trips", "repaired",
                   "repair_wire_bytes", "declared_shard_bytes", "healed",
                   "bit_exact", "steps_lost", "walkback_bit_exact",
                   "refused")
TOL_CKPT_TIME = 0.40

# graftmc envelope rows (MC_ENVELOPE_r*.json): per-route cell counts
# and states explored are exact two-sided — the corpus is deterministic,
# so ANY drift means the envelope or the models changed, and a silent
# envelope SHRINK (fewer cells claimed verified) must fail CI exactly
# like a growth nobody re-banked.  The POR reduction factor gates
# higher-is-better (a collapsing reduction signals an unsound-or-
# degraded persistent set), and wall time gates lower-is-better with a
# wide tolerance: it is the state-explosion tripwire, not a perf SLO
# (graftlint additionally enforces an absolute budget in-process).
MC_ROUTE_EXACT = ("cells", "states")
TOL_MC_TIME = 1.00
TOL_MC_REDUCTION = 0.50


def collective_metric(key: str) -> str:
    return f"collective.{key}"


def sweep_metric(size_mb, arm: str) -> str:
    return f"sweep.{size_mb}mb.{arm}_gbps"


def fused_opt_metric(kind: str, key: str) -> str:
    return f"fused_opt.{kind}.{key}"


def reshard_metric(trainer: str, codec: str, key: str) -> str:
    return f"reshard.{trainer}.{codec}.{key}"


def tune_metric(regime: str, key: str) -> str:
    return f"tune.{regime}.{key}"


def serve_metric(max_reqs, key: str) -> str:
    return f"serve.c{max_reqs}.{key}"


def fleet_metric(scenario: str, key: str) -> str:
    return f"fleet.{scenario}.{key}"


def fleet_slo_metric(scenario: str, key: str) -> str:
    """Per-scenario SLO-observatory keys (windowed tick-domain
    percentiles + autoscaler decision counts) — exact two-sided."""
    return f"fleet.slo.{scenario}.{key}"


def integrity_metric(route: str, key: str) -> str:
    return f"integrity.{route}.{key}"


def adapt_metric(scenario: str, key: str) -> str:
    return f"adapt.{scenario}.{key}"


def ckpt_metric(row: str, key: str) -> str:
    return f"ckpt.{row}.{key}"


def mc_metric(route: str, key: str) -> str:
    return f"mc.{route}.{key}"


def _load(path):
    with open(path) as f:
        return json.load(f)


def _newest(pattern):
    paths = sorted(glob.glob(os.path.join(ROOT, pattern)))
    return paths[-1] if paths else None


def _metric(value, source, *, higher=True, tol=TOL_RATE,
            two_sided=False):
    """two_sided: ANY relative change beyond tol is a regression — for
    exact accounting facts (byte counts) where a silent shrink is as
    wrong as a growth (a halved moment-state byte count means the state
    dtype/layout changed, not that memory 'improved')."""
    return {"value": float(value), "source": source,
            "higher_is_better": bool(higher), "rel_tol": float(tol),
            "two_sided": bool(two_sided)}


def build_banked_summary() -> dict:
    """Extract the gate's metric set from the newest banked artifact of
    each family.  Families without a banked artifact simply contribute no
    metrics — the gate never invents a baseline."""
    metrics = {}

    # -- headline training throughput (driver record) -----------------------
    p = _newest("BENCH_r*.json")
    if p:
        d = _load(p).get("parsed") or {}
        if d.get("value") is not None:
            metrics["bench.samples_per_sec_per_chip"] = _metric(
                d["value"], os.path.basename(p), tol=TOL_THROUGHPUT)

    # -- collective / wire path ---------------------------------------------
    p = (_newest("artifacts/collective_tpu_*.json")
         or _newest("COLLECTIVE_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        for key in COLLECTIVE_GATE_KEYS:
            if d.get(key):
                tol = (TOL_LOOPBACK if key == "fused_ring_loopback_gbps"
                       else TOL_RATE)
                metrics[collective_metric(key)] = _metric(d[key], src,
                                                          tol=tol)
        for row in d.get("sweep") or d.get("mesh_sweep") or []:
            for arm in SWEEP_GATE_ARMS:
                v = row.get(f"{arm}_gbps")
                if v:
                    metrics[sweep_metric(row["size_mb"], arm)] = \
                        _metric(v, src)

    # -- codec matrix --------------------------------------------------------
    p = (_newest("artifacts/codec_bench_*.json")
         or _newest("CODEC_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        for row in d.get("rows", []):
            base = f"codec_matrix.{row['codec']}.{row['class']}"
            for stage in ("roundtrip", "encode", "decode"):
                v = row.get(f"{stage}_gbps")
                if v:
                    metrics[f"{base}.{stage}_gbps"] = _metric(v, src)

    # -- fused-optimizer bench ----------------------------------------------
    p = (_newest("artifacts/fused_opt_bench_*.json")
         or _newest("FUSED_OPT_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (FUSED_OPT_BYTE_KEYS if d.get("dryrun")
                else FUSED_OPT_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:       # 0 is a real value (sgd moment bytes)
                    continue
                if key == "speedup_vs_ring_then_opt":
                    m = _metric(v, src, tol=TOL_FUSED_OPT_TIME)
                elif key in FUSED_OPT_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                else:
                    m = _metric(v, src, higher=False,
                                tol=TOL_FUSED_OPT_TIME)
                metrics[fused_opt_metric(row["kind"], key)] = m

    # -- reshard MTTR bench -------------------------------------------------
    p = (_newest("artifacts/reshard_bench_*.json")
         or _newest("RESHARD_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (RESHARD_BYTE_KEYS if d.get("dryrun")
                else RESHARD_BYTE_KEYS + RESHARD_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in RESHARD_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                elif key == "mttr_speedup":
                    m = _metric(v, src, tol=TOL_RESHARD_TIME)
                else:
                    m = _metric(v, src, higher=False,
                                tol=TOL_RESHARD_TIME)
                metrics[reshard_metric(row["trainer"], row["codec"],
                                       key)] = m

    # -- autotune matrix ------------------------------------------------------
    p = (_newest("artifacts/tune_bench_*.json")
         or _newest("TUNE_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (TUNE_BYTE_KEYS if d.get("dryrun")
                else TUNE_BYTE_KEYS + TUNE_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in TUNE_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                else:
                    m = _metric(v, src, higher=False, tol=TOL_TUNE_TIME)
                metrics[tune_metric(row["regime"], key)] = m

    # -- serving curve --------------------------------------------------------
    p = (_newest("artifacts/serve_bench_*.json")
         or _newest("SERVE_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (SERVE_BYTE_KEYS if d.get("dryrun")
                else SERVE_BYTE_KEYS + SERVE_GATE_KEYS)
        for row in d.get("rows", []):
            impl = row.get("attend_impl", "reference")
            prefix = "" if impl == "reference" else f"{impl}."
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in SERVE_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                elif key == "throughput_tok_s":
                    m = _metric(v, src, tol=TOL_SERVE_TIME)
                else:
                    m = _metric(v, src, higher=False, tol=TOL_SERVE_TIME)
                metrics[serve_metric(row["max_reqs"], prefix + key)] = m
        att = d.get("attend")
        if att:
            for key in SERVE_ATTEND_KEYS:
                v = att.get(key)
                if v is None:
                    continue
                metrics[f"serve.attend.{key}"] = _metric(
                    v, src, tol=TOL_EXACT, two_sided=True)

    # -- fleet (replica-kill / disaggregation) --------------------------------
    p = (_newest("artifacts/fleet_bench_*.json")
         or _newest("FLEET_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (FLEET_BYTE_KEYS if d.get("dryrun")
                else FLEET_BYTE_KEYS + FLEET_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in FLEET_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                elif key == "throughput_tok_s":
                    m = _metric(v, src, tol=TOL_FLEET_TIME)
                else:
                    m = _metric(v, src, higher=False,
                                tol=TOL_FLEET_TIME)
                metrics[fleet_metric(row["scenario"], key)] = m
            # the SLO observatory block: windowed tick-domain
            # percentiles, pressure peaks and the autoscaler's decision
            # ledger are deterministic per seed on ANY machine (request
            # milestones are fleet-tick-stamped), so every value pins
            # two-sided-exact even on dryrun rows — a changed decision
            # count or shifted p99 IS a controller/scheduler change
            for key, v in sorted((row.get("slo") or {}).items()):
                if v is None or isinstance(v, str):
                    continue
                metrics[fleet_slo_metric(row["scenario"], key)] = \
                    _metric(float(v), src, tol=TOL_EXACT,
                            two_sided=True)

    # -- wire integrity (checksum overhead + trip->recovery) ------------------
    p = (_newest("artifacts/integrity_bench_*.json")
         or _newest("INTEGRITY_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (INTEGRITY_BYTE_KEYS if d.get("dryrun")
                else INTEGRITY_BYTE_KEYS + INTEGRITY_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in INTEGRITY_BYTE_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                else:
                    m = _metric(v, src, higher=False,
                                tol=TOL_INTEGRITY_TIME)
                metrics[integrity_metric(row["route"], key)] = m
        for row in d.get("mttr_rows", []):
            name = row["site"] + (f".{row['variant']}"
                                  if row.get("variant") else "")
            for key in INTEGRITY_MTTR_EXACT:
                v = row.get(key)
                if v is None:
                    continue
                metrics[integrity_metric(name, key)] = _metric(
                    v, src, tol=TOL_EXACT, two_sided=True)
            if not d.get("dryrun") and row.get("mttr_s") is not None:
                metrics[integrity_metric(name, "mttr_s")] = _metric(
                    row["mttr_s"], src, higher=False,
                    tol=TOL_INTEGRITY_TIME)

    # -- adaptive tuning (drift detection -> recompile-free switch) -----------
    p = (_newest("artifacts/adapt_bench_*.json")
         or _newest("ADAPT_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (ADAPT_EXACT_KEYS if d.get("dryrun")
                else ADAPT_EXACT_KEYS + ADAPT_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in ADAPT_EXACT_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                else:
                    m = _metric(v, src, higher=False, tol=TOL_ADAPT_TIME)
                metrics[adapt_metric(row["scenario"], key)] = m

    # -- durable-state integrity (audited checkpoint plane) -------------------
    p = (_newest("artifacts/ckpt_bench_*.json")
         or _newest("CKPT_BENCH_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        keys = (CKPT_EXACT_KEYS if d.get("dryrun")
                else CKPT_EXACT_KEYS + CKPT_GATE_KEYS)
        for row in d.get("rows", []):
            for key in keys:
                v = row.get(key)
                if v is None:
                    continue
                if key in CKPT_EXACT_KEYS:
                    m = _metric(v, src, tol=TOL_EXACT, two_sided=True)
                else:
                    m = _metric(v, src, higher=False, tol=TOL_CKPT_TIME)
                metrics[ckpt_metric(row["row"], key)] = m

    # -- graftmc envelope (protocol-verification coverage) --------------------
    p = (_newest("artifacts/mc_envelope_*.json")
         or _newest("MC_ENVELOPE_r*.json"))
    if p:
        d = _load(p)
        src = os.path.relpath(p, ROOT)
        for row in d.get("routes", []):
            for key in MC_ROUTE_EXACT:
                v = row.get(key)
                if v is None:
                    continue
                metrics[mc_metric(row["route"], key)] = _metric(
                    v, src, tol=TOL_EXACT, two_sided=True)
        for cmp_row in d.get("compare", []):
            cell = "x".join(str(c) for c in cmp_row.get("cell", []))
            v = cmp_row.get("reduction")
            if v:
                metrics[f"mc.compare.{cell}.reduction"] = _metric(
                    v, src, tol=TOL_MC_REDUCTION)
        if d.get("total_cells"):
            metrics["mc.total_cells"] = _metric(
                d["total_cells"], src, tol=TOL_EXACT, two_sided=True)
        if d.get("wall_s"):
            metrics["mc.wall_s"] = _metric(d["wall_s"], src,
                                           higher=False, tol=TOL_MC_TIME)

    return {"schema_version": SCHEMA_VERSION, "metrics": metrics}


def _normalize_candidate(d: dict, banked: dict) -> dict:
    """Accept the full schema or a flat {name: value} mapping (direction
    and tolerance then inherited from the banked metric)."""
    if "metrics" in d:
        ver = d.get("schema_version")
        if ver != SCHEMA_VERSION:
            raise ValueError(f"candidate summary schema v{ver!r} != "
                             f"supported v{SCHEMA_VERSION}")
        return {k: float(v["value"]) if isinstance(v, dict) else float(v)
                for k, v in d["metrics"].items()}
    return {k: float(v) for k, v in d.items()
            if isinstance(v, (int, float))}


def gate(candidate: dict, banked: dict,
         threshold_scale: float = 1.0) -> dict:
    """Compare candidate values against banked metrics.  Returns the
    verdict dict: regressions (beyond tol), improvements, compared /
    missing accounting, ok flag."""
    cand = _normalize_candidate(candidate, banked)
    regressions, improvements, compared = [], [], 0
    for name, spec in banked["metrics"].items():
        if name not in cand:
            continue
        compared += 1
        ref, got = spec["value"], cand[name]
        tol = spec["rel_tol"] * threshold_scale
        if spec.get("two_sided"):
            # exact accounting: any drift beyond tol fails (ref == 0
            # degenerates to "any nonzero value fails")
            bad = abs(got - ref) > abs(ref) * tol
            better = False
        elif spec["higher_is_better"]:
            bad = got < ref * (1.0 - tol)
            better = got > ref * (1.0 + tol)
        else:
            bad = got > ref * (1.0 + tol)
            better = got < ref * (1.0 - tol)
        entry = {"metric": name, "banked": ref, "got": got,
                 "rel_change": round((got - ref) / ref, 4) if ref else None,
                 "rel_tol": tol, "source": spec["source"]}
        if bad:
            regressions.append(entry)
        elif better:
            improvements.append(entry)
    return {"schema_version": SCHEMA_VERSION,
            "ok": not regressions,
            "compared": compared,
            "banked_total": len(banked["metrics"]),
            "candidate_total": len(cand),
            "missing_from_candidate": len(banked["metrics"]) - compared,
            "regressions": regressions,
            "improvements": improvements}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--summary", default=None,
                    help="candidate telemetry summary JSON to gate "
                         "(default: the banked summary itself — a "
                         "self-diff that must pass trivially)")
    ap.add_argument("--write-summary", metavar="FILE", default=None,
                    help="write the banked summary to FILE and exit 0 "
                         "unless gating also fails")
    ap.add_argument("--save-artifact", action="store_true",
                    help="bank the summary + verdict under artifacts/ "
                         "(obs_summary_*.json)")
    ap.add_argument("--threshold-scale", type=float, default=1.0,
                    help="multiply every per-metric tolerance (e.g. 0.5 "
                         "for a stricter manual check)")
    args = ap.parse_args(argv)

    banked = build_banked_summary()
    if not banked["metrics"]:
        print(json.dumps({"ok": False,
                          "error": "no banked artifacts to gate against"}))
        return 1
    if args.write_summary:
        with open(args.write_summary, "w") as f:
            json.dump(banked, f, indent=1)
    candidate = _load(args.summary) if args.summary else banked
    verdict = gate(candidate, banked,
                   threshold_scale=args.threshold_scale)
    verdict["mode"] = "candidate" if args.summary else "self"
    if args.save_artifact:
        from bench_common import save_artifact
        save_artifact("obs_summary", {"summary": banked,
                                      "verdict": verdict})
    print(json.dumps(verdict, indent=1))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
