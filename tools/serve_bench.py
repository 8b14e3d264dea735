#!/usr/bin/env python
"""Serving bench: throughput-vs-latency over the paged continuous-batching
engine, with the contiguous-cache HBM comparison and the recompile gate.

One fixed request trace (deterministic: seeded prompts, all submitted at
t0) served at increasing concurrency (`max_reqs` = decode slots): more
slots batch more decode work per tick (throughput up) while each request
shares the tick with more peers (TTFT/latency up) — the throughput-vs-
latency CURVE a serving SLO is negotiated on.  Per row the bench banks:

  - request latency stats (TTFT / TPOT / p95) + tokens/s throughput
  - EXACT byte accounting: the paged pool + page table vs what
    `init_cache` would zero-fill up front for the same concurrency at
    max_seq — the measured version of the `[B, kv, max_seq, hd]`
    up-front HBM cost
  - pool utilization (peak pages in use / usable pages) and evictions
  - ``recompiles_steady`` — MUST be 0: the whole schedule (admissions,
    evictions, page churn) runs on the warmup traces (graftlint J10)
  - token-exactness: every request's greedy continuation equals the
    isolated `generate()` reference (the correctness floor under
    batching/eviction)
  - the KERNEL AXIS: every concurrency point runs under both
    ``attend_impl`` values (gathered-view reference and the Pallas
    paged gather-attend kernel), each row carrying its MODELED decode
    roofline (bytes/token, hbm_bound_frac, TPOT HBM floor — see
    `decode_roofline`); the artifact's ``attend`` block summarizes the
    modeled bytes/token reduction at the top concurrency

CPU rows are dryrun-class: latencies carry oversubscription noise, so
`make obs-gate` holds dryrun artifacts only to the exact byte accounting
and the zero-recompile fact (tools/obs_gate.py SERVE_BYTE_KEYS); re-run
on a TPU surface for a gated latency verdict.

    python tools/serve_bench.py            # bank artifacts/serve_bench_*
    make serve-bench ROUND=r10             # + snapshot SERVE_BENCH_r10.json
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from bench_common import (cpu_env, hbm_peak, is_tpu_platform, log,  # noqa: E402
                          save_artifact)

# CPU-mesh battery: re-exec once with the virtual CPU environment before
# jax is imported (same discipline as chaos_bench).
if os.environ.get("_SERVE_BENCH_REEXEC") != "1":
    env = cpu_env(8)
    env["_SERVE_BENCH_REEXEC"] = "1"
    os.execve(sys.executable,
              [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
              env)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fpga_ai_nic_tpu.models import llama, llama_decode as dec  # noqa: E402
from fpga_ai_nic_tpu.serve import ServeConfig, ServeEngine  # noqa: E402

CFG = llama.LlamaConfig.tiny()
SEED = 17
N_REQUESTS = 18
MAX_NEW = 8
PAGE_SIZE = 8
PAGES_PER_SEQ = 8                      # max_seq 64: the ADDRESSABLE bound
CONCURRENCIES = (1, 2, 4, 8)
# pool provisioning per slot, in pages: the workload's worst request
# (prompt 16 + 8 new = 24 positions) needs 3 pages, so 3/slot + slack
# serves the whole trace eviction-free — while init_cache would zero-fill
# the full max_seq=64 extent per slot.  THAT gap is the paging story.
POOL_PAGES_PER_SLOT = 3
# the chip the modeled HBM floor is held against (bench_common.CHIP_PEAKS)
MODELED_CHIP = "TPU v5 lite"


def _workload():
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, CFG.vocab, int(n)).astype(np.int32)
            for n in rng.integers(4, 17, N_REQUESTS)]


def _reference(params, prompts, max_new=MAX_NEW):
    """Greedy per-request reference continuations (isolated generate) —
    THE token-exactness reference for the curve and the fleet bench
    alike (one definition, so an eos/reference fix cannot skew one
    verdict and not the other)."""
    out = []
    for p in prompts:
        full = np.asarray(dec.generate(
            params, jnp.asarray(p)[None], max_new, CFG))[0]
        out.append(full[len(p):].tolist())
    return out


def decode_roofline(attend_impl: str, max_reqs: int, prompts) -> dict:
    """MODELED decode-step HBM traffic — deterministic, computed from
    the workload's schedule, never measured (CPU rows cannot measure
    HBM; the model is what obs-gate pins exactly and PERF.md reports).

    Model: each decode step re-reads the weights once and every active
    slot re-reads its K+V across all layers.  The impls differ ONLY in
    the per-slot KV extent:

      reference — the gathered ``[R, kv, P*page_size, hd]`` view spans
        the ALLOCATED table width (max_pages_per_seq pages) regardless
        of how much KV is live; the gather builds + reads it per layer.
      pallas    — the kernel DMAs only LIVE pages: ceil(ctx/page_size)
        pages at context length ctx, averaged exactly over every decode
        position of the seeded trace (all slots assumed occupied — the
        saturated-curve model).

    ``hbm_bound_frac`` = kv_bytes_per_step / (kv + weight bytes): the
    fraction of the step's HBM floor that is KV traffic — the part the
    kernel axis shrinks.  ``tpot_hbm_floor_s`` divides the step bytes by
    `bench_common.hbm_peak` of the v5e — this battery runs on the CPU
    mesh, so the chip the bytes are held against is named, not detected."""
    dt = jnp.dtype(CFG.dtype).itemsize
    per_pos = 2 * CFG.n_kv_heads * CFG.head_dim * dt * CFG.n_layers
    spans = []
    for p in prompts:
        for t in range(1, MAX_NEW + 1):
            ctx = int(len(p)) + t
            spans.append(-(-ctx // PAGE_SIZE) * PAGE_SIZE)
    live_mean = float(np.mean(spans))
    alloc = PAGES_PER_SEQ * PAGE_SIZE
    slot_pos = alloc if attend_impl == "reference" else live_mean
    kv_step = max_reqs * slot_pos * per_pos
    weight = llama.num_params(CFG) * dt
    step = kv_step + weight
    peak, label = hbm_peak(MODELED_CHIP)
    return {
        "kv_bytes_per_step": int(round(kv_step)),
        "weight_read_bytes": int(weight),
        "bytes_per_token": int(round(step / max_reqs)),
        "hbm_bound_frac": round(kv_step / step, 4),
        "tpot_hbm_floor_s": round(step / peak, 9),
        "hbm_peak_label": label,
    }


def run_row(params, prompts, ref, max_reqs: int,
            attend_impl: str = "reference") -> dict:
    t0 = time.time()
    # pool sized to the WORKING SET (see POOL_PAGES_PER_SLOT), not the
    # addressable worst case init_cache must provision
    n_pages = max_reqs * POOL_PAGES_PER_SLOT + 3
    scfg = ServeConfig(max_reqs=max_reqs, page_size=PAGE_SIZE,
                       n_pages=n_pages, max_pages_per_seq=PAGES_PER_SEQ,
                       prefill_chunk=PAGE_SIZE)
    eng = ServeEngine(params, CFG, scfg, attend_impl=attend_impl)
    reqs = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    s = eng.run()
    exact = all(q.generated == want for q, want in zip(reqs, ref))
    r = s["requests"]
    row = {
        "max_reqs": max_reqs,
        "attend_impl": attend_impl,
        "decode_roofline": decode_roofline(attend_impl, max_reqs,
                                           prompts),
        "n_requests": len(prompts),
        "steps_total": s["ticks"],
        "throughput_tok_s": s["throughput_tok_s"],
        "ttft_mean_s": r.get("ttft_mean_s"),
        "ttft_p95_s": r.get("ttft_p95_s"),
        "tpot_mean_s": r.get("tpot_mean_s"),
        "latency_p95_s": r.get("latency_p95_s"),
        "queue_wait_mean_s": r.get("queue_wait_mean_s"),
        "pages_in_use_peak": s["pages_in_use_peak"],
        "page_util_peak": s["page_util_peak"],
        "evictions": s["evictions"],
        "pool_bytes": s["serve"]["pool_bytes"],
        "page_table_bytes": s["serve"]["page_table_bytes"],
        "contiguous_cache_bytes": s["serve"]["contiguous_cache_bytes"],
        "hbm_vs_contiguous": round(
            s["serve"]["contiguous_cache_bytes"]
            / s["serve"]["pool_bytes"], 3),
        "recompiles_steady": s["recompiles_steady"],
        "trace_counts": s["trace_counts"],
        "token_exact": exact,
        "completed": s["completed"],
        "wall_s": round(time.time() - t0, 2),
    }
    row["ok"] = bool(exact and s["completed"] == len(prompts)
                     and s["recompiles_steady"] == 0)
    return row


# -- fleet bench (make fleet-bench / slo-bench -> FLEET_BENCH artifact) ------
#
# Six scenarios over seeded `serve.traffic` workloads.  Two run the
# fixed 1-prefill/2-decode fleet without a controller — `steady` (the
# disaggregated pipeline, fault-free, token-exact vs isolated generate)
# and `replica_kill` (a decode replica preempted mid-run; every
# surviving stream must be BYTE-identical to the steady fleet run, with
# zero replay — the handoff tier).  Four close the loop: a
# `serve.autoscale.Autoscaler` reads the fleet's windowed SLO metrics
# every tick and drives scale-out / role rebalance / admission shedding
# against `spike`, `diurnal`, `thundering_herd` and `chaos` (spike +
# replica kill) traffic.
#
# Every latency the rows gate lives in the FLEET-TICK domain (request
# milestones are tick-stamped by the fleet's SLO observatory), so a
# seeded run banks bit-identical percentiles and decision counts on CPU
# dryrun and TPU alike: obs-gate pins the per-row `slo` block exactly
# (fleet.slo.* keys, two-sided) next to the exact byte accounting
# (handoff_wire_bytes / handoffs / replays / recoveries / recompiles).
# Wall-clock latencies stay dryrun-class — MTTR and TTFT-seconds gate
# on a TPU surface only.

FLEET_N_REQUESTS = 12
FLEET_KILL_TICK = 11                   # steady traffic has live decode work
#                                        mid-flight here (migration needs a
#                                        victim that actually holds KV)
CHAOS_KILL_TICK = 18                   # mid-spike: scale-out then a kill
SPIKE_TICK = 12
SPIKE_N = 16
# tick-domain SLO budget for the closed-loop rows: windowed p99 TTFT
# must stay under this even across the spike/herd/kill — the semantic
# claim; the exact banked value is what obs-gate pins
TTFT_P99_BUDGET_TICKS = 40.0


def _fleet_scfg(max_reqs=8):
    # per-replica slots/pages provisioned so ONE decode survivor can
    # absorb the victim's whole live set (the zero-replay bar): 8 slots
    # and 3 pages/slot + slack per replica.  The closed-loop tier runs
    # max_reqs=4 — tighter slots make offered load visibly BACKLOG
    # (queue_depth) instead of soaking into batch slack, which is the
    # signal the autoscaler's CUSUM integrates
    from fpga_ai_nic_tpu.serve import ServeConfig
    return ServeConfig(max_reqs=max_reqs, page_size=PAGE_SIZE,
                       n_pages=28, max_pages_per_seq=PAGES_PER_SEQ,
                       prefill_chunk=PAGE_SIZE)


def _traffic_reference(params, wl):
    """Isolated-generate reference per traffic request (its OWN max_new
    — traffic draws heavy-tailed lengths, unlike the fixed-max_new
    curve workload)."""
    out = []
    for req, p in zip(wl.requests, wl.prompts(CFG.vocab)):
        full = np.asarray(dec.generate(
            params, jnp.asarray(p)[None], req.max_new, CFG))[0]
        out.append(full[len(p):].tolist())
    return out


def _drive_fleet(fleet, wl, *, autoscaler=None, max_ticks=600,
                 drain_ticks=0):
    """Tick-driven serve loop: submit each traffic request on its
    arrival tick, tick the fleet, then let the autoscaler observe —
    the closed loop the bench gates.  ``drain_ticks`` keeps ticking an
    idle fleet after the last completion so the controller's scale-IN
    side (sustained-idle CUSUM) is witnessed too.  Returns requests in
    uid order."""
    by_tick = wl.arrivals_by_tick()
    prompts = wl.prompts(CFG.vocab)
    last_arrival = max(by_tick) if by_tick else 0
    reqs = {}
    drain = None
    while True:
        for tr in by_tick.get(fleet.ticks, ()):
            reqs[tr.uid] = fleet.submit(prompts[tr.uid - 1],
                                        max_new=tr.max_new,
                                        tenant=tr.tenant)
        fleet.tick()
        if autoscaler is not None:
            autoscaler.observe_tick()
        if (drain is None and fleet.ticks > last_arrival
                and not fleet._arrivals
                and all(r.done for r in reqs.values())):
            drain = drain_ticks
        if drain is not None:
            if drain <= 0:
                return [reqs[u] for u in sorted(reqs)]
            drain -= 1
        if fleet.ticks >= max_ticks:
            raise RuntimeError(
                f"fleet drive exceeded {max_ticks} ticks with "
                f"{sum(1 for r in reqs.values() if not r.done)} open")


def _fleet_serve(params, wl, plan, *, n_prefill=1, n_decode=2,
                 max_reqs=8, autoscale=False, drain_ticks=0):
    from fpga_ai_nic_tpu.runtime import chaos
    from fpga_ai_nic_tpu.serve import (Autoscaler, FleetConfig,
                                       ServeFleet)
    fleet = ServeFleet(params, CFG, _fleet_scfg(max_reqs),
                       FleetConfig(n_prefill=n_prefill,
                                   n_decode=n_decode), chaos=plan)
    scaler = (Autoscaler(fleet, fleet.slo,
                         events=fleet.profiler.events)
              if autoscale else None)
    with chaos.activate(plan):
        reqs = _drive_fleet(fleet, wl, autoscaler=scaler,
                            drain_ticks=drain_ticks)
    return fleet, reqs, fleet.summary(), scaler


def _slo_block(s, reqs, scaler=None, *, spike_tick=None) -> dict:
    """The deterministic (tick-domain) SLO sub-dict obs-gate pins
    exactly: windowed percentiles, pressure peaks, token-loss and the
    controller's decision ledger."""
    w = s["slo"]["windows"]
    g = s["slo"]["gauges"]
    out = {
        "ticks": s["ticks"],
        "tokens_lost": (sum(r.max_new for r in reqs)
                        - s["tokens_out"]),
        "ttft_p50_ticks": w["ttft"]["p50"],
        "ttft_p95_ticks": w["ttft"]["p95"],
        "ttft_p99_ticks": w["ttft"]["p99"],
        "queue_wait_p95_ticks": w["queue_wait"]["p95"],
        "tpot_p95_ticks": w["tpot"]["p95"],
        "queue_depth_peak": g["queue_depth"]["peak"],
        "pages_in_use_peak": g["pages_in_use"]["peak"],
    }
    if scaler is not None:
        out.update(scaler.summary())
        if spike_tick is not None and out["first_scale_out_tick"] >= 0:
            out["scale_latency_ticks"] = (out["first_scale_out_tick"]
                                          - spike_tick)
    return out


def _fleet_row(scenario, s, reqs, reference, t0, *, scaler=None,
               spike_tick=None, expect_kills=0,
               allow_replays=False) -> dict:
    token_exact = all(list(q.generated) == want
                      for q, want in zip(reqs, reference))
    r = s["requests"]
    slo = _slo_block(s, reqs, scaler, spike_tick=spike_tick)
    row = {
        "scenario": scenario,
        "n_requests": s["n_requests"],
        "completed": s["completed"],
        "throughput_tok_s": s["throughput_tok_s"],
        "ttft_p95_s": r.get("ttft_p95_s"),
        "latency_p95_s": r.get("latency_p95_s"),
        "handoffs": s["handoffs"],
        "handoff_wire_bytes": s["handoff_wire_bytes"],
        "handoff_host_bytes": s["handoff_host_bytes"],
        "fleet_replays": s["fleet_replays"],
        "serve_recoveries": s["serve_recoveries"],
        "kills": s["kills"],
        "grows": s["grows"],
        "fleet_mttr_s": round(s["recovery"]["mttr_mean_s"], 4),
        "recompiles_steady": s["recompiles_steady"],
        "survivors": sum(1 for x in s["replicas"] if x["alive"]),
        "token_exact": token_exact,
        "slo": slo,
        "wall_s": round(time.time() - t0, 2),
    }
    # kills nets out controller-driven drains: a scale-in IS a
    # kill_replica call, but a planned one, not the chaos preemption
    # expect_kills counts
    ok = (token_exact
          and s["completed"] == s["n_requests"]
          and slo["tokens_lost"] == 0
          and s["recompiles_steady"] == 0
          and s["serve_recoveries"] == 0
          and s["kills"] - slo.get("scale_ins", 0) == expect_kills
          and (allow_replays or s["fleet_replays"] == 0))
    if scaler is not None:
        # the closed-loop bar: the controller must have acted, and the
        # windowed tail must have been restored within budget
        ok = (ok and slo["scale_outs"] >= 1
              and slo["ttft_p99_ticks"] is not None
              and slo["ttft_p99_ticks"] <= TTFT_P99_BUDGET_TICKS)
    row["ok"] = bool(ok)
    return row


def run_fleet_bench(args) -> int:
    from fpga_ai_nic_tpu.runtime import chaos
    from fpga_ai_nic_tpu.serve import traffic
    plat = jax.devices()[0].platform
    log(f"platform={plat} devices={len(jax.devices())} bench=fleet")
    params = llama.init(jax.random.PRNGKey(0), CFG)

    workloads = {
        # interval 1.0: dense enough that the kill tick catches live
        # decode work mid-flight (the migration claim needs a victim
        # that actually holds KV)
        "steady": traffic.generate(
            traffic.steady_config(FLEET_N_REQUESTS, SEED,
                                  base_interval_ticks=1.0)),
        "spike": traffic.generate(
            traffic.spike_config(SPIKE_N, SEED, spike_tick=SPIKE_TICK)),
        # one full cycle: the peak overloads a 1-decode fleet (scale
        # OUT) and the trough idles the grown fleet (scale IN)
        "diurnal": traffic.generate(
            traffic.diurnal_config(SPIKE_N, SEED, period=24,
                                   amplitude=0.9,
                                   base_interval_ticks=1.0)),
        "thundering_herd": traffic.generate(
            traffic.thundering_herd_config(FLEET_N_REQUESTS, SEED)),
    }
    refs = {}
    for name, wl in workloads.items():
        log(f"phase=reference scenario={name} n={len(wl)}")
        refs[name] = _traffic_reference(params, wl)

    rows = []

    # fixed-fleet tier: steady + replica_kill over the SAME workload
    t0 = time.time()
    _f, reqs, s, _ = _fleet_serve(params, workloads["steady"], None)
    steady = _fleet_row("steady", s, reqs, refs["steady"], t0)
    # the kill row's reference is the steady FLEET streams
    # (byte-identity is the migration claim)
    fleet_ref = [list(q.generated) for q in reqs]
    rows.append(steady)

    t0 = time.time()
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("preemption", "fleet.membership",
                         step=FLEET_KILL_TICK)], seed=SEED)
    _f2, reqs2, s2, _ = _fleet_serve(params, workloads["steady"], plan)
    kill = _fleet_row("replica_kill", s2, reqs2, fleet_ref, t0,
                      expect_kills=1)
    kill["chaos_fired"] = len(plan.fired)
    kill["ok"] = bool(kill["ok"] and len(plan.fired) == 1
                      and s2["handoffs"] > s["handoffs"])
    rows.append(kill)

    # closed-loop tier: 1 prefill + 1 decode + spares, autoscaler on.
    # diurnal drains 24 idle ticks past the last completion so its
    # trough trips the scale-IN side too (peak grows, trough shrinks —
    # the full cycle)
    for name, spike_tick, kill_tick, drain in (
            ("spike", SPIKE_TICK, None, 0),
            ("diurnal", None, None, 24),
            ("thundering_herd", 0, None, 0),
            ("chaos", SPIKE_TICK, CHAOS_KILL_TICK, 0)):
        wl = workloads.get(name) or workloads["spike"]
        ref = refs.get(name) or refs["spike"]
        t0 = time.time()
        cplan = None
        if kill_tick is not None:
            cplan = chaos.FaultPlan(
                [chaos.FaultSpec("preemption", "fleet.membership",
                                 step=kill_tick)], seed=SEED)
        _fl, qs, ss, scaler = _fleet_serve(
            params, wl, cplan, n_prefill=1, n_decode=1, max_reqs=4,
            autoscale=True, drain_ticks=drain)
        row = _fleet_row(name, ss, qs, ref, t0, scaler=scaler,
                         spike_tick=spike_tick,
                         expect_kills=0 if kill_tick is None else 1,
                         allow_replays=kill_tick is not None)
        if cplan is not None:
            row["chaos_fired"] = len(cplan.fired)
            row["ok"] = bool(row["ok"] and len(cplan.fired) == 1)
        if drain:
            row["ok"] = bool(row["ok"] and row["slo"]["scale_ins"] >= 1)
        rows.append(row)

    for row in rows:
        slo = row["slo"]
        log(f"row {row['scenario']}: ticks={slo['ticks']} "
            f"ttft_p99={slo['ttft_p99_ticks']}t "
            f"lost={slo['tokens_lost']} grows={row['grows']} "
            f"handoffs={row['handoffs']} replays={row['fleet_replays']} "
            f"{'ok' if row['ok'] else 'FAILED'} ({row['wall_s']}s)")

    result = {
        "bench": "fleet",
        "platform": plat,
        "n_devices": len(jax.devices()),
        # wall-clock latencies are dryrun-class on CPU; the per-row
        # `slo` block is tick-domain and gates EXACTLY either way
        "dryrun": not is_tpu_platform(plat),
        "model": {"dim": CFG.dim, "n_layers": CFG.n_layers,
                  "n_heads": CFG.n_heads, "n_kv_heads": CFG.n_kv_heads,
                  "vocab": CFG.vocab, "dtype": CFG.dtype},
        "fleet": {"n_prefill": 1, "n_decode": 2,
                  "kill_tick": FLEET_KILL_TICK,
                  "chaos_kill_tick": CHAOS_KILL_TICK,
                  "ttft_p99_budget_ticks": TTFT_P99_BUDGET_TICKS},
        "workload": {name: wl.summary() | {"fingerprint": wl.fingerprint()}
                     for name, wl in workloads.items()},
        "rows": rows,
        "ok": all(r["ok"] for r in rows),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if not args.no_artifact:
        save_artifact("fleet_bench", result)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("rows", "workload")} |
                     {"rows_ok": sum(r["ok"] for r in rows),
                      "rows_total": len(rows)}, indent=1))
    return 0 if result["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="also write the result JSON to this path")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip the artifacts/ evidence write")
    ap.add_argument("--fleet", action="store_true",
                    help="run the FLEET bench (disaggregated steady row "
                         "+ replica-kill row) instead of the "
                         "concurrency curve; banked as the FLEET_BENCH "
                         "artifact by `make fleet-bench`")
    args = ap.parse_args()

    if args.fleet:
        return run_fleet_bench(args)

    plat = jax.devices()[0].platform
    log(f"platform={plat} devices={len(jax.devices())}")
    params = llama.init(jax.random.PRNGKey(0), CFG)
    prompts = _workload()
    log(f"phase=reference n={len(prompts)} max_new={MAX_NEW}")
    ref = _reference(params, prompts)

    rows = []
    for c in CONCURRENCIES:
        # the kernel axis: the same curve point under both attend impls
        # — token-exactness pins the kernel to the reference on every
        # row, and the modeled roofline quantifies the bytes story
        for impl in ("reference", "pallas"):
            row = run_row(params, prompts, ref, c, attend_impl=impl)
            rl = row["decode_roofline"]
            log(f"row max_reqs={c} attend={impl}: "
                f"{row['throughput_tok_s']} tok/s "
                f"ttft_p95={row['ttft_p95_s']}s evict={row['evictions']} "
                f"recompiles={row['recompiles_steady']} "
                f"B/tok={rl['bytes_per_token']} "
                f"hbm_frac={rl['hbm_bound_frac']} "
                f"{'ok' if row['ok'] else 'FAILED'} ({row['wall_s']}s)")
            rows.append(row)

    top = rows[len(rows) - 1]
    result = {
        "bench": "serve",
        "platform": plat,
        "n_devices": len(jax.devices()),
        # CPU rows are dryrun-class: obs-gate holds them only to the
        # exact byte accounting + zero recompiles (SERVE_BYTE_KEYS)
        "dryrun": not is_tpu_platform(plat),
        "model": {"dim": CFG.dim, "n_layers": CFG.n_layers,
                  "n_heads": CFG.n_heads, "n_kv_heads": CFG.n_kv_heads,
                  "vocab": CFG.vocab, "dtype": CFG.dtype},
        "workload": {"n_requests": N_REQUESTS, "max_new": MAX_NEW,
                     "prompt_lens": [int(p.shape[0]) for p in prompts],
                     "page_size": PAGE_SIZE,
                     "max_pages_per_seq": PAGES_PER_SEQ,
                     "seed": SEED},
        "rows": rows,
        # the init_cache comparison at the curve's top concurrency: what
        # the contiguous [B, kv, max_seq, hd] zero-fill would cost vs
        # the shared pool actually allocated
        "init_cache_comparison": {
            "max_reqs": top["max_reqs"],
            "contiguous_cache_bytes": top["contiguous_cache_bytes"],
            "paged_pool_bytes": top["pool_bytes"],
            "page_table_bytes": top["page_table_bytes"],
            "savings_ratio": top["hbm_vs_contiguous"],
        },
        "ok": all(r["ok"] for r in rows),
    }
    # the kernel axis at the curve's top concurrency: the modeled
    # decode roofline of the gathered view vs the paged kernel — the
    # numbers obs-gate pins exactly (serve.attend.*)
    by = {(r["max_reqs"], r["attend_impl"]): r["decode_roofline"]
          for r in rows}
    c_top = CONCURRENCIES[len(CONCURRENCIES) - 1]
    rl_ref = by[(c_top, "reference")]
    rl_pal = by[(c_top, "pallas")]
    result["attend"] = {
        "modeled": True,
        "max_reqs": c_top,
        "page_size": PAGE_SIZE,
        "hbm_peak_label": rl_ref["hbm_peak_label"],
        "reference_bytes_per_token": rl_ref["bytes_per_token"],
        "pallas_bytes_per_token": rl_pal["bytes_per_token"],
        "bytes_per_token_reduction": round(
            rl_ref["bytes_per_token"] / rl_pal["bytes_per_token"], 3),
        "reference_hbm_bound_frac": rl_ref["hbm_bound_frac"],
        "pallas_hbm_bound_frac": rl_pal["hbm_bound_frac"],
        "kv_bytes_per_step_reduction": round(
            rl_ref["kv_bytes_per_step"] / rl_pal["kv_bytes_per_step"],
            3),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if not args.no_artifact:
        save_artifact("serve_bench", result)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"} |
                     {"rows_ok": sum(r["ok"] for r in rows),
                      "rows_total": len(rows)}, indent=1))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
