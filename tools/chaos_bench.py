#!/usr/bin/env python
"""Chaos bench: the fault matrix, end to end, with a JSON verdict per cell.

The reference's documented failure mode is a nondeterministic infinite
hang with no recovery path — OPAE reads/writes that never complete
(hw/README:3-5), a `kill_syn_e0` kill CSR that is declared but never
wired (hw/all_reduce.sv:83), and "full shell reset" as the remedy
(sw/mlp_mpi_example_f32.cpp:54-57).  This driver is the standing proof
that our stack survives that story ON PURPOSE: every fault class the
chaos harness can inject (runtime/chaos.py), at every legal injection
site, against every wire format, is provoked deterministically inside a
real supervised training run (parallel/elastic.py) on the 8-device
virtual CPU mesh — and every cell must end with the model trained to the
target step and the fault visible in the observability stats dump.

    python tools/chaos_bench.py --fast     # the full matrix, CI-sized
    make chaos-bench                       # same

Matrix axes:

  kind    hang | slowdown | exception | corruption | preemption
  site    queue.issue | queue.wait | staging | collective
          (exception/preemption are host-only: raising inside an XLA
          callback aborts the runtime, so those cells do not exist)
  wire    f32 ring | BFP-compressed ring (the EQuARX-style quantized
          all-reduce whose codec adds the silent-corruption surface the
          integrity checksums exist for)

Per-cell verdict (one JSON object in `cells`):

  recovered   the fault was detected AND the run completed after >=1
              checkpoint restore — the recoverable classes.
  absorbed    slowdown only: a straggler below the watchdog limit must
              be survived WITHOUT tripping recovery (faults_total == 0).
  ok          the cell met its class's expectation; the process exits
              nonzero unless every cell is ok.

A final `soak` entry replays a seeded FaultPlan.random mixed-fault
schedule through one longer run.  The artifact (artifacts/chaos_*.json)
carries the last run's full Profiler.report() so the recovery counters
(faults, restores, MTTR) are visible exactly where the collective stats
already live.

Per wire the matrix also runs a `preempt-shrink` cell: the same mid-run
preemption recovered once by the LIVE-RESHARD tier (ReshardPolicy armed:
the TrainState migrates dp8->dp4 by collective redistribution,
parallel/reshard.py — no checkpoint, no replay) and once by
checkpoint-restore, banking the two MTTRs side by side.  `--reshard-
bench` runs the full trainer x codec version of that comparison and
banks it as the RESHARD_BENCH artifact (`make reshard-bench`); CPU
timings are dryrun-class, only the plan's exact wire-byte accounting is
gate-worthy (docs/RESHARD.md).
"""

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from bench_common import cpu_env, log, save_artifact  # noqa: E402

# The matrix is a CPU-mesh battery: re-exec once with the 8-device
# virtual CPU environment before jax is imported.
if os.environ.get("_CHAOS_BENCH_REEXEC") != "1":
    env = cpu_env(8)
    env["_CHAOS_BENCH_REEXEC"] = "1"
    os.execve(sys.executable,
              [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
              env)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fpga_ai_nic_tpu.models import mlp  # noqa: E402
from fpga_ai_nic_tpu.parallel import (DPTrainer, FSDPTrainer,  # noqa: E402
                                      make_mesh)
from fpga_ai_nic_tpu.parallel import reshard as reshard_lib  # noqa: E402
from fpga_ai_nic_tpu.parallel.elastic import (ElasticConfig,  # noqa: E402
                                              ElasticTrainer,
                                              ReshardPolicy)
from fpga_ai_nic_tpu.runtime import chaos  # noqa: E402
from fpga_ai_nic_tpu.utils.config import (BFPConfig,  # noqa: E402
                                          CollectiveConfig, MeshConfig,
                                          MLPConfig, OptimizerConfig,
                                          TrainConfig)

MCFG = MLPConfig(layer_sizes=(32, 64, 64, 10), dtype="float32")
SEED = 11
FAULT_STEP = 3          # mid-run: clean steps before AND after the fault

WIRES = {
    "f32": None,
    "bfp": BFPConfig(),
}

# corruption payload shaping per site: the collective site must exercise
# the checksum path (finite but wrong sums), host sites the NaN guards
_CORRUPTION_MODE = {"collective": "scale"}


def _prewarm_restore(trainer, state) -> None:
    """Steady-state fairness for the MTTR comparison: the reshard tier
    prewarms its transfer/step, so the restore tier gets the same
    courtesy — one throwaway save+restore warms the gather/repad jit
    dispatch caches the timed restore will hit.  Without this the
    restore MTTR carries a one-off compile and the reshard speedup reads
    ~10x too flattering on the dp trainers."""
    from fpga_ai_nic_tpu.utils.checkpoint import Checkpointer
    with tempfile.TemporaryDirectory() as wd:
        c = Checkpointer(wd)
        c.save(int(state.step), state)
        trainer.restore_state(c.restore(int(state.step)))


def _loss_fn(params, batch):
    return mlp.loss_fn(params, batch, MCFG)


def _data(n=64):
    r = np.random.default_rng(0)
    x = r.standard_normal((n, 32)).astype(np.float32)
    w = r.standard_normal((32, 10)).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _legal_cells():
    # the TRAINING matrix: serve.step is the serving plane's site and has
    # its own cell battery (run_serve_cells) with a request-level verdict
    for site in chaos.TRAIN_SITES:
        for kind in chaos.FAULT_KINDS:
            if site == "collective" and kind in ("exception", "preemption"):
                continue
            yield kind, site, _CORRUPTION_MODE.get(site, "nan")


class WireRig:
    """One trainer per wire format, compiled once and shared by every
    cell (cells differ only in the fault plan and their fresh state)."""

    def __init__(self, wire: str, n_steps: int):
        self.wire = wire
        self.n_steps = n_steps
        self.trainer = self._build(8)
        # host copy of the init params: step_fn donates its input state,
        # so every cell must rebuild TrainState from an undonated source
        self.params0 = jax.device_get(mlp.init(jax.random.PRNGKey(0), MCFG))
        self.host_batch = _data()
        self.batch = self.trainer.shard_batch(self.host_batch)
        self._shrunk = {}
        state = self.fresh_state()
        t0 = time.time()
        self.trainer.step_fn.lower(state, self.batch).compile()
        log(f"wire={wire}: step compiled in {time.time() - t0:.1f}s")

    def _build(self, n: int):
        cfg = TrainConfig(
            iters=self.n_steps, global_batch=64, mesh=MeshConfig(dp=n),
            collective=CollectiveConfig(impl="ring",
                                        compression=WIRES[self.wire],
                                        integrity_check=True),
            optimizer=OptimizerConfig())
        return DPTrainer(_loss_fn, make_mesh(cfg.mesh), cfg)

    def shrink_trainer(self, n: int):
        """The shrink-target trainer, cached so its compiled step (a
        cached_property) is shared by every cell that reshards to n —
        the spare-capacity config a production supervisor would keep."""
        if n not in self._shrunk:
            self._shrunk[n] = self._build(n)
        return self._shrunk[n]

    def fresh_state(self):
        return self.trainer.init_state(
            jax.tree_util.tree_map(jnp.asarray, self.params0))


def run_cell(rig: WireRig, kind: str, site: str, mode: str,
             ecfg: ElasticConfig, n_steps: int,
             hang_s: float, slow_s: float) -> dict:
    t0 = time.time()
    dur = hang_s if kind == "hang" else slow_s
    plan = chaos.FaultPlan(
        [chaos.FaultSpec(kind, site, step=FAULT_STEP, mode=mode,
                         duration_s=dur)], seed=SEED)
    cell = {"kind": kind, "site": site, "wire": rig.wire, "steps": n_steps,
            "mode": mode if kind == "corruption" else None}
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage)
        try:
            state, metrics = et.run(state, lambda i: rig.batch, n_steps)
        except Exception as err:  # noqa: BLE001 — the cell verdict IS the point
            cell.update(ok=False, error=repr(err),
                        recovery=et.profiler.recovery.as_dict(),
                        wall_s=round(time.time() - t0, 2))
            return cell
        rec = et.profiler.recovery.as_dict()
        report = et.profiler.report()

    completed = int(state.step) == n_steps
    finite = bool(np.isfinite(float(metrics["loss"])))
    injected = len(plan.fired) >= 1
    if kind == "slowdown":
        # a straggler below the watchdog limit: survive, do NOT recover
        cell["absorbed"] = completed and injected and rec["faults_total"] == 0
        ok = cell["absorbed"]
    else:
        cell["recovered"] = (completed and injected
                             and rec["faults_total"] >= 1
                             and rec["recoveries"] >= 1
                             and rec["checkpoint_restores"] >= 1)
        ok = cell["recovered"]
    ev = report.get("events", {})
    cell.update(
        ok=bool(ok and finite),
        final_loss=round(float(metrics["loss"]), 6),
        faults=rec["faults"], recoveries=rec["recoveries"],
        checkpoint_restores=rec["checkpoint_restores"],
        mttr_mean_s=round(rec["mttr_mean_s"], 4),
        stats_dump_has_recovery="recovery" in report,
        # the structured stream's view of the same run: injected-fault /
        # detection / recovery instants landed as events (obs.events),
        # with honest drop accounting
        events_recorded=ev.get("recorded", 0),
        events_dropped=ev.get("events_dropped", 0),
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def _run_tier(tier: str, src_trainer, factory, fresh_state, batch,
              host_batch, ecfg: ElasticConfig, n_steps: int,
              shrink_to: int) -> dict:
    """One tier of the reshard-vs-restore comparison: the same seeded
    mid-run preemption recovered by the named tier (ReshardPolicy armed
    + prewarmed for 'reshard'; policy absent + restore path prewarmed
    for 'restore' -- neither side pays a one-off compile inside the
    timed window).  The reshard tier must recover WITHOUT touching a
    checkpoint; the restore tier must not reshard."""
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("preemption", "queue.issue",
                         step=FAULT_STEP)], seed=SEED)
    pol = (ReshardPolicy(factory, shrink_to=shrink_to)
           if tier == "reshard" else None)
    state = fresh_state()
    if pol is None:
        _prewarm_restore(src_trainer, state)
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(src_trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage, reshard=pol)
        if pol is not None:
            et.prewarm_reshard(state, host_batch)
        try:
            state, metrics = et.run(state, lambda i: batch, n_steps)
        except Exception as err:  # noqa: BLE001 -- the verdict IS the point
            return {"ok": False, "error": repr(err),
                    "recovery": et.profiler.recovery.as_dict()}
        rec = et.profiler.recovery.as_dict()
    completed = int(state.step) == n_steps
    finite = bool(np.isfinite(float(metrics["loss"])))
    if tier == "reshard":
        ok = (completed and finite and rec["reshards"] == 1
              and rec["checkpoint_restores"] == 0
              and rec["faults"].get("shrinkable", 0) == 1
              and et.trainer.n == shrink_to)
        mttr = rec["mttr_reshard_mean_s"]
    else:
        ok = (completed and finite and rec["checkpoint_restores"] >= 1
              and rec["reshards"] == 0)
        mttr = rec["mttr_restore_mean_s"]
    return {"ok": bool(ok), "mttr_s": round(mttr, 4),
            "final_loss": round(float(metrics["loss"]), 6),
            "faults": rec["faults"], "recoveries": rec["recoveries"],
            "reshards": rec["reshards"],
            "checkpoint_restores": rec["checkpoint_restores"]}


def _tier_comparison(src_trainer, factory, fresh_state, batch, host_batch,
                     ecfg: ElasticConfig, n_steps: int,
                     shrink_to: int) -> dict:
    """Both tiers against the same fault + the plan's exact byte facts --
    the shared core of the preempt-shrink matrix cell and the
    RESHARD_BENCH rows (one harness, one set of verdict predicates)."""
    tiers = {tier: _run_tier(tier, src_trainer, factory, fresh_state,
                             batch, host_batch, ecfg, n_steps, shrink_to)
             for tier in ("reshard", "restore")}
    facts = reshard_lib.plan_for(src_trainer,
                                 factory(shrink_to)).describe()
    r, s = tiers["reshard"], tiers["restore"]
    return {
        "ok": bool(r.get("ok") and s.get("ok")),
        "tiers": tiers,
        "mttr_reshard_s": r.get("mttr_s"),
        "mttr_restore_s": s.get("mttr_s"),
        "mttr_speedup": (round(s["mttr_s"] / r["mttr_s"], 2)
                         if r.get("mttr_s") and s.get("mttr_s")
                         else None),
        "reshard_beats_restore": (
            bool(r["mttr_s"] < s["mttr_s"])
            if r.get("mttr_s") is not None
            and s.get("mttr_s") is not None else None),
        "reshard_wire_bytes": facts["wire_bytes"],
        "plan": facts,
    }


def run_shrink_cell(rig: WireRig, ecfg: ElasticConfig, n_steps: int,
                    shrink_to: int = 4) -> dict:
    """The preempt-shrink cell: the SAME preemption recovered twice --
    tier 1 (live mesh reshard dp8->dpN) vs tier 2 (checkpoint-restore)
    -- so the cell banks a like-for-like MTTR comparison.  CPU timings
    are dryrun-class (oversubscription noise), so ok gates recovery
    tier + completion, never the speedup."""
    t0 = time.time()
    cell = {"kind": "preemption", "site": "queue.issue", "wire": rig.wire,
            "steps": n_steps, "shrink": f"dp8->dp{shrink_to}",
            "mode": None}
    cell.update(_tier_comparison(
        rig.trainer, rig.shrink_trainer, rig.fresh_state, rig.batch,
        rig.host_batch, ecfg, n_steps, shrink_to))
    cell.update(recovered=cell["ok"], wall_s=round(time.time() - t0, 2))
    return cell


# ---------------------------------------------------------------------------
# serving cells: request-level SLO under fault (docs/SERVING.md)
# ---------------------------------------------------------------------------

SERVE_FAULTS = ("hang", "slowdown", "exception", "corruption",
                "preemption")
SERVE_FAULT_TICK = 3        # mid-run: prefill and decode both in flight
# corruption at serve.step NaN-damages the tick's KV payload; a high
# fraction guarantees visible positions are hit so the in-graph
# NaN/garbage-logits guard MUST trip (serve.engine._logit_guard) —
# recovery, never a poisoned stream.  This cell runs with
# page_integrity=False so it pins the VALUE tier in isolation: with the
# exact per-page ledger on (the default), the checksum trips FIRST and
# the fault lands as "wire-corruption" — that routing is exactly what
# the wirebit battery below (run_integrity_cells) pins, so the two
# cells together prove both tiers and their ordering.
SERVE_CORRUPTION_FRACTION = 0.5


class ServeRig:
    """One serving workload + its fault-free reference token streams.
    Greedy decode is deterministic, so the reference run IS the SLO: a
    faulted run must complete every request with the IDENTICAL tokens —
    recovery that loses or corrupts a request cannot hide behind
    latency."""

    def __init__(self):
        from fpga_ai_nic_tpu.models import llama as llama_lib
        self.llama_cfg = llama_lib.LlamaConfig.tiny()
        self.params = llama_lib.init(jax.random.PRNGKey(0), self.llama_cfg)
        rng = np.random.default_rng(SEED)
        self.prompts = [rng.integers(0, self.llama_cfg.vocab,
                                     int(n)).astype(np.int32)
                        for n in rng.integers(4, 12, 6)]
        self.max_new = 5
        ref_eng, ref_reqs, _ = self.serve(None, None)
        self.reference = [list(r.generated) for r in ref_reqs]

    def scfg(self, timeout_s, page_integrity=True):
        from fpga_ai_nic_tpu.serve import ServeConfig
        return ServeConfig(max_reqs=3, page_size=4, n_pages=14,
                           max_pages_per_seq=5, prefill_chunk=6,
                           step_timeout_s=timeout_s, backoff_s=0.01,
                           page_integrity=page_integrity)

    def serve(self, plan, timeout_s, page_integrity=True):
        from fpga_ai_nic_tpu.serve import ServeEngine
        eng = ServeEngine(self.params, self.llama_cfg,
                          self.scfg(timeout_s, page_integrity), chaos=plan)
        reqs = [eng.submit(p, max_new=self.max_new) for p in self.prompts]
        with chaos.activate(plan):
            summary = eng.run()
        return eng, reqs, summary


def run_serve_cell(rig: ServeRig, kind: str, timeout_s: float,
                   hang_s: float, slow_s: float) -> dict:
    t0 = time.time()
    kw: dict = {}
    if kind in ("hang", "slowdown"):
        kw["duration_s"] = hang_s if kind == "hang" else slow_s
    elif kind == "corruption":
        kw.update(mode="nan", fraction=SERVE_CORRUPTION_FRACTION)
    plan = chaos.FaultPlan(
        [chaos.FaultSpec(kind, "serve.step", step=SERVE_FAULT_TICK, **kw)],
        seed=SEED)
    cell = {"kind": kind, "site": "serve.step", "wire": "serve",
            "requests": len(rig.prompts), "max_new": rig.max_new}
    try:
        # the NaN cell isolates the value tier (see the fraction comment
        # above); every other kind runs the production default
        eng, reqs, s = rig.serve(plan, timeout_s,
                                 page_integrity=kind != "corruption")
    except Exception as err:  # noqa: BLE001 — the cell verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    completed = s["completed"] == len(rig.prompts)
    token_exact = all(list(q.generated) == want
                      for q, want in zip(reqs, rig.reference))
    injected = len(plan.fired) >= 1
    if kind == "slowdown":
        # a straggler tick below the watchdog limit: absorb, no recovery
        cell["absorbed"] = (completed and injected
                            and s["serve_recoveries"] == 0)
        ok = cell["absorbed"]
    else:
        cell["recovered"] = (completed and injected
                             and s["serve_recoveries"] >= 1
                             and s["recovery"]["faults"].get(
                                 "preemption" if kind == "preemption"
                                 else kind, 0) >= 1)
        ok = cell["recovered"]
    r = s["requests"]
    cell.update(
        ok=bool(ok and token_exact and s["recompiles_steady"] == 0),
        token_exact=token_exact,
        serve_recoveries=s["serve_recoveries"],
        faults=s["recovery"]["faults"],
        mttr_mean_s=round(s["recovery"]["mttr_mean_s"], 4),
        recompiles_steady=s["recompiles_steady"],
        evictions=s["evictions"],
        ttft_p95_s=r.get("ttft_p95_s"),
        latency_p95_s=r.get("latency_p95_s"),
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_serve_cells(timeout_s: float, hang_s: float,
                    slow_s: float, rig: "ServeRig" = None) -> list:
    rig = rig if rig is not None else ServeRig()
    cells = []
    for kind in SERVE_FAULTS:
        cell = run_serve_cell(rig, kind, timeout_s, hang_s, slow_s)
        verdict = ("recovered" if cell.get("recovered")
                   else "absorbed" if cell.get("absorbed")
                   else "FAILED")
        log(f"cell serve {kind:10s} @ serve.step  : {verdict:9s} "
            f"token_exact={cell.get('token_exact')} "
            f"recoveries={cell.get('serve_recoveries')} "
            f"({cell['wall_s']:.1f}s)")
        cells.append(cell)
    return cells


# ---------------------------------------------------------------------------
# fleet cells: replica-kill + handoff-fault SLO over the elastic fleet
# (serve/fleet.py, docs/SERVING.md "The fleet")
# ---------------------------------------------------------------------------

FLEET_FAULTS = ("replica_kill", "handoff_exception")
FLEET_KILL_TICK = 6         # mid-decode under load (prefills done, decoders live)


class FleetRig:
    """One fleet workload + its fault-free reference streams.  The
    fault-free FLEET run is the reference (not isolated generate): the
    replica-kill verdict is BYTE-identity of surviving streams, which
    the deterministic scheduler + page-assignment-invariant forward
    guarantee structurally — any divergence is a migration bug."""

    def __init__(self):
        from fpga_ai_nic_tpu.models import llama as llama_lib
        from fpga_ai_nic_tpu.serve import FleetConfig, ServeConfig
        self.llama_cfg = llama_lib.LlamaConfig.tiny()
        self.params = llama_lib.init(jax.random.PRNGKey(0), self.llama_cfg)
        rng = np.random.default_rng(SEED)
        self.prompts = [rng.integers(0, self.llama_cfg.vocab,
                                     int(n)).astype(np.int32)
                        for n in rng.integers(4, 14, 6)]
        self.max_new = 6
        self.scfg = ServeConfig(max_reqs=4, page_size=4, n_pages=40,
                                max_pages_per_seq=6, prefill_chunk=6)
        self.fcfg = FleetConfig(n_prefill=1, n_decode=2)
        _f, ref_reqs, self.ref_summary = self.serve(None)
        self.reference = [list(r.generated) for r in ref_reqs]

    def serve(self, plan):
        from fpga_ai_nic_tpu.serve import ServeFleet
        fleet = ServeFleet(self.params, self.llama_cfg, self.scfg,
                           self.fcfg, chaos=plan)
        reqs = [fleet.submit(p, max_new=self.max_new)
                for p in self.prompts]
        with chaos.activate(plan):
            summary = fleet.run()
        return fleet, reqs, summary


def run_fleet_cell(rig: FleetRig, kind: str) -> dict:
    t0 = time.time()
    if kind == "replica_kill":
        plan = chaos.FaultPlan(
            [chaos.FaultSpec("preemption", "fleet.membership",
                             step=FLEET_KILL_TICK)], seed=SEED)
    else:   # handoff_exception: fault EVERY early handoff attempt —
            # each degraded request must land on the replay tier and
            # still complete (specs fire at most once per step)
        plan = chaos.FaultPlan(
            [chaos.FaultSpec("exception", "serve.handoff", step=s)
             for s in range(12)], seed=SEED)
    cell = {"kind": kind, "site": ("fleet.membership"
                                   if kind == "replica_kill"
                                   else "serve.handoff"),
            "wire": "fleet", "requests": len(rig.prompts),
            "max_new": rig.max_new}
    try:
        fleet, reqs, s = rig.serve(plan)
    except Exception as err:  # noqa: BLE001 — the cell verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    completed = s["completed"] == len(rig.prompts)
    token_exact = all(list(q.generated) == want
                      for q, want in zip(reqs, rig.reference))
    injected = len(plan.fired) >= 1
    if kind == "replica_kill":
        # THE acceptance: handoff tier used, replay tier NOT fired —
        # zero replay-from-prompt for migrated requests
        cell["recovered"] = (completed and injected
                             and s["kills"] == 1
                             and s["fleet_replays"] == 0
                             and s["serve_recoveries"] == 0
                             and s["handoffs"]
                             > rig.ref_summary["handoffs"])
    else:
        # degraded-but-never-lost: every faulted handoff fell back to
        # replay, all requests still completed token-exact
        cell["recovered"] = (completed and injected
                             and s["fleet_replays"] >= 1)
    ok = cell["recovered"]
    r = s["requests"]
    cell.update(
        ok=bool(ok and token_exact and s["recompiles_steady"] == 0),
        token_exact=token_exact,
        kills=s["kills"],
        handoffs=s["handoffs"],
        handoff_wire_bytes=s["handoff_wire_bytes"],
        fleet_replays=s["fleet_replays"],
        serve_recoveries=s["serve_recoveries"],
        faults=s["recovery"]["faults"],
        fleet_mttr_s=round(s["recovery"]["mttr_mean_s"], 4),
        recompiles_steady=s["recompiles_steady"],
        ttft_p95_s=r.get("ttft_p95_s"),
        latency_p95_s=r.get("latency_p95_s"),
        survivors=[x["replica"] for x in s["replicas"] if x["alive"]],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_fleet_cells(rig: "FleetRig" = None) -> list:
    rig = rig if rig is not None else FleetRig()
    cells = []
    for kind in FLEET_FAULTS:
        cell = run_fleet_cell(rig, kind)
        verdict = "recovered" if cell.get("recovered") else "FAILED"
        log(f"cell fleet {kind:17s}: {verdict:9s} "
            f"token_exact={cell.get('token_exact')} "
            f"handoffs={cell.get('handoffs')} "
            f"replays={cell.get('fleet_replays')} "
            f"({cell['wall_s']:.1f}s)")
        cells.append(cell)
    return cells


# ---------------------------------------------------------------------------
# integrity cells: the FINITE "wirebit" corruption class at every wire
# (docs/CHAOS.md "Exact wire integrity").  Every cell flips a LOW bit in
# bytes that cross (or sit behind) a wire — encoded ring frames, reshard
# segments, KV handoff page blocks, pool float words — so the damage is
# plausible, in-band and invisible to NaN/norm/magnitude guards BY
# CONSTRUCTION; only the exact checksums (ops.integrity) can see it.
# The battery is the matrix that proves the honest boundary closed: the
# exact tier must trip (never the value/logit tier), and recovery must
# end token-/bit-exact vs the fault-free reference.
# ---------------------------------------------------------------------------

def _ref_loss(rig: WireRig, ecfg: ElasticConfig, n_steps: int) -> float:
    """Fault-free supervised reference loss — the bit-exact recovery
    bar for the training integrity cells."""
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d:
        et = ElasticTrainer(rig.trainer, d, ecfg)
        state, metrics = et.run(state, lambda i: rig.batch, n_steps)
    return float(metrics["loss"])


def run_integrity_train_cell(rig: WireRig, ecfg: ElasticConfig,
                             n_steps: int, ref_loss: float) -> dict:
    """wirebit on a ring hop's ENCODED frame mid-run: the exact tier
    must trip (fault class `wire-corruption` — the value band sees a
    finite, in-band number and says nothing), the gated/invalidated
    step recovers by restore, and the finished run is BIT-exact vs the
    fault-free reference."""
    t0 = time.time()
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("corruption", "collective", step=FAULT_STEP,
                         mode="wirebit", fraction=0.01)], seed=SEED)
    cell = {"kind": "corruption", "mode": "wirebit", "site": "collective",
            "wire": rig.wire, "steps": n_steps}
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage)
        try:
            state, metrics = et.run(state, lambda i: rig.batch, n_steps)
        except Exception as err:  # noqa: BLE001 — the verdict IS the point
            cell.update(ok=False, error=repr(err),
                        recovery=et.profiler.recovery.as_dict(),
                        wall_s=round(time.time() - t0, 2))
            return cell
        rec = et.profiler.recovery.as_dict()
    loss = float(metrics["loss"])
    bit_exact = loss == ref_loss
    cell["recovered"] = (int(state.step) == n_steps
                         and len(plan.fired) == 1
                         and rec["faults"].get("wire-corruption", 0) >= 1
                         and rec["faults"].get("corruption", 0) == 0
                         and rec["recoveries"] >= 1)
    cell.update(
        ok=bool(cell["recovered"] and bit_exact),
        bit_exact=bit_exact, final_loss=loss, ref_loss=ref_loss,
        faults=rec["faults"], recoveries=rec["recoveries"],
        checkpoint_restores=rec["checkpoint_restores"],
        mttr_mean_s=round(rec["mttr_mean_s"], 4),
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_integrity_reshard_cell(rig: WireRig, ecfg: ElasticConfig,
                               n_steps: int, shrink_to: int = 4) -> dict:
    """wirebit on a reshard SEGMENT's wire: a preemption arms the
    reshard tier, the transfer's exact verdict trips
    (WireIntegrityError) before the landed state reaches the target
    trainer, and the ladder falls through to checkpoint-restore instead
    of training on silently corrupted masters — degraded, never
    wrong."""
    t0 = time.time()
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("preemption", "queue.issue", step=FAULT_STEP),
         chaos.FaultSpec("corruption", "reshard.transfer",
                         step=FAULT_STEP, mode="wirebit",
                         fraction=0.02)], seed=SEED)
    cell = {"kind": "corruption", "mode": "wirebit",
            "site": "reshard.transfer", "wire": rig.wire,
            "steps": n_steps, "shrink": f"dp8->dp{shrink_to}"}
    pol = ReshardPolicy(rig.shrink_trainer, shrink_to=shrink_to)
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage, reshard=pol)
        et.prewarm_reshard(state, rig.host_batch)
        try:
            state, metrics = et.run(state, lambda i: rig.batch, n_steps)
        except Exception as err:  # noqa: BLE001 — the verdict IS the point
            cell.update(ok=False, error=repr(err),
                        recovery=et.profiler.recovery.as_dict(),
                        wall_s=round(time.time() - t0, 2))
            return cell
        rec = et.profiler.recovery.as_dict()
    # the tripped transfer must NOT count as a reshard; the restore tier
    # finishes the job on the ORIGINAL mesh (trainer width unchanged)
    cell["recovered"] = (int(state.step) == n_steps
                         and len(plan.fired) == 2
                         and rec["reshards"] == 0
                         and rec["checkpoint_restores"] >= 1
                         and et.trainer.n == 8)
    cell.update(
        ok=bool(cell["recovered"]
                and np.isfinite(float(metrics["loss"]))),
        final_loss=round(float(metrics["loss"]), 6),
        faults=rec["faults"], recoveries=rec["recoveries"],
        reshards=rec["reshards"],
        checkpoint_restores=rec["checkpoint_restores"],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_integrity_serve_cell(rig: ServeRig, timeout_s: float) -> dict:
    """wirebit on the serve pool's float words: wrong-but-normal-
    magnitude logits — the class docs/SERVING.md's honest boundary
    documented as invisible.  The per-page ledger (NOT the logit guard)
    must trip, recovery replays, and the streams end byte-identical."""
    t0 = time.time()
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("corruption", "serve.step",
                         step=SERVE_FAULT_TICK, mode="wirebit",
                         fraction=0.25)], seed=SEED)
    cell = {"kind": "corruption", "mode": "wirebit", "site": "serve.step",
            "wire": "serve", "requests": len(rig.prompts),
            "max_new": rig.max_new}
    try:
        eng, reqs, s = rig.serve(plan, timeout_s)
    except Exception as err:  # noqa: BLE001 — the verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    token_exact = all(list(q.generated) == want
                      for q, want in zip(reqs, rig.reference))
    cell["recovered"] = (s["completed"] == len(rig.prompts)
                         and len(plan.fired) >= 1
                         and s["page_trips"] >= 1
                         and s["logit_trips"] == 0
                         and s["recovery"]["faults"].get(
                             "wire-corruption", 0) >= 1)
    cell.update(
        ok=bool(cell["recovered"] and token_exact
                and s["recompiles_steady"] == 0),
        token_exact=token_exact,
        page_trips=s["page_trips"], logit_trips=s["logit_trips"],
        serve_recoveries=s["serve_recoveries"],
        faults=s["recovery"]["faults"],
        mttr_mean_s=round(s["recovery"]["mttr_mean_s"], 4),
        recompiles_steady=s["recompiles_steady"],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_integrity_handoff_cell(rig: FleetRig, exhaust: bool) -> dict:
    """wirebit on the KV handoff wire.  One spec per tick: the landed-
    page checksum trips, ONE bounded retry re-sends the intact source
    pages and the migration completes — zero replay.  ``exhaust``
    doubles the specs so the retry trips too: the request degrades to
    the replay tier — counted, never lost, never silently wrong.
    Either way the streams end byte-identical to the fault-free run."""
    t0 = time.time()
    # the wire tap consumes ONE spec per payload array (2 * n_layers
    # arrays per handoff attempt): one spec per step trips only the
    # first attempt (retry clean); exhaust arms more specs than one
    # attempt can consume, so the bounded retry trips too and the
    # request must degrade to replay
    reps = 8 if exhaust else 1
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("corruption", "serve.handoff", step=s,
                         mode="wirebit", fraction=0.2)
         for s in range(20) for _ in range(reps)], seed=SEED)
    cell = {"kind": "corruption", "mode": "wirebit",
            "site": "serve.handoff", "wire": "fleet",
            "variant": "retry-exhausted" if exhaust else "bounded-retry",
            "requests": len(rig.prompts), "max_new": rig.max_new}
    try:
        fleet, reqs, s = rig.serve(plan)
    except Exception as err:  # noqa: BLE001 — the verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    token_exact = all(list(q.generated) == want
                      for q, want in zip(reqs, rig.reference))
    completed = s["completed"] == len(rig.prompts)
    if exhaust:
        cell["recovered"] = (completed
                             and s["handoff_integrity_trips"] >= 2
                             and s["fleet_replays"] >= 1
                             and s["recovery"]["faults"].get(
                                 "wire-corruption", 0) >= 1)
    else:
        cell["recovered"] = (completed
                             and s["handoff_integrity_trips"] >= 1
                             and s["fleet_replays"] == 0
                             and s["serve_recoveries"] == 0)
    cell.update(
        ok=bool(cell["recovered"] and token_exact
                and s["recompiles_steady"] == 0),
        token_exact=token_exact,
        handoff_integrity_trips=s["handoff_integrity_trips"],
        handoffs=s["handoffs"], fleet_replays=s["fleet_replays"],
        serve_recoveries=s["serve_recoveries"],
        faults=s["recovery"]["faults"],
        recompiles_steady=s["recompiles_steady"],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_integrity_cells(ecfg: ElasticConfig, n_steps: int,
                        timeout_s: float, wire_rigs=None,
                        serve_rig=None, fleet_rig=None) -> list:
    """The full wirebit battery: every wire site, exact tier trips,
    token-/bit-exact recovery.  Pre-built rigs are reused when the full
    matrix already compiled them."""
    cells = []
    rigs = wire_rigs if wire_rigs else {"bfp": WireRig("bfp", n_steps)}
    for wire, rig in sorted(rigs.items()):
        ref = _ref_loss(rig, ecfg, n_steps)
        cell = run_integrity_train_cell(rig, ecfg, n_steps, ref)
        log(f"cell integrity wirebit @ collective       wire={wire}: "
            f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
            f"bit_exact={cell.get('bit_exact')} "
            f"faults={cell.get('faults')} ({cell['wall_s']:.1f}s)")
        cells.append(cell)
    rig = rigs.get("bfp") or next(iter(rigs.values()))
    cell = run_integrity_reshard_cell(rig, ecfg, n_steps)
    log(f"cell integrity wirebit @ reshard.transfer : "
        f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
        f"restores={cell.get('checkpoint_restores')} "
        f"reshards={cell.get('reshards')} ({cell['wall_s']:.1f}s)")
    cells.append(cell)
    srig = serve_rig if serve_rig is not None else ServeRig()
    cell = run_integrity_serve_cell(srig, timeout_s)
    log(f"cell integrity wirebit @ serve.step       : "
        f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
        f"page_trips={cell.get('page_trips')} "
        f"logit_trips={cell.get('logit_trips')} "
        f"token_exact={cell.get('token_exact')} "
        f"({cell['wall_s']:.1f}s)")
    cells.append(cell)
    frig = fleet_rig if fleet_rig is not None else FleetRig()
    for exhaust in (False, True):
        cell = run_integrity_handoff_cell(frig, exhaust)
        log(f"cell integrity wirebit @ serve.handoff    "
            f"[{cell['variant']}]: "
            f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
            f"trips={cell.get('handoff_integrity_trips')} "
            f"replays={cell.get('fleet_replays')} "
            f"token_exact={cell.get('token_exact')} "
            f"({cell['wall_s']:.1f}s)")
        cells.append(cell)
    return cells


# ---------------------------------------------------------------------------
# durability cells: faults at the checkpoint plane itself (docs/
# DURABILITY.md).  The last recovery tier every ladder falls back to is
# the one place a fault is not allowed to be survivable-by-luck: a
# stored bit flipped at rest must be repaired from the dp peer mirror
# (bit-exact) or refused with a walk-back to the previous verified step
# — never restored silently; a save killed mid-sequence (or starved by
# ENOSPC) must leave the directory restoring exactly the previous
# verified step; and a ladder that exhausts must still dump the live
# state as an emergency checkpoint.  Every completing cell's final loss
# is BIT-equal to the fault-free reference (deterministic replay
# through the audited restore).
# ---------------------------------------------------------------------------

def _run_durability_cell(rig: WireRig, name: str, specs, ecfg,
                         n_steps: int, ref_loss: float,
                         expect: dict) -> dict:
    """One supervised run under durability specs; verdict = completion +
    BIT-exact final loss + the expected durability counters."""
    t0 = time.time()
    plan = chaos.FaultPlan(list(specs), seed=SEED)
    cell = {"cell": name, "site": "ckpt.save", "wire": rig.wire,
            "steps": n_steps}
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage)
        try:
            state, metrics = et.run(state, lambda i: rig.batch, n_steps)
        except Exception as err:  # noqa: BLE001 — the verdict IS the point
            cell.update(ok=False, error=repr(err),
                        recovery=et.profiler.recovery.as_dict(),
                        wall_s=round(time.time() - t0, 2))
            return cell
        rec = et.profiler.recovery.as_dict()
        verified = et.ckpt.latest_step(verified=True)
    loss = float(metrics["loss"])
    bit_exact = loss == ref_loss
    # expect: {counter: exact int} or {counter: (min,)} for >=
    counters_ok = all(
        rec.get(k, 0) >= v[0] if isinstance(v, tuple)
        else rec.get(k, 0) == v
        for k, v in expect.items())
    cell["recovered"] = (int(state.step) == n_steps
                         and len(plan.fired) == len(list(specs))
                         and counters_ok)
    cell.update(
        ok=bool(cell["recovered"] and bit_exact),
        bit_exact=bit_exact, final_loss=loss, ref_loss=ref_loss,
        latest_verified_step=verified,
        faults=rec["faults"], recoveries=rec["recoveries"],
        checkpoint_restores=rec["checkpoint_restores"],
        ckpt_repairs=rec["ckpt_repairs"],
        ckpt_repair_wire_bytes=rec["ckpt_repair_wire_bytes"],
        ckpt_save_failures=rec["ckpt_save_failures"],
        emergency_dumps=rec["emergency_dumps"],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_durability_emergency_cell(rig: WireRig, ecfg,
                                  n_steps: int) -> dict:
    """Ladder exhaustion: every retry of one step fails (max_retries+1
    exception specs) -> RecoveryExhausted is EXPECTED, and the 'dump
    before dying' tier must leave an emergency-flagged, audit-clean
    checkpoint of the live state behind."""
    from fpga_ai_nic_tpu.parallel.elastic import RecoveryExhausted
    t0 = time.time()
    plan = chaos.FaultPlan(
        [chaos.FaultSpec("exception", "queue.issue", step=FAULT_STEP)
         for _ in range(ecfg.max_retries + 1)], seed=SEED)
    cell = {"cell": "emergency-dump", "site": "ckpt.save",
            "wire": rig.wire, "steps": n_steps}
    state = rig.fresh_state()
    raised = False
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage)
        try:
            et.run(state, lambda i: rig.batch, n_steps)
        except RecoveryExhausted:
            raised = True
        rec = et.profiler.recovery.as_dict()
        dump_step = et.ckpt.latest_step(verified=True)
        flagged = (dump_step is not None
                   and et.ckpt.is_emergency(dump_step))
        restorable = (dump_step is not None
                      and et.ckpt.audit_step(dump_step,
                                             repair="probe").restorable)
    cell.update(
        ok=bool(raised and rec["emergency_dumps"] == 1 and flagged
                and restorable and dump_step == FAULT_STEP),
        recovered=raised, emergency_dumps=rec["emergency_dumps"],
        emergency_flagged=flagged, emergency_restorable=restorable,
        dump_step=dump_step, failed_recoveries=rec["failed_recoveries"],
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_durability_cells(ecfg, n_steps: int, rig: WireRig = None) -> list:
    rig = rig or WireRig("f32", n_steps)
    ref = _ref_loss(rig, ecfg, n_steps)
    save_step = FAULT_STEP - 1   # the save that commits state FAULT_STEP
    matrix = [
        ("bitflip-repair",
         # a stored primary bit flips at rest right after the commit;
         # the preemption's restore must peer-repair it bit-exactly
         [chaos.FaultSpec("corruption", "ckpt.save", step=save_step,
                          mode="wirebit"),
          chaos.FaultSpec("preemption", "queue.issue", step=FAULT_STEP)],
         {"ckpt_repairs": (1,), "checkpoint_restores": (1,),
          "ckpt_save_failures": 0}),
        ("stale-manifest-walkback",
         # the newest step's manifest is swapped for the previous
         # step's; the audit must reject it and the restore walk back
         [chaos.FaultSpec("corruption", "ckpt.save", step=save_step,
                          mode="stale_manifest"),
          chaos.FaultSpec("preemption", "queue.issue", step=FAULT_STEP)],
         {"ckpt_repairs": 0, "checkpoint_restores": (1,)}),
        ("kill-during-save",
         # the save's file-op sequence truncated mid-write (pre-commit):
         # absorbed, and the later restore lands the previous step
         [chaos.FaultSpec("kill", "ckpt.save", step=save_step,
                          fraction=0.5),
          chaos.FaultSpec("preemption", "queue.issue", step=FAULT_STEP)],
         {"ckpt_save_failures": 1, "checkpoint_restores": (1,)}),
        ("disk-full",
         # ENOSPC mid-sequence: absorbed and recorded, the run finishes,
         # later cadence saves succeed
         [chaos.FaultSpec("diskfull", "ckpt.save", step=save_step,
                          fraction=0.5)],
         {"ckpt_save_failures": 1, "checkpoint_restores": 0}),
    ]
    cells = []
    for name, specs, expect in matrix:
        cell = _run_durability_cell(rig, name, specs, ecfg, n_steps,
                                    ref, expect)
        log(f"cell durability {name:24s}: "
            f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
            f"bit_exact={cell.get('bit_exact')} "
            f"repairs={cell.get('ckpt_repairs')} "
            f"save_failures={cell.get('ckpt_save_failures')} "
            f"({cell['wall_s']:.1f}s)")
        cells.append(cell)
    cell = run_durability_emergency_cell(rig, ecfg, n_steps)
    log(f"cell durability {'emergency-dump':24s}: "
        f"{'recovered' if cell.get('recovered') else 'FAILED':9s} "
        f"dumps={cell.get('emergency_dumps')} "
        f"flagged={cell.get('emergency_flagged')} "
        f"restorable={cell.get('emergency_restorable')} "
        f"({cell['wall_s']:.1f}s)")
    cells.append(cell)
    return cells


# ---------------------------------------------------------------------------
# adaptive-tuning cells: the forced regime shift (docs/TUNING.md "Online
# plan adaptation").  A SUSTAINED slowdown@collective — one spec per
# step, FaultPlan.sustained — is the chaos stand-in for the wire whose
# codec break-even moved (SparCML): the drift observatory must DETECT it
# from measured-vs-modeled step residuals and SWITCH to a pre-compiled
# alternate plan at a step boundary with zero new traces (graftlint
# J13), while a fault-free run must never switch (the false-positive
# guard).  Detection rides real wall-clock measurement; only the
# calibration priors are fixture-pinned (fast wire -> plan 0 is the
# uncompressed ring, so the shift has a cheaper wire format to move to)
# — deterministic plan IDENTITY without faking the measured path.
# ---------------------------------------------------------------------------

ADAPT_STEPS = 12
ADAPT_FAULT_STEP = 5
ADAPT_SLOW_S = 0.25


class AdaptRig:
    """One AdaptiveTrainer workload per cell (controller/detector state
    is per-run, so cells never share a trainer).  The fixture regime is
    the SHARED one (tune.calibration.fixture_calibration — also the J13
    lint surface's), and ``self.cfg`` is the single source the cells AND
    the ADAPT_BENCH meta block derive from."""

    def __init__(self):
        from fpga_ai_nic_tpu.tune.calibration import fixture_calibration
        from fpga_ai_nic_tpu.utils.config import AdaptConfig
        self.calib = fixture_calibration()
        self.cfg = TrainConfig(
            iters=ADAPT_STEPS, global_batch=64, mesh=MeshConfig(dp=8),
            collective=CollectiveConfig(impl="ring", codec="auto"),
            optimizer=OptimizerConfig(),
            # slightly wider slack/threshold than the defaults: the
            # oversubscribed CPU mesh jitters run to run, and the
            # steady cell's zero-switch verdict must hold against that
            # noise while the 0.25s sustained slowdown (r >> 10) still
            # trips on its first post-warmup observation
            adapt=AdaptConfig(enabled=True, n_candidates=3,
                              live_calibration=False, warmup_steps=3,
                              drift_rel=1.0, cusum_threshold=4.0,
                              cooldown_steps=8))
        self.params0 = jax.device_get(mlp.init(jax.random.PRNGKey(0),
                                               MCFG))
        self.host_batch = _data()

    def plans_meta(self) -> dict:
        """The candidate set + calibration provenance the bench banks —
        derived through the same tuner call the rig's trainers resolve
        with, from the rig's OWN cfg (n_candidates, mesh width), so it
        can never diverge from the rows it annotates."""
        from fpga_ai_nic_tpu import tune
        total = sum(int(np.prod(np.shape(l))) or 1
                    for l in jax.tree_util.tree_leaves(self.params0))
        plans = tune.tune_topk(
            total, self.cfg.mesh.dp, self.cfg.adapt.n_candidates,
            calibration=self.calib,
            slice_elems=self.cfg.collective.slice_elems, depths=(1,))
        return {"n_candidates": len(plans),
                "candidates": [p.describe() for p in plans],
                "calibration": self.calib.describe()}

    def build(self):
        from fpga_ai_nic_tpu.obs import EventStream
        from fpga_ai_nic_tpu.tune import adapt as adapt_lib
        events = EventStream()
        at = adapt_lib.AdaptiveTrainer(
            _loss_fn, make_mesh(self.cfg.mesh), self.cfg, events=events,
            calibration=self.calib)
        state = at.init_state(
            jax.tree_util.tree_map(jnp.asarray, self.params0))
        batch = at.shard_batch(self.host_batch)
        at.prewarm(batch)
        return at, state, batch, events


def _run_adapt(rig: AdaptRig, plan) -> dict:
    at, state, batch, events = rig.build()
    with chaos.activate(plan):          # activate(None) is a clean no-op
        for i in range(ADAPT_STEPS):
            if plan is not None:
                plan.begin_step(i)
            state, loss = at.step(state, batch)
    return {"at": at, "loss": float(loss), "events": events,
            "final_step": int(state.step)}


def run_adapt_shift_cell(rig: AdaptRig) -> dict:
    """THE end-to-end adaptation proof: sustained slowdown@collective ->
    detected from measured-vs-modeled residuals -> step-boundary switch
    to a pre-compiled plan, recompiles_across_switch == 0."""
    t0 = time.time()
    plan = chaos.FaultPlan.sustained(
        "slowdown", "collective", start_step=ADAPT_FAULT_STEP,
        n_steps=ADAPT_STEPS - ADAPT_FAULT_STEP, duration_s=ADAPT_SLOW_S,
        seed=SEED)
    cell = {"kind": "slowdown-shift", "site": "collective",
            "wire": "adapt", "steps": ADAPT_STEPS,
            "fault_start_step": ADAPT_FAULT_STEP}
    try:
        r = _run_adapt(rig, plan)
    except Exception as err:  # noqa: BLE001 — the cell verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    at = r["at"]
    switch = at.switch_events[0] if at.switch_events else None
    switch_instants = [e for e in r["events"].snapshot()
                       if e["name"] == "adapt.switch"]
    detected = switch is not None
    cell["recovered"] = (
        detected and at.switches == 1
        and r["final_step"] == ADAPT_STEPS
        and switch["step"] > ADAPT_FAULT_STEP
        and at.recompiles_across_switch == 0
        and len(plan.fired) >= 1
        # the switch event is a first-class obs fact, with evidence
        and len(switch_instants) == 1
        and switch_instants[0]["attrs"]["from_plan"]
        != switch_instants[0]["attrs"]["to_plan"])
    cell.update(
        ok=bool(cell["recovered"] and np.isfinite(r["loss"])),
        detected=int(detected),
        switches=at.switches,
        switch_step=switch["step"] if switch else None,
        detection_latency_steps=(switch["step"] - ADAPT_FAULT_STEP
                                 if switch else None),
        from_plan=switch["from_plan"] if switch else None,
        to_plan=switch["to_plan"] if switch else None,
        evidence=switch["evidence"] if switch else None,
        recompiles_across_switch=at.recompiles_across_switch,
        trace_counts=at.trace_counts(),
        n_candidates=len(at.plans),
        final_loss=round(r["loss"], 6),
        chaos_fired=len(plan.fired),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_adapt_steady_cell(rig: AdaptRig) -> dict:
    """The false-positive guard: a fault-free run must end with ZERO
    switches — the detector's hysteresis/slack absorbing CPU noise."""
    t0 = time.time()
    cell = {"kind": "steady", "site": None, "wire": "adapt",
            "steps": ADAPT_STEPS}
    try:
        r = _run_adapt(rig, None)
    except Exception as err:  # noqa: BLE001 — the cell verdict IS the point
        cell.update(ok=False, error=repr(err),
                    wall_s=round(time.time() - t0, 2))
        return cell
    at = r["at"]
    cell.update(
        ok=bool(at.switches == 0 and at.recompiles_across_switch == 0
                and r["final_step"] == ADAPT_STEPS
                and np.isfinite(r["loss"])),
        detected=0, switches=at.switches, false_switches=at.switches,
        recompiles_across_switch=at.recompiles_across_switch,
        trace_counts=at.trace_counts(),
        n_candidates=len(at.plans),
        final_loss=round(r["loss"], 6),
        wall_s=round(time.time() - t0, 2))
    return cell


def run_adapt_cells(rig: "AdaptRig" = None) -> list:
    rig = rig if rig is not None else AdaptRig()
    cells = []
    cell = run_adapt_steady_cell(rig)
    log(f"cell adapt steady            : "
        f"{'ok' if cell['ok'] else 'FAILED':9s} "
        f"switches={cell.get('switches')} "
        f"recompiles={cell.get('recompiles_across_switch')} "
        f"({cell['wall_s']:.1f}s)")
    cells.append(cell)
    cell = run_adapt_shift_cell(rig)
    log(f"cell adapt slowdown-shift    : "
        f"{'detected' if cell.get('detected') else 'FAILED':9s} "
        f"switch {cell.get('from_plan')} -> {cell.get('to_plan')} "
        f"@ step {cell.get('switch_step')} "
        f"recompiles={cell.get('recompiles_across_switch')} "
        f"({cell['wall_s']:.1f}s)")
    cells.append(cell)
    return cells


RESHARD_CODECS = (None, "bfp", "topk", "int8")


def run_reshard_row(kind: str, codec, ecfg: ElasticConfig,
                    n_steps: int = 6, n_src: int = 8,
                    n_tgt: int = 4) -> dict:
    """One RESHARD_BENCH row: trainer x codec through the SAME tier
    harness as the matrix's preempt-shrink cell (_tier_comparison),
    plus the plan's exact wire-byte accounting (the only number the
    obs gate holds dryrun artifacts to)."""
    t0 = time.time()
    axis = "dp" if kind == "dp" else "fsdp"
    cls = DPTrainer if kind == "dp" else FSDPTrainer

    def build(n):
        cfg = TrainConfig(
            iters=n_steps, global_batch=64, mesh=MeshConfig(**{axis: n}),
            collective=CollectiveConfig(impl="ring", codec=codec),
            optimizer=OptimizerConfig(kind="adamw", learning_rate=3e-3))
        return cls(_loss_fn, make_mesh(cfg.mesh), cfg)

    src = build(n_src)
    params0 = jax.device_get(mlp.init(jax.random.PRNGKey(0), MCFG))
    host_batch = _data()
    batch = src.shard_batch(host_batch)

    def fresh_state():
        return src.init_state(
            jax.tree_util.tree_map(jnp.asarray, params0))

    state0 = fresh_state()
    src.step_fn.lower(state0, batch).compile()
    tgt_cache = {}

    def factory(n):
        if n not in tgt_cache:
            tgt_cache[n] = build(n)
        return tgt_cache[n]

    row = {"trainer": kind, "codec": codec or "none",
           "shrink": f"{axis}{n_src}->{axis}{n_tgt}", "steps": n_steps,
           "prewarmed": True}
    row.update(_tier_comparison(src, factory, fresh_state, batch,
                                host_batch, ecfg, n_steps, n_tgt))
    row.update(wall_s=round(time.time() - t0, 2))
    return row


def run_reshard_bench(ecfg: ElasticConfig, plat: str) -> dict:
    """The full trainer x codec MTTR matrix (`--reshard-bench`, banked as
    RESHARD_BENCH artifact by `make reshard-bench`)."""
    rows = []
    for kind in ("dp", "fsdp"):
        for codec in RESHARD_CODECS:
            row = run_reshard_row(kind, codec, ecfg)
            log(f"reshard {kind:4s} x {row['codec']:5s}: "
                f"{'ok' if row['ok'] else 'FAILED':6s} "
                f"mttr reshard={row.get('mttr_reshard_s')}s vs "
                f"restore={row.get('mttr_restore_s')}s "
                f"speedup={row.get('mttr_speedup')} "
                f"({row['wall_s']:.1f}s)")
            rows.append(row)
    beats = [r["reshard_beats_restore"] for r in rows
             if r.get("reshard_beats_restore") is not None]
    return {
        "bench": "reshard_mttr",
        "platform": plat,
        "n_devices": len(jax.devices()),
        # CPU rows are dryrun-class per the artifact-honesty convention:
        # MTTRs are recorded for inspection, but oversubscription noise
        # means only the plan's exact byte accounting is gate-worthy
        # (tools/obs_gate.py RESHARD_BYTE_KEYS); re-run on a TPU surface
        # for a gated timing verdict
        "dryrun": plat != "tpu",
        "prewarmed": True,
        "rows": rows,
        "reshard_beats_restore_rows": sum(beats),
        "rows_with_timing": len(beats),
        "ok": all(r["ok"] for r in rows),
    }


def run_soak(rig: WireRig, ecfg: ElasticConfig, n_steps: int) -> dict:
    """One longer run under a seeded random mixed-fault schedule — the
    'production weather' complement to the one-fault-per-cell matrix."""
    t0 = time.time()
    plan = chaos.FaultPlan.random(SEED, n_steps, rate=0.4, duration_s=0.05)
    state = rig.fresh_state()
    with tempfile.TemporaryDirectory() as d, chaos.activate(plan):
        et = ElasticTrainer(rig.trainer, d, ecfg, plan=plan,
                            stage_fn=plan.stage)
        try:
            state, metrics = et.run(state, lambda i: rig.batch, n_steps)
        except Exception as err:  # noqa: BLE001 — the verdict IS the point
            return {"wire": rig.wire, "steps": n_steps,
                    "planned_faults": len(plan.faults),
                    "fired": len(plan.fired), "ok": False,
                    "error": repr(err),
                    "recovery": et.profiler.recovery.as_dict(),
                    "wall_s": round(time.time() - t0, 2)}
        rec = et.profiler.recovery.as_dict()
        report = et.profiler.report()
    loss = float(metrics["loss"])
    return {"wire": rig.wire, "steps": n_steps,
            "planned_faults": len(plan.faults),
            "fired": len(plan.fired),
            "ok": bool(int(state.step) == n_steps and np.isfinite(loss)),
            "final_loss": round(loss, 6),
            "recovery": rec,
            "profiler_report": report,
            "wall_s": round(time.time() - t0, 2)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized timeouts/durations (the matrix itself is "
                         "always full)")
    ap.add_argument("--wire", choices=sorted(WIRES), default=None,
                    help="restrict to one wire format (default: all)")
    ap.add_argument("--serve-only", action="store_true",
                    help="run ONLY the serving SLO-under-fault cells "
                         "(the CI-sized gate; the full matrix also "
                         "includes them)")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run ONLY the fleet cells (replica-kill KV "
                         "migration + handoff-fault degradation; the "
                         "CI-sized gate — the full matrix also includes "
                         "them)")
    ap.add_argument("--integrity-only", action="store_true",
                    help="run ONLY the wirebit integrity cells (the "
                         "finite-corruption class at every wire site, "
                         "exact-tier trips + token-/bit-exact recovery; "
                         "the CI-sized gate — the full matrix also "
                         "includes them)")
    ap.add_argument("--adapt-only", action="store_true",
                    help="run ONLY the adaptive-tuning cells (sustained "
                         "slowdown@collective regime shift detected "
                         "from measured-vs-modeled residuals -> "
                         "step-boundary switch to a pre-compiled plan "
                         "with zero new traces, plus the zero-switch "
                         "steady guard; the CI-sized gate — the full "
                         "matrix also includes them)")
    ap.add_argument("--durability-only", action="store_true",
                    help="run ONLY the durability cells (faults at the "
                         "checkpoint plane: stored-bit flip -> peer "
                         "repair, stale manifest -> walk-back, "
                         "kill-during-save / disk-full absorbed by the "
                         "commit protocol, ladder exhaustion -> "
                         "emergency dump; the CI-sized gate — the full "
                         "matrix also includes them)")
    ap.add_argument("--reshard-bench", action="store_true",
                    help="run the trainer x codec reshard-vs-restore MTTR "
                         "matrix instead of the fault matrix (banked as "
                         "the RESHARD_BENCH artifact by `make "
                         "reshard-bench`)")
    ap.add_argument("--out", default=None,
                    help="also write the verdict JSON to this path")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip the artifacts/ evidence write")
    args = ap.parse_args()

    n_steps = 6
    soak_steps = 10 if args.fast else 24
    timeout_s = 1.5 if args.fast else 4.0
    hang_s = timeout_s * 2.5          # decisively past the watchdog
    slow_s = timeout_s * 0.15         # decisively below it
    ecfg = ElasticConfig(step_timeout_s=timeout_s, stall_after_s=60.0,
                         max_retries=4, backoff_s=0.01, ckpt_every=1)

    plat = jax.devices()[0].platform
    log(f"platform={plat} devices={len(jax.devices())} fast={args.fast}")
    chaos.install_collective_tap()     # before any step is traced
    # the ENCODED-payload wire tap rides next to it (identity copy when
    # no wirebit spec is pending): the integrity cells corrupt encoded
    # ring frames / reshard segments / handoff page blocks through it.
    # The tap is consulted at TRACE time and must precede ALL tracing
    # when wirebit cells will run (the reshard/handoff transfer
    # programs are module-level lru-memoized — a late install would
    # reuse tap-free programs and the specs would silently never fire),
    # but it threads one host callback per payload per hop into every
    # traced collective, so the serve-/fleet-only/reshard-bench lanes —
    # whose cells never fire wirebit through the wire tap — skip it and
    # keep their banked MTTR rows tap-free (comparable with the
    # pre-tap rounds' artifacts)
    if not (args.serve_only or args.fleet_only or args.reshard_bench
            or args.adapt_only or args.durability_only):
        chaos.install_wire_tap()

    if args.durability_only:
        durability_cells = run_durability_cells(ecfg, n_steps)
        result = {
            "bench": "chaos_durability",
            "fast": args.fast,
            "platform": plat,
            "n_devices": len(jax.devices()),
            "dryrun": plat != "tpu",
            "durability_cells": durability_cells,
            "ok": all(c["ok"] for c in durability_cells),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("chaos_durability", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "durability_cells"} |
                         {"durability_cells_ok":
                          sum(c["ok"] for c in durability_cells),
                          "durability_cells_total":
                          len(durability_cells)}, indent=1))
        return 0 if result["ok"] else 1

    if args.adapt_only:
        adapt_cells = run_adapt_cells()
        result = {
            "bench": "chaos_adapt",
            "fast": args.fast,
            "platform": plat,
            "n_devices": len(jax.devices()),
            "dryrun": plat != "tpu",
            "adapt_cells": adapt_cells,
            "ok": all(c["ok"] for c in adapt_cells),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("chaos_adapt", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "adapt_cells"} |
                         {"adapt_cells_ok":
                          sum(c["ok"] for c in adapt_cells),
                          "adapt_cells_total": len(adapt_cells)},
                         indent=1))
        return 0 if result["ok"] else 1

    if args.integrity_only:
        integrity_cells = run_integrity_cells(ecfg, n_steps, timeout_s)
        result = {
            "bench": "chaos_integrity",
            "fast": args.fast,
            "platform": plat,
            "n_devices": len(jax.devices()),
            "dryrun": plat != "tpu",
            "integrity_cells": integrity_cells,
            "ok": all(c["ok"] for c in integrity_cells),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("chaos_integrity", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "integrity_cells"} |
                         {"integrity_cells_ok":
                          sum(c["ok"] for c in integrity_cells),
                          "integrity_cells_total":
                          len(integrity_cells)}, indent=1))
        return 0 if result["ok"] else 1

    if args.fleet_only:
        fleet_cells = run_fleet_cells()
        result = {
            "bench": "chaos_fleet",
            "fast": args.fast,
            "platform": plat,
            "n_devices": len(jax.devices()),
            "dryrun": plat != "tpu",
            "fleet_cells": fleet_cells,
            "ok": all(c["ok"] for c in fleet_cells),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("chaos_fleet", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "fleet_cells"} |
                         {"fleet_cells_ok":
                          sum(c["ok"] for c in fleet_cells),
                          "fleet_cells_total": len(fleet_cells)},
                         indent=1))
        return 0 if result["ok"] else 1

    if args.serve_only:
        serve_cells = run_serve_cells(timeout_s, hang_s, slow_s)
        result = {
            "bench": "chaos_serve",
            "fast": args.fast,
            "platform": plat,
            "n_devices": len(jax.devices()),
            "dryrun": plat != "tpu",
            "serve_cells": serve_cells,
            "ok": all(c["ok"] for c in serve_cells),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("chaos_serve", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "serve_cells"} |
                         {"serve_cells_ok":
                          sum(c["ok"] for c in serve_cells),
                          "serve_cells_total": len(serve_cells)},
                         indent=1))
        return 0 if result["ok"] else 1

    if args.reshard_bench:
        result = run_reshard_bench(ecfg, plat)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        if not args.no_artifact:
            save_artifact("reshard_bench", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "rows"} |
                         {"rows_ok": sum(r["ok"] for r in result["rows"]),
                          "rows_total": len(result["rows"])}, indent=1))
        return 0 if result["ok"] else 1

    wires = [args.wire] if args.wire else sorted(WIRES)
    cells, soaks, shrink_cells = [], [], []
    wire_rig_map = {}
    for wire in wires:
        rig = WireRig(wire, n_steps)
        wire_rig_map[wire] = rig
        for kind, site, mode in _legal_cells():
            cell = run_cell(rig, kind, site, mode, ecfg, n_steps,
                            hang_s, slow_s)
            verdict = ("recovered" if cell.get("recovered")
                       else "absorbed" if cell.get("absorbed")
                       else "FAILED")
            log(f"cell wire={wire} {kind:10s} @ {site:12s}: {verdict:9s} "
                f"faults={cell.get('faults')} "
                f"mttr={cell.get('mttr_mean_s', 0):.3f}s "
                f"({cell['wall_s']:.1f}s)")
            cells.append(cell)
        # the preempt-shrink cell: the same preemption recovered by BOTH
        # tiers — live reshard (dp8->dp4, no checkpoint) vs restore
        shrink = run_shrink_cell(rig, ecfg, n_steps)
        log(f"cell wire={wire} preempt-shrink {shrink['shrink']}: "
            f"{'recovered' if shrink['ok'] else 'FAILED':9s} "
            f"mttr reshard={shrink.get('mttr_reshard_s')}s vs "
            f"restore={shrink.get('mttr_restore_s')}s "
            f"({shrink['wall_s']:.1f}s)")
        shrink_cells.append(shrink)
        soak = run_soak(rig, ecfg, soak_steps)
        log(f"soak wire={wire}: ok={soak['ok']} "
            f"fired={soak['fired']}/{soak['planned_faults']} "
            f"recoveries={soak['recovery']['recoveries']} "
            f"({soak['wall_s']:.1f}s)")
        soaks.append(soak)

    # the serving plane's cell battery: request-level SLO (completion +
    # token-exactness + recovery class) under the same fault kinds
    serve_rig = ServeRig()
    serve_cells = run_serve_cells(timeout_s, hang_s, slow_s,
                                  rig=serve_rig)
    # the fleet battery: replica-kill KV migration + handoff degradation
    fleet_rig = FleetRig()
    fleet_cells = run_fleet_cells(rig=fleet_rig)
    # the wirebit integrity battery: the finite-corruption class at
    # every wire, exact tier trips, token-/bit-exact recovery
    integrity_cells = run_integrity_cells(
        ecfg, n_steps, timeout_s, wire_rigs=wire_rig_map,
        serve_rig=serve_rig, fleet_rig=fleet_rig)
    # the durability battery: faults at the checkpoint plane itself
    # (stored-bit flip -> peer repair, stale manifest -> walk-back,
    # kill-during-save / disk-full, ladder exhaustion -> emergency dump)
    durability_cells = run_durability_cells(
        ecfg, n_steps, rig=wire_rig_map.get("f32"))
    # the adaptive-tuning battery: forced regime shift -> detection ->
    # recompile-free plan switch, plus the zero-switch steady guard
    adapt_cells = run_adapt_cells()

    result = {
        "bench": "chaos_matrix",
        "fast": args.fast,
        "platform": plat,
        "n_devices": len(jax.devices()),
        "dryrun": plat != "tpu",       # CPU-mesh evidence, marked as such
        "matrix": {"kinds": list(chaos.FAULT_KINDS),
                   "sites": list(chaos.TRAIN_SITES), "wires": wires,
                   "serve_site": "serve.step",
                   "fleet_sites": ["fleet.membership", "serve.handoff"],
                   "integrity_sites": ["collective", "reshard.transfer",
                                       "serve.step", "serve.handoff"],
                   "adapt_cells": ["steady", "slowdown-shift"],
                   "durability_sites": list(chaos.CKPT_SITES)},
        "cells": cells,
        "shrink_cells": shrink_cells,
        "serve_cells": serve_cells,
        "fleet_cells": fleet_cells,
        "integrity_cells": integrity_cells,
        "durability_cells": durability_cells,
        "adapt_cells": adapt_cells,
        "soak": soaks,
        "ok": (all(c["ok"] for c in cells)
               and all(c["ok"] for c in shrink_cells)
               and all(c["ok"] for c in serve_cells)
               and all(c["ok"] for c in fleet_cells)
               and all(c["ok"] for c in integrity_cells)
               and all(c["ok"] for c in durability_cells)
               and all(c["ok"] for c in adapt_cells)
               and all(s["ok"] for s in soaks)),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if not args.no_artifact:
        save_artifact("chaos", result)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("cells", "soak")} |
                     {"cells_ok": sum(c["ok"] for c in cells),
                      "cells_total": len(cells)}, indent=1))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
