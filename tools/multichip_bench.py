#!/usr/bin/env python
"""Multi-chip measurement kit.

The fused wire path's first run on a real ring is `chip_smoke.py --chips 4`
(a correctness proof, no rates); this tool is the measurement that follows
it, one command on a multi-chip host through the chip tool:

    make multichip-bench          # real hardware (needs >= 2 real chips)
    make multichip-dryrun         # 8-device virtual CPU mesh validation

The parent imports no jax: each stage child is in turn the one process
that holds the chips.  Stages escalate, each under its own watchdog, each
banked as soon as it ends:

  canary   tiny-payload parity on the real mesh: XLA psum vs numpy, and
           the fused Pallas BFP ring vs the XLA BFP ring (bit-identical
           per-hop quantization) — a protocol bug burns seconds here.
  busbw    the headline measurement the reference made on its 3-FPGA
           ring (readme.pdf §4.1): bf16 psum vs explicit f32 ring vs
           BFP-compressed ring vs the fused kernel, swept over payload
           sizes, slope-timed (K vs 2K chained steps in one dispatch so
           the per-dispatch floor cancels), busbw accounting
           2*(n-1)/n.  THE CLAIM THIS WILL SETTLE: whether per-hop BFP
           compression (3.76x fewer wire bytes than f32,
           hw/bfp_adapter.sv:30,63-77) beats the uncompressed psum on
           real ICI — the repo's break-even table says the codec must
           sustain 2*W GB/s per direction at link rate W; the fused
           kernel's loopback rate is the current bound.
  trace    a sharded DP train step under jax.profiler.trace ->
           trace_analysis.analyze_any -> per-collective overlapped vs
           exposed seconds (the stall attribution of
           hw/all_reduce.sv:94-97) banked in the same artifact.

--dryrun runs the identical stage children on the virtual CPU mesh
(JAX_PLATFORMS=cpu, 8 devices): rates are memory-bound and meaningless,
but every code path the real window needs is executed end to end, and
the artifacts are marked {"dryrun": true} so they can never be mistaken
for hardware evidence.  The fused-ring stages cap the dryrun mesh at
n=4 — the threaded Mosaic interpreter's validated envelope
(tests/test_ring_pallas.py; n=8 livelocks in kernel-entry allocation).

State: artifacts/multichip_state.json, keyed separately for real vs
dryrun; re-runs skip banked stages (--force redoes).
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from bench_common import (cpu_env, log, run_attempt,  # noqa: E402
                          save_artifact)

STATE_PATH = os.path.join(REPO, "artifacts", "multichip_state.json")
SWEEP_MB = (16, 64)
CHAIN_K = 8


def _load_state() -> dict:
    try:
        with open(STATE_PATH) as f:
            return json.load(f)
    except Exception:  # noqa: BLE001
        return {}


def _save_state(state: dict) -> None:
    os.makedirs(os.path.dirname(STATE_PATH), exist_ok=True)
    with open(STATE_PATH, "w") as f:
        json.dump(state, f, indent=1)


# ---------------------------------------------------------------------------
# stage children
# ---------------------------------------------------------------------------

def _child_common():
    t0 = time.time()
    print("[bench] phase=import t=0.0s", flush=True)
    import jax
    import jax.numpy as jnp
    from bench_common import enable_compile_cache
    enable_compile_cache()
    print(f"[bench] phase=devices t={time.time() - t0:.1f}s", flush=True)
    n = jax.device_count()
    platform = jax.default_backend()
    dryrun = os.environ.get("MULTICHIP_DRYRUN") == "1"
    if not dryrun:                  # --dryrun is the CPU-mesh mode
        from bench_common import require_tpu
        require_tpu("multichip_bench")
    if not dryrun and n < 2:
        print(json.dumps({"ok": False, "skipped": True, "n_devices": n,
                          "reason": "needs >= 2 real chips; this surface "
                                    "has one — run --dryrun for the "
                                    "virtual-mesh validation"}), flush=True)
        sys.exit(0)
    _scalar = jax.jit(lambda t: sum(
        jnp.sum(jnp.asarray(l).astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(tree):
        return float(_scalar(tree))

    return t0, jax, n, platform, dryrun, sync


def child_canary() -> None:
    t0, jax, n, platform, dryrun, sync = _child_common()
    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from fpga_ai_nic_tpu.ops import ring as ring_ops
    from fpga_ai_nic_tpu.ops import ring_pallas as rp
    from fpga_ai_nic_tpu.utils.config import BFPConfig

    out = {"stage": "canary", "platform": platform, "n_devices": n,
           "dryrun": dryrun, "checks": {}}
    # fused-kernel mesh: the threaded interpreter (dryrun) is validated
    # to n=4; real hardware uses every chip.  codec="pallas" on BOTH
    # rings: the fused kernel's in-kernel codec is the pallas sublane
    # layout, and the bit-exact contract (test_ring_pallas) holds only
    # when the XLA-op ring runs the identical codec
    n_fused = min(n, 4) if dryrun else n
    cfg = BFPConfig(codec="pallas")

    def check(name, fn):
        print(f"[bench] phase=canary_{name} t={time.time() - t0:.1f}s",
              flush=True)
        try:
            ok, detail = fn()
            out["checks"][name] = {"ok": bool(ok), **detail}
        except Exception as e:  # noqa: BLE001
            out["checks"][name] = {"ok": False, "error": repr(e)[:300]}

    def psum_parity():
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        L = n * 2048
        x = jax.random.normal(jax.random.PRNGKey(0), (L,), jnp.float32)
        f = jax.jit(jax.shard_map(
            lambda v: lax.psum(lax.pcast(v, "dp", to="varying"), "dp"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        got = np.asarray(f(x))
        want = np.asarray(x) * n
        return np.allclose(got, want, rtol=1e-6), {}

    def bfp_ring_parity():
        # fused Pallas ring vs the XLA-op ring on the SAME codec + slice
        # plan: bit-exact by contract (test_ring_pallas bit-exactness
        # suite; transitively golden vs ops.bfp_golden)
        mesh = Mesh(np.array(jax.devices()[:n_fused]), ("dp",))
        SLICE = cfg.block_size * rp.LANES
        C = SLICE * 2
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (n_fused * n_fused * C,), jnp.float32)

        def shmap(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                check_vma=False))

        xla_ring = shmap(lambda v: ring_ops.ring_all_reduce(
            v, "dp", compression=cfg, slice_elems=SLICE))
        fused = shmap(lambda v: rp.ring_all_reduce_fused(
            v, "dp", compression=cfg, slice_elems=SLICE))
        a, b = np.asarray(xla_ring(x)), np.asarray(fused(x))
        bit_exact = bool((a == b).all() and np.isfinite(a).all())
        return bit_exact, {"bit_exact": bit_exact, "n_fused": n_fused}

    check("psum_parity", psum_parity)
    if not dryrun or n >= 2:
        check("fused_bfp_ring_parity", bfp_ring_parity)
    out["ok"] = all(c.get("ok") for c in out["checks"].values())
    out["t_total"] = round(time.time() - t0, 1)
    print(json.dumps(out), flush=True)


def child_busbw() -> None:
    t0, jax, n, platform, dryrun, sync = _child_common()
    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from bench_common import is_tpu_platform, slope_timeit
    from fpga_ai_nic_tpu.ops import ring as ring_ops
    from fpga_ai_nic_tpu.ops import ring_pallas as rp
    from fpga_ai_nic_tpu.utils.config import BFPConfig

    on_tpu = is_tpu_platform(platform)
    cfg = BFPConfig(codec="auto" if on_tpu else "xla")
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    out = {"stage": "busbw", "platform": platform, "n_devices": n,
           "dryrun": dryrun, "sweep": [],
           "method": f"slope over K/2K chained all-reduces (K={CHAIN_K}) "
                     "in one dispatch; busbw = 2*(n-1)/n * bytes / t",
           "claim_when_real": (
               "on >= 2 real chips this table is the reference's §4.1 "
               "measurement: ring_bfp vs psum_bf16 busbw decides whether "
               "per-hop BFP compression wins on ICI (break-even: each "
               "codec direction must sustain 2x the per-direction link "
               "rate; wire ratio 3.76x vs f32 / 1.88x vs bf16)")}

    def shmap(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False))

    inv_n = 1.0 / n

    def make_chain(coll):
        # v <- coll(v) * (1/n): data-dependent chain, values stay bounded
        # (all-reduce multiplies magnitude by n); the elementwise rescale
        # is O(bytes) vs the collective's O(wire) — noted in the method
        def mk(k):
            def body_fn(v):
                def body(i, v):
                    return coll(v) * inv_n
                return lax.fori_loop(0, k, body, v)
            return shmap(lambda v: body_fn(lax.pcast(v, "dp",
                                                     to="varying")))
        return mk

    bus = 2 * (n - 1) / n
    sizes = SWEEP_MB if not dryrun else (4,)
    for mb in sizes:
        L = mb * (1 << 20) // 4
        L -= L % (n * cfg.block_size * 128)
        # slice plan derived from the actual per-device chunk — a
        # hard-coded 8192 does not divide the chunk on non-power-of-two
        # rings (the reference's own topology was THREE nodes)
        sl = rp.pick_slice_elems(L // n, 8192, cfg.block_size)
        print(f"[bench] phase=sweep_{mb}MiB t={time.time() - t0:.1f}s "
              f"slice={sl}", flush=True)
        xs = jax.random.normal(jax.random.PRNGKey(1), (L,), jnp.float32)
        xb = xs.astype(jnp.bfloat16)
        row = {"size_mb": mb, "slice_elems": sl}
        impls = [
            ("psum_bf16", lambda v: lax.psum(v, "dp"), xb, L * 2),
            ("ring_f32", lambda v: ring_ops.ring_all_reduce(v, "dp"),
             xs, L * 4),
            ("ring_bfp", lambda v: ring_ops.ring_all_reduce(
                v, "dp", compression=cfg, slice_elems=sl), xs, L * 4),
        ]
        if on_tpu:
            impls.append(("fused_bfp", lambda v: rp.ring_all_reduce_fused(
                v, "dp", compression=cfg, slice_elems=sl), xs, L * 4))
        for name, coll, x, nbytes in impls:
            try:
                t_iter, diag = slope_timeit(make_chain(coll), (x,),
                                            CHAIN_K, sync)
                if t_iter > 0:
                    row[f"{name}_gbps"] = round(bus * nbytes / t_iter / 1e9,
                                                3)
                    row[f"{name}_diag"] = diag
                else:
                    row[f"{name}_error"] = "non-positive slope (noise)"
                print(f"[bench] {mb}MiB {name}: "
                      f"{row.get(f'{name}_gbps')} GB/s", flush=True)
            except Exception as e:  # noqa: BLE001
                row[f"{name}_error"] = repr(e)[:200]
                print(f"[bench] {mb}MiB {name} failed: {e!r}", flush=True)
        if "ring_bfp_gbps" in row and "psum_bf16_gbps" in row:
            row["bfp_speedup_vs_psum_bf16"] = round(
                row["ring_bfp_gbps"] / row["psum_bf16_gbps"], 3)
        out["sweep"].append(row)
    out["ok"] = any(any(k.endswith("_gbps") for k in r) for r in out["sweep"])
    bfp_rows = [r["ring_bfp_gbps"] for r in out["sweep"]
                if "ring_bfp_gbps" in r]
    if bfp_rows:
        out["value"] = max(bfp_rows)
        out["unit"] = "GB/s"
    elif out["ok"]:
        # other impls measured but the BFP ring produced no number on any
        # row: an explicit invalid marker, never a fake 0.0 GB/s headline
        # (same convention as bench_collective's fused_ring_loopback_error)
        out["ring_bfp_error"] = next(
            (r["ring_bfp_error"] for r in out["sweep"]
             if "ring_bfp_error" in r),
            "no sweep row produced ring_bfp_gbps")
    print(json.dumps(out), flush=True)


def child_trace() -> None:
    t0, jax, n, platform, dryrun, sync = _child_common()
    import tempfile
    import numpy as np
    import jax.numpy as jnp
    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
    from fpga_ai_nic_tpu.utils import trace_analysis as ta
    from fpga_ai_nic_tpu.utils.config import (CollectiveConfig, MeshConfig,
                                              MLPConfig, OptimizerConfig,
                                              TrainConfig)

    out = {"stage": "trace", "platform": platform, "n_devices": n,
           "dryrun": dryrun}
    mcfg = MLPConfig(layer_sizes=(2048,) * 4, dtype="float32")
    cfg = TrainConfig(iters=4, global_batch=n * 128,
                      mesh=MeshConfig(dp=n),
                      collective=CollectiveConfig(impl="ring"),
                      optimizer=OptimizerConfig(kind="momentum"))
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, mcfg), make_mesh(cfg.mesh),
                   cfg)
    state = tr.init_state(mlp.init(jax.random.PRNGKey(0), mcfg))
    kx = jax.random.PRNGKey(1)
    x = jax.random.normal(kx, (cfg.global_batch, 2048), jnp.float32)
    y = jax.random.randint(kx, (cfg.global_batch,), 0, 2048, jnp.int32)
    batch = tr.shard_batch((x, y))
    print(f"[bench] phase=warmup t={time.time() - t0:.1f}s", flush=True)
    state, _ = tr.step(state, batch)
    sync(state.params)
    tdir = tempfile.mkdtemp(prefix="multichip_trace_")
    print(f"[bench] phase=trace t={time.time() - t0:.1f}s", flush=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 3       # CPU thunk mode needs per-op events
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for _ in range(cfg.iters):
        state, loss = tr.step(state, batch)
    sync(state.params)
    jax.profiler.stop_trace()
    print(f"[bench] phase=analyze t={time.time() - t0:.1f}s", flush=True)
    rep = ta.analyze_any(tdir)
    agg = ta.summarize(rep)
    out["overlap"] = agg
    out["mode"] = next(iter(rep["devices"].values())).get("mode",
                                                          "device-planes")
    out["ok"] = agg["async_collective_s"] > 0
    out["note"] = ("async_collective_s > 0 closes round-4's 'collective "
                   "overlap never attributed anywhere real' gap; "
                   "overlapped vs exposed is the hw/all_reduce.sv:94-97 "
                   "stall split")
    import shutil
    shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)


CHILDREN = {"canary": child_canary, "busbw": child_busbw,
            "trace": child_trace}

STAGES = [
    ("canary", 240.0, 120.0),
    ("busbw", 480.0, 200.0),
    ("trace", 420.0, 200.0),
]


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        CHILDREN[sys.argv[2]]()
        return 0
    dryrun = "--dryrun" in sys.argv
    force = "--force" in sys.argv
    known = {s[0] for s in STAGES}
    only = None
    for a in sys.argv[1:]:
        if a.startswith("--stages="):       # e.g. --stages=canary,busbw
            only = {s for s in a.split("=", 1)[1].split(",") if s}
            bad = only - known
            if bad or not only:
                # an unattended run that silently matched zero stages
                # would log 'complete' having done nothing
                log(f"--stages: unknown/empty {sorted(bad) or '(empty)'}; "
                    f"valid: {sorted(known)}")
                return 2
    key = "dryrun" if dryrun else "real"
    state = _load_state()
    done = state.setdefault(key, {})
    if force:
        # clear only what this invocation will re-run: a filtered --force
        # must not wipe banked evidence (incl. the canary gate) for
        # stages it is not going to redo
        for name in (only or known):
            done.pop(name, None)
    env = cpu_env(8) if dryrun else dict(os.environ)
    env["MULTICHIP_DRYRUN"] = "1" if dryrun else "0"
    here = os.path.abspath(__file__)
    rc = 0
    for name, budget, silence in STAGES:
        if only is not None and name not in only:
            continue
        if name in done:
            log(f"stage {name} [{key}]: already banked — skipping")
            continue
        if name != "canary" and not done.get("canary", {}).get("ok"):
            log(f"stage {name}: no passing canary — refusing to escalate")
            return 1
        log(f"=== stage {name} [{key}] ===")
        try:
            result = run_attempt(
                name, [sys.executable, "-u", here, "--child", name],
                env=env, budget_s=budget, silence_s=silence, cwd=REPO)
        except Exception as e:  # noqa: BLE001
            log(f"stage {name} failed: {e}")
            if name == "canary":
                return 1
            rc = 1
            continue
        if result.get("skipped"):
            log(f"stage {name}: {result.get('reason')}")
            print(json.dumps(result), flush=True)
            return 3
        ok = bool(result.get("ok"))
        save_artifact(f"multichip_{name}" + ("_dryrun" if dryrun else ""),
                      result)
        if ok:
            done[name] = {"ok": True, "at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
            _save_state(state)
        else:
            rc = 1          # executed-but-failed: artifact banked for
            # forensics, exit nonzero so an unattended caller retries
        if name == "canary" and not ok:
            log("canary FAILED — banked evidence; refusing to escalate")
            return 1
    log(f"multichip ladder [{key}] complete: {sorted(done)}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
