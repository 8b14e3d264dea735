#!/usr/bin/env python
"""All-reduce bandwidth benchmark — the first-named BASELINE metric:
"all-reduce GB/s over ICI (bf16 vs BFP-compressed)".

Measures three implementations over a sweep of flat-vector sizes:

  - psum_bf16:  XLA's native all-reduce on bf16 (the TPU incumbent)
  - ring_f32:   the explicit ppermute ring, uncompressed f32
  - ring_bfp:   the same ring with per-hop BFP compression
                (8-bit mantissa, shared exponent per 16 — 3.76x fewer wire
                bytes than f32, 1.88x than bf16; hw/bfp_adapter.sv:30,63-77)

plus standalone codec throughput (encode/decode GB/s), which bounds the
compressed ring's critical path on a single chip.

Bandwidth accounting follows the standard ring model: an n-device
all-reduce of B bytes moves 2*(n-1)/n * B per device over the wire, so
  busbw = 2*(n-1)/n * B / t      (the "effective" wire bandwidth)
  algbw = B / t                  (application-visible)
The reference's comparable envelope: 80 Gbps link model (readme.pdf §3.2),
3.76x wire ratio under BFP.

Single-chip runs (the current TPU surface) measure codec throughput and
report the *projected* BFP ring advantage = wire-ratio / codec-overhead;
multi-device meshes (virtual CPU mesh here, real multi-chip ICI when
available) measure the rings directly.

The parent never imports jax, so its one child is the one process that
holds the chip; the child fails where jax finds no TPU — a rate from any
other platform is not a device metric.  Run it through the chip tool.
"""

import json
import os
import sys
import time

from bench_common import (enable_compile_cache, is_tpu_platform, log,
                          require_tpu, run_attempt, save_artifact,
                          slope_timeit)

SWEEP_MB = (16, 64, 256)          # flat f32 vector sizes to sweep
CODEC_MB = 64                     # standalone codec payload
CODEC_K = 64                      # slope-measurement chain length
TIMED_ITERS = 3


# ---------------------------------------------------------------------------
# child
# ---------------------------------------------------------------------------

def _timeit(fn, sync, iters=TIMED_ITERS):
    """Median-free simple timing: warmup (compile) + timed loop + honest
    sync (jitted scalar reduction fetch)."""
    out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / iters


def child_main() -> None:
    t0 = time.time()

    # structured telemetry: every phase lands as a span in an obs event
    # stream, and the artifact carries the stream's summary — the same
    # DETAILED_PROFILE-style wall-clock breakdown the trainers get,
    # without grepping [bench] log lines
    from fpga_ai_nic_tpu.obs import EventStream
    events = EventStream()
    _open_phase = [None]            # (name, ns) of the running phase span

    def phase(name):
        now = EventStream.now_ns()
        if _open_phase[0] is not None:
            pname, pns = _open_phase[0]
            events.emit("span", f"phase.{pname}", t_ns=pns,
                        dur_ns=now - pns)
        _open_phase[0] = (name, now)
        log(f"phase={name} t={time.time() - t0:.1f}s")

    phase("import")
    import jax
    require_tpu("bench_collective")
    enable_compile_cache()
    phase("devices")
    n_dev = jax.device_count()
    platform = jax.default_backend()
    log(f"platform={platform} n_dev={n_dev}")

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from fpga_ai_nic_tpu.ops import ring as ring_ops
    from fpga_ai_nic_tpu.utils.config import BFPConfig

    cfg = BFPConfig()   # 16-elem blocks, 8-bit mantissa — the wire format
    # On TPU use the fused Pallas codec (the wire-path kernel); off TPU the
    # XLA codec (pallas interpret mode would measure the emulator).
    from bench_common import is_tpu_platform
    on_tpu = is_tpu_platform(platform)
    codec_cfg = BFPConfig(codec="auto" if on_tpu else "xla")

    _scalar = jax.jit(lambda t: sum(
        jnp.sum(l.astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(tree):
        return float(_scalar(tree))

    report = {
        "metric": "allreduce_busbw_gbps",
        "unit": "GB/s",
        "platform": platform,
        "n_devices": n_dev,
        "wire_compression_vs_f32": round(cfg.compression_ratio_vs_f32, 3),
        "wire_compression_vs_bf16": round(cfg.compression_ratio_vs_f32 / 2, 3),
    }

    # -- standalone codec throughput (always; single-chip meaningful) -------
    # SLOPE-based (round-5 fix): r04's K=4 chains under the per-dispatch
    # floor reported rates that were provably floored — measured
    # roundtrip (10.76 GB/s) was ~2x the harmonic sum of its own measured
    # stages (6.1 GB/s), impossible for a compute-bound pipeline.  Timing
    # chains of K and 2K data-dependent iterations and differencing kills
    # every per-dispatch constant; a self-consistency field below makes
    # the artifact flag itself if the stages still don't add up.
    phase(f"codec throughput ({CODEC_MB} MiB, slope K={CODEC_K})")
    n_elems = CODEC_MB * (1 << 20) // 4
    x = jax.random.normal(jax.random.PRNGKey(0), (n_elems,), jnp.float32)
    enc_fn, dec_fn = ring_ops._codec(codec_cfg, n_elems)
    gb = n_elems * 4 / 1e9

    def make_rt_chain(k):
        # roundtrip: v <- dec(enc(v)) is naturally data-dependent, so the
        # loop body can neither be hoisted nor overlapped across iterations
        @jax.jit
        def chain(v):
            def body(i, v):
                m, s = enc_fn(v)
                return dec_fn(m, s, v.dtype)
            return lax.fori_loop(0, k, body, v)
        return chain

    # Output consumption: the chains must consume the codec outputs or XLA
    # dead-code-eliminates the work (measured on the CPU rung: consuming
    # only s[0] let XLA slice the encode down to ONE 16-element block —
    # 1,963 "GB/s").  A pallas_call is an opaque custom call, so consuming
    # ANY output runs the WHOLE kernel — O(1) consumption is exact there.
    # The XLA codec is fusible/splittable, so its arm must reduce over the
    # full outputs, which adds one read of the consumed buffer (~+20%
    # encode / ~+80% decode traffic) — those rates are floors, flagged in
    # the artifact, and the consistency gate only applies to the pallas arm.
    exact_consume = ring_ops._use_pallas(codec_cfg, n_elems)

    def make_enc_chain(k):
        # encode-only: the next iteration's input is perturbed in place
        # (O(1) dynamic-update-slice on the loop carry) by a scalar from
        # the previous iteration's outputs, so successive encodes are
        # serialized by real data flow
        @jax.jit
        def chain(v):
            def body(i, carry):
                v, acc = carry
                v = v.at[0].add(acc.astype(jnp.float32) * 1e-40)
                m, s = enc_fn(v)
                if exact_consume:
                    consumed = s[0].astype(jnp.int32)
                else:
                    consumed = (jnp.sum(m.astype(jnp.int32))
                                + jnp.sum(s.astype(jnp.int32)))
                return v, consumed
            return lax.fori_loop(0, k, body, (v, jnp.int32(0)))[1]
        return chain

    mant0, se0 = jax.jit(enc_fn)(x)

    def make_dec_chain(k):
        # decode-only: roll the (small, 1/16-sized) scale vector by the
        # loop index so the decode is never loop-invariant; the mantissa
        # buffer re-read dominates the traffic
        @jax.jit
        def chain(mant, se):
            def body(i, acc):
                out = dec_fn(mant, jnp.roll(se, i), jnp.float32)
                return acc + (out[0] if exact_consume else jnp.sum(out))
            return lax.fori_loop(0, k, body, jnp.float32(0))
        return chain

    slope_diag = {}
    rates = {}
    for name, mk, args in (("roundtrip", make_rt_chain, (x,)),
                           ("encode", make_enc_chain, (x,)),
                           ("decode", make_dec_chain, (mant0, se0))):
        t_iter, diag = slope_timeit(mk, args, CODEC_K, sync)
        slope_diag[name] = diag
        rates[name] = (gb / t_iter) if t_iter > 0 else 0.0
        log(f"codec {name}: slope {rates[name]:.2f} GB/s "
            f"(naive-at-K would say {gb / diag['naive_t_iter_s']:.2f})")
    report["codec_roundtrip_gbps"] = round(rates["roundtrip"], 2)
    report["codec_encode_gbps"] = round(rates["encode"], 2)
    report["codec_decode_gbps"] = round(rates["decode"], 2)
    report["codec_measurement"] = {
        "method": f"slope over K/2K chained passes (K={CODEC_K}) in one "
                  "dispatch; per-dispatch constants cancel exactly",
        "consumption": ("O(1) (pallas kernels are opaque to DCE: exact)"
                        if exact_consume else
                        "full output reductions (XLA codec is DCE-"
                        "splittable; encode/decode rates are FLOORS, "
                        "~20%/~80% consumption overhead included)"),
        "chains": slope_diag,
    }
    # internal consistency: a compute-bound roundtrip must cost what its
    # stages cost — rate_rt ~= 1/(1/enc + 1/dec).  r04's numbers failed
    # this by 76%; a future floored/miswired measurement re-flags itself.
    # Only the pallas arm is held to the gate: the XLA arm's stage rates
    # carry deliberate consumption overhead (see codec_measurement).
    if rates["encode"] > 0 and rates["decode"] > 0 and rates["roundtrip"] > 0:
        pred = 1.0 / (1.0 / rates["encode"] + 1.0 / rates["decode"])
        rel = (rates["roundtrip"] - pred) / pred
        report["codec_consistency"] = {
            "predicted_roundtrip_gbps": round(pred, 2),
            "measured_roundtrip_gbps": round(rates["roundtrip"], 2),
            "rel_err": round(rel, 3),
            "applicable": bool(exact_consume),
            "self_consistent": bool(abs(rel) <= 0.15) if exact_consume
            else None,
            "rule": "roundtrip within 15% of 1/(1/encode+1/decode), else "
                    "this artifact is floored or miswired (enforced on "
                    "the exact-consumption pallas arm only)",
        }
    else:
        report["codec_consistency"] = {
            "applicable": bool(exact_consume),
            "self_consistent": False,
            "rule": "a slope measurement came out non-positive (noise "
                    "swamped the chain-length difference); rates invalid",
        }

    # -- fused compress-into-hop kernel, single-chip loopback ---------------
    # (ops.ring_pallas: the depth-D pipeline — encode slice g+D on the VPU
    # while D RDMAs are in flight and decode+accumulate g retires; RDMAs
    # self-addressed on the 1-chip surface.)  Every row carries the full
    # per-stage decomposition: the SAME schedule slope-timed with exactly
    # one stage compiled in (ring_pallas ablate=), combined by
    # ops.ring_cost into a modeled pipeline time, the binding stage, and
    # pipeline_efficiency — the accounting that turns "1.29 GB/s, somewhere
    # slow" into "stage X binds, the schedule hides the rest".
    fused_rows = []
    if on_tpu:
        phase("fused ring kernel (loopback, staged decomposition)")
        try:
            from bench_common import chain_kernel_calls
            from fpga_ai_nic_tpu.ops import ring_cost, ring_pallas
            # attach the (mutating) row list up front: a failure on the
            # second row must not discard the first row's banked
            # decomposition — partial evidence is evidence
            report["fused_ring_loopback"] = fused_rows
            vn = 8
            # resident row at 4 MiB (the kernel holds input + acc copies in
            # VMEM; 2x8 MiB + frames exceeds v5e's 16 MiB scoped vmem —
            # measured on first contact, and the router's cap); streaming
            # row at 32 MiB (adds the HBM slice load/store stage)
            for mib, slice_elems, streaming in ((4, 1 << 16, False),
                                                (32, 1 << 16, True)):
                L = mib * (1 << 20) // 4
                L -= L % (vn * slice_elems)
                xf = jax.random.normal(jax.random.PRNGKey(2), (L,),
                                       jnp.float32)
                hop_bytes = (vn - 1) * (L // vn) * 4   # f32 through pipe

                def measure(ablate, _x=xf, _se=slice_elems, _st=streaming):
                    kw = {"slice_elems": _se, "streaming": _st}
                    if ablate:
                        kw["ablate"] = ablate
                    phase(f"loopback {mib}MiB stage="
                          f"{ablate or 'full'}")

                    def mk(k):
                        return chain_kernel_calls(
                            lambda v: ring_pallas.loopback_microbench(
                                v, vn, **kw), k)
                    t_iter, _ = slope_timeit(mk, (_x,), 8, sync)
                    return t_iter

                row = dict(mib=mib, streaming=streaming,
                           **ring_cost.decompose(measure, streaming,
                                                 hop_bytes))
                fused_rows.append(row)
                log(f"fused loopback {mib}MiB stream={streaming}: "
                    f"{row.get('pipeline_gbps')} GB/s, binding "
                    f"{row.get('binding_stage')}, efficiency "
                    f"{row.get('pipeline_efficiency')}")
            best = max((r for r in fused_rows if r.get("pipeline_gbps")),
                       key=lambda r: r["pipeline_gbps"], default=None)
            if best:
                report["fused_ring_loopback_gbps"] = best["pipeline_gbps"]
            else:
                # same convention as a failed probe: an explicit error
                # marker, never a silently absent (or fake-0.0) rate
                report["fused_ring_loopback_error"] = (
                    "non-positive slope (noise swamped the chain-length "
                    "difference); measurement invalid")
            report["fused_ring_loopback_note"] = (
                "self-addressed RDMA on one chip, slope-timed: sustained "
                "rate of the fused encode->DMA->decode+add pipeline per "
                "hop direction; on multi-chip ICI the DMA stage rides "
                "the interconnect instead of local HBM.  stages = the "
                "same schedule with one stage compiled in; modeled_t_ms "
                "and pipeline_efficiency per ops.ring_cost (vpu = "
                "encode+decode serial minus one skeleton)")
        except Exception as e:  # noqa: BLE001 — measurement is best-effort
            report["fused_ring_loopback_error"] = repr(e)[:300]
            log(f"fused loopback failed: {e!r}")

    # -- break-even: when does the BFP wire path beat bf16 psum? ------------
    # Rebuilt from SELF-CONSISTENT numbers (ops.ring_cost.break_even):
    # the codec stages share the VPU so their costs ADD (the old
    # max(1/enc, 1/dec) model is part of what let the dispatch-floored
    # r04 table pass), and the stage rates come from the fused kernel's
    # own ablation decomposition when a loopback row produced one — the
    # schedule the wire actually runs — falling back to the standalone
    # codec chains.
    from fpga_ai_nic_tpu.ops import ring_cost
    r = cfg.compression_ratio_vs_f32                   # 3.76x vs f32
    # the FUSED kernels' RDMA frames carry 8-row tile padding on top of
    # the live 17-flit rate (ring_pallas._frame_rows): 72/68 of the live
    # bytes at the default R=64 slice plan.  The XLA separate-op ring
    # sends unpadded arrays, so `r` stays exact for it; report the fused
    # wire ratio separately and use the WORSE of the two in break-even.
    from fpga_ai_nic_tpu.ops.ring_pallas import _frame_rows
    R_default = 8192 // 128
    r_fused = r * (R_default + R_default // cfg.block_size) \
        / _frame_rows(R_default, cfg.block_size)
    report["wire_compression_fused_vs_f32"] = round(r_fused, 3)
    enc_g = report.get("codec_encode_gbps", 0.0)
    dec_g = report.get("codec_decode_gbps", 0.0)
    src = "standalone codec slope chains"
    staged = next((row for row in fused_rows
                   if row.get("stages", {}).get("encode")
                   and row.get("stages", {}).get("decode")), None)
    if staged:
        # skeleton-corrected asymptotic stage rates (ring_cost.codec_
        # rates): break_even ADDS the two stage costs, so raw ablated
        # rates — each carrying the bare-loop skeleton — would count it
        # twice and bias the verdict against BFP
        fe, fd = ring_cost.codec_rates(staged["stages"],
                                       staged["payload_bytes"])
        if fe and fd:
            enc_g, dec_g = fe, fd
            src = (f"fused-kernel stage ablation, skeleton-corrected "
                   f"({staged['mib']} MiB loopback row)")
    # link-rate candidates routed through the calibration loader: the
    # measured wire rate (when banked) joins the documented fallback
    # constants, and the table carries calibrated so model-only rows
    # can be badged (docs/TUNING.md)
    lr = ring_cost.link_rate_candidates()
    report["break_even"] = ring_cost.break_even(
        enc_g, dec_g, r_fused, r, link_rates=lr["rates"], source=src,
        calibrated=lr["calibrated"])
    report["break_even"]["link_rates_source"] = lr["source"]

    # -- ring sweep (needs a multi-device axis) -----------------------------
    if n_dev >= 2:
        mesh = Mesh(jax.devices(), ("dp",))
        sweep = []
        for mb in SWEEP_MB:
            phase(f"sweep {mb} MiB")
            L = mb * (1 << 20) // 4
            L -= L % (n_dev * cfg.block_size)
            xs = jax.device_put(
                jax.random.normal(jax.random.PRNGKey(1), (L,), jnp.float32),
                jax.sharding.NamedSharding(mesh, P()))
            xb = xs.astype(jnp.bfloat16)
            bytes_f32, bytes_bf16 = L * 4, L * 2
            bus = 2 * (n_dev - 1) / n_dev

            def shmap(fn):
                return jax.jit(jax.shard_map(
                    fn, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False))

            psum_bf16 = shmap(lambda v: lax.psum(
                lax.pcast(v, "dp", to="varying"), "dp"))
            ring_f32 = shmap(lambda v: ring_ops.ring_all_reduce(
                lax.pcast(v, "dp", to="varying"), "dp"))
            ring_bfp = shmap(lambda v: ring_ops.ring_all_reduce(
                lax.pcast(v, "dp", to="varying"), "dp",
                compression=codec_cfg, slice_elems=8192))

            row = {"size_mb": mb}
            for label, fn, nbytes in (
                    ("psum_bf16", lambda: psum_bf16(xb), bytes_bf16),
                    ("ring_f32", lambda: ring_f32(xs), bytes_f32),
                    ("ring_bfp", lambda: ring_bfp(xs), bytes_f32)):
                dt = _timeit(fn, sync)
                row[f"{label}_gbps"] = round(bus * nbytes / dt / 1e9, 3)
                log(f"{mb} MiB {label}: {row[f'{label}_gbps']} GB/s "
                    f"(t={dt * 1e3:.1f} ms)")
            row["bfp_speedup_vs_ring_f32"] = round(
                row["ring_bfp_gbps"] / row["ring_f32_gbps"], 3)
            sweep.append(row)
        report["sweep"] = sweep
        best = max(sweep, key=lambda r: r["ring_bfp_gbps"])
        report["value"] = best["ring_bfp_gbps"]
        report["best_psum_bf16_gbps"] = max(
            r["psum_bf16_gbps"] for r in sweep)
    else:
        # single chip: no wire to measure; report the projection — the BFP
        # ring beats a bf16 psum by up to the wire ratio (1.88x) provided
        # the codec sustains the link rate, which codec_roundtrip_gbps
        # bounds from below (it includes both encode and decode passes).
        phase("single device: projecting ring advantage from codec rate")
        # the headline metric must not silently change meaning: a single
        # device has no wire, so rename rather than report codec compute
        # throughput under the busbw metric
        report["metric"] = "bfp_codec_roundtrip_gbps"
        report["value"] = report["codec_roundtrip_gbps"]
        report["projected_max_speedup_vs_bf16_psum"] = round(
            cfg.compression_ratio_vs_f32 / 2, 3)
        report["note"] = (
            "single-device run: value is codec roundtrip GB/s (the wire-"
            "path compute bound); ring busbw sweep needs >= 2 devices — "
            "see mesh_sweep for the virtual-mesh measurement")

    phase("done")
    report["telemetry"] = events.summary()
    # gate-compatible flat summary (tools/obs_gate.py --summary), built
    # from the gate's OWN name contract so producer and extractor can
    # never drift apart (a drifted name would silently gate nothing)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import obs_gate
    gate_metrics = {}
    for key in obs_gate.COLLECTIVE_GATE_KEYS:
        if report.get(key):
            gate_metrics[obs_gate.collective_metric(key)] = report[key]
    for row in report.get("sweep", []):
        for arm in obs_gate.SWEEP_GATE_ARMS:
            if row.get(f"{arm}_gbps"):
                gate_metrics[obs_gate.sweep_metric(row["size_mb"], arm)] = \
                    row[f"{arm}_gbps"]
    report["gate_summary"] = gate_metrics
    print(json.dumps(report), flush=True)


# ---------------------------------------------------------------------------
# codec matrix (`make codec-bench`): codec x {vmem, streaming} payloads
# ---------------------------------------------------------------------------

# the two payload classes mirror the fused ring's residency split
# (ops.ring_pallas): "vmem" = fits the resident kernel's on-chip working
# set, "streaming" = the HBM-streaming size class.  For the separate-op
# codec chains they are honest size regimes either way (small enough to
# stay cache-warm vs large enough to stream memory).
CODEC_MATRIX_MB = (("vmem", 4), ("streaming", 32))
CODEC_MATRIX_K = 16

# eval-suited constructor opts per codec (registry defaults otherwise)
CODEC_MATRIX_OPTS = {"bfp": (), "topk": (), "int8": ()}


def codec_matrix_child() -> None:
    """Measure every registered codec's encode/decode/roundtrip GB/s at
    both payload classes (slope-timed chains — per-dispatch constants
    cancel, bench_common.slope_timeit), plus per-codec compression ratio
    and the serial-VPU break-even table (ops.ring_cost.codec_break_even).
    One JSON line on stdout; merged/saved by the parent."""
    t0 = time.time()

    def phase(name):
        log(f"phase={name} t={time.time() - t0:.1f}s")

    phase("import")
    import jax
    require_tpu("bench_collective")
    enable_compile_cache()
    import jax.numpy as jnp
    from jax import lax

    from fpga_ai_nic_tpu import compress
    from fpga_ai_nic_tpu.ops import ring_cost

    platform = jax.default_backend()
    report = {
        "metric": "codec_matrix",
        "platform": platform,
        "n_devices": jax.device_count(),
        "payload_classes": {name: f"{mib} MiB" for name, mib
                            in CODEC_MATRIX_MB},
        "method": (f"slope over K/2K chained passes (K={CODEC_MATRIX_K}) "
                   "in one dispatch; rates are floors off-TPU (full-"
                   "output consumption defeats DCE on the fusible XLA "
                   "codecs — same caveat as the main collective bench)"),
        "codec_table": ring_cost.codec_table(),
        "rows": [],
    }

    _scalar = jax.jit(lambda t: sum(
        jnp.sum(l.astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(tree):
        return float(_scalar(tree))

    # one calibration load for the whole matrix (it re-reads the banked
    # artifact globs; identical for every row of this run)
    lr = ring_cost.link_rate_candidates()

    for name in compress.available_codecs():
        codec = compress.get_codec(name, dict(CODEC_MATRIX_OPTS.get(name,
                                                                    ())))
        for klass, mib in CODEC_MATRIX_MB:
            n_elems = mib * (1 << 20) // 4
            n_elems -= n_elems % codec.pad_elems
            gb = n_elems * 4 / 1e9
            phase(f"{name} {klass} ({mib} MiB)")
            x = jax.random.normal(jax.random.PRNGKey(0), (n_elems,),
                                  jnp.float32)

            def mk_rt(k, _c=codec):
                @jax.jit
                def chain(v):
                    def body(i, v):
                        return _c.roundtrip(v)
                    return lax.fori_loop(0, k, body, v)
                return chain

            def mk_enc(k, _c=codec):
                @jax.jit
                def chain(v):
                    def body(i, carry):
                        v, acc = carry
                        v = v.at[0].add(acc * 1e-40)
                        pay = _c.encode(v)
                        acc = sum(jnp.sum(p.astype(jnp.float32))
                                  for p in pay)
                        return v, acc
                    return lax.fori_loop(0, k, body, (v, jnp.float32(0)))[1]
                return chain

            pay0 = jax.jit(codec.encode)(x)

            def mk_dec(k, _c=codec, _n=n_elems):
                @jax.jit
                def chain(*pay):
                    def body(i, acc):
                        rolled = (jnp.roll(pay[0], i, axis=0),) + pay[1:]
                        out = _c.decode(rolled, _n, jnp.float32)
                        return acc + jnp.sum(out)
                    return lax.fori_loop(0, k, body, jnp.float32(0))
                return chain

            row = {"codec": name, "class": klass, "mib": mib,
                   "compression_ratio_vs_f32":
                       round(codec.compression_ratio_vs_f32, 3),
                   "wire_bytes_per_value":
                       round(codec.wire_bytes(n_elems) / n_elems, 4)}
            for stage, mk, args in (("roundtrip", mk_rt, (x,)),
                                    ("encode", mk_enc, (x,)),
                                    ("decode", mk_dec, tuple(pay0))):
                try:
                    t_iter, diag = slope_timeit(mk, args, CODEC_MATRIX_K,
                                                sync)
                except Exception as e:  # noqa: BLE001 — best-effort cell
                    row[f"{stage}_error"] = repr(e)[:200]
                    continue
                row[f"{stage}_gbps"] = (round(gb / t_iter, 2)
                                        if t_iter > 0 else 0.0)
                log(f"{name} {klass} {stage}: {row.get(f'{stage}_gbps')} "
                    "GB/s")
            enc_g = row.get("encode_gbps") or 0.0
            dec_g = row.get("decode_gbps") or 0.0
            if klass == "streaming" and enc_g and dec_g:
                row["break_even"] = ring_cost.codec_break_even(
                    codec, enc_g, dec_g, link_rates=lr["rates"],
                    source=f"{klass} slope chains ({platform})",
                    calibrated=lr["calibrated"])
                row["break_even"]["link_rates_source"] = lr["source"]
            report["rows"].append(row)

    phase("done")
    print(json.dumps(report), flush=True)


def codec_matrix_main() -> None:
    """Parent for `make codec-bench`."""
    _supervise("--codec-matrix-child", "codec_matrix", "codec_bench")


# ---------------------------------------------------------------------------
# autotune matrix (`make tune-bench`): the tuned plan vs every fixed
# (codec, depth, bucket, topology) config per payload regime
# ---------------------------------------------------------------------------

# payload regimes mirror SparCML's size-switched strategy space: small
# (latency/dispatch-bound), medium (the codec break-even neighborhood),
# large (stream-bound)
TUNE_REGIMES = (("small", 1), ("medium", 16), ("large", 64))
TUNE_INTRA_SIZE = 2           # declared fast/slow factorization of the
                              # bench mesh (8 = 2 intra x 4 inter)


def autotune_child() -> None:
    """Per payload regime: run the tuner (calibrated from the banked
    artifacts), score EVERY fixed candidate with the same model, check
    the argmin property (tuned <= every fixed config), and measure the
    tuned plan against the fixed flat-default ring on the live mesh.
    Wire bytes are exact plan declarations (obs-gate keys tune.*);
    measured times are dryrun-class off TPU, same honesty rule as the
    fused-opt bench.  One JSON line on stdout; merged/saved by the
    parent."""
    t0 = time.time()

    def phase(name):
        log(f"phase={name} t={time.time() - t0:.1f}s")

    phase("import")
    import jax
    require_tpu("bench_collective")
    enable_compile_cache()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from fpga_ai_nic_tpu import tune as tune_lib
    from fpga_ai_nic_tpu.ops import fused_update
    from fpga_ai_nic_tpu.utils.config import CollectiveConfig

    platform = jax.default_backend()
    n_dev = jax.device_count()
    on_tpu = is_tpu_platform(platform)
    calib = tune_lib.load_calibration()
    report = {
        "metric": "tune_bench",
        "platform": platform,
        "n_devices": n_dev,
        "intra_size": TUNE_INTRA_SIZE,
        "calibration": calib.describe(),
        "method": ("per payload regime: tuner argmin over the full "
                   "(codec x depth x bucket x topology) grid under the "
                   "calibrated ring_cost model; tuned_vs_best_fixed is "
                   "the modeled ratio (<= 1 by construction — gated "
                   "exactly, so a scoring/grid change cannot slip by); "
                   "measured arms time the tuned plan vs the fixed flat "
                   "bfp ring on the live mesh"),
        "rows": [],
    }

    _scalar = jax.jit(lambda t: sum(
        jnp.sum(l.astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(tree):
        return float(_scalar(tree))

    mesh = Mesh(jax.devices(), ("dp",)) if n_dev >= 2 else None

    def measure_coll(coll, L):
        """Wall time of one routed all-reduce of [L] f32 under coll."""
        xs = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(1), (L,), jnp.float32),
            jax.sharding.NamedSharding(mesh, P()))

        fn = jax.jit(jax.shard_map(
            lambda v: fused_update.ring_all_reduce_routed(
                lax.pcast(v, "dp", to="varying"), "dp", coll, L // n_dev),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        return _timeit(lambda: fn(xs), sync)

    for regime, mib in TUNE_REGIMES:
        phase(f"regime {regime} ({mib} MiB)")
        L = mib * (1 << 20) // 4
        L -= L % (n_dev * 2048)     # whole codec units for every codec
        plan = tune_lib.tune(L, n_dev, intra_size=TUNE_INTRA_SIZE,
                             calibration=calib)
        cands = tune_lib.enumerate_candidates(n_dev, TUNE_INTRA_SIZE)
        matrix = {}
        best_fixed = None
        for cand in cands:
            s = tune_lib.score_candidate(L, n_dev, cand, calib)
            key = f"{cand.codec or 'none'}/{cand.topology}"
            cur = matrix.get(key)
            if cur is None or s["exposed_s"] < cur["modeled_exposed_ms"] / 1e3:
                matrix[key] = {
                    "codec": cand.codec or "none",
                    "topology": cand.topology,
                    "pipeline_depth": cand.pipeline_depth,
                    "bucket_elems": cand.bucket_elems,
                    "modeled_exposed_ms": round(s["exposed_s"] * 1e3, 4),
                    "modeled_collective_ms":
                        round(s["collective_s"] * 1e3, 4),
                    "wire_bytes": s["wire_bytes_per_device"],
                }
            if best_fixed is None or s["exposed_s"] < best_fixed:
                best_fixed = s["exposed_s"]
        row = {
            "regime": regime,
            "payload_mib": mib,
            "payload_elems": L,
            "tuned": {k: v for k, v in plan.describe().items()
                      if k != "calibration"},
            "tuned_modeled_ms": round(plan.modeled_exposed_s * 1e3, 4),
            "best_fixed_modeled_ms": round(best_fixed * 1e3, 4),
            "tuned_vs_best_fixed": round(
                plan.modeled_exposed_s / best_fixed, 4),
            "tuned_beats_all_fixed":
                bool(plan.modeled_exposed_s <= best_fixed * (1 + 1e-9)),
            "tuned_wire_bytes": plan.wire_bytes_per_device,
            "n_candidates": plan.n_candidates,
            "matrix": sorted(matrix.values(),
                             key=lambda r: r["modeled_exposed_ms"]),
        }
        if mesh is not None:
            c = plan.candidate
            tuned_coll = CollectiveConfig(
                impl="ring", codec=c.codec,
                pipeline_depth=c.pipeline_depth,
                bucket_elems=c.bucket_elems, topology=c.topology,
                intra_size=c.intra_size if c.topology == "hier" else 0)
            fixed_coll = CollectiveConfig(impl="ring", codec="bfp")
            try:
                row["tuned_measured_ms"] = round(
                    measure_coll(tuned_coll, L) * 1e3, 3)
                row["flat_fixed_measured_ms"] = round(
                    measure_coll(fixed_coll, L) * 1e3, 3)
                row["tuned_measured_speedup_vs_flat_bfp"] = round(
                    row["flat_fixed_measured_ms"]
                    / row["tuned_measured_ms"], 3)
            except Exception as e:  # noqa: BLE001 — best-effort cell
                row["measure_error"] = repr(e)[:300]
        log(f"{regime}: tuned {row['tuned']['codec']}/"
            f"{row['tuned']['topology']} D={row['tuned']['pipeline_depth']}"
            f" B={row['tuned']['bucket_elems']} modeled "
            f"{row['tuned_modeled_ms']} ms (best fixed "
            f"{row['best_fixed_modeled_ms']}); measured tuned "
            f"{row.get('tuned_measured_ms')} vs flat-bfp "
            f"{row.get('flat_fixed_measured_ms')} ms")
        report["rows"].append(row)

    phase("done")
    if not on_tpu:
        # same honesty rule as the fused-opt/reshard benches: CPU-mesh
        # timings are recorded for inspection, never gated; the exact
        # plan declarations (wire bytes, modeled ratio) gate everywhere
        report["dryrun"] = True
        report["dryrun_note"] = (
            "cpu mesh rung: measured arms carry oversubscription noise "
            "~ the effect size, so `make obs-gate` gates only the exact "
            "plan accounting (tuned_wire_bytes, tuned_vs_best_fixed); "
            "re-run `make tune-bench` on a TPU surface for the gated "
            "measured rows")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import obs_gate
    gate_metrics = {}
    gate_keys = (obs_gate.TUNE_BYTE_KEYS if report.get("dryrun")
                 else obs_gate.TUNE_BYTE_KEYS + obs_gate.TUNE_GATE_KEYS)
    for row in report["rows"]:
        for key in gate_keys:
            if row.get(key) is not None:
                gate_metrics[obs_gate.tune_metric(row["regime"], key)] = \
                    row[key]
    report["gate_summary"] = gate_metrics
    print(json.dumps(report), flush=True)


def autotune_main() -> None:
    """Parent for `make tune-bench`."""
    _supervise("--autotune-matrix-child", "tune_bench", "tune_bench")


# ---------------------------------------------------------------------------
# fused-optimizer bench (`make fused-opt-bench`): fused
# decode+accumulate+update vs ring-then-optimizer
# ---------------------------------------------------------------------------

FUSED_OPT_MB = 8                  # flat f32 vector size for the comparison
FUSED_OPT_K = 8                   # slope-measurement chain length
FUSED_OPT_KINDS = ("sgd", "momentum", "adamw")


def fused_opt_child() -> None:
    """Per optimizer kind, slope-time three data-dependent chains on the
    dp mesh: the FUSED step (ring reduce-scatter with the update fused —
    in-kernel on TPU, XLA-fused after the reduce elsewhere), the ring
    ALONE, and the standalone optimizer pass ALONE.  The unfused baseline
    is ring + optimizer (they are sequential passes by construction —
    the sum is a LOWER bound on the two-dispatch schedule, so a fused win
    against it is conservative).  The success metric of ROADMAP item 4:
    fused_ms < ring_then_opt_ms by ~ the optimizer's standalone time,
    i.e. the optimizer runs on zero exposed time.  On TPU the row also
    carries the full per-stage loopback decomposition (ablate= incl. the
    new "update" stage, ops.ring_cost fused_opt=True).  One JSON line on
    stdout; merged/saved by the parent."""
    t0 = time.time()

    def phase(name):
        log(f"phase={name} t={time.time() - t0:.1f}s")

    phase("import")
    import jax
    require_tpu("bench_collective")
    enable_compile_cache()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from fpga_ai_nic_tpu import optim
    from fpga_ai_nic_tpu.ops import fused_update, ring_cost
    from fpga_ai_nic_tpu.utils.config import (CollectiveConfig,
                                              OptimizerConfig,
                                              OptimizerSpec)

    platform = jax.default_backend()
    n_dev = jax.device_count()
    on_tpu = is_tpu_platform(platform)
    # fused_kernel=True so the TPU rung times the IN-KERNEL Pallas path
    # (off TPU, reduce_scatter_update falls back to the separate-op ring
    # + the XLA-fused shared formula — the dryrun arms)
    coll = CollectiveConfig(impl="ring", codec="bfp", fused_kernel=True,
                            fused_optimizer=True)
    from fpga_ai_nic_tpu.compress import resolve
    codec = resolve(coll)
    L = FUSED_OPT_MB * (1 << 20) // 4
    L -= L % (n_dev * codec.pad_elems * 128)
    C = L // n_dev
    mesh = Mesh(jax.devices(), ("dp",))

    _scalar = jax.jit(lambda t: sum(
        jnp.sum(l.astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(tree):
        return float(_scalar(tree))

    report = {
        "metric": "fused_opt_bench",
        "platform": platform,
        "n_devices": n_dev,
        "flat_mib": FUSED_OPT_MB,
        "chunk_bytes": C * 4,
        "codec": "bfp",
        "method": (f"slope over K/2K data-dependent chained steps "
                   f"(K={FUSED_OPT_K}) inside one dispatch per arm; "
                   "ring_then_opt = ring-alone + optimizer-alone (a "
                   "LOWER bound on the unfused two-pass schedule, so "
                   "the fused win is conservative).  Off-TPU the fused "
                   "update is the XLA-fused shared formula, not the "
                   "Pallas in-kernel path — rates are dryrun-class "
                   "floors, the schedule comparison is still honest"),
        "rows": [],
    }

    rng = jax.random.PRNGKey(0)
    x0 = jax.random.normal(rng, (L,), jnp.float32)

    def shmap(fn, n_extra):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) + (P("dp"),) * n_extra,
            out_specs=(P(),) + (P("dp"),) * n_extra, check_vma=False))

    for kind in FUSED_OPT_KINDS:
        phase(f"fused-opt {kind}")
        spec = OptimizerSpec(kind=kind)
        opt_cfg = OptimizerConfig(kind=kind, learning_rate=1e-3)
        hyper = optim.fused_hyperparams(opt_cfg, jnp.zeros((), jnp.int32))
        w0 = jnp.zeros((n_dev * C,), jnp.float32)
        st0 = tuple(jnp.zeros((n_dev * C,), jnp.float32)
                    for _ in spec.state_keys)
        nst = spec.n_state

        def mk_fused(k, _kind=kind, _spec=spec):
            def body_fn(x, w, *st):
                def body(i, carry):
                    x, w, st = carry
                    g, w2, st2 = fused_update.reduce_scatter_update(
                        x, w, dict(zip(_spec.state_keys, st)),
                        jnp.int32(0), "dp", coll, opt_cfg)
                    # full data dependence: next input reads every
                    # element of this step's outputs (no cross-iteration
                    # overlap, no DCE)
                    x = x + jnp.tile(g, n_dev) * 1e-30
                    return x, w2, tuple(st2[k2]
                                        for k2 in _spec.state_keys)
                x, w, st = lax.fori_loop(0, k, body, (x, w, st))
                return (x, w) + st
            return shmap(body_fn, 1 + nst)

        def mk_ring(k):
            def body_fn(x):
                def body(i, x):
                    g = fused_update.reduce_scatter(x, "dp", coll)
                    return x + jnp.tile(g, n_dev) * 1e-30
                return (lax.fori_loop(0, k, body, x),)
            return shmap(body_fn, 0)

        def mk_opt(k, _spec=spec):
            def body_fn(g, w, *st):
                def body(i, carry):
                    w, st = carry
                    w2, st2 = optim.fused_apply_flat(
                        _spec, w, g + w * 1e-30,
                        dict(zip(_spec.state_keys, st)), hyper, n_dev)
                    return w2, tuple(st2[k2] for k2 in _spec.state_keys)
                w, st = lax.fori_loop(0, k, body, (w, st))
                return (g, w) + st
            # every operand is an owned [C] shard (the standalone ZeRO-1
            # optimizer pass the fused kernel absorbs)
            return jax.jit(jax.shard_map(
                body_fn, mesh=mesh, in_specs=(P("dp"),) * (2 + nst),
                out_specs=(P("dp"),) * (2 + nst), check_vma=False))

        row = {"kind": kind}
        row.update(ring_cost.optimizer_roofline(kind, C * 4))
        try:
            t_f, _ = slope_timeit(mk_fused, (x0, w0) + st0, FUSED_OPT_K,
                                  sync)
            t_r, _ = slope_timeit(mk_ring, (x0,), FUSED_OPT_K, sync)
            g0 = jnp.zeros((n_dev * C,), jnp.float32)
            t_o, _ = slope_timeit(mk_opt, (g0, w0) + st0, FUSED_OPT_K,
                                  sync)
        except Exception as e:  # noqa: BLE001 — best-effort cell
            row["error"] = repr(e)[:300]
            report["rows"].append(row)
            continue
        if t_f <= 0 or t_r <= 0 or t_o <= 0:
            row["error"] = ("non-positive slope (noise swamped the "
                            "chain-length difference); row invalid")
            report["rows"].append(row)
            continue
        row["fused_ms"] = round(t_f * 1e3, 3)
        row["ring_ms"] = round(t_r * 1e3, 3)
        row["opt_standalone_ms"] = round(t_o * 1e3, 3)
        row["ring_then_opt_ms"] = round((t_r + t_o) * 1e3, 3)
        row["opt_exposed_ms"] = round((t_f - t_r) * 1e3, 3)
        row["speedup_vs_ring_then_opt"] = round((t_r + t_o) / t_f, 3)
        row["fused_wins"] = bool(t_f < t_r + t_o)
        row["opt_fully_hidden"] = bool(t_f <= t_r * 1.05)
        log(f"{kind}: fused {row['fused_ms']} ms vs ring+opt "
            f"{row['ring_then_opt_ms']} ms (opt alone "
            f"{row['opt_standalone_ms']} ms) -> "
            f"speedup {row['speedup_vs_ring_then_opt']}")
        report["rows"].append(row)

    # TPU only: the per-stage loopback decomposition with the in-kernel
    # update stage (ablate="update") — the Perfetto-level evidence that
    # the update rides inside the ring schedule
    if on_tpu:
        phase("fused-opt loopback decomposition (TPU)")
        try:
            from bench_common import chain_kernel_calls
            from fpga_ai_nic_tpu.ops import ring_pallas
            vn = 8
            rows = []
            report["fused_opt_loopback"] = rows
            for mib, slice_elems, streaming in ((4, 1 << 16, False),
                                                (32, 1 << 16, True)):
                Lb = mib * (1 << 20) // 4
                Lb -= Lb % (vn * slice_elems)
                xf = jax.random.normal(jax.random.PRNGKey(2), (Lb,),
                                       jnp.float32)
                hop_bytes = (vn - 1) * (Lb // vn) * 4

                def measure(ablate, _x=xf, _se=slice_elems, _st=streaming):
                    kw = {"slice_elems": _se, "streaming": _st,
                          "opt_kind": "adamw"}
                    if ablate:
                        kw["ablate"] = ablate
                    phase(f"fused-opt loopback {mib}MiB stage="
                          f"{ablate or 'full'}")

                    def mk(k):
                        return chain_kernel_calls(
                            lambda v: ring_pallas.loopback_update_microbench(
                                v, vn, **kw), k)
                    t_iter, _ = slope_timeit(mk, (_x,), 8, sync)
                    return t_iter

                rows.append(dict(
                    mib=mib, streaming=streaming, opt_kind="adamw",
                    **ring_cost.decompose(measure, streaming, hop_bytes,
                                          fused_opt=True)))
        except Exception as e:  # noqa: BLE001 — best-effort
            report["fused_opt_loopback_error"] = repr(e)[:300]

    phase("done")
    if not on_tpu:
        # rates on the 8-way-oversubscribed virtual CPU mesh carry run-
        # to-run noise of the same order as the effect (measured: the
        # IDENTICAL ring chain varied ~30% across kinds/runs), so the
        # cpu rung banks code-path validation + exact byte accounting,
        # never a timing verdict — same convention as the multichip
        # dryrun artifacts
        report["dryrun"] = True
        report["dryrun_note"] = (
            "cpu mesh rung: fused/ring/opt times are recorded for "
            "inspection but are NOT gated and carry no win/loss claim "
            "(oversubscription noise ~ the effect size); the schedule "
            "verdict is a TPU measurement — run `make fused-opt-bench` "
            "on a TPU surface for the gated row")
        for row in report["rows"]:
            row.pop("fused_wins", None)
            row.pop("opt_fully_hidden", None)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import obs_gate
    gate_metrics = {}
    gate_keys = (obs_gate.FUSED_OPT_BYTE_KEYS if report.get("dryrun")
                 else obs_gate.FUSED_OPT_GATE_KEYS)
    for row in report["rows"]:
        for key in gate_keys:
            # zero is a real value for the byte-accounting keys (sgd has
            # no moment state) — only absence skips
            if row.get(key) is not None:
                gate_metrics[obs_gate.fused_opt_metric(row["kind"],
                                                       key)] = row[key]
    report["gate_summary"] = gate_metrics
    print(json.dumps(report), flush=True)


def fused_opt_main() -> None:
    """Parent for `make fused-opt-bench`."""
    _supervise("--fused-optimizer-child", "fused_opt_bench",
               "fused_opt_bench")


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _supervise(child_flag: str, metric: str, artifact: str,
               budget_s: float = 600.0, silence_s: float = 240.0) -> None:
    """Run this file's `child_flag` child under the activity watchdog, bank
    its result JSON as an artifact and print it.  One attempt: without a
    chip the child fails, and so does this."""
    here = os.path.abspath(__file__)
    try:
        result = run_attempt(
            metric, [sys.executable, "-u", here, child_flag],
            budget_s=budget_s, silence_s=silence_s,
            cwd=os.path.dirname(here))
    except RuntimeError as e:          # run_attempt's: killed or no result
        log(str(e))
        print(json.dumps({"metric": metric, "error": str(e)[:800]}),
              flush=True)
        sys.exit(1)
    save_artifact(artifact, result)
    print(json.dumps(result), flush=True)


def main() -> None:
    # the budget covers the loopback stage decomposition: 2 rows x
    # (full + 4-5 ablated stages) x a K/2K slope pair each
    _supervise("--child", "allreduce_busbw_gbps", "collective_tpu",
               budget_s=780.0, silence_s=300.0)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        child_main()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--codec-matrix-child":
        codec_matrix_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--codec-matrix":
        codec_matrix_main()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--fused-optimizer-child":
        fused_opt_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--fused-optimizer":
        fused_opt_main()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--autotune-matrix-child":
        autotune_child()
    elif len(sys.argv) >= 2 and sys.argv[1] == "--autotune-matrix":
        autotune_main()
    else:
        main()
