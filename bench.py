#!/usr/bin/env python
"""Headline benchmark: MLP training samples/sec/chip (BASELINE.json metric).

Runs the reference's canonical model — a 10-layer 2048x2048 MLP with softmax
cross-entropy (sw/run.sh:16: 20 iters, global MB 5376, 3 nodes) — as a full
fused training step (fwd + bwd + fused reduce-scatter/SGD/all-gather) and
reports per-chip throughput.

One process: it imports jax, holds the chip, and fails when jax finds no
TPU — a rate from any other platform is not this metric.  Run it through
the chip tool.

vs_baseline: ratio against the reference system's estimated per-node
throughput.  The reference repo publishes no absolute numbers (BASELINE.md);
we model its canonical node — Xeon Platinum 8280, 28 cores, AVX-512, libxsmm
f32 GEMMs at ~80% of a ~4.3 TFLOP/s peak — over the reference FLOP
accounting of 243.3 MFLOP/sample (sw/mlp_mpi_example_f32.cpp:794-798):
~3.4e12 / 243.3e6 ~= 14,000 samples/s/node.

TPU-first choice: compute dtype bf16 (MXU native rate; the reference used
f32 because its CPUs had no reduced-precision GEMM path); master weights and
the fused optimizer stay f32.
"""

import json
import shutil
import sys
import tempfile
import time

from bench_common import (bf16_peak, enable_compile_cache, log as _log,
                          require_tpu)

BASELINE_SAMPLES_PER_SEC_PER_NODE = 14_000.0
METRIC = "mlp_train_samples_per_sec_per_chip"
LAYERS, BATCH_PER_CHIP, ITERS = 10, 4096, 20


def main() -> int:
    t0 = time.time()

    def phase(name):
        _log(f"phase={name} t={time.time() - t0:.1f}s")

    phase("import")
    import jax
    import jax.numpy as jnp

    phase("devices")
    dev = require_tpu("bench")
    n_dev, kind = len(dev), dev[0].device_kind
    _log(f"device_kind={kind!r} n_dev={n_dev}")
    enable_compile_cache()

    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
    from fpga_ai_nic_tpu.utils.config import (
        CollectiveConfig, MeshConfig, MLPConfig, OptimizerConfig, TrainConfig)

    phase("init")
    mcfg = MLPConfig(layer_sizes=(2048,) * (LAYERS + 1), dtype="bfloat16")
    cfg = TrainConfig(
        iters=ITERS,
        global_batch=BATCH_PER_CHIP * n_dev,
        mesh=MeshConfig(dp=n_dev),
        collective=CollectiveConfig(impl="xla"),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1),
    )
    mesh = make_mesh(cfg.mesh)
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, mcfg), mesh, cfg)
    params = mlp.init(jax.random.PRNGKey(0), mcfg)
    state = tr.init_state(params)

    phase("data")
    # the batch is made on the device: loading it is set-up, not the step
    @jax.jit
    def make_batch(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (cfg.global_batch, 2048), jnp.bfloat16)
        y = jax.random.randint(ky, (cfg.global_batch,), 0, 2048, jnp.int32)
        return x, y

    batch_dev = tr.shard_batch(make_batch(jax.random.PRNGKey(1)))

    phase("compile")
    state, loss = tr.step(state, batch_dev)   # first step compiles
    jax.block_until_ready(state.params)

    phase("warmup")
    for _ in range(2):
        state, loss = tr.step(state, batch_dev)
    jax.block_until_ready(state.params)

    phase("timed")
    t_loop = time.perf_counter()
    for i in range(cfg.iters):
        state, loss = tr.step(state, batch_dev)
        if (i + 1) % 5 == 0:
            _log(f"iter {i + 1}/{cfg.iters}")
    phase("sync")
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t_loop

    per_chip = cfg.iters * cfg.global_batch / dt / n_dev
    phase(f"done dt={dt:.3f}s")
    flops = mlp.flops_per_sample(mcfg) * per_chip
    peak, peak_label = bf16_peak(kind)
    out = {
        "metric": METRIC,
        "value": round(per_chip, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(per_chip / BASELINE_SAMPLES_PER_SEC_PER_NODE, 3),
        # the denominator is a MODEL, not a measurement — the reference
        # repo publishes no absolute numbers (BASELINE.md); this field
        # rides every artifact so the ratio can never be read as
        # measured-vs-measured
        "baseline_model": ("estimated 14,000 samples/s/node: Xeon Platinum "
                           "8280 libxsmm f32 @80% of 4.3 TFLOP/s over "
                           "243.3 MFLOP/sample"),
        "platform": dev[0].platform,
        "device_kind": kind,
        "n_devices": n_dev,
        "loss": float(loss),
        "tflops_per_chip": round(flops / 1e12, 3),
        "mfu": round(flops / peak, 4),
        "mfu_peak_ref": peak_label,
    }

    # the trace is a bonus: losing it must not lose the measured number.
    # No training call of the timed loop sits under this catch.
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        phase("trace")
        from fpga_ai_nic_tpu.utils import trace_analysis
        with jax.profiler.trace(tdir):
            for _ in range(3):
                state, loss = tr.step(state, batch_dev)
            jax.block_until_ready(state.params)
        out["trace_overlap"] = trace_analysis.summarize(
            trace_analysis.analyze_trace(tdir))
    except Exception as e:  # noqa: BLE001 — see above
        _log(f"trace capture failed: {e!r}")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
