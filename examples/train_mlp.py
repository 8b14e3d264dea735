#!/usr/bin/env python
"""Canonical MLP training driver — the reference benchmark as one CLI.

Mirrors sw/run.sh:16 + sw/mlp_mpi_example_f32.cpp's positional-arg driver
(iters MB fuse_type type bn bk bc C1..CN, :269-296) with typed --dotted
flags, and its PERFDUMP report (:794-816) with a JSON line.  Defaults are
the canonical benchmark: 20 iters, global batch 5376, 10 layers of
2048x2048 (bf16 here — MXU-native; the reference's f32 was a CPU
constraint).

Examples:
  python examples/train_mlp.py                          # canonical config
  python examples/train_mlp.py --mesh.dp=8 --collective.impl=ring \
      --model.dtype=bfloat16 --optimizer.learning_rate=0.05
  python examples/train_mlp.py --bfp=1                  # BFP-compressed ring

Flags split by prefix: --model.* -> MLPConfig, everything else ->
TrainConfig; --bfp=1 turns on the BFP wire codec (implies the explicit
ring collective).

--queue=fused|explicit selects the execution schedule: "fused" (default)
is the one-program ZeRO-1 trainer XLA overlaps on its own; "explicit"
reproduces the reference's host-side issue/wait loop (one collective
dispatch per gradient bucket through the bounded CollectiveQueue,
sw/mlp_mpi_example_f32.cpp:735-787) and reports live stall/overlap/
wire-byte attribution in the output JSON's profile.collectives.

--trace-dir=PATH captures a JAX profiler trace of the timed loop (XProf
viewable) — the overlap evidence SURVEY.md §5 says must come from trace
analysis on TPU rather than hardware counters.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    import jax
    import jax.numpy as jnp

    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import (DPTrainer, QueuedDDPTrainer,
                                          make_mesh)
    from fpga_ai_nic_tpu.runtime.watchdog import Watchdog
    from fpga_ai_nic_tpu.utils.config import (
        BFPConfig, MLPConfig, TrainConfig, from_flags)
    from fpga_ai_nic_tpu.utils.observability import Profiler

    model_flags = [a for a in argv if a.startswith("--model.")]
    bfp_flags = [a.partition("=")[2].lower() for a in argv
                 if a.startswith("--bfp=")]
    bfp = any(v in ("1", "true", "yes", "on") for v in bfp_flags)
    if bfp_flags and not bfp and any(
            v not in ("0", "false", "no", "off") for v in bfp_flags):
        raise ValueError(f"unrecognized --bfp value: {bfp_flags}")
    queue_mode = "fused"
    trace_dir = None
    for a in argv:
        if a.startswith("--queue="):
            queue_mode = a.partition("=")[2]
            if queue_mode not in ("fused", "explicit"):
                raise ValueError(f"--queue must be fused|explicit, "
                                 f"got {queue_mode!r}")
        elif a.startswith("--trace-dir="):
            trace_dir = a.partition("=")[2]
    rest = [a for a in argv
            if not a.startswith("--model.") and not a.startswith("--bfp=")
            and not a.startswith("--queue=")
            and not a.startswith("--trace-dir=")]
    mcfg = from_flags(MLPConfig,
                      [a.replace("--model.", "--") for a in model_flags])
    cfg = from_flags(TrainConfig, rest)
    if bfp:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, collective=dataclasses.replace(
                cfg.collective, impl="ring", compression=BFPConfig()))

    mesh = make_mesh(cfg.mesh)
    prof = Profiler()
    # failure detection: any device-touching call (dispatch or the final
    # sync) that wedges raises DeviceHangError instead of spinning forever
    # like the reference's wait() poll (sw/mlp_mpi_example_f32.cpp:157-180)
    wd = Watchdog(timeout_s=600.0)
    loss_fn = lambda p, b: mlp.loss_fn(p, b, mcfg)  # noqa: E731
    if queue_mode == "explicit":
        tr = QueuedDDPTrainer(loss_fn, mesh, cfg, profiler=prof)
    else:
        tr = DPTrainer(loss_fn, mesh, cfg)

    with prof.bucket("init"):
        state = tr.init_state(mlp.init(jax.random.PRNGKey(cfg.seed), mcfg))
        rng = np.random.default_rng(cfg.seed)
        dt = jnp.dtype(mcfg.dtype)
        x = jnp.asarray(
            rng.standard_normal((cfg.global_batch, mcfg.layer_sizes[0])), dt)
        y = jnp.asarray(rng.integers(
            0, mcfg.num_classes or mcfg.layer_sizes[-1], cfg.global_batch),
            jnp.int32)
        batch = tr.shard_batch((x, y))

    def scalar_loss(v):
        # with integrity_check the step returns a metrics dict (the
        # wire/value verdicts ride next to the loss) instead of the bare
        # loss scalar
        return float(v["loss"] if isinstance(v, dict) else v)

    with prof.bucket("warmup"):            # compile + first step
        state, loss = wd.run(tr.step, state, batch)
        loss = wd.run(scalar_loss, loss)

    import contextlib
    trace_cm = (jax.profiler.trace(trace_dir) if trace_dir
                else contextlib.nullcontext())
    # the warmup step is compile-dominated; reset the per-step buckets and
    # collective stats so the report attributes the *timed* loop only (the
    # queue reads profiler.collectives per call, so it sees the fresh stats;
    # the init/warmup buckets keep their compile wall-time)
    from fpga_ai_nic_tpu.utils.observability import CollectiveStats
    prof.collectives = CollectiveStats()
    for k in ("grads", "issue", "update"):
        prof.buckets.pop(k, None)
        prof.counts.pop(k, None)
    t0 = time.perf_counter()
    with trace_cm, prof.bucket("train"):
        for _ in range(cfg.iters):
            state, loss = wd.run(tr.step, state, batch)
        loss = wd.run(scalar_loss, loss)   # materializes the chain
    wall = time.perf_counter() - t0

    fl = mlp.flops_per_sample(mcfg) * cfg.global_batch * cfg.iters
    out = {
        "loss": loss,
        "samples_per_sec": cfg.iters * cfg.global_batch / wall,
        "gflops": fl / wall / 1e9,         # PERFDUMP equivalent (:804-808)
        "wall_s": wall,
        "profile": prof.report(),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
