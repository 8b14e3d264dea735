#!/usr/bin/env python
"""Quickest proof that the system still starts on the chip.

One process, no child: it imports jax itself, refuses to run without a TPU,
and drives the fused-ring MLP trainer through the entry points a user calls
(`make_mesh`, `DPTrainer`, `mlp.init`, `mlp.loss_fn` — examples/train_mlp.py)
at the full width of the paper's canonical model: 10 layers of 2048x2048,
bf16 compute, f32 master, 4096 samples per chip (sw/run.sh:16), random
weights from --seed.

    python chip_smoke.py            one chip:  train at dp=1 against a plain
                                    jax.numpy SGD step; the ring kernels in
                                    loopback at 32 MiB and the codec at the
                                    gradient's size against their goldens
    python chip_smoke.py --chips 4  four chips: the same model at dp=4 over
                                    the fused ring, and what it is compared
                                    with — the same steps over XLA's own
                                    collectives.  No other phase.

Lines before the last are labelled wall times, compile times and losses;
none is a benchmark number.  Any phase that raises ends the run non-zero.
The last line is the result the driver reads.
"""

import argparse
import contextlib
import json
import sys
import time
import warnings

import numpy as np

WIDTH, LAYERS, BATCH_PER_CHIP = 2048, 10, 4096
LR = 0.1                      # the reference's (sw/run.sh): the loss falls from step one
STEPS = 5                     # after the warm-up step
LOOPBACK_BYTES = 32 << 20
VIRTUAL_N = 4
SLICE_ELEMS = 8192            # CollectiveConfig.slice_elems' default

# The gather hands every replica the BFP roundtrip of the updated master, so
# params after one step differ from an unquantized reference by the codec's
# error: 7.29e-3 relative L2 at 8 mantissa bits (docs/BFP_CONVERGENCE.md,
# roundtrip table); twice that is allowed.
PARAM_TOL = 1.5e-2
# The master shard itself is never quantized at dp=1: its first update may
# differ from the reference's only as two compilations of the same bf16
# matmuls do.
UPDATE_TOL = 2e-2
# Fused ring (BFP on gradients and weights) against impl="xla", per step:
# the m8 regression gate of docs/BFP_CONVERGENCE.md (loss ratio <= 1.05).
LOSS_TOL = 0.05
# numpy emulates the FMA contraction XLA:CPU applies to the update formula
# (optim.golden_fused_apply); a backend that does not contract differs in
# the last place, and adamw's sqrt and divide by a little more.
UPDATE_RTOL = {"sgd": 1e-6, "adamw": 1e-4}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def timed(label: str):
    t0 = time.perf_counter()
    yield
    log(f"{label}: {time.perf_counter() - t0:.2f} s wall")


def ring_config():
    from fpga_ai_nic_tpu.utils.config import BFPConfig, CollectiveConfig
    return CollectiveConfig(impl="ring", compression=BFPConfig(),
                            fused_kernel=True, fused_optimizer=True)


def build(coll, dp: int, width: int, layers: int, batch_per_chip: int,
          seed: int):
    """(trainer, model config, initial params, device batch) through the
    calls examples/train_mlp.py makes."""
    import jax
    import jax.numpy as jnp

    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
    from fpga_ai_nic_tpu.utils.config import (MeshConfig, MLPConfig,
                                              OptimizerConfig, TrainConfig)

    mcfg = MLPConfig(layer_sizes=(width,) * (layers + 1), dtype="bfloat16")
    cfg = TrainConfig(
        global_batch=batch_per_chip * dp, mesh=MeshConfig(dp=dp),
        collective=coll, seed=seed,
        optimizer=OptimizerConfig(kind="sgd", learning_rate=LR))
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, mcfg), make_mesh(cfg.mesh),
                   cfg)
    params = mlp.init(jax.random.PRNGKey(seed), mcfg)

    @jax.jit
    def make_batch(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (cfg.global_batch, width), jnp.bfloat16)
        y = jax.random.randint(ky, (cfg.global_batch,), 0, width, jnp.int32)
        return x, y

    batch = tr.shard_batch(make_batch(jax.random.PRNGKey(seed + 1)))
    return tr, mcfg, params, batch


def flat_f32(tree):
    """A pytree as one f32 vector in leaf order — the trainer's flat master
    layout, written out again so the reference does not lean on it."""
    import jax
    import jax.numpy as jnp
    return jnp.concatenate([leaf.astype(jnp.float32).reshape(-1)
                            for leaf in jax.tree_util.tree_leaves(tree)])


def reference_sgd_step(params, batch, mcfg):
    """One SGD step of the same model in plain jax.numpy, no trainer and no
    collective: (loss, flat f32 master before, flat f32 master after)."""
    import jax

    from fpga_ai_nic_tpu.models import mlp

    @jax.jit
    def step(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: mlp.loss_fn(q, b, mcfg))(p)
        w = flat_f32(p)
        return loss, w, w - LR * flat_f32(grads)

    return step(params, batch)


def rel_l2(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def expected_ring_kernels(tr) -> int:
    """Pallas calls the fused-ring step must hold: at dp=1 the codec's
    encode and decode (the wire is routed around); at dp>1 the fused
    reduce-scatter+update and one gather per segment."""
    from fpga_ai_nic_tpu.ops import ring_pallas
    if tr.n == 1:
        return 2
    bcfg = tr.cfg.collective.compression
    owned = tr.obs_static_metrics()["padded_len"] // tr.n
    return 1 + len(ring_pallas.ag_stream_segments(owned, SLICE_ELEMS,
                                                  bcfg.block_size))


def train(tr, params, batch, steps: int, on_chip: bool, label: str):
    """Warm-up step plus `steps` steps of `tr`.  Returns (losses, state after
    the first step as (params, w_own) copies, final state).  On the chip the
    first step runs with warnings as errors and its compiled program must
    hold the ring's kernels: a silent reroute to the separate-op ring cannot
    pass."""
    import jax
    import jax.numpy as jnp

    state = tr.init_state(params)
    want_kernels = tr.cfg.collective.fused_kernel
    losses, walls = [], []

    def step():
        nonlocal state
        t0 = time.perf_counter()
        state, loss = tr.step(state, batch)
        losses.append(float(loss))            # waits for the device
        walls.append(time.perf_counter() - t0)

    with warnings.catch_warnings():
        if on_chip and want_kernels:
            warnings.simplefilter("error")
        t0 = time.perf_counter()
        hlo = tr.step_fn.lower(state, batch).compile().as_text()
        log(f"{label}: step compile {time.perf_counter() - t0:.2f} s")
        step()
    if on_chip and want_kernels:
        n_calls, need = hlo.count("tpu_custom_call"), expected_ring_kernels(tr)
        log(f"{label}: {n_calls} tpu_custom_call in the step's HLO "
            f"(needs {need})")
        if n_calls < need:
            raise AssertionError(
                f"{label}: the compiled step holds {n_calls} Pallas calls, "
                f"the fused ring needs {need} — the kernels did not run")
    # the next step donates this state: keep what the checks read
    first = (jax.tree_util.tree_map(jnp.copy, state.params),
             jnp.copy(state.w_own))
    for _ in range(steps):
        step()
    log(f"{label}: losses " + " ".join(f"{v:.4f}" for v in losses))
    # the second step compiles too: its state is the first step's output,
    # committed to the mesh, where init_state's was not
    log(f"{label}: wall s per step, warm-up first: "
        + " ".join(f"{w:.3f}" for w in walls))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{label}: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    return losses, first, state


def phase_train(width: int = WIDTH, layers: int = LAYERS,
                batch_per_chip: int = BATCH_PER_CHIP, steps: int = STEPS,
                seed: int = 0, on_chip: bool = True) -> None:
    """dp=1 through DPTrainer with the fused-ring config; the first step is
    held to a plain jax.numpy SGD step on the same seed."""
    import jax.numpy as jnp

    tr, mcfg, params, batch = build(ring_config(), 1, width, layers,
                                    batch_per_chip, seed)
    ref_loss, w_old, w_ref = reference_sgd_step(params, batch, mcfg)
    losses, (params1, w_own1), _ = train(tr, params, batch, steps, on_chip,
                                         "train dp=1")
    live = w_old.shape[0]
    upd = rel_l2(w_own1[:live] - w_old, w_ref - w_old)
    par = rel_l2(flat_f32(params1),
                 w_ref.astype(jnp.bfloat16).astype(jnp.float32))
    log(f"train dp=1: first step vs plain SGD: loss {losses[0]:.4f} vs "
        f"{float(ref_loss):.4f}; master update rel L2 {upd:.2e} "
        f"(tol {UPDATE_TOL}); params rel L2 {par:.2e} (tol {PARAM_TOL})")
    if not abs(losses[0] - float(ref_loss)) <= 1e-2 * abs(float(ref_loss)):
        raise AssertionError("first loss differs from the reference")
    if not upd <= UPDATE_TOL:
        raise AssertionError("first master update differs from plain SGD")
    if not par <= PARAM_TOL:
        raise AssertionError("params after the first step differ from "
                             "plain SGD beyond the BFP bound")


def phase_loopback(total_bytes: int = LOOPBACK_BYTES,
                   slice_elems: int = SLICE_ELEMS, seed: int = 0,
                   on_chip: bool = True) -> None:
    """The ring kernels themselves on one chip — dp=1 training routes around
    the wire — as a virtual ring of four, streaming, hardware flow control
    on, against the numpy goldens."""
    import jax
    import jax.numpy as jnp

    from fpga_ai_nic_tpu import optim
    from fpga_ai_nic_tpu.ops import ring_golden, ring_pallas
    from fpga_ai_nic_tpu.utils.config import BFPConfig, OptimizerConfig

    cfg, n, interpret = BFPConfig(), VIRTUAL_N, not on_chip
    x = jax.random.normal(jax.random.PRNGKey(seed), (total_bytes // 4,),
                          jnp.float32)
    x_np = np.asarray(x)
    kw = dict(slice_elems=slice_elems, streaming=True, interpret=interpret)

    want_rs = ring_golden.loopback_reduce_scatter(x_np, n, cfg)
    got = np.asarray(ring_pallas.loopback_microbench(x, n, **kw))
    np.testing.assert_array_equal(got, want_rs)
    log(f"loopback reduce-scatter {total_bytes / 2**20:g} MiB: bit-equal")

    zeros = np.zeros_like(want_rs)
    for kind in ("sgd", "adamw"):
        hyper = optim.fused_hyperparams(
            OptimizerConfig(kind=kind, learning_rate=1e-3),
            jnp.zeros((), jnp.int32))
        want, _ = optim.golden_fused_apply(
            kind, zeros, want_rs, {"m": zeros, "v": zeros},
            np.asarray(hyper), n)
        got = np.asarray(ring_pallas.loopback_update_microbench(
            x, n, opt_kind=kind, hyper=hyper, **kw))
        np.testing.assert_allclose(got, want, rtol=UPDATE_RTOL[kind], atol=0)
        log(f"loopback reduce-scatter+{kind} update: within "
            f"{UPDATE_RTOL[kind]:g} of the golden")

    owned = x[:x.shape[0] // n]
    got = np.asarray(ring_pallas.loopback_gather_microbench(owned, n, **kw))
    np.testing.assert_array_equal(
        got, ring_golden.loopback_all_gather(np.asarray(owned), n, cfg))
    segs = len(ring_pallas.ag_stream_segments(owned.shape[0], slice_elems,
                                              cfg.block_size))
    log(f"loopback all-gather {total_bytes / 2**20:g} MiB in {segs} "
        "segment(s): bit-equal")


def phase_codec(n_elems: int, seed: int = 0, on_chip: bool = True) -> None:
    """bfp_encode / bfp_decode at the gradient's size, bit for bit against
    ops.bfp_golden."""
    import jax
    import jax.numpy as jnp

    from fpga_ai_nic_tpu.ops import bfp_golden, bfp_pallas

    @jax.jit
    def make(key):
        # exponents spread over 40 binades, one value in 16 exactly zero
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (n_elems,), jnp.float32) * jnp.exp2(
            jnp.floor(jax.random.uniform(k2, (n_elems,), jnp.float32,
                                         -20.0, 20.0)))
        return jnp.where(jnp.arange(n_elems) % 16 == 3, 0.0, x)

    with timed("codec: input made"):
        x = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    with timed("codec: kernels compiled and run"):
        mant, scale = bfp_pallas.bfp_encode(x, interpret=not on_chip)
        out = jax.block_until_ready(
            bfp_pallas.bfp_decode(mant, scale, interpret=not on_chip))
    g_mant, g_scale = bfp_golden.bfp_encode(np.asarray(x), 16, 8, "nearest",
                                            layout="sublane")
    np.testing.assert_array_equal(np.asarray(mant), g_mant)
    np.testing.assert_array_equal(np.asarray(scale), g_scale)
    np.testing.assert_array_equal(
        np.asarray(out),
        bfp_golden.bfp_decode(g_mant, g_scale, 16, layout="sublane"))
    log(f"codec encode+decode {n_elems} f32: bit-equal")


def phase_dp(dp: int = 4, width: int = WIDTH, layers: int = LAYERS,
             batch_per_chip: int = BATCH_PER_CHIP, steps: int = STEPS,
             seed: int = 0, on_chip: bool = True) -> None:
    """Data-parallel training over the real ring: the fused-ring config on
    `dp` chips against the same steps with impl="xla" on the same mesh."""
    import jax

    from fpga_ai_nic_tpu.utils.config import CollectiveConfig

    if len(jax.devices()) != dp:
        raise AssertionError(f"--chips {dp} needs exactly {dp} devices, "
                             f"jax reports {len(jax.devices())}")
    tr, _, params, batch = build(ring_config(), dp, width, layers,
                                 batch_per_chip, seed)
    ids = sorted(d.id for d in tr.mesh.devices.flat)
    log(f"mesh device ids {ids}")
    if len(set(ids)) != dp:
        raise AssertionError(f"mesh does not hold {dp} distinct devices")
    ring_losses, _, state = train(tr, params, batch, steps, on_chip,
                                  f"ring dp={dp}")

    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if len(shards) != dp or len({s.device.id for s in
                                     leaf.addressable_shards}) != dp:
            raise AssertionError("params are not held by every device")
        for other in shards[1:]:
            if not np.array_equal(shards[0], other):
                raise AssertionError(
                    "params differ between devices after the gather")
    log(f"ring dp={dp}: params bit-identical on all {dp} devices")

    tr_x, _, params_x, batch_x = build(CollectiveConfig(impl="xla"), dp,
                                       width, layers, batch_per_chip, seed)
    xla_losses, _, _ = train(tr_x, params_x, batch_x, steps, on_chip,
                             f"xla dp={dp}")
    worst = max(abs(r / x - 1.0) for r, x in zip(ring_losses, xla_losses))
    log(f"ring vs xla: worst per-step loss ratio error {worst:.2e} "
        f"(tol {LOSS_TOL})")
    if not worst <= LOSS_TOL:
        raise AssertionError("fused-ring losses leave the BFP bound around "
                             "the impl='xla' trajectory")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from bench_common import enable_compile_cache, require_tpu
    dev = require_tpu("chip_smoke")
    cache = enable_compile_cache()
    log(f"device_kind {dev[0].device_kind!r}, {len(dev)} device(s); compile "
        f"cache at {jax.config.jax_compilation_cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        with timed("phase dp=4"):
            phase_dp(4, seed=args.seed)
    else:
        with timed("phase train"):
            phase_train(seed=args.seed)
        with timed("phase loopback"):
            phase_loopback(seed=args.seed)
        with timed("phase codec"):
            # the dp=1 trainer's flat gradient: 10 x (2048^2 + 2048)
            phase_codec(LAYERS * (WIDTH * WIDTH + WIDTH), seed=args.seed)
    log(f"total {time.perf_counter() - t0:.2f} s wall; compile cache "
        f"{cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
