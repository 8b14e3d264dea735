"""models/nemotron_h.py against the benchmark family's plain float32 reference
(benchmark/families/nemotron_h.py, which imports nothing of the package):
loss and every gradient leaf with and without a chip's share of the experts,
the chunked scan against the recurrence position by position, relu**2
experts through both programs of the dropless dispatch, the share test of
the model-configs guide, the balanced selection bias, the parameter count,
and DPTrainer steps on the CPU mesh.  Tiny widths, float32, seeded weights."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import loader
from fpga_ai_nic_tpu.models import decoder, lfm2_moe, nemotron_h
from fpga_ai_nic_tpu.ops import moe, ring_attention
from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
from fpga_ai_nic_tpu.utils.config import (
    BFPConfig, CollectiveConfig, MeshConfig, OptimizerConfig, TrainConfig)

FAMILY = loader.load_module("families", "nemotron_h")
CONFIG_FILE = os.path.join(
    loader.ROOT, "benchmark/configs/nemotron-twotower-30b-l9-e8of128.json")

# the configuration file's keys at a size the CPU runs in a second: hidden
# 64, 4 Mamba heads of 16 over 2 groups, state 16, chunks of 8, 16 experts
# top-3 of which this chip holds 8, the cell's own pattern
TINY = dict(
    hidden_size=64, hybrid_override_pattern="MEMEM*EME", num_hidden_layers=9,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts=8,
    router_width=16, ep_size=2, ep_rank=1, num_experts_per_tok=3,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
    n_shared_experts=1, layer_norm_epsilon=1e-5, tie_word_embeddings=False,
    use_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    mlp_bias=False, mlp_hidden_act="relu2", vocab_size=128,
    compute_dtype="float32", attn_impl="xla", attn_block=8)
UNCUT = dict(TINY, n_routed_experts=16, ep_size=1, ep_rank=0)
JOB = dict(dp=1, batch_per_chip=2, seq_len=16)


def rel_l2(a, b):
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))


def reference_loss(config, batch):
    def loss(params):
        with jax.default_matmul_precision("highest"):
            total, count = FAMILY.reference_nll(params, batch, config)
        return total / count
    return loss


def block(params, cfg, index):
    """The leaves of block `index` of the pattern, its run's axis taken."""
    run, at = 0, index
    for _, n in cfg.runs:
        if at < n:
            break
        run, at = run + 1, at - n
    return jax.tree_util.tree_map(lambda a: a[at], params["blocks"][run])


# -- (a) loss and every gradient leaf ----------------------------------------

@pytest.mark.parametrize("config,job", [
    (TINY, JOB), (UNCUT, JOB), (TINY, dict(JOB, seq_len=20)),
    (dict(TINY, chunk_size=32), JOB),
    (dict(TINY, hybrid_override_pattern="MME*EM", num_hidden_layers=6), JOB),
    (dict(TINY, attn_block=6), JOB), (dict(TINY, num_key_value_heads=4), JOB),
    (dict(TINY, num_key_value_heads=1), JOB),
    (dict(TINY, norm_topk_prob=False), JOB), (dict(TINY, n_groups=4), JOB),
    (dict(TINY, n_groups=1, conv_kernel=2), JOB)],
    ids=["held-8-of-16", "all-held", "a-ragged-last-chunk",
         "one-chunk-longer-than-the-sequence", "runs-of-two-blocks",
         "a-ragged-block-of-keys", "as-many-keys-as-queries", "one-key-head",
         "gates-not-normalised", "a-group-a-head", "one-group-two-taps"])
def test_loss_and_gradient_match_the_reference(config, job):
    """The program as the harness builds it — weights from the key, the
    bias balanced on the batch of the same key — against the reference on
    the same weights: the loss and EVERY gradient leaf."""
    init, loss_fn = FAMILY.program(config, job)
    key = jax.random.PRNGKey(0)
    params = jax.jit(init)(key)
    batch = FAMILY.make_batch(jax.random.fold_in(key, 1), config, job)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        reference_loss(config, batch)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want_grads):
        name, g = jax.tree_util.keystr(path), got[path]
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if "expert_bias" in name:       # it steers; no gradient step moves it
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.sum(w ** 2)) > 0, name
        assert rel_l2(g, w) < 2e-4, (name, rel_l2(g, w))


# -- (b) the chunked scan is the recurrence ----------------------------------

def recurrence(x, step, a, b, c):
    """S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t, y_t = C_t . S_t, one
    position after the other in numpy float64."""
    x, step, a, b, c = (np.asarray(t, np.float64) for t in (x, step, a, b, c))
    bsz, s, heads, p = x.shape
    per = heads // b.shape[2]
    state = np.zeros((bsz, heads, p, b.shape[-1]))
    y = np.zeros(x.shape)
    for t in range(s):
        for h in range(heads):
            state[:, h] = (np.exp(step[:, t, h] * a[h])[:, None, None]
                           * state[:, h]
                           + (step[:, t, h, None] * x[:, t, h])[:, :, None]
                           * b[:, t, h // per][:, None, :])
            y[:, t, h] = np.einsum("bpn,bn->bp", state[:, h],
                                   c[:, t, h // per])
    return y


def scan_inputs(seq, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, seq, 4, 6))
    step = jax.nn.softplus(jax.random.normal(ks[1], (2, seq, 4)))
    a = -jnp.exp(jax.random.normal(ks[2], (4,)))
    b = jax.random.normal(ks[3], (2, seq, 2, 5))
    c = jax.random.normal(ks[4], (2, seq, 2, 5))
    return x, step, a, b, c


@pytest.mark.parametrize("seq", [32, 29, 8, 5, 1, 17],
                         ids=lambda s: f"{s}-positions-chunks-of-8")
def test_the_chunked_scan_is_the_recurrence(seq):
    """Sequence lengths that are and are not a multiple of the chunk, one
    shorter than a chunk, one position: the matrix form against the loop
    over positions, which shares no algebra with it."""
    inputs = scan_inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *t: nemotron_h.ssd_scan(*t, 8))(*inputs)
    assert got.shape == (2, seq, 4, 6) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), recurrence(*inputs),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seq,chunk", [(24, 8), (21, 8), (6, 8), (16, 4)])
def test_the_chunked_scan_s_gradient_is_the_recurrence_s(seq, chunk):
    """Every input's gradient — the steps', through the decay and through
    D x, the decay rates', B's and C's — against autodiff through the
    reference's position-by-position loop."""
    inputs = scan_inputs(seq, seed=1)

    def through(fn):
        def loss(*t):
            with jax.default_matmul_precision("highest"):
                return jnp.sum(jnp.sin(fn(*t)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*inputs)

    got = through(lambda *t: nemotron_h.ssd_scan(*t, chunk))
    want = through(lambda x, step, a, b, c: FAMILY._recurrence(
        x, step, a, jnp.repeat(b, 2, axis=2), jnp.repeat(c, 2, axis=2),
        chunk))
    for name, g, w in zip("x step a b c".split(), got, want):
        assert rel_l2(g, w) < 1e-4, (name, rel_l2(g, w))


def test_the_scan_has_a_memory_and_no_future():
    """Position t reads nothing after t, and reads what came chunks
    before it."""
    x, step, a, b, c = scan_inputs(32, seed=2)
    step = step * 0.05                  # a slow decay: a long memory
    base = nemotron_h.ssd_scan(x, step, a, b, c, 8)
    later = nemotron_h.ssd_scan(x.at[:, 20:].add(1.0), step, a, b, c, 8)
    np.testing.assert_allclose(np.asarray(later[:, :20]),
                               np.asarray(base[:, :20]), atol=1e-6)
    earlier = nemotron_h.ssd_scan(x.at[:, 2].add(1.0), step, a, b, c, 8)
    assert float(jnp.max(jnp.abs(earlier[:, 31] - base[:, 31]))) > 1e-4


def test_the_mixer_is_the_reference_s_and_is_causal():
    cfg = FAMILY.model_config(TINY)
    lyr = block(nemotron_h.init(jax.random.PRNGKey(5), cfg), cfg, 0)
    lyr["conv_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                               lyr["conv_bias"].shape)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 21, 64))
    got = nemotron_h.mamba_mixer(lyr, h, cfg)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._mixer(lyr, h, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # the faults the chip's controls plant read otherwise: no decay, and a
    # convolution without its bias
    still = nemotron_h.mamba_mixer(dict(lyr, A_log=lyr["A_log"] - 20.0), h,
                                   cfg)
    assert float(jnp.max(jnp.abs(still - want))) > 1e-3
    plain = nemotron_h.mamba_mixer(dict(lyr, conv_bias=0 * lyr["conv_bias"]),
                                   h, cfg)
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-3
    later = h.at[:, 13:].add(1.0)
    np.testing.assert_allclose(
        np.asarray(nemotron_h.mamba_mixer(lyr, later, cfg)[:, :13]),
        np.asarray(got[:, :13]), atol=1e-5)


def test_the_mixer_s_scalars_start_as_mamba_2_s():
    cfg = nemotron_h.NemotronHConfig(ssm_heads=4096, ssm_groups=8)
    s = nemotron_h._ssm_scalars(jax.random.PRNGKey(0), cfg, 1)
    a, step = np.exp(np.asarray(s["A_log"])), np.asarray(
        jax.nn.softplus(s["dt_bias"]))
    assert 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    assert 0.001 <= step.min() < 0.0012 and 0.09 < step.max() <= 0.1001
    assert abs(np.median(np.log(step)) - np.log(0.01)) < 0.1   # log-uniform
    assert (np.asarray(s["d_skip"]) == 1).all()
    assert all(v.dtype == jnp.float32 and v.shape == (1, 4096)
               for v in s.values())


def test_attention_is_the_reference_s_with_grouped_keys_and_no_rotation():
    config = dict(TINY, num_attention_heads=8, num_key_value_heads=2)
    cfg = FAMILY.model_config(config)
    lyr = block(nemotron_h.init(jax.random.PRNGKey(5), cfg), cfg, 5)
    assert lyr["wqkv"].shape == (64, (8 + 2 + 2) * 16)
    assert lyr["wo"].shape == (8 * 16, 64)      # heads x width is not hidden
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    got = nemotron_h.attention(lyr, h, cfg)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._attention(lyr, h, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    q, k, v = jnp.split(lyr["wqkv"], [8 * 16, 10 * 16], axis=1)

    def repeated(cols):                 # key head i // 4 for query head i
        return jnp.repeat(cols.reshape(64, 2, 16), 4, axis=1).reshape(64, -1)

    full = dict(lyr, wqkv=jnp.concatenate([q, repeated(k), repeated(v)], 1))
    same = nemotron_h.attention(full, h, FAMILY.model_config(
        dict(config, num_key_value_heads=8)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                               rtol=1e-5, atol=1e-6)
    # no rotary embedding: a token's addend depends on WHICH tokens came
    # before it and not on their order
    swapped = nemotron_h.attention(lyr, h.at[:, :2].set(h[:, 1::-1]), cfg)
    np.testing.assert_allclose(np.asarray(swapped[:, 2:]),
                               np.asarray(got[:, 2:]), rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(swapped[:, 1] - got[:, 1]))) > 1e-3


def test_a_block_s_checkpoint_keeps_attention_s_output():
    assert nemotron_h.KEEP is decoder.KEEP is lfm2_moe.KEEP
    cfg = nemotron_h.NemotronHConfig.tiny()
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), TINY, JOB)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: nemotron_h.loss_fn(p, batch, cfg)))(params))
    assert ring_attention.SAVED in text


# -- (c) relu**2 experts through both programs of the dropless dispatch ------

D, F = 16, 12


def relu2_params(rng, n=8, held=None, shared=True):
    h = n if held is None else len(held)
    p = {"wr": rng.standard_normal((D, n)) * D ** -0.5,
         "w1": rng.standard_normal((h, D, F)) * D ** -0.5,
         "w2": rng.standard_normal((h, F, D)) * F ** -0.5}
    if shared:
        p.update(sw1=rng.standard_normal((D, 2 * F)) * .3,
                 sw2=rng.standard_normal((2 * F, D)) * .3)
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def per_token_relu2(params, x, top_k, held, scale, bias=None):
    """Per-token numpy loop: sigmoid scores, top-k of score + bias, gates
    normalised over the selection, only held experts computed, each
    relu(x w1)**2 w2 — two matrices, no gate branch."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    held = list(range(p["wr"].shape[1])) if held is None else list(held)
    scores = 1.0 / (1.0 + np.exp(-(xf @ p["wr"])))
    y = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        choice = scores[t] + (0.0 if bias is None else np.asarray(bias))
        top = np.argsort(-choice, kind="stable")[:top_k]
        gates = scale * scores[t, top] / scores[t, top].sum()
        for g, e in zip(gates, top):
            if e in held:
                s = held.index(e)
                y[t] += g * np.maximum(xf[t] @ p["w1"][s], 0) ** 2 @ p["w2"][s]
        if "sw1" in p:
            y[t] += np.maximum(xf[t] @ p["sw1"], 0) ** 2 @ p["sw2"]
    return y.reshape(x.shape)


def to_the_held_experts(params, held, n=8):
    """The router replaced by one that sends every token of an all-positive
    batch to the first two experts of `held`."""
    wr = np.full((D, n), -1.0, np.float32)
    wr[:, held[0]], wr[:, held[1]] = 1.0, 0.5
    return dict(params, wr=jnp.asarray(wr))


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "routed"])
@pytest.mark.parametrize("case", ["all-held", "compact-fits",
                                  "compact-overflows"])
def test_relu2_experts_through_the_dropless_dispatch(case, shared):
    """`dropless_experts` reads the expert's function off the weights: no
    `w3`, so relu(x w1)**2 w2.  One program where every expert is held;
    with 2 of 8 held the compact program on a routing that fits its C = 512
    rows, and the full program on one that does not."""
    rng = np.random.default_rng(0)
    held = None if case == "all-held" else (5, 2)
    params = relu2_params(rng, held=held, shared=shared)
    x = jnp.asarray(rng.standard_normal((2, 256, D)), jnp.float32)
    if case == "compact-overflows":
        params, x = to_the_held_experts(params, held), jnp.abs(x)
    assert "w3" not in params and "sw3" not in params
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(lambda p, x: moe.held_experts_ffn(
            p, x, num_experts=8, top_k=2, held=held, scale=2.5,
            with_counts=True))(params, x)
    assert int(counts["dropped"]) == 0
    assert int(counts["capacity"]) == (1024 if held is None else 512)
    assert float(counts["fit"]) == (0.0 if case == "compact-overflows"
                                    else 1.0)
    if case == "compact-overflows":
        assert np.asarray(counts["rows"]).tolist() == [512, 512]
    np.testing.assert_allclose(
        np.asarray(y), per_token_relu2(params, x, 2, held, 2.5),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("fits", [True, False],
                         ids=["compact", "full-on-overflow"])
def test_relu2_experts_gradient_is_plain_autodiff_s(fits):
    """The hand-written backward of either program, with two matrices an
    expert: every leaf's gradient and x's against autodiff through a dense
    jax.numpy layer that computes every held expert on every token."""
    rng = np.random.default_rng(1)
    held = (5, 2)
    params = relu2_params(rng, held=held)
    x = jnp.asarray(rng.standard_normal((2, 256, D)), jnp.float32)
    if not fits:
        params, x = to_the_held_experts(params, held), jnp.abs(x)

    def dense(p, x):
        xf = x.reshape(-1, D)
        scores = jax.nn.sigmoid(xf @ p["wr"])
        top = jnp.sort(scores, axis=-1)[:, -2][:, None]
        gates = jnp.where(scores >= top, scores, 0.0)
        gates = 2.5 * gates / jnp.sum(gates, axis=-1, keepdims=True)
        y = jnp.square(jax.nn.relu(xf @ p["sw1"])) @ p["sw2"]
        for slot, e in enumerate(held):
            y = y + gates[:, e, None] * (
                jnp.square(jax.nn.relu(xf @ p["w1"][slot])) @ p["w2"][slot])
        return y.reshape(x.shape)

    def grads(layer):
        def loss(p, x):
            with jax.default_matmul_precision("highest"):
                return jnp.sum(jnp.sin(layer(p, x)))
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)

    got = grads(lambda p, x: moe.held_experts_ffn(
        p, x, num_experts=8, top_k=2, held=held, scale=2.5))
    want = grads(dense)
    assert sorted(got[0]) == sorted(params)
    for leaf in sorted(params):
        assert rel_l2(got[0][leaf], want[0][leaf]) < 1e-4, leaf
    assert rel_l2(got[1], want[1]) < 1e-4


def test_an_expert_with_three_matrices_is_still_swiglu():
    """The same call with a `w3` among the weights computes SwiGLU, as the
    GLM and LFM2 models rely on: the function is the weights' property."""
    rng = np.random.default_rng(2)
    params = relu2_params(rng, shared=False)
    x = jnp.asarray(rng.standard_normal((1, 32, D)), jnp.float32)
    with_w3 = dict(params, w3=jnp.asarray(
        rng.standard_normal((8, D, F)) * D ** -0.5, jnp.float32))
    kw = dict(num_experts=8, top_k=2, scale=1.0)
    with jax.default_matmul_precision("highest"):
        two, three = (moe.held_experts_ffn(p, x, **kw)
                      for p in (params, with_w3))
    p = {k: np.asarray(v, np.float64) for k, v in with_w3.items()}
    xf = np.asarray(x, np.float64)[0]
    scores = 1.0 / (1.0 + np.exp(-(xf @ p["wr"])))
    want = np.zeros_like(xf)
    for t in range(32):
        top = np.argsort(-scores[t], kind="stable")[:2]
        for e in top:
            a = xf[t] @ p["w1"][e]
            want[t] += scores[t, e] / scores[t, top].sum() * (
                a / (1 + np.exp(-a)) * (xf[t] @ p["w3"][e])) @ p["w2"][e]
    np.testing.assert_allclose(np.asarray(three[0]), want, rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(two - three))) > 1e-2


# -- (d) the shares add up to the uncut layer --------------------------------

def test_all_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """A 128-expert layer over 16 chips under a selection bias: the routed
    part each share computes, summed, plus the shared expert — which every
    chip computes alike — counted ONCE, is what the uncut reference gives
    for the whole layer."""
    d, f, n, k, tokens = 16, 8, 128, 6, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    whole = {"wr": jax.random.normal(ks[0], (d, n)) * d ** -0.5,
             "expert_bias": 0.3 * jax.random.normal(ks[1], (n,)),
             "w1": jax.random.normal(ks[2], (n, d, f)) * d ** -0.5,
             "w2": jax.random.normal(ks[3], (n, f, d)) * f ** -0.5,
             "sw1": jax.random.normal(ks[4], (d, 2 * f)) * d ** -0.5,
             "sw2": jax.random.normal(ks[5], (2 * f, d)) * f ** -0.5}
    x = jax.random.normal(ks[6], (1, tokens, d))
    config = dict(num_experts_per_tok=k, routed_scaling_factor=2.5,
                  norm_topk_prob=True)
    with jax.default_matmul_precision("highest"):
        want, _ = FAMILY._experts(whole, x[0], config, held=range(n))
        shared = moe.shared_expert(whole, x[0])
    routed, rows = jnp.zeros_like(x), 0
    for rank in range(16):
        held = tuple(range(rank * 8, rank * 8 + 8))
        mine = {"wr": whole["wr"], "sw1": whole["sw1"], "sw2": whole["sw2"],
                **{w: whole[w][rank * 8:rank * 8 + 8] for w in ("w1", "w2")}}
        with jax.default_matmul_precision("highest"):
            part, counts = moe.held_experts_ffn(
                mine, x, num_experts=n, top_k=k, held=held, scale=2.5,
                bias=whole["expert_bias"], with_counts=True)
        routed = routed + (part - shared)       # this chip's routed part
        rows += int(counts["rows"].sum())
        assert int(counts["dropped"]) == 0
    assert rows == tokens * k           # every assignment on exactly one
    np.testing.assert_allclose(np.asarray(routed[0] + shared),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(shared))) > 0.05


# -- (e) the selection bias --------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_rule_evens_the_loads_and_leaves_the_gates(seed):
    """4,096 tokens, the top 6 of 128: a skewed router before, the fullest
    expert within 1.05 of the mean after, over ALL experts; a token whose
    selection the bias did not change has the gates it had — the gates
    are the selected scores over their sum, no trace of the bias."""
    kw, kx, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    wr = jax.random.normal(kw, (32, 128)) * 32 ** -0.5
    x = jax.random.normal(kx, (4096, 32)) + 0.15 * jax.random.normal(kc, (32,))
    scores = moe.router_scores(wr, x)
    bias = jax.jit(lambda s: moe.balanced_bias(s, 6, 1.05))(scores)
    assert bias.shape == (128,) and bias.dtype == jnp.float32

    def loads(b):
        _, experts = moe.sigmoid_route(wr, x, top_k=6, bias=b, scale=2.5)
        return np.bincount(np.asarray(experts).reshape(-1), minlength=128)

    before, after = loads(jnp.zeros((128,))), loads(bias)
    assert before.max() / before.mean() > 1.3
    assert after.max() / after.mean() <= 1.05 and after.sum() == 4096 * 6
    gates0, experts0 = moe.sigmoid_route(wr, x, top_k=6, scale=2.5)
    gates, experts = moe.sigmoid_route(wr, x, top_k=6, bias=bias, scale=2.5)
    same = np.asarray(jnp.all(jnp.sort(experts0) == jnp.sort(experts),
                              axis=1))
    assert 0.03 < same.mean() < 0.95
    np.testing.assert_allclose(
        np.sort(np.asarray(gates)[same]), np.sort(np.asarray(gates0)[same]),
        rtol=1e-6)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(experts), 1)
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-5)


def test_balance_bias_sets_every_expert_block_and_nothing_else():
    """Block by block in forward order, each on the residual the balanced
    blocks before it give: every expert block's loads end within 1.05 of
    the mean over all 16 experts, every other leaf is bit-identical, and
    the function is one of the seed alone."""
    cfg = FAMILY.model_config(dict(UNCUT, num_experts_per_tok=2))
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 256), 0, 128)
    balanced = nemotron_h.balance_bias(params, tokens, cfg)
    for (path, old), new in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree_util.tree_leaves(balanced)):
        if "expert_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(old).any() and np.asarray(new).any()
            assert new.dtype == jnp.float32 and new.shape == old.shape
        else:
            np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    before = nemotron_h.routing_stats(params, (tokens, tokens), cfg)
    after = nemotron_h.routing_stats(balanced, (tokens, tokens), cfg)
    assert np.asarray(after["rows"]).shape == (4, 16)
    assert float(np.max(np.asarray(before["max_over_mean"]))) > 1.2
    assert (np.asarray(after["max_over_mean"]) <= 1.05).all()
    again = nemotron_h.balance_bias(params, tokens, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(balanced),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_program_s_weights_are_a_function_of_the_seed_alone():
    """`program`'s init balances on the batch the harness makes from the
    same key: two calls give the same leaves, another key other ones, and
    the reference reads the same bias."""
    init, _ = FAMILY.program(TINY, JOB)
    first, again = (jax.jit(init)(jax.random.PRNGKey(7)) for _ in range(2))
    other = jax.jit(init)(jax.random.PRNGKey(8))
    for a, b in zip(jax.tree_util.tree_leaves(first),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    bias = [np.asarray(b["expert_bias"]) for b in first["blocks"]
            if "expert_bias" in b]
    assert len(bias) == 4 and all(b.any() for b in bias)
    assert not np.array_equal(bias[0], np.asarray(
        other["blocks"][1]["expert_bias"]))


@pytest.mark.parametrize("fault", ["zero", "one-expert-favoured"])
def test_the_reference_refuses_an_uneven_bias_by_its_own_selection(fault):
    """The bias is data the program prepares and the reference is handed:
    the reference holds it to even loads by ITS selection.  The balanced
    bias costs nothing; a zero bias, or one that favours an expert, counts
    UNEVEN_NLL a position, which `LOSS_RTOL` cannot pass."""
    init, _ = FAMILY.program(TINY, JOB)
    key = jax.random.PRNGKey(0)
    params = jax.jit(init)(key)
    batch = FAMILY.make_batch(jax.random.fold_in(key, 1), TINY, JOB)
    loss = jax.jit(reference_loss(TINY, batch))
    sound = float(loss(params))
    assert sound < 10

    def spoil(bias):
        return (jnp.zeros_like(bias) if fault == "zero"
                else bias.at[..., 3].add(1.0))

    spoiled = dict(params, blocks=[
        dict(b, expert_bias=spoil(b["expert_bias"]))
        if "expert_bias" in b else b for b in params["blocks"]])
    with jax.default_matmul_precision("highest"):
        uneven = FAMILY._hidden(spoiled, batch[0], TINY)[1]
    assert float(jnp.max(uneven)) > FAMILY.EVEN
    assert float(loss(spoiled)) > sound + 0.99 * FAMILY.UNEVEN_NLL
    assert FAMILY.UNEVEN_NLL > 10 * FAMILY.LOSS_RTOL * sound


# -- parameters --------------------------------------------------------------

def test_parameter_count_is_the_tree():
    cfg = FAMILY.model_config(TINY)
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    assert nemotron_h.num_params(cfg) == sum(
        p.size for p in jax.tree_util.tree_leaves(params))
    assert cfg.runs == tuple((kind, 1) for kind in "MEMEM*EME")
    mixer, experts = params["blocks"][0], params["blocks"][1]
    assert mixer["w_in"].shape == (1, 64, 64 + (64 + 2 * 2 * 16) + 4)
    assert mixer["conv_w"].shape == (1, 4, 128)
    assert mixer["conv_bias"].dtype == mixer["A_log"].dtype == jnp.float32
    assert experts["wr"].shape == (1, 64, 16)       # the router's width
    assert experts["w1"].shape == (1, 8, 64, 24)    # the experts held
    assert "w3" not in experts and "sw3" not in experts
    assert experts["wr"].dtype == experts["expert_bias"].dtype == jnp.float32
    assert params["head"].shape == (64, 128) and "wqkv" in params["blocks"][5]


PARTS = {"M": 38_744_896, "E": 100_125_440, "*": 23_399_040}   # ISSUE 39


def test_published_size_has_the_issue_s_count():
    """MEMEM*EME, 8 of 128 experts, 16,384 rows of an untied vocabulary:
    666,963,456 parameters from shapes alone, and each kind of block its
    part of the issue's table."""
    cfg = FAMILY.model_config(loader.read_json(CONFIG_FILE))
    assert nemotron_h.num_params(cfg) == 666_963_456
    like = jax.eval_shape(lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
    assert sum(np.prod(p.shape) for p in
               jax.tree_util.tree_leaves(like)) == 666_963_456
    assert cfg.held == tuple(range(8)) and cfg.n_routed_experts == 128
    for (kind, n), stack in zip(cfg.runs, like["blocks"]):
        assert n == 1 and sum(np.prod(p.shape) for p in stack.values()) \
            == PARTS[kind]
    assert like["blocks"][0]["w_in"].shape == (1, 2688, 10304)
    assert like["blocks"][1]["w1"].shape == (1, 8, 2688, 1856)
    assert like["blocks"][1]["sw1"].shape == (1, 2688, 3712)
    assert like["blocks"][5]["wqkv"].shape == (1, 2688, 4096 + 2 * 256)
    assert like["tok_emb"].shape == (16384, 2688)
    assert like["head"].shape == (2688, 16384)
    assert 2 * 44_040_192 + 2688 + 4 * PARTS["M"] + 4 * PARTS["E"] \
        + PARTS["*"] == 666_963_456
    assert moe.compact_capacity(8192 * 6, 8, 128) == 6144


def test_the_default_is_the_published_stack():
    cfg = nemotron_h.NemotronHConfig()
    assert len(cfg.pattern) == 52
    assert [cfg.pattern.count(kind) for kind in "ME*"] == [23, 23, 6]
    assert cfg.pattern.startswith("MEMEM*EME")
    assert max(n for _, n in cfg.runs) == 1     # a block a run, as published
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 6144)
    assert nemotron_h.NemotronHConfig(pattern="MMEE*").runs == (
        ("M", 2), ("E", 2), ("*", 1))


@pytest.mark.parametrize("kw,match", [
    (dict(pattern="MEX"), "M .Mamba-2., E .experts. or"),
    (dict(pattern=""), "a block is"),
    (dict(ssm_groups=3), "groups do not divide"),
    (dict(n_kv_heads=3), "key/value heads")])
def test_a_config_that_is_no_such_model_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        nemotron_h.NemotronHConfig.tiny(**kw)


@pytest.mark.parametrize("change,match", [
    (dict(tie_word_embeddings=True), "untied head"),
    (dict(use_conv_bias=False), "convolution bias"),
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(n_group=2), "group-limited"),
    (dict(num_hidden_layers=8), "a pattern of 9 blocks"),
    (dict(ep_size=4), "router")])
def test_a_configuration_the_program_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.model_config(dict(TINY, **change))


# -- routing_stats -----------------------------------------------------------

def test_routing_stats_count_the_rows_of_the_batch():
    init, _ = FAMILY.program(TINY, JOB)
    cfg = FAMILY.model_config(TINY)
    key = jax.random.PRNGKey(0)
    params = jax.jit(init)(key)
    batch = FAMILY.make_batch(jax.random.fold_in(key, 1), TINY, JOB)
    stats = jax.jit(lambda p, b: nemotron_h.routing_stats(p, b, cfg))(params,
                                                                     batch)
    layers, tokens, k = 4, 32, 3
    assert stats["rows"].shape == (layers, 8)
    assert stats["selected"].shape == (layers, tokens, k)
    held = np.isin(np.asarray(stats["selected"]), cfg.held)
    np.testing.assert_array_equal(np.asarray(stats["rows"]).sum(axis=1),
                                  held.sum(axis=(1, 2)))
    assert np.asarray(stats["dropped"]).tolist() == [0] * layers
    with jax.default_matmul_precision("highest"):
        chosen = FAMILY._hidden(params, batch[0], TINY,
                                with_selection=True)[2]
    assert chosen.shape == (layers, tokens, 16)
    mine = np.zeros(chosen.shape, bool)
    for j in range(k):
        np.put_along_axis(mine, np.asarray(stats["selected"])[..., j:j + 1],
                          True, axis=-1)
    assert (mine == np.asarray(chosen)).all()


# -- (f) through DPTrainer ---------------------------------------------------

@pytest.mark.parametrize("dp", [1, 2])
def test_dp_trainer_steps(dp):
    job = dict(JOB, dp=dp)
    init, loss_fn = FAMILY.program(TINY, job)
    cfg = TrainConfig(
        global_batch=FAMILY.global_batch(TINY, job), mesh=MeshConfig(dp=dp),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=True),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=1e-2))
    tr = DPTrainer(loss_fn, make_mesh(cfg.mesh, devices=jax.devices()[:dp]),
                   cfg)
    key = jax.random.PRNGKey(0)
    params = jax.jit(init)(key)
    bias = [np.asarray(b["expert_bias"]) for b in params["blocks"]
            if "expert_bias" in b]
    a_log = np.asarray(params["blocks"][0]["A_log"])
    state = tr.init_state(params)
    batch = tr.shard_batch(FAMILY.make_batch(jax.random.fold_in(key, 1),
                                             TINY, job))
    losses = []
    for i in range(4):
        state, loss = tr.step(state, batch)
        losses.append(float(loss))
        if i == 0:
            after_one = [np.asarray(b["expert_bias"])
                         for b in state.params["blocks"]
                         if "expert_bias" in b]
    assert tr.step_traces <= 2          # init_state's uncommitted state
    traces = tr.step_traces
    state, loss = tr.step(state, batch)
    assert tr.step_traces == traces     # steady: no further trace
    assert float(loss) < losses[0] and np.isfinite(losses).all()
    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert all(np.array_equal(shards[0], s) for s in shards[1:])
    # the float32 leaves keep their type through the flat master and back.
    # No gradient step moves the balanced bias: what the gather hands out
    # is the codec's roundtrip of the master (8-bit mantissas a block of
    # 16, as of every leaf; at these sizes a block straddles leaves and
    # takes a norm's exponent), the same after one step and after five
    after = [b for b in state.params["blocks"] if "expert_bias" in b]
    for run, first, was in zip(after, after_one, bias):
        assert run["wr"].dtype == run["expert_bias"].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(run["expert_bias"]), first)
        assert rel_l2(run["expert_bias"], was) < 0.1
    mixer = state.params["blocks"][0]
    assert mixer["A_log"].dtype == mixer["dt_bias"].dtype == jnp.float32
    assert not np.array_equal(np.asarray(mixer["A_log"]), a_log)
