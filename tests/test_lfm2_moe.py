"""models/lfm2_moe.py against the benchmark family's plain float32 reference
(benchmark/families/lfm2_moe.py, which imports nothing of the package): loss
and gradient with and without a chip's share of the experts, the share test
of the model-configs guide, the short convolution against its three terms
written out, grouped keys against repeated keys, the selection bias, the
parameter count, and DPTrainer steps on the CPU mesh.  Tiny widths, float32,
seeded weights."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import loader
from fpga_ai_nic_tpu.models import decoder, glm_moe, lfm2_moe
from fpga_ai_nic_tpu.ops import moe, ring_attention
from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
from fpga_ai_nic_tpu.utils.config import (
    BFPConfig, CollectiveConfig, MeshConfig, OptimizerConfig, TrainConfig)

FAMILY = loader.load_module("families", "lfm2_moe")
PATTERN = ["conv", "full_attention", "conv", "conv", "conv"]

# the configuration file's keys at a size the CPU runs in a second
TINY = dict(
    hidden_size=32, intermediate_size=64, moe_intermediate_size=24,
    num_attention_heads=4, num_key_value_heads=2, num_experts=2,
    router_width=8, ep_size=4, ep_rank=1, routed_scaling_factor=1,
    num_experts_per_tok=2, num_dense_layers=1, num_hidden_layers=5,
    layer_types=PATTERN, norm_eps=1e-5, conv_L_cache=3, conv_bias=False,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    use_expert_bias=True, tie_word_embeddings=True, norm_topk_prob=True,
    vocab_size=128, compute_dtype="float32", attn_impl="xla", attn_block=8)
UNCUT = dict(TINY, num_experts=8, ep_size=1, ep_rank=0)
JOB = dict(dp=1, batch_per_chip=2, seq_len=16)
CONFIG_FILE = os.path.join(loader.ROOT,
                           "benchmark/configs/lfm2-24b-l5-e8of64.json")


def rel_l2(tree, ref):
    a, b = jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(ref)
    assert len(a) == len(b)
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(a, b))
    return (num / sum(float(jnp.sum(y ** 2)) for y in b)) ** 0.5


def reference_loss(config, batch):
    def loss(params):
        with jax.default_matmul_precision("highest"):
            total, count = FAMILY.reference_nll(params, batch, config)
        return total / count
    return loss


# -- (a) loss and gradient ---------------------------------------------------

@pytest.mark.parametrize("config", [
    TINY, UNCUT, dict(TINY, attn_block=16), dict(TINY, attn_block=6),
    dict(TINY, num_dense_layers=2, num_hidden_layers=7,
         layer_types=["conv", "conv"] + PATTERN),
    dict(TINY, conv_L_cache=4), dict(TINY, num_key_value_heads=4),
    dict(TINY, num_key_value_heads=1), dict(TINY, norm_topk_prob=False)],
    ids=["held-2-of-8", "all-held", "queries-in-one-chunk",
         "a-ragged-last-chunk", "two-leading-layers-as-published",
         "four-taps", "as-many-keys-as-queries", "one-key-head",
         "gates-not-normalised"])
def test_loss_and_gradient_match_the_reference(config):
    init, loss_fn = FAMILY.program(config, JOB)
    params = init(jax.random.PRNGKey(0))
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), config, JOB)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        reference_loss(config, batch)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    assert rel_l2(grads, want_grads) < 1e-5
    # every leaf has a gradient — the router's through the gates, the tied
    # embedding's from both its uses — but the selection bias, which no
    # gradient step moves
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        moved = float(jnp.sum(g ** 2)) > 0
        assert moved != ("expert_bias" in jax.tree_util.keystr(path)), path


def test_the_tied_head_is_one_leaf_with_two_uses():
    """The embedding's gradient is the gather's plus the head's: with the
    head cut off the same leaf gets the gather's alone."""
    cfg = lfm2_moe.Lfm2MoeConfig.tiny()
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), TINY, JOB)
    whole = jax.grad(lambda p: lfm2_moe.loss_fn(p, batch, cfg))(params)

    def gather_only(emb):
        p = dict(params, tok_emb=emb)
        x = lfm2_moe.hidden(p, batch[0], cfg)
        valid = (batch[1] >= 0).reshape(-1)
        nll = decoder.head_nll(
            p["final_norm"], jax.lax.stop_gradient(emb),
            x.reshape(-1, cfg.dim), jnp.where(valid, batch[1].reshape(-1), 0),
            cfg.norm_eps, tied=True)
        return decoder.next_token_loss(nll, valid)

    part = jax.grad(gather_only)(params["tok_emb"])
    assert 0.05 < rel_l2(part, whole["tok_emb"]) < 2.0


# -- parameters --------------------------------------------------------------

def test_parameter_count_is_the_tree():
    cfg = FAMILY.model_config(TINY)
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    assert lfm2_moe.num_params(cfg) == sum(
        p.size for p in jax.tree_util.tree_leaves(params))
    assert cfg.runs == (("conv", "dense", 1), ("full_attention", "moe", 1),
                        ("conv", "moe", 3))
    dense, attn, conv = params["layers"]
    assert attn["wr"].dtype == conv["expert_bias"].dtype == jnp.float32
    assert conv["wr"].shape == (3, 32, 8)          # the router's width
    assert conv["w1"].shape == (3, 2, 32, 24)      # the experts held
    assert conv["conv_w"].shape == (3, 3, 32) and "wqkv" not in conv
    assert attn["wqkv"].shape == (1, 32, (4 + 2 * 2) * 8)
    assert dense["w1"].shape == (1, 32, 64) and "wr" not in dense
    assert not np.asarray(conv["expert_bias"]).any()
    assert np.asarray(attn["q_norm"] == 1).all()


PARTS = {           # ISSUE 35's table, from shapes alone
    ("conv", "dense"): 89_139_200, ("conv", "moe"): 92_416_064,
    ("full_attention", "moe"): 86_118_592}


def test_published_size_has_the_issue_s_count():
    """Layer 0 + a c c c, 8 of 64 experts, 8,192 rows of a tied embedding:
    469,285,248 parameters, and each kind of layer its part of the table."""
    cfg = FAMILY.model_config(loader.read_json(CONFIG_FILE))
    assert lfm2_moe.num_params(cfg) == 469_285_248
    like = jax.eval_shape(lambda: lfm2_moe.init(jax.random.PRNGKey(0), cfg))
    assert sum(np.prod(p.shape) for p in
               jax.tree_util.tree_leaves(like)) == 469_285_248
    assert cfg.held == tuple(range(8)) and cfg.n_routed_experts == 64
    for (mixer, ffn, n), stack in zip(cfg.runs, like["layers"]):
        assert sum(np.prod(p.shape[1:]) for p in stack.values()) \
            == PARTS[mixer, ffn]
    assert like["tok_emb"].shape == (8192, 2048)
    assert 16_779_264 + sum(n * PARTS[m, f] for m, f, n in cfg.runs) \
        == 469_285_248


def test_the_default_is_the_published_stack():
    cfg = lfm2_moe.Lfm2MoeConfig()
    assert len(cfg.layer_types) == 40
    assert [i for i, kind in enumerate(cfg.layer_types)
            if kind == "full_attention"] == list(range(2, 40, 4))
    assert cfg.runs[0] == ("conv", "dense", 2) and len(cfg.runs) == 21
    assert (cfg.head_dim, cfg.n_heads // cfg.n_kv_heads) == (64, 4)


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("conv", "window")), "conv or full_attention"),
    (dict(n_heads=3), "do not divide"), (dict(n_kv_heads=3), "do not divide"),
    (dict(n_dense_layers=6), "n_dense_layers")])
def test_a_config_that_is_no_such_model_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        lfm2_moe.Lfm2MoeConfig.tiny(**kw)


def test_the_shared_leaves_initialise_as_glm_s_did():
    """`decoder.init_leaves` is what `glm_moe._init_leaves` was: the same
    keys give the same leaves, and a selection bias starts at zero."""
    shapes = {"a_norm": (8,), "wr": (8, 4), "w1": (8, 6), "x_bias": (4,)}
    out = decoder.init_leaves(jax.random.PRNGKey(3), shapes, (2,),
                              jnp.bfloat16)
    assert glm_moe._init_leaves is decoder.init_leaves
    assert out["wr"].dtype == jnp.float32 and out["w1"].dtype == jnp.bfloat16
    assert out["x_bias"].dtype == jnp.float32 and not out["x_bias"].any()
    assert out["w1"].shape == (2, 8, 6) and (out["a_norm"] == 1).all()
    assert 0.2 < float(jnp.std(out["wr"])) < 0.5       # 8 ** -0.5 = 0.354


# -- (b) the shares add up to the uncut layer --------------------------------

def test_all_shares_sum_to_the_uncut_layer():
    """A 64-expert layer over 8 chips, a selection bias in it: the routed
    part each share computes, summed, is what the uncut reference gives for
    the whole layer (no shared expert: nothing is computed alike on every
    chip but the mixers, which this layer does not hold)."""
    d, f, n, k, tokens = 16, 8, 64, 4, 48
    kr, k1, k2, k3, kb, kx = jax.random.split(jax.random.PRNGKey(3), 6)
    whole = {"wr": jax.random.normal(kr, (d, n)) * d ** -0.5,
             "expert_bias": 0.3 * jax.random.normal(kb, (n,)),
             "w1": jax.random.normal(k1, (n, d, f)) * d ** -0.5,
             "w3": jax.random.normal(k3, (n, d, f)) * d ** -0.5,
             "w2": jax.random.normal(k2, (n, f, d)) * f ** -0.5}
    x = jax.random.normal(kx, (1, tokens, d))
    config = dict(num_experts_per_tok=k, routed_scaling_factor=1,
                  norm_topk_prob=True)
    with jax.default_matmul_precision("highest"):
        want, _ = FAMILY._expert_ffn(whole, x[0], config, held=range(n))
    routed = jnp.zeros_like(x)
    rows = 0
    for rank in range(8):
        held = tuple(range(rank * 8, rank * 8 + 8))
        mine = {"wr": whole["wr"], **{w: whole[w][rank * 8:rank * 8 + 8]
                                      for w in ("w1", "w3", "w2")}}
        part, counts = moe.held_experts_ffn(
            mine, x, num_experts=n, top_k=k, held=held,
            bias=whole["expert_bias"], with_counts=True)
        routed = routed + part
        rows += int(counts["rows"].sum())
        assert int(counts["dropped"]) == 0
    assert rows == tokens * k           # every assignment on exactly one
    np.testing.assert_allclose(np.asarray(routed[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- (c) the short convolution -----------------------------------------------

@pytest.mark.parametrize("taps", [3, 2, 4])
def test_the_convolution_is_the_sum_written_out(taps):
    """c_t = sum_j w[j] u_{t-(L-1)+j}, channel by channel, u zero before the
    sequence: also at a sequence's first positions, where fewer than L
    terms exist.  `jnp.convolve` flips its kernel, so the taps reversed give
    the same numbers: the taps are a correlation."""
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 5))
    w = jax.random.normal(jax.random.PRNGKey(5), (taps, 5))
    got = np.asarray(lfm2_moe.causal_conv(u, w))
    un, wn = np.asarray(u), np.asarray(w)
    for b in range(2):
        for ch in range(5):
            for t in range(9):
                want = sum(wn[j, ch] * un[b, t - (taps - 1) + j, ch]
                           for j in range(taps) if t - (taps - 1) + j >= 0)
                assert abs(got[b, t, ch] - want) < 1e-5
            full = np.convolve(un[b, :, ch], wn[::-1, ch])[:9]
            np.testing.assert_allclose(got[b, :, ch], full, atol=1e-5)
    np.testing.assert_allclose(got[:, 0], wn[-1] * un[:, 0], atol=1e-6)


def test_the_convolution_mixer_is_the_reference_s_and_is_causal():
    cfg = FAMILY.model_config(TINY)
    lyr = jax.tree_util.tree_map(
        lambda a: a[0], lfm2_moe.init(jax.random.PRNGKey(5), cfg)["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 32))
    got = lfm2_moe.conv_mixer(lyr, h)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._conv_mixer(lyr, h, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # reversed taps are another mixer (the reference's planted fault)
    flipped = lfm2_moe.conv_mixer(dict(lyr, conv_w=lyr["conv_w"][::-1]), h)
    assert float(jnp.max(jnp.abs(flipped - want))) > 1e-3
    # position t sees nothing after t
    later = h.at[:, 7:].add(1.0)
    np.testing.assert_allclose(
        np.asarray(lfm2_moe.conv_mixer(lyr, later)[:, :7]),
        np.asarray(got[:, :7]), atol=1e-6)


# -- (d) grouped keys --------------------------------------------------------

def test_grouped_keys_equal_explicitly_repeated_keys():
    """8 query heads over 2 key/value heads: the block equals one whose
    k and v columns are written out four times and which is told it has as
    many key heads as query heads."""
    config = dict(TINY, hidden_size=64, num_attention_heads=8,
                  num_key_value_heads=2)
    cfg = FAMILY.model_config(config)
    lyr = jax.tree_util.tree_map(
        lambda a: a[0], lfm2_moe.init(jax.random.PRNGKey(5), cfg)["layers"][1])
    hd = cfg.head_dim
    assert lyr["wqkv"].shape == (64, (8 + 2 + 2) * hd)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    pos = jnp.arange(24, dtype=jnp.int32)
    got = lfm2_moe.gqa(lyr, h, pos, cfg)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._attention(lyr, h, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    q, k, v = jnp.split(lyr["wqkv"], [8 * hd, 10 * hd], axis=1)

    def repeated(cols):                 # key head i // 4 for query head i
        return jnp.repeat(cols.reshape(64, 2, hd), 4, axis=1).reshape(64, -1)

    full = dict(lyr, wqkv=jnp.concatenate([q, repeated(k), repeated(v)], 1))
    same = lfm2_moe.gqa(full, h, pos,
                        FAMILY.model_config(dict(config,
                                                 num_key_value_heads=8)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                               rtol=1e-5, atol=1e-6)
    # the rotary part matters: without positions the block reads otherwise
    flat = lfm2_moe.gqa(lyr, h, jnp.zeros((24,), jnp.int32), cfg)
    assert float(jnp.max(jnp.abs(flat - want))) > 1e-3


def test_a_layer_s_checkpoint_keeps_what_glm_s_keeps():
    """The layers are checkpointed under `decoder.KEEP`, which is
    glm_moe's: the attention route's `out` and `lse` by name."""
    assert glm_moe._KEEP is decoder.KEEP is lfm2_moe.KEEP
    cfg = lfm2_moe.Lfm2MoeConfig.tiny()
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), TINY, JOB)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: lfm2_moe.loss_fn(p, batch, cfg)))(params))
    assert ring_attention.SAVED in text


# -- (e) the selection bias --------------------------------------------------

def test_a_bias_changes_the_selection_and_not_the_gates():
    wr = jax.random.normal(jax.random.PRNGKey(7), (16, 8)) * 0.25
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 16))
    gates0, experts0 = moe.sigmoid_route(wr, x, top_k=2)
    bias = jnp.zeros((8,)).at[5].set(10.0)      # expert 5 wins every row
    gates, experts = moe.sigmoid_route(wr, x, top_k=2, bias=bias)
    assert (np.asarray(experts) == 5).any(axis=1).all()
    assert not (np.asarray(experts0) == 5).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(
        jnp.dot(x, wr, precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(scores, np.asarray(experts), axis=1)
    # the gates are the selected SCORES over their sum: no trace of the 10
    np.testing.assert_allclose(np.asarray(gates),
                               picked / picked.sum(axis=1, keepdims=True),
                               rtol=1e-5)


def test_the_model_passes_its_bias_leaf_to_the_router():
    cfg = FAMILY.model_config(UNCUT)
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), UNCUT, JOB)
    stats = lfm2_moe.routing_stats(params, batch, cfg)
    params["layers"][2]["expert_bias"] = \
        params["layers"][2]["expert_bias"].at[:, 3].set(10.0)
    steered = lfm2_moe.routing_stats(params, batch, cfg)
    rows, before = np.asarray(steered["rows"]), np.asarray(stats["rows"])
    assert (rows[1:, 3] == 32).all() and (before[1:, 3] < 32).all()
    np.testing.assert_array_equal(rows[0], before[0])   # layer 2's own bias
    with jax.default_matmul_precision("highest"):
        want, _ = FAMILY.reference_nll(params, batch, UNCUT)
    got = lfm2_moe.loss_fn(params, batch, cfg) * 30
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))


# -- routing_stats -----------------------------------------------------------

def test_routing_stats_count_the_rows_of_the_batch():
    cfg = FAMILY.model_config(TINY)
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), TINY, JOB)
    stats = jax.jit(lambda p, b: lfm2_moe.routing_stats(p, b, cfg))(params,
                                                                    batch)
    layers, tokens, k = 4, 32, 2        # the attention layer's, then three
    assert stats["rows"].shape == (layers, 2)
    assert stats["selected"].shape == (layers, tokens, k)
    held = np.isin(np.asarray(stats["selected"]), cfg.held)
    np.testing.assert_array_equal(np.asarray(stats["rows"]).sum(axis=1),
                                  held.sum(axis=(1, 2)))
    np.testing.assert_allclose(np.asarray(stats["held_share"]),
                               held.mean(axis=(1, 2)), rtol=1e-6)
    assert np.asarray(stats["dropped"]).tolist() == [0] * layers
    with jax.default_matmul_precision("highest"):
        chosen = FAMILY._hidden(params, batch[0], TINY,
                                with_selection=True)[1]
    assert chosen.shape == (layers, tokens, 8)
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
    state = tr.init_state(init(jax.random.PRNGKey(0)))
    batch = tr.shard_batch(FAMILY.make_batch(jax.random.PRNGKey(1), TINY,
                                             job))
    losses = []
    for _ in range(4):
        state, loss = tr.step(state, batch)
        losses.append(float(loss))
    assert tr.step_traces <= 2          # init_state's uncommitted state
    traces = tr.step_traces
    state, loss = tr.step(state, batch)
    assert tr.step_traces == traces     # steady: no further trace
    assert float(loss) < losses[0] and np.isfinite(losses).all()
    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert all(np.array_equal(shards[0], s) for s in shards[1:])
    # the router and the bias keep their type through the flat master and
    # back, and four AdamW steps leave the bias where it was
    for run in state.params["layers"][1:]:
        assert run["wr"].dtype == run["expert_bias"].dtype == jnp.float32
        assert not np.asarray(run["expert_bias"]).any()
