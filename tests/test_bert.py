"""BERT + bucketed DDP all-reduce (BASELINE.json config 4).

Verifies: bucket planning (reverse-leaf issue order, BFP padding), bucketed
all-reduce == per-leaf psum mean, the DDP trainer against a single-device
reference SGD step, masked-token loss weighting under dp, and convergence
with the BFP-compressed bucketed ring.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fpga_ai_nic_tpu.models import bert
from fpga_ai_nic_tpu.ops import bucketed
from fpga_ai_nic_tpu.parallel import DDPTrainer, make_mesh
from fpga_ai_nic_tpu.utils.config import (
    BFPConfig, CollectiveConfig, MeshConfig, OptimizerConfig, TrainConfig)

MCFG = bert.BertConfig.tiny()


def _cfg(**kw):
    base = dict(
        iters=4, global_batch=16, mesh=MeshConfig(dp=8),
        collective=CollectiveConfig(bucket_elems=4096),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    base.update(kw)
    return TrainConfig(**base)


def _data(rng, n=16, S=32, mask_frac=0.15):
    """MLM batch: 15% of non-pad positions masked, labels -100 elsewhere."""
    toks = rng.integers(1, MCFG.vocab, (n, S)).astype(np.int32)
    toks[:, S - 4:] = MCFG.pad_id                    # padded tail
    labels = np.full((n, S), -100, np.int32)
    m = (rng.random((n, S)) < mask_frac) & (toks != MCFG.pad_id)
    m[:, 0] = True                                   # >=1 target per row
    labels[m] = toks[m]
    toks[m] = 3                                      # [MASK]-style id
    return jnp.asarray(toks), jnp.asarray(labels)


# -- bucket planning ---------------------------------------------------------

def test_plan_buckets_covers_all_leaves_in_reverse_order():
    params = bert.init(jax.random.PRNGKey(0), MCFG)
    coll = CollectiveConfig(bucket_elems=5000)
    plan = bucketed.plan_buckets(params, coll, 8)
    seen = [i for b in plan.buckets for i in b.leaf_ids]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert sorted(seen) == list(range(n_leaves))
    # issue order is reverse flatten order (backward availability)
    assert seen == list(reversed(range(n_leaves)))
    sizes = [int(np.prod(s)) if s else 1 for s in plan.shapes]
    for b in plan.buckets[:-1]:
        assert sum(b.sizes) >= coll.bucket_elems or len(b.leaf_ids) == 1
    for b in plan.buckets:
        assert b.padded_len % 8 == 0
        assert b.padded_len >= sum(sizes[i] for i in b.leaf_ids)


def test_plan_buckets_pads_for_bfp_blocks():
    params = bert.init(jax.random.PRNGKey(0), MCFG)
    coll = CollectiveConfig(impl="ring", compression=BFPConfig(),
                            bucket_elems=5000)
    plan = bucketed.plan_buckets(params, coll, 8)
    for b in plan.buckets:
        assert b.padded_len % (8 * 16) == 0


# -- bucketed all-reduce -----------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "ring"])
def test_bucketed_all_reduce_is_mean(rng, impl):
    mesh = make_mesh(MeshConfig(dp=8))
    coll = CollectiveConfig(impl=impl, bucket_elems=500)
    trees = [
        {"a": jnp.asarray(rng.standard_normal((8, 40, 7)), jnp.float32),
         "b": [jnp.asarray(rng.standard_normal((8, 333)), jnp.float32),
               jnp.asarray(rng.standard_normal((8, 2, 3)), jnp.float32)]}]
    tree = trees[0]

    def run(t):
        out = bucketed.all_reduce_bucketed(t, "dp", coll)
        if impl == "xla":
            out = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, "dp", to="varying"), out)
        return out

    got = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("dp"),),
                                out_specs=P("dp")))(tree)
    want = jax.tree_util.tree_map(lambda x: np.broadcast_to(
        np.mean(np.asarray(x), axis=0, keepdims=True), x.shape), tree)
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(np.asarray(g), w, atol=1e-6),
        got, want)


def test_bucketed_flat_keeps_f32_for_bf16_leaves(rng):
    """The flat variant must not round the dp-mean through the leaf dtype
    (bf16 models keep f32 masters for exactly this reason)."""
    mesh = make_mesh(MeshConfig(dp=8))
    coll = CollectiveConfig(bucket_elems=64)
    tree = {"w": jnp.asarray(rng.standard_normal((8, 100)), jnp.bfloat16),
            "b": jnp.asarray(rng.standard_normal((8, 33)), jnp.bfloat16)}

    def run(t):
        flat = bucketed.all_reduce_bucketed_flat(t, "dp", coll)
        return lax.pcast(flat, "dp", to="varying")

    got = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("dp"),),
                                out_specs=P("dp")))(tree)
    got = np.asarray(got).reshape(8, -1)[0]
    assert got.dtype == np.float32
    want = np.concatenate([
        np.mean(np.asarray(tree["b"], np.float32), axis=0).reshape(-1),
        np.mean(np.asarray(tree["w"], np.float32), axis=0).reshape(-1)])
    # forward leaf order: dict flattens alphabetically -> b then w
    np.testing.assert_allclose(got, want, atol=1e-6)
    # and it is strictly more precise than the bf16-rounded tree path
    rounded = want.astype(jnp.bfloat16).astype(np.float32)
    assert np.any(got != rounded)


# -- DDP trainer -------------------------------------------------------------

def _loss(params, batch):
    return bert.loss_fn(params, batch, MCFG, dp_axis="dp")


def _reference_step(params, batch, lr):
    """Single-device global-mean MLM gradient + SGD."""
    g = jax.grad(lambda p, b: bert.loss_fn(p, b, MCFG))(params, batch)
    return jax.tree_util.tree_map(
        lambda w, gg: (w.astype(jnp.float32) - lr * gg.astype(jnp.float32)
                       ).astype(w.dtype), params, g)


@pytest.mark.parametrize("impl", ["xla", "ring"])
def test_ddp_matches_single_device_reference(rng, impl):
    cfg = _cfg(collective=CollectiveConfig(impl=impl, bucket_elems=4096))
    tr = DDPTrainer(_loss, make_mesh(cfg.mesh), cfg)
    params = bert.init(jax.random.PRNGKey(0), MCFG)
    state = tr.init_state(params)
    batch_host = _data(rng)
    # reference first: the trainer's donated step invalidates `params`
    want = _reference_step(params, batch_host, cfg.optimizer.learning_rate)
    ref_loss = float(bert.loss_fn(params, batch_host, MCFG))
    state, loss = tr.step(state, tr.shard_batch(batch_host))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-4, atol=2e-5), state.params, want)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-4)


@pytest.mark.slow
def test_ddp_bfp_ring_converges(rng):
    cfg = _cfg(
        iters=8,
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    bucket_elems=4096),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=3e-3))
    tr = DDPTrainer(_loss, make_mesh(cfg.mesh), cfg)
    state = tr.init_state(bert.init(jax.random.PRNGKey(0), MCFG))
    batch = tr.shard_batch(_data(rng))
    first = None
    for _ in range(cfg.iters):
        state, loss = tr.step(state, batch)
        first = float(loss) if first is None else first
    assert np.isfinite(float(loss))
    assert float(loss) < first, (float(loss), first)


def test_ddp_replicas_stay_identical(rng):
    """Master copy must remain bit-identical across devices after steps
    (the reference's invariant: every node's DDR holds the same weights)."""
    cfg = _cfg(collective=CollectiveConfig(impl="ring", bucket_elems=2048))
    tr = DDPTrainer(_loss, make_mesh(cfg.mesh), cfg)
    state = tr.init_state(bert.init(jax.random.PRNGKey(0), MCFG))
    for _ in range(2):
        state, _ = tr.step(state, tr.shard_batch(_data(rng)))
    shards = [np.asarray(s.data) for s in
              state.w_master.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


# -- model sanity ------------------------------------------------------------

def test_bert_forward_shapes_and_padding_mask(rng):
    params = bert.init(jax.random.PRNGKey(1), MCFG)
    toks, _ = _data(rng, n=4)
    logits = bert.apply(params, toks, MCFG)
    assert logits.shape == (4, 32, MCFG.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    # padding keys must not influence non-pad positions: perturb pad tokens
    toks2 = np.asarray(toks).copy()
    pads = toks2 == MCFG.pad_id
    toks2[pads] = 7
    mask = jnp.asarray(~pads)
    l1 = bert.apply(params, toks, MCFG)
    l2 = bert.apply(params, jnp.asarray(toks2), MCFG, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(l1[~pads]), np.asarray(l2[~pads]),
                               atol=1e-5)


def test_num_params_matches_init():
    params = bert.init(jax.random.PRNGKey(0), MCFG)
    total = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(params))
    assert total == bert.num_params(MCFG)


# -- the MLM head runs on the masked positions only (PR 26) -------------------

def _plain_loss(params, batch, cfg):
    """The formula `loss_fn` had before it went block by block: the head on
    every position, log-softmax over [B, S, vocab], the mask last."""
    tokens, labels = batch
    valid = labels >= 0
    logz = jax.nn.log_softmax(
        bert.apply(params, tokens, cfg).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logz, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def _masked_batch(rng, cfg, n, S, share):
    """share: a fraction of the positions, or "one" for a single position."""
    toks = rng.integers(4, cfg.vocab, (n, S)).astype(np.int32)
    if share == "one":
        m = np.zeros((n, S), bool)
        m[n // 2, S // 3] = True
    else:
        m = rng.random((n, S)) < share
    labels = np.where(m, toks, -100).astype(np.int32)
    return jnp.asarray(np.where(m, 3, toks)), jnp.asarray(labels)


def _rel_err(got, want):
    """Relative L2 error of every leaf, in float32."""
    def one(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return float(jnp.linalg.norm((g - w).ravel())
                     / jnp.maximum(jnp.linalg.norm(w.ravel()), 1e-30))
    return jax.tree_util.tree_map(one, got, want)


def _assert_no_further_from_float32(got_l, got_g, want_l, want_g, true_l,
                                    true_g):
    """Two bfloat16 roundings of one formula held against it in float32:
    ours may be no further from the truth than half again theirs."""
    assert abs(float(got_l) - float(true_l)) <= max(
        abs(float(want_l) - float(true_l)), 1e-3 * float(true_l))
    ours, theirs = _rel_err(got_g, true_g), _rel_err(want_g, true_g)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_less(a, 1.5 * b + 3e-3),
        ours, theirs)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [4, 5], ids=["T=2blocks", "T=2.5blocks"])
@pytest.mark.parametrize("share", ["one", 0.15, 0.5, 1.0, 0.0],
                         ids=["one", "15%", "50%", "all", "none"])
def test_blockwise_loss_and_gradients_match_the_plain_formula(
        rng, monkeypatch, share, n, dtype):
    """float32: equal to 2e-6 on every leaf.  bfloat16: two roundings of
    the same mathematics differ by more than that from each other, so both
    are held against the plain formula in float32 on the same weights, and
    the blockwise one may be no further from it than half again the plain
    one's distance."""
    monkeypatch.setattr(bert, "MLM_BLOCK", 64)
    cfg = dataclasses.replace(MCFG, dtype=dtype)
    cfg32 = dataclasses.replace(MCFG, dtype="float32")
    params = bert.init(jax.random.PRNGKey(2), cfg)
    batch = _masked_batch(rng, cfg, n, 32, share)
    plain = jax.value_and_grad(_plain_loss)
    want_l, want_g = plain(params, batch, cfg)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p, b: bert.loss_fn(p, b, cfg)))(params, batch)
    for got, want in zip(jax.tree_util.tree_leaves(got_g),
                         jax.tree_util.tree_leaves(want_g)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.all(np.isfinite(np.asarray(got, np.float32)))
    if share == 0.0:
        assert float(got_l) == 0.0
        for leaf in jax.tree_util.tree_leaves(got_g):
            assert not np.any(np.asarray(leaf, np.float32))
        return
    if dtype == "float32":
        np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
        errs = _rel_err(got_g, want_g)
        # tok_emb takes the head's gradient and the embedding's
        assert errs["tok_emb"] <= 2e-6, errs
        assert max(jax.tree_util.tree_leaves(errs)) <= 2e-6, errs
        return
    true_l, true_g = plain(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), params), batch, cfg32)
    _assert_no_further_from_float32(got_l, got_g, want_l, want_g, true_l,
                                    true_g)


@pytest.mark.parametrize("check_vma", [True, False])
def test_blockwise_loss_under_dp_with_unequal_counts(rng, monkeypatch,
                                                     check_vma):
    """dp=4, one block a shard and a half: the shards hold 0, 1, 20 and 48
    masked positions (none, one block, one block, two), so their loops run
    different trip counts.  Loss = the global token-weighted mean; the
    trainer-effective gradient (sum over replicas / n) = the single-device
    gradient."""
    monkeypatch.setattr(bert, "MLM_BLOCK", 32)
    n_dp, per, S = 4, 3, 16                 # 48 positions a shard
    params = bert.init(jax.random.PRNGKey(3), MCFG)
    toks = rng.integers(4, MCFG.vocab, (n_dp * per, S)).astype(np.int32)
    m = np.zeros((n_dp, per * S), bool)
    m[1, 7] = True
    m[2, rng.choice(per * S, 20, replace=False)] = True
    m[3] = True
    m = m.reshape(n_dp * per, S)
    batch = (jnp.asarray(np.where(m, 3, toks)),
             jnp.asarray(np.where(m, toks, -100).astype(np.int32)))
    want_l, want_g = jax.value_and_grad(_plain_loss)(params, batch, MCFG)

    def shard(p, b):
        p = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, "dp", to="varying"), p)
        loss, g = jax.value_and_grad(
            lambda pp: bert.loss_fn(pp, b, MCFG, dp_axis="dp"))(p)
        return (lax.pmax(loss, "dp"), jax.tree_util.tree_map(
            lambda x: lax.psum(x, "dp") / n_dp, g))

    mesh = make_mesh(MeshConfig(dp=n_dp), devices=jax.devices()[:n_dp])
    got_l, got_g = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(), P("dp")), out_specs=(P(), P()),
        check_vma=check_vma))(params, batch)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
    errs = _rel_err(got_g, want_g)
    assert max(jax.tree_util.tree_leaves(errs)) <= 2e-6, errs


def _array_sizes(text):
    """Element counts of every tensor type in a lowered module's text."""
    return [int(np.prod([int(d) for d in dims[:-1].split("x")]))
            for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)]


def test_no_array_of_tokens_by_vocab_in_the_gradient(rng, monkeypatch):
    """vocab 512 and T = 128: tokens x vocab = 65,536 elements, twice the
    next largest array of the step (tok_emb, 32,768).  The plain formula's
    gradient holds such arrays; the blockwise one holds [block, vocab]."""
    monkeypatch.setattr(bert, "MLM_BLOCK", 32)
    cfg = dataclasses.replace(MCFG, vocab=512)
    params = bert.init(jax.random.PRNGKey(4), cfg)
    batch = _masked_batch(rng, cfg, 4, 32, 0.15)
    T = batch[0].size

    def sizes(loss):
        return _array_sizes(jax.jit(jax.grad(loss)).lower(
            params, batch).as_text())

    plain = sizes(lambda p, b: _plain_loss(p, b, cfg))
    assert max(plain) >= T * cfg.vocab          # the test can see them
    got = sizes(lambda p, b: bert.loss_fn(p, b, cfg))
    assert 32 * cfg.vocab in got                # one block of logits
    assert max(got) < T * cfg.vocab, max(got)
    assert max(got) == cfg.vocab * cfg.dim      # tok_emb and its gradient


@pytest.mark.parametrize("T,masked,block,want", [
    (160, 0, 64, 0), (160, 1, 64, 64), (160, 64, 64, 64), (160, 65, 64, 128),
    (160, 160, 64, 192),                # 2.5 blocks: the last one is padded
    (16384, 2458, 1024, 3072),          # the benchmark's cells at 15%
    (16384, 16384, 1024, 16384),
    (100, 1, 1024, 100)])               # fewer positions than a block
def test_mlm_head_rows_against_counts_worked_by_hand(monkeypatch, T, masked,
                                                     block, want):
    monkeypatch.setattr(bert, "MLM_BLOCK", block)
    labels = np.full(T, -100, np.int32)
    labels[np.random.default_rng(T + masked).choice(T, masked,
                                                    replace=False)] = 7
    assert bert.mlm_head_rows(labels.reshape(4, -1)) == (want, T)


def test_apply_is_the_head_on_the_encoder_at_every_position(rng):
    params = bert.init(jax.random.PRNGKey(1), MCFG)
    toks, _ = _data(rng, n=3)
    hidden = bert.encode(params, toks, MCFG)
    assert hidden.shape == (3, 32, MCFG.dim)
    logits = bert.apply(params, toks, MCFG)
    assert logits.shape == (3, 32, MCFG.vocab)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(bert.mlm_head(params, hidden, MCFG)))
    rows = bert.mlm_head(params, hidden.reshape(-1, MCFG.dim)[5:9], MCFG)
    np.testing.assert_allclose(np.asarray(rows),
                               np.asarray(logits).reshape(-1, MCFG.vocab)[5:9],
                               atol=1e-5)


# -- attention is one call of the blocked route (PR 33) ----------------------

def _plain_attention(q, k, v, *, causal, sm_scale, impl, key_bias):
    """What `encode` did itself before it called the route, kept as the
    golden: every score of the batch at once, float32[B, H, S, S]."""
    assert not causal
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(s + key_bias[:, None, None, :], axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _padded_batch(rng, cfg, n, S):
    """An MLM batch whose sequences end in padded tails of unequal length
    (one has none), 15% of the other positions masked."""
    toks, labels = (np.asarray(x).copy()
                    for x in _masked_batch(rng, cfg, n, S, 0.15))
    for row, tail in enumerate(rng.integers(1, S // 2, n - 1)):
        toks[row, S - tail:] = cfg.pad_id
        labels[row, S - tail:] = -100
    labels[:, 0] = toks[:, 0]                  # a target in every sequence
    return jnp.asarray(toks), jnp.asarray(labels)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S,budget", [(32, None), (64, None),
                                      (64, 2 * 4 * 64 * 64 * 4)],
                         ids=["S=32", "S=64", "S=64-two-groups"])
def test_loss_and_gradients_match_the_plain_attention(rng, monkeypatch, S,
                                                      budget, dtype):
    """`bert.loss_fn` through the route against the same loss with the
    plain einsum / softmax / einsum in the route's place, on a batch with
    padded tails.  float32: 1e-5 on every leaf (the two differ by the
    order of float32 sums).  bfloat16: both against the plain formula in
    float32 on the same weights, the route's no further than half again
    the plain one's distance."""
    from fpga_ai_nic_tpu.ops import ring_attention as ra
    if budget is not None:
        monkeypatch.setattr(ra, "SCORE_BLOCK_BYTES", budget)
    cfg = dataclasses.replace(MCFG, dtype=dtype)
    params = bert.init(jax.random.PRNGKey(5), cfg)
    batch = _padded_batch(rng, cfg, 4, S)

    def value_and_grad(params, cfg):
        return jax.jit(jax.value_and_grad(
            lambda p, b: bert.loss_fn(p, b, cfg)))(params, batch)

    got_l, got_g = value_and_grad(params, cfg)
    monkeypatch.setattr(bert, "flash_attention_remat", _plain_attention)
    want_l, want_g = value_and_grad(params, cfg)
    for got, want in zip(jax.tree_util.tree_leaves(got_g),
                         jax.tree_util.tree_leaves(want_g)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.all(np.isfinite(np.asarray(got, np.float32)))
    if dtype == "float32":
        np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
        errs = _rel_err(got_g, want_g)
        assert max(jax.tree_util.tree_leaves(errs)) <= 1e-5, errs
        return
    true_l, true_g = value_and_grad(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
        dataclasses.replace(cfg, dtype="float32"))
    _assert_no_further_from_float32(got_l, got_g, want_l, want_g, true_l,
                                    true_g)


def test_no_array_of_every_score_in_the_gradient(rng, monkeypatch):
    """B x H x S x S float32 over the route's budget: the plain attention's
    gradient holds the scores of the whole batch, the route's holds a
    group's block [b, H, S, S] and nothing of the whole's size."""
    from fpga_ai_nic_tpu.ops import ring_attention as ra
    B, S, H = 8, 32, MCFG.n_heads
    monkeypatch.setattr(ra, "SCORE_BLOCK_BYTES", 2 * H * S * S * 4)
    params = bert.init(jax.random.PRNGKey(6), MCFG)
    batch = _padded_batch(rng, MCFG, B, S)

    def lowered():
        return jax.jit(jax.grad(
            lambda p, b: bert.loss_fn(p, b, MCFG))).lower(
                params, batch).as_text()

    whole, block = (f"tensor<{b}x{H}x{S}x{S}xf32>" for b in (B, 2))
    got = lowered()
    assert block in got and whole not in got
    monkeypatch.setattr(bert, "flash_attention_remat", _plain_attention)
    assert whole in lowered()                   # the test can see them


@pytest.mark.parametrize("scope", ["ainic.attn.fwd", "ainic.attn.bwd"])
def test_lowered_gradient_holds_the_routes_scopes(rng, scope):
    """The route's scopes stand in BERT's lowered gradient, `attn_impl`
    pinned to "xla" as the benchmark's configuration pins it (under
    jax.grad a location reads "transpose(jvp(ainic.attn.bwd))/mul")."""
    cfg = dataclasses.replace(MCFG, attn_impl="xla")
    params = bert.init(jax.random.PRNGKey(7), cfg)
    text = jax.jit(jax.grad(lambda p, b: bert.loss_fn(p, b, cfg))).lower(
        params, _padded_batch(rng, cfg, 4, 32)).as_text(debug_info=True)
    assert re.search(r"[/\"(]%s[/)]" % re.escape(scope), text)
