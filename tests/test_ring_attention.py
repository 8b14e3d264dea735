"""Ring attention (sequence parallel) vs full attention golden."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fpga_ai_nic_tpu.ops import ring_attention as ra

SP = 8
B, H, S, DH = 2, 4, 64, 32   # S = global sequence


def _mesh():
    return Mesh(jax.devices()[:SP], ("sp",))


def _qkv(rng):
    shape = (B, H, S, DH)
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(rng, causal):
    q, k, v = _qkv(rng)
    want = np.asarray(ra.full_attention(q, k, v, causal=causal))

    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp", causal=causal),
        mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_ring_attention_bf16(rng):
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(rng))
    want = np.asarray(ra.full_attention(q, k, v), np.float32)
    got = np.asarray(jax.jit(jax.shard_map(
        lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp"),
        mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v), np.float32)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)


def test_single_device_degenerates(rng):
    q, k, v = _qkv(rng)
    mesh = Mesh(jax.devices()[:1], ("sp",))
    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp"),
        mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ra.full_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_full(rng, causal):
    """Flash-style k-blocking (k_block < S_local) must agree with full
    attention: blocking changes the accumulation schedule, not the math."""
    q, k, v = _qkv(rng)
    want = np.asarray(ra.full_attention(q, k, v, causal=causal))
    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp", causal=causal,
                                             k_block=4),   # S_local=8 -> 2 blocks
        mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_blockwise_peak_memory_is_o_s():
    """Compiled peak temp memory with k-blocking must stay ~flat as the
    local sequence grows, while the whole-chunk schedule grows O(S^2) —
    the reason the blocked path is the default for long contexts."""
    B2, H2, DH2 = 1, 2, 64

    def temp_bytes(S_local, k_block):
        q = jnp.zeros((B2, H2, S_local, DH2), jnp.float32)
        # trace via shard_map on a 1-device mesh (S_local is the whole seq)
        mesh = Mesh(jax.devices()[:1], ("sp",))
        fn = jax.jit(jax.shard_map(
            lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp",
                                                 k_block=k_block),
            mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp")))
        mem = fn.lower(q, q, q).compile().memory_analysis()
        return mem.temp_size_in_bytes

    blocked_1k = temp_bytes(1024, 256)
    blocked_4k = temp_bytes(4096, 256)
    whole_4k = temp_bytes(4096, None)
    # whole-chunk scores at S=4096: [1,2,4096,4096] f32 ~ 134 MB
    assert whole_4k > 4 * blocked_4k, (whole_4k, blocked_4k)
    # blocked grows ~linearly in S (allow 8x for 4x seq growth slack)
    assert blocked_4k < 8 * max(blocked_1k, 1), (blocked_1k, blocked_4k)


@pytest.mark.parametrize("causal", [True, False])
def test_unrolled_matches_rolled(rng, causal):
    """The hop-loop unroll knob (CollectiveConfig.unroll_hops analogue) is a
    schedule choice only — unrolled and rolled must agree bitwise-ish."""
    q, k, v = _qkv(rng)

    def run(unroll):
        return np.asarray(jax.jit(jax.shard_map(
            lambda q_, k_, v_: ra.ring_attention(
                q_, k_, v_, "sp", causal=causal, unroll=unroll),
            mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp")))(q, k, v))

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("unroll", [True, False])
def test_causal_skip_lowers_to_conditional(unroll):
    """The future-block skip must survive compilation as a real HLO
    ``conditional`` — which executes only the taken branch — not a
    select-both-branches rewrite that would silently keep the dead
    attention FLOPs.  Static cost analysis cannot show the elision (it
    counts every conditional branch once regardless), so the honest check
    is structural: causal keeps >= 1 conditional (n-1 when unrolled, one
    per hop), non-causal has none."""
    q = jnp.zeros((1, 2, SP * 8, 32), jnp.float32)

    def compiled(causal):
        return jax.jit(jax.shard_map(
            lambda q_, k_, v_: ra.ring_attention(
                q_, k_, v_, "sp", causal=causal, k_block=None, unroll=unroll),
            mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"))).lower(q, q, q).compile()

    n_causal = compiled(True).as_text().count("conditional(")
    n_full = compiled(False).as_text().count("conditional(")
    assert n_full == 0, n_full
    assert n_causal >= (SP - 1 if unroll else 1), (n_causal, unroll)


def test_blockwise_nondivisor_kblock(rng):
    """k_block that doesn't divide S_local drops to the largest divisor,
    keeping the memory bound instead of silently going whole-chunk."""
    q, k, v = _qkv(rng)
    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ra.ring_attention(q_, k_, v_, "sp", k_block=3),
        mesh=_mesh(), in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)   # S_local=8 -> divisor 2
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ra.full_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,k_block", [(True, 8), (True, None),
                                            (False, 8)])
def test_gathered_matches_full(rng, causal, k_block):
    """gathered_attention (KV all-gather + local flash blocking — the
    cond-safe sequence-parallel form the 1F1B schedulers use) must match
    full attention on the unsharded sequence."""
    B, H, S, dh, n = 2, 2, 32, 16, 4
    q = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    want = ra.full_attention(q, k, v, causal=causal)

    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    got = jax.jit(jax.shard_map(
        lambda a, b, c: ra.gathered_attention(a, b, c, "sp", causal=causal,
                                              k_block=k_block),
        mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp")))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_gathered_grads_match_full(rng):
    B, H, S, dh, n = 1, 2, 16, 8, 4
    q = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))

    def sharded_loss(q, k, v):
        def f(a, b, c):
            o = ra.gathered_attention(a, b, c, "sp", k_block=4)
            return jax.lax.psum(jnp.sum(o * o), "sp")
        return jax.shard_map(f, mesh=mesh,
                             in_specs=(P(None, None, "sp"),) * 3,
                             out_specs=P())(q, k, v)

    def ref_loss(q, k, v):
        o = ra.full_attention(q, k, v)
        return jnp.sum(o * o)

    got = jax.jit(jax.grad(sharded_loss, argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal,k_block", [(True, 8), (False, 16)])
def test_flash_matches_full(rng, causal, k_block):
    """Single-device flash-blocked attention == full attention (same
    online softmax as the sharded variants, no collectives)."""
    B, H, S, dh = 2, 2, 32, 16
    q = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    want = ra.full_attention(q, k, v, causal=causal)
    got = jax.jit(lambda a, b, c: ra.flash_attention(
        a, b, c, causal=causal, k_block=k_block))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# -- the XLA route of model code: flash_attention_remat(impl="xla") ----------
# out and lse forward, a hand-written backward (PR 31)

def _scan_route(q, k, v, *, causal, k_block, q_offset=0):
    """The route as it was before its backward was written by hand, kept
    here as the reference: the online-softmax scan over k blocks under
    jax.checkpoint, differentiated by JAX."""
    def attend(q, k, v):
        B, H, S, dh = q.shape
        pos = q_offset + jnp.arange(S, dtype=jnp.int32)
        m, l, o = ra._attend_chunk(
            q.astype(jnp.float32), k, v, pos, 0, *ra._init_acc(B, H, S, dh),
            dh ** -0.5, causal, k_block)
        return ra._finish(o, l, q.dtype)
    return jax.checkpoint(attend)(q, k, v)


def _full_rows(q, k, v, *, causal, k_block=None, q_offset=0):
    """full_attention of the rows q holds: q laid at rows q_offset.. of a
    whole sequence's queries, the other rows zero and dropped again."""
    if q.shape[2] == k.shape[2]:
        return ra.full_attention(q, k, v, causal=causal)
    whole = jnp.zeros(k.shape[:3] + q.shape[3:], q.dtype)
    whole = jax.lax.dynamic_update_slice_in_dim(whole, q, q_offset, axis=2)
    return ra.full_attention(whole, k, v, causal=causal)[
        :, :, q_offset:q_offset + q.shape[2]]


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(np.sum((a - b) ** 2) / np.sum(b ** 2)))


def _out_and_grads(fn, q, k, v, w, **kw):
    """(out, dQ, dK, dV) of the loss sum(fn(q, k, v, **kw) * w)."""
    def loss(q, k, v):
        out = fn(q, k, v, **kw)
        return jnp.sum((out * w).astype(jnp.float32)), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize(
    "Sq,Sk,k_block,q_offset,causal,dtype,reference,tol", [
        (16, 16, 16, 0, True, jnp.float32, _full_rows, 1e-5),
        (64, 64, 16, 0, True, jnp.float32, _full_rows, 1e-5),
        (128, 128, 16, 0, True, jnp.float32, _full_rows, 1e-5),
        (48, 48, 10, 0, True, jnp.float32, _full_rows, 1e-5),
        (32, 64, 16, 32, True, jnp.float32, _full_rows, 1e-5),
        (32, 64, 16, 24, True, jnp.float32, _full_rows, 1e-5),
        (64, 64, 16, 0, False, jnp.float32, _full_rows, 1e-5),
        (64, 64, None, 0, True, jnp.float32, _full_rows, 1e-5),
        (64, 64, 16, 0, True, jnp.bfloat16, _scan_route, 1e-2),
        (32, 64, 16, 32, True, jnp.bfloat16, _scan_route, 1e-2),
    ], ids=["one-block", "four-blocks", "eight-blocks",
            "a-k_block-that-divides-nothing", "a-shard-of-the-queries",
            "a-shard-that-starts-inside-a-block", "no-mask", "no-blocks",
            "bfloat16-against-the-scan", "bfloat16-shard-against-the-scan"])
def test_xla_route_out_and_gradients(rng, Sq, Sk, k_block, q_offset, causal,
                                     dtype, reference, tol):
    """out, dQ, dK and dV of the route against full attention (float32) or
    against the scan it replaced (bfloat16: same operand types, so the two
    differ by the output's rounding alone), B and H above one."""
    B, H, dh = 2, 3, 16
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, H, S_, dh)), dtype)
                  for S_ in (Sq, Sk, Sk, Sq))

    def run(fn):
        return _out_and_grads(fn, q, k, v, w, causal=causal, k_block=k_block,
                              q_offset=q_offset)

    got = run(lambda *a, **kw: ra.flash_attention_remat(*a, impl="xla", **kw))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, run(reference)):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        assert _rel_l2(a, b) <= tol, (name, _rel_l2(a, b))


def test_xla_route_takes_a_traced_offset(rng):
    """A sequence-parallel caller's q_offset is axis_index * S_local: a
    traced value, which decides the loops' trip counts at run time."""
    B, H, dh = 1, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S_, dh)), jnp.float32)
               for S_ in (16, 32, 32))
    got = jax.jit(lambda off: ra.flash_attention(
        q, k, v, k_block=8, q_offset=off))(jnp.int32(16))
    want = _full_rows(q, k, v, causal=True, q_offset=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_xla_route_backward_memory_is_o_s():
    """What the jax.checkpoint wrapper was there to guarantee: compiled
    temporaries of the gradient grow about linearly in S at one k_block —
    the backward holds one block of scores, not every block's."""
    def temp_bytes(S_):
        x = jnp.zeros((1, 2, S_, 64), jnp.float32)
        grad = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ra.flash_attention_remat(
                q, k, v, k_block=256, impl="xla")), argnums=(0, 1, 2)))
        return grad.lower(x, x, x).compile().memory_analysis(
            ).temp_size_in_bytes

    at_1k, at_4k = temp_bytes(1024), temp_bytes(4096)
    assert at_4k < 8 * at_1k, (at_1k, at_4k)
    # the whole square of float32 scores at S = 4,096 alone: 134 MB
    assert at_4k < 2 * 4096 * 4096 * 4 // 4, at_4k


@pytest.mark.parametrize("policy,products", [
    ("the-model's", 3), ("nothing-saved", 4)])
def test_layer_checkpoint_keeps_out_and_lse(policy, products):
    """A layer under the model's checkpoint policy computes each block of
    scores once forward (q k^T) and once backward (q k^T again and dO v^T):
    three products shaped [B, H, block, block] in the compiled gradient.
    Were `lse` not saved with the output, the layer's recompute would run
    the route's forward again for it: four, as the control shows."""
    from fpga_ai_nic_tpu.models import glm_moe
    cfg = glm_moe.GlmMoeConfig(
        vocab=64, dim=32, n_layers=1, n_dense_layers=1, n_heads=2,
        q_lora_rank=16, kv_lora_rank=8, qk_nope_dim=12, qk_rope_dim=4,
        v_dim=16, ffn_dim=64, moe_ffn_dim=24, n_routed_experts=2, held=(0,),
        top_k=1, dtype="float32", attn_block=8, attn_impl="xla")
    lyr = glm_moe.init(jax.random.PRNGKey(0), cfg)["dense"][0]
    x = jnp.zeros((2, 32, cfg.dim), jnp.float32)
    pos = jnp.arange(32, dtype=jnp.int32)
    keep = {"the-model's": glm_moe._KEEP,
            "nothing-saved": jax.checkpoint_policies.nothing_saveable}[policy]

    def loss(lyr, x):
        return jnp.sum(jax.checkpoint(
            lambda l, y: glm_moe._dense_block(l, y, pos, cfg),
            policy=keep)(lyr, x) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        lyr, x).compile().as_text()
    assert len(re.findall(r"f32\[2,2,8,8\]\S* dot\(", text)) == products


# -- the route's bias over the keys and its groups of sequences (PR 33) ------

def _padding_bias(B, Sk, valid):
    """[B, Sk] float32: 0 on each sequence's first valid[b] keys, -1e30 on
    the padded tail (valid[b] == 0: every key of that sequence padded)."""
    keep = np.arange(Sk)[None, :] < np.asarray(valid)[:, None]
    return jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)


def _route(q, k, v, **kw):
    return ra.flash_attention_remat(q, k, v, impl="xla", **kw)


@pytest.mark.parametrize("S,k_block,causal,dtype,valid,budget,tol", [
    (32, 32, False, jnp.float32, (20, 32, 7, 29), None, 1e-5),
    (64, 16, False, jnp.float32, (50, 64, 1, 33), None, 1e-5),
    (64, 16, True, jnp.float32, (50, 64, 17, 33), None, 1e-5),
    (48, 10, True, jnp.float32, (48, 5, 40, 24), None, 1e-5),
    (64, 16, False, jnp.bfloat16, (50, 64, 9, 33), None, 1e-2),
    (64, 16, True, jnp.bfloat16, (50, 64, 9, 33), None, 1e-2),
    (32, 32, False, jnp.float32, (20, 0, 32, 0), None, 1e-5),
    (64, 16, True, jnp.float32, (0, 64, 0, 33), None, 1e-5),
    (64, 16, False, jnp.float32, (50, 64, 0, 33), 2 * 3 * 16 * 16 * 4, 1e-5),
    (64, 16, True, jnp.float32, (50, 64, 1, 33), 3 * 16 * 16 * 4, 1e-5),
    (64, 16, True, jnp.bfloat16, (50, 64, 9, 33), 2 * 3 * 16 * 16 * 4, 1e-2),
], ids=["one-block", "four-blocks", "four-blocks-causal",
        "a-k_block-that-divides-nothing", "bfloat16", "bfloat16-causal",
        "sequences-all-padding", "sequences-all-padding-causal",
        "two-groups-of-two", "four-groups-of-one", "bfloat16-two-groups"])
def test_xla_route_key_bias_out_and_gradients(rng, monkeypatch, S, k_block,
                                              causal, dtype, valid, budget,
                                              tol):
    """out, dQ, dK and dV with a padding bias over the keys against
    full_attention with the same bias added: softmax(s + bias), which for
    a sequence whose keys are all padding is the uniform one.  `budget`
    (bytes of one score block) makes the batch go through in groups."""
    B, H, dh = 4, 3, 16
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, H, S, dh)), dtype)
                  for _ in range(4))
    bias = _padding_bias(B, S, valid)
    if budget is not None:
        monkeypatch.setattr(ra, "SCORE_BLOCK_BYTES", budget)
        qb = ra._fit_block(S, k_block)
        assert ra._group(B, H, qb, qb) == budget // (H * qb * qb * 4) < B
    got = _out_and_grads(_route, q, k, v, w, causal=causal, k_block=k_block,
                         key_bias=bias)
    want = _out_and_grads(ra.full_attention, q, k, v, w, causal=causal,
                          key_bias=bias)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        assert np.all(np.isfinite(np.asarray(a, np.float32))), name
        assert _rel_l2(a, b) <= tol, (name, _rel_l2(a, b))


@pytest.mark.parametrize("causal,biased", [(False, True), (True, True),
                                           (True, False)])
def test_xla_route_groups_equal_one_group(rng, monkeypatch, causal, biased):
    """A batch whose B x H forces several groups gives what the same call
    gives under a budget that takes it whole: the groups are the same
    algorithm on fewer sequences at a time."""
    B, H, S, dh = 6, 2, 32, 8
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, H, S, dh)),
                              jnp.float32) for _ in range(4))
    kw = dict(causal=causal, k_block=8,
              key_bias=_padding_bias(B, S, (32, 20, 0, 9, 32, 1))
              if biased else None)

    def run(budget, groups):
        monkeypatch.setattr(ra, "SCORE_BLOCK_BYTES", budget)
        assert B // ra._group(B, H, 8, 8) == groups
        return _out_and_grads(_route, q, k, v, w, **kw)

    whole = run(B * H * 8 * 8 * 4, 1)
    for budget, groups in ((3 * H * 8 * 8 * 4, 2), (5 * H * 8 * 8 * 4, 2),
                           (2 * H * 8 * 8 * 4, 3), (1, 6)):
        for name, a, b in zip(("out", "dq", "dk", "dv"),
                              run(budget, groups), whole):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("shape,blocks,group", [
    ((2, 20, 4096, 256), 512, 2),       # the GLM cell: one group, as PR 31
    ((32, 12, 512, 64), 512, 4),        # bert-base-seq512: 12 MiB a sequence
    ((128, 12, 128, 64), 512, 64),      # bert-base-seq128: 0.75 MiB
    ((1, 32, 8192, 128), 512, 1),
    ((3, 64, 2048, 128), 512, 1),       # no sequence fits: one at a time
    ((6, 16, 1024, 64), 256, 6),
], ids=["glm47-s4096", "bert-s512", "bert-s128", "one-sequence",
        "over-budget-alone", "small-blocks"])
def test_group_size_follows_from_the_shape(shape, blocks, group):
    """The group is a function of (B, H, qb, kb) and the module's constant:
    the largest divisor of B whose float32 score block stays inside it."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    qb, kb, _, _ = ra._blocking(x, x, 0, blocks, True)
    b = ra._group(*shape[:2], qb, kb)
    assert b == group and shape[0] % b == 0
    assert b == 1 or b * shape[1] * qb * kb * 4 <= ra.SCORE_BLOCK_BYTES


def _count_eqns(jaxpr, want):
    """Equations of a jaxpr and of every jaxpr inside it that `want`
    accepts."""
    def inner(p):
        if hasattr(p, "eqns"):
            yield p
        elif hasattr(p, "jaxpr"):
            yield from inner(p.jaxpr)
        elif isinstance(p, (tuple, list)):
            for x in p:
                yield from inner(x)

    return sum(int(want(e)) + sum(_count_eqns(j, want)
                                  for p in e.params.values()
                                  for j in inner(p))
               for e in jaxpr.eqns)


def test_no_key_bias_adds_nothing_to_the_program():
    """key_bias=None traces to the program the route had before it knew a
    bias (the GLM cell and llama pass none; since PR 33 inside the jit
    around the forward and the one around the backward): the same jaxpr
    as a call that does not name the argument, one group at the GLM
    cell's shape (no 5-D array: no outer loop), and no addition on a
    block of scores; with a bias there are two, the forward's and the
    recomputed p's."""
    x = jax.ShapeDtypeStruct((2, 20, 4096, 256), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((2, 4096), jnp.float32)

    def grad(**kw):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v, *b: jnp.sum(ra.flash_attention_remat(
                q, k, v, causal=True, k_block=512, impl="xla",
                **(dict(kw, key_bias=b[0]) if b else kw)
            ).astype(jnp.float32)), argnums=(0, 1, 2)))

    none = grad(key_bias=None)(x, x, x)
    assert str(none) == str(grad()(x, x, x))

    def adds_on_scores(e):
        return e.primitive.name == "add" and any(
            v.aval.shape == (2, 20, 512, 512) for v in e.outvars)

    def five_d(e):
        return any(getattr(v.aval, "ndim", 0) == 5 and v.aval.shape[0] == 1
                   for v in e.outvars)

    assert _count_eqns(none.jaxpr, adds_on_scores) == 0
    assert _count_eqns(none.jaxpr, five_d) == 0
    assert _count_eqns(grad()(x, x, x, bias).jaxpr, adds_on_scores) == 2
