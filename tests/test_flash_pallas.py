"""Parity of the fused Pallas flash-attention kernels (fwd + custom-vjp
bwd) against the exact XLA paths in ops.ring_attention — the golden-model
strategy every fused kernel in this repo follows (cf. test_bfp_pallas.py,
test_ring_pallas.py): the Mosaic emulator (interpret=True) runs the real
kernel logic on the CPU mesh, and differences vs the direct softmax must
be f32-reassociation noise only."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fpga_ai_nic_tpu.ops import flash_pallas
from fpga_ai_nic_tpu.ops.ring_attention import flash_attention as flash_xla
from fpga_ai_nic_tpu.ops.ring_attention import full_attention


def _qkv(rng, B=1, H=2, S=256, dh=64, dtype=jnp.float32):
    def one(k):
        return jnp.asarray(rng.standard_normal((B, H, S, dh)), dtype)
    return one(0), one(1), one(2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dh", [64, 128])
def test_fwd_matches_full_attention(rng, causal, dh):
    q, k, v = _qkv(rng, S=256, dh=dh)
    got = flash_pallas.flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128,
                                       interpret=True)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_fwd_uneven_blocks(rng):
    # S=384 with 128-blocks: 3 q-blocks x 3 k-blocks, diagonal masking
    # crosses block boundaries unevenly
    q, k, v = _qkv(rng, S=384)
    got = flash_pallas.flash_attention(q, k, v, causal=True,
                                       block_q=128, block_k=128,
                                       interpret=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_fwd_bf16_matches_xla_flash(rng):
    q, k, v = _qkv(rng, S=256, dtype=jnp.bfloat16)
    got = flash_pallas.flash_attention(q, k, v, causal=True,
                                       block_q=128, block_k=128,
                                       interpret=True)
    want = flash_xla(q, k, v, causal=True, k_block=128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_full_attention(rng, causal):
    q, k, v = _qkv(rng, S=256, dh=64)

    def loss_pl(q, k, v):
        o = flash_pallas.flash_attention(q, k, v, causal=causal,
                                         block_q=128, block_k=128,
                                         interpret=True)
        return jnp.sum(o * jnp.cos(o))       # nonlinear downstream grad

    def loss_ref(q, k, v):
        o = full_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_grads_bf16_finite_and_close(rng):
    q, k, v = _qkv(rng, S=128, dh=64, dtype=jnp.bfloat16)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        return f

    gp = jax.grad(loss(lambda q, k, v: flash_pallas.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: full_attention(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.all(jnp.isfinite(a.astype(jnp.float32)))
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_supported_predicate():
    assert flash_pallas.supported((2, 4, 256, 64))
    assert flash_pallas.supported((1, 1, 128, 128))
    assert not flash_pallas.supported((2, 4, 100, 64))    # S not lane-mult
    assert not flash_pallas.supported((2, 4, 256, 300))   # dh too large
    assert not flash_pallas.supported((2, 256, 64))       # rank
    # Sk is part of the contract too (cross-attention / visiting chunks)
    assert flash_pallas.supported((2, 4, 256, 64), kv_seq_len=128)
    assert not flash_pallas.supported((2, 4, 256, 64), kv_seq_len=100)


def test_bad_kv_seq_len_raises_before_mosaic(rng):
    """ADVICE r5: a non-lane-tileable Sk used to pass supported() (which
    only sees q) and die later inside the Mosaic compile; the public entry
    must reject it with a real error."""
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 2, 100, 64)), jnp.float32)
    with pytest.raises(ValueError, match="K/V sequence length"):
        flash_pallas.flash_attention(q, kv, kv, interpret=True)


def test_llama_attn_impl_parity(rng):
    """Full llama loss with attn_impl='pallas' (fused kernels through the
    Mosaic emulator) vs 'xla' (checkpointed blocked scan) — the two
    backends the attn_block knob can select must agree end to end."""
    import dataclasses
    from fpga_ai_nic_tpu.models import llama

    mcfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype="float32",
                               attn_block=128)
    params = llama.init(jax.random.PRNGKey(0), mcfg)
    toks = jnp.asarray(rng.integers(0, mcfg.vocab, (2, 129)), jnp.int32)
    batch = (toks[:, :-1], toks[:, 1:])

    def loss(impl):
        c = dataclasses.replace(mcfg, attn_impl=impl)
        return llama.loss_fn(params, batch, c)

    def grad_norm(impl):
        c = dataclasses.replace(mcfg, attn_impl=impl)
        g = jax.grad(lambda p: llama.loss_fn(p, batch, c))(params)
        return jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(x.astype(jnp.float32) ** 2), g, 0.0)

    l_pl, l_xla = float(loss("pallas")), float(loss("xla"))
    np.testing.assert_allclose(l_pl, l_xla, rtol=1e-5)
    np.testing.assert_allclose(float(grad_norm("pallas")),
                               float(grad_norm("xla")), rtol=1e-4)


def test_pinned_pallas_refuses_unsupported_shapes(rng):
    from fpga_ai_nic_tpu.ops.ring_attention import flash_attention_remat
    q = jnp.zeros((1, 2, 100, 64), jnp.float32)     # S=100: no lane tile
    with pytest.raises(ValueError, match="pinned"):
        flash_attention_remat(q, q, q, impl="pallas")
    with pytest.raises(ValueError, match="auto.pallas.xla"):
        flash_attention_remat(q, q, q, impl="pallsa")


def test_offsets_match_sliced_full_attention(rng):
    """Global-position causality: a q shard attending the whole sequence
    with q_offset must reproduce the matching row-slice of unsharded
    full attention."""
    S, Sl, dh = 512, 128, 64
    q, k, v = _qkv(rng, S=S, dh=dh)
    want = full_attention(q, k, v, causal=True)
    for i in range(S // Sl):
        got = flash_pallas.flash_attention(
            q[:, :, i * Sl:(i + 1) * Sl], k, v, causal=True,
            q_offset=i * Sl, block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, :, i * Sl:(i + 1) * Sl]),
            atol=2e-5, rtol=2e-5)


class TestRingFlash:
    """Sequence-parallel flash attention on the 8-device CPU mesh (Mosaic
    emulator inside shard_map): forward parity vs the XLA ring and the
    unsharded direct softmax, and gradients THROUGH the hop scan + lse
    merge — the d_lse-folds-into-delta property the per-hop custom vjp
    rests on."""

    def _run(self, fn, n):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None), check_vma=False))

    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_matches_ring_and_full(self, rng, causal):
        from fpga_ai_nic_tpu.ops.ring_attention import ring_attention
        n, Sl, dh = 4, 128, 64
        q, k, v = _qkv(rng, S=n * Sl, dh=dh)
        got = self._run(lambda q, k, v: flash_pallas.ring_flash_attention(
            q, k, v, "sp", causal=causal, block_q=128, block_k=128,
            interpret=True), n)(q, k, v)
        want_full = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_full),
                                   atol=3e-5, rtol=3e-5)
        want_ring = self._run(lambda q, k, v: ring_attention(
            q, k, v, "sp", causal=causal), n)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_ring),
                                   atol=3e-5, rtol=3e-5)

    def test_grads_match_full(self, rng):
        n, Sl, dh = 4, 128, 64
        q, k, v = _qkv(rng, S=n * Sl, dh=dh)

        def loss_ring(q, k, v):
            run = self._run(
                lambda q, k, v: flash_pallas.ring_flash_attention(
                    q, k, v, "sp", causal=True, block_q=128, block_k=128,
                    interpret=True), n)
            o = run(q, k, v)
            return jnp.sum(o * jnp.cos(o))

        def loss_full(q, k, v):
            o = full_attention(q, k, v, causal=True)
            return jnp.sum(o * jnp.cos(o))

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gr, gf, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3,
                                       err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("variant", ["ring", "gather"])
def test_sp_impl_routing_parity(rng, variant):
    """ops.ring_attention's sp entry points with impl='pallas' (fused
    kernels through the emulator) must match their own XLA path."""
    from fpga_ai_nic_tpu.ops import ring_attention as ra
    from jax.sharding import Mesh, PartitionSpec as P
    n, Sl, dh = 4, 128, 64
    q, k, v = _qkv(rng, S=n * Sl, dh=dh)
    fn = ra.ring_attention if variant == "ring" else ra.gathered_attention

    def run(impl):
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        f = jax.jit(jax.shard_map(
            lambda q, k, v: fn(q, k, v, "sp", causal=True, impl=impl),
            mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None), check_vma=False))
        return np.asarray(f(q, k, v))

    np.testing.assert_allclose(run("pallas"), run("xla"),
                               atol=3e-5, rtol=3e-5)


def test_key_bias_matches_masked_softmax(rng):
    """The key_bias channel (padding masks) must reproduce the plain
    masked-softmax result, forward and through the (q,k,v) gradients —
    the bias itself is non-differentiable by contract."""
    B, H, S, dh = 2, 2, 256, 64
    q, k, v = _qkv(rng, B=B, H=H, S=S, dh=dh)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), bool)
    mask = mask.at[:, 0].set(True)             # every row sees >= 1 key
    bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)

    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        p = jax.nn.softmax(s + bias[:, None, None, :], axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(q.dtype)

    got = flash_pallas.flash_attention(q, k, v, causal=False,
                                       key_bias=bias, block_q=128,
                                       block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gp = jax.grad(loss(lambda q, k, v: flash_pallas.flash_attention(
        q, k, v, causal=False, key_bias=bias, block_q=128, block_k=128,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_bert_attn_impl_parity(rng):
    """BERT loss with attn_impl='pallas' (mask through the kernels'
    key_bias channel) vs 'xla' — end-to-end with a real padding mask."""
    import dataclasses
    from fpga_ai_nic_tpu.models import bert
    mcfg = dataclasses.replace(bert.BertConfig.tiny(), max_pos=128,
                               n_heads=2)     # head_dim 32: %8, tiles
    params = bert.init(jax.random.PRNGKey(0), mcfg)
    toks = jnp.asarray(rng.integers(4, mcfg.vocab, (2, 128)), jnp.int32)
    toks = toks.at[:, 100:].set(mcfg.pad_id)  # real padding tail
    labels = jnp.where(jnp.asarray(rng.integers(0, 5, (2, 128))) == 0,
                       toks, -100)

    def loss(impl):
        c = dataclasses.replace(mcfg, attn_impl=impl)
        return float(bert.loss_fn(params, (toks, labels), c))

    np.testing.assert_allclose(loss("pallas"), loss("xla"), rtol=1e-5)


def test_ring_flash_bf16_close_to_xla_ring(rng):
    """bf16 activations, n=4 ring: the f32 running output across the hop
    scan must keep the fused ring within bf16 noise of the XLA ring's
    single-final-cast result (the per-hop-requantize regression case)."""
    from fpga_ai_nic_tpu.ops.ring_attention import ring_attention
    from jax.sharding import Mesh, PartitionSpec as P
    n, Sl, dh = 4, 128, 64
    q, k, v = _qkv(rng, S=n * Sl, dh=dh, dtype=jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))

    def run(fn):
        f = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None), check_vma=False))
        return np.asarray(f(q, k, v), np.float32)

    got = run(lambda q, k, v: flash_pallas.ring_flash_attention(
        q, k, v, "sp", causal=True, block_q=128, block_k=128,
        interpret=True))
    want = run(lambda q, k, v: ring_attention(q, k, v, "sp", causal=True,
                                              impl="xla"))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_gqa_grouped_matches_expanded(rng):
    """Grouped-KV (GQA) kernels vs the repeat-expanded form: forward and
    all grads must match — dk/dv of the grouped form are the SUM over
    the group's query heads (accumulated inside the dkv kernel's
    extended sequential axis, not by a post-hoc reshape-reduce)."""
    B, H, Hkv, S, dh = 2, 8, 2, 256, 64
    G = H // Hkv
    q = jnp.asarray(rng.standard_normal((B, H, S, dh)), jnp.float32)
    kg = jnp.asarray(rng.standard_normal((B, Hkv, S, dh)), jnp.float32)
    vg = jnp.asarray(rng.standard_normal((B, Hkv, S, dh)), jnp.float32)

    def grouped(q, kg, vg):
        return flash_pallas.flash_attention(q, kg, vg, causal=True,
                                            block_q=128, block_k=128,
                                            interpret=True)

    def expanded(q, kg, vg):
        return full_attention(q, jnp.repeat(kg, G, axis=1),
                              jnp.repeat(vg, G, axis=1), causal=True)

    np.testing.assert_allclose(np.asarray(grouped(q, kg, vg)),
                               np.asarray(expanded(q, kg, vg)),
                               atol=2e-5, rtol=2e-5)

    def loss(fn):
        def f(*a):
            o = fn(*a)
            return jnp.sum(o * jnp.cos(o))
        return f

    gp = jax.grad(loss(grouped), argnums=(0, 1, 2))(q, kg, vg)
    gr = jax.grad(loss(expanded), argnums=(0, 1, 2))(q, kg, vg)
    for a, b, name in zip(gp, gr, ("dq", "dk", "dv")):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3, err_msg=name)


def test_gqa_ring_flash_matches_full(rng):
    """GQA through the sp ring: grouped K/V chunks rotate (1/G the wire
    bytes) and the result still matches unsharded expanded attention."""
    from jax.sharding import Mesh, PartitionSpec as P
    n, Sl, H, Hkv, dh = 4, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((1, H, n * Sl, dh)), jnp.float32)
    kg = jnp.asarray(rng.standard_normal((1, Hkv, n * Sl, dh)), jnp.float32)
    vg = jnp.asarray(rng.standard_normal((1, Hkv, n * Sl, dh)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    f = jax.jit(jax.shard_map(
        lambda q, k, v: flash_pallas.ring_flash_attention(
            q, k, v, "sp", causal=True, block_q=128, block_k=128,
            interpret=True),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None), check_vma=False))
    want = full_attention(q, jnp.repeat(kg, 2, axis=1),
                          jnp.repeat(vg, 2, axis=1), causal=True)
    np.testing.assert_allclose(np.asarray(f(q, kg, vg)), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
