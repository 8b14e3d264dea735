"""Ring attention: exact attention over sequence shards with K/V blocks
rotating a unidirectional device ring.

The reference has no attention (MLP only — SURVEY.md §5 "long-context:
not present"), but its defining dataflow — stream a neighbor's block in,
combine locally, forward it on (hw/all_reduce.sv st_eth_t REDUCE/FORWARD
states) — is exactly the ring-attention schedule: each hop, the local query
block attends to the visiting K/V block with a numerically-stable online
softmax (flash-attention accumulation), while the K/V payload moves to the
next neighbor over ``lax.ppermute``.  XLA overlaps the permute with the
local attention compute the way the FPGA overlapped wire and adders.

Causal masking uses global token positions, so the result is bit-equivalent
to full attention on the unsharded sequence (up to fp reassociation).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.names import scope

# "minus infinity" that survives exp() safely.  A plain float, NOT a
# jnp scalar: creating a device array at import time initializes the XLA
# backend, which breaks jax.distributed.initialize() in every process
# that imports this package before calling it (multihost.initialize must
# come first)
_NEG = -1e30


def _iota(n):
    return lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def _rows(x, i, n):
    """Block i of n rows of [B, H, S, .]."""
    return lax.dynamic_slice_in_dim(x, i * n, n, axis=2)


def _varying(x, vma):
    """`x` widened to `vma` when the caller sits inside shard_map (loop
    carries must enter with the vma type the body produces)."""
    vma = tuple(sorted(vma))
    return lax.pcast(x, vma, to="varying") if vma else x


def _init_acc(B, H, S, dh, vma=()):
    """Fresh online-softmax accumulators (running max / normalizer /
    output), widened to `vma`."""
    return tuple(_varying(z, vma) for z in (
        jnp.full((B, H, S, 1), _NEG, jnp.float32),
        jnp.zeros((B, H, S, 1), jnp.float32),
        jnp.zeros((B, H, S, dh), jnp.float32)))


def _finish(o, l, out_dtype):
    """Normalize the accumulated output; rows with no visible keys keep a
    zero output (cannot happen causally: a token always sees itself)."""
    return (o / jnp.where(l == 0, 1.0, l)).astype(out_dtype)


def _scores(q, k, q_pos, k_pos, sm_scale, causal, bias=None):
    """Scaled float32 scores [B,H,Sq,Sk] of one block, _NEG where a key
    lies after its query.  bias: `_bias_block`'s pair, added over the keys."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = k_pos[None, :] > q_pos[:, None]           # [Sq, Sk]
        s = jnp.where(mask[None, None], _NEG, s)
    if bias is not None:
        # the sum rounds as the plain softmax(s + bias) rounds it; taking
        # the sequence's largest bias off afterwards leaves every row's
        # softmax as it is and keeps `lse` of a sequence whose keys are
        # all masked (bias -1e30) in a range where log l still counts
        s = (s + bias[0]) - bias[1]
    return s


def _bias_block(key_bias, top, j, n):
    """`_scores`' bias: block j of n keys of a [B, Sk] bias and `top`, each
    sequence's largest value [B], both to broadcast against [B,H,Sq,n]
    scores; None where there is no bias."""
    if key_bias is None:
        return None
    return (lax.dynamic_slice_in_dim(key_bias, j * n, n, axis=1)[:, None, None],
            top[:, None, None, None])


def _block_attend(q, k, v, q_pos, k_pos, m, l, o, sm_scale, causal,
                  bias=None):
    """One online-softmax accumulation step against a visiting K/V block.

    q: [B,H,Sq,dh]; k,v: [B,H,Sk,dh]; positions: [Sq]/[Sk];
    m,l: [B,H,Sq,1] running max / normalizer; o: [B,H,Sq,dh] running output.
    """
    s = _scores(q, k, q_pos, k_pos, sm_scale, causal, bias)
    m_blk = jnp.max(s, axis=-1, keepdims=True)           # [B,H,Sq,1]
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)                           # rescale old state
    p = jnp.exp(s - m_new)                               # [B,H,Sq,Sk]
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                   v.astype(jnp.float32),
                                   preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _fit_block(S: int, block: Optional[int]) -> int:
    """The block size that keeps the memory bound for any S: the largest
    divisor of S <= `block` (smaller blocks cost iterations, never memory);
    S itself where `block` is None."""
    if block is None or block >= S:
        return S
    return next(d for d in range(block, 0, -1) if S % d == 0)


def _attend_chunk(qf, k, v, q_pos, k_pos0, m, l, o, sm_scale, causal,
                  k_block: Optional[int],
                  remat_blocks: Optional[bool] = None):
    """Online-softmax accumulation against one visiting K/V chunk, scanning
    it in k-blocks so at most [B,H,Sq,k_block] scores materialize — the
    flash-attention blocking that keeps peak memory O(S*k_block) instead of
    O(S^2).  k_block=None (or >= S) processes the chunk whole.

    The streamed-block structure is the same move the reference makes in
    hardware: it never buffers a whole vector, it streams 32 KiB slices
    through fixed-size working sets (hw/all_reduce.sv:101-103)."""
    S = k.shape[2]
    k_block = _fit_block(S, k_block)
    if k_block == S:
        k_pos = k_pos0 + _iota(S)
        return _block_attend(qf, k.astype(jnp.float32), v, q_pos, k_pos,
                             m, l, o, sm_scale, causal)

    def step(carry, j):
        kp = k_pos0 + j * k_block + _iota(k_block)
        return _block_attend(
            qf, _rows(k, j, k_block).astype(jnp.float32),
            _rows(v, j, k_block), q_pos, kp, *carry, sm_scale, causal), None

    # remat_blocks: recompute each block's scores in the backward (the
    # flash-attention backward) — without it, differentiating the scan
    # saves every block's [Sq, k_block] residuals SIMULTANEOUSLY, which
    # at long S reconstitutes O(S^2/k_block * k_block) = O(S^2) memory
    # (measured: 22 GB at S=16384 where the forward needs < 2 GB).
    # None = auto: recompute only past a few blocks — at short S the
    # residuals are small and the recompute is a pure slowdown (measured
    # -2.5 MFU points on a S=1024 config with it always-on)
    if remat_blocks is None:
        remat_blocks = S // k_block > 4
    if remat_blocks:
        step = jax.checkpoint(step)
    (m, l, o), _ = lax.scan(step, (m, l, o), jnp.arange(S // k_block))
    return m, l, o


def pallas_route(impl: str, q_shape, kv_seq_len: Optional[int] = None
                 ) -> bool:
    """Shared attention-backend dispatch: the fused kernels when pinned
    or (auto) on TPU with tiling shapes; pinned-but-unsupported raises (a
    silent xla fallback would invalidate A/B runs).  ``q_shape`` is the
    [B, H, S, dh] tuple (or an array with that .shape); pass
    ``kv_seq_len`` for cross-attention (Sk != Sq) so auto can route a
    non-lane-tileable Sk to the xla path instead of raising downstream."""
    from . import flash_pallas
    q_shape = getattr(q_shape, "shape", q_shape)
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"attn impl {impl!r}: want auto|pallas|xla")
    if impl == "pallas" and not flash_pallas.supported(
            q_shape, kv_seq_len=kv_seq_len):
        raise ValueError(
            f"impl='pallas' pinned but q shape {q_shape} / kv_seq_len="
            f"{kv_seq_len} does not tile (need S % 128 == 0, "
            "head_dim % 8 == 0, head_dim <= 256, Sk % 128 == 0)")
    return (impl == "pallas" or (impl == "auto" and flash_pallas._is_tpu()
                                 and flash_pallas.supported(
                                     q_shape, kv_seq_len=kv_seq_len)))


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   *, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   k_block: Optional[int] = 512,
                   unroll: bool = False, impl: str = "auto") -> jax.Array:
    """Sequence-parallel exact attention inside ``shard_map``.

    q, k, v: [B, H, S_local, dh] — the local sequence shard; shards are
    contiguous: device i holds global positions [i*S_local, (i+1)*S_local).
    Returns [B, H, S_local, dh] in q's dtype.

    k_block: flash-style blocking of each visiting K/V chunk (see
    `_attend_chunk`); the default keeps peak score memory at
    [B, H, S_local, 512] regardless of sequence length.  None disables
    blocking (the whole-chunk reference schedule).

    unroll: unroll the n-1 hop loop at trace time — same knob and default
    as ``CollectiveConfig.unroll_hops`` (marginally better codegen at tiny
    n, O(n) compile-time blowup at pod scale; the rolled ``fori_loop`` is
    the default for the same reason as in ops.ring).

    impl: "auto" routes each hop's local attention through the fused
    Pallas kernels on TPU (ops.flash_pallas.ring_flash_attention — same
    K/V rotation, logsumexp hop merge, per-hop flash vjp); "xla"/"pallas"
    pin a backend.  The unroll and k_block=None knobs are XLA-path
    schedules: in auto mode requesting either keeps the XLA path (an
    explicitly-set knob must never be silently ignored); pinned "pallas"
    rejects them.
    """
    xla_only_knobs = unroll or k_block is None
    if impl == "pallas" and xla_only_knobs:
        raise ValueError(
            "impl='pallas' cannot honor unroll=True / k_block=None — "
            "the fused ring is a rolled scan of blocked kernels; drop "
            "the knob or use impl='xla'")
    if not xla_only_knobs and pallas_route(impl, q,
                                           kv_seq_len=k.shape[2]):
        from . import flash_pallas
        return flash_pallas.ring_flash_attention(
            q, k, v, axis_name, causal=causal, sm_scale=sm_scale,
            block_q=k_block, block_k=k_block)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S, dh = q.shape
    if sm_scale is None:
        sm_scale = dh ** -0.5
    qf = q.astype(jnp.float32)
    q_pos = idx * S + _iota(S)

    # hop 0: attend the local block first (a causal token always sees
    # itself, so the row max is finite and the carry enters the ring loop
    # already device-varying — no variance-cast ops needed)
    # accumulators start device-varying: the k-block scan in _attend_chunk
    # carries them, and a scan carry's variance type must match its output
    # (which is varying as soon as it touches q/k)
    m0, l0, o0 = _init_acc(B, H, S, dh,
                           {axis_name} | set(jax.typeof(qf).vma))
    m, l, o = _attend_chunk(qf, k, v, q_pos, idx * S, m0, l0, o0,
                            sm_scale, causal, k_block)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(s_i, carry):
        m, l, o, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        src = (idx - s_i) % n                 # whose K/V we hold this hop

        def attend(mlo):
            return _attend_chunk(qf, kc, vc, q_pos, src * S, *mlo,
                                 sm_scale, causal, k_block)

        if causal:
            # blocks entirely in the future (src > idx: every key position
            # exceeds every local query position) are fully masked — skip
            # their attention compute, keep only the ring hop itself.  This
            # halves the attention FLOPs at large n, the same dead-beat
            # elision the reference's FSM gets by construction (it never
            # reduces slices it hasn't reached, hw/all_reduce.sv:923-987).
            m, l, o = lax.cond(src > idx, lambda mlo: mlo, attend, (m, l, o))
        else:
            m, l, o = attend((m, l, o))
        return m, l, o, kc, vc

    m, l, o, _, _ = lax.fori_loop(1, n, hop, (m, l, o, k, v), unroll=unroll)
    return _finish(o, l, q.dtype)


def full_attention(q, k, v, *, causal=True, sm_scale=None, key_bias=None):
    """Unsharded reference implementation (the golden model for tests);
    key_bias [B, Sk] float32 is added to every query's scores."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    S = q.shape[2]
    if causal:
        pos = _iota(S)
        s = jnp.where((pos[None, :] > pos[:, None])[None, None], _NEG, s)
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def flash_attention_remat(q, k, v, *, causal=True, sm_scale=None,
                          k_block: Optional[int] = 512, impl: str = "auto",
                          q_offset=0, key_bias=None):
    """Memory-bounded exact attention for model code, over the whole
    sequence's queries: what every model's `attn_impl` selects.  Both
    backends have one contract: the forward gives `out` and
    `lse = m + log l`, the hand-written backward recomputes p from `lse`,
    so residual memory is O(S) and no ``jax.checkpoint`` wrapper is needed
    (one would only run the forward again).

    - ``pallas`` (auto on TPU when shapes tile): the fused
      ops.flash_pallas kernels.
    - ``xla`` (auto off-TPU / odd shapes): `flash_attention` below.

    `q_offset` is the position of q's first row among the keys, for a
    caller that holds a shard of a causal sequence's queries.  `key_bias`
    [B, Sk] float32 is added to every query's scores over the keys (a
    padding mask: 0 to attend, -1e30 not to); it has no gradient."""
    from . import flash_pallas
    if pallas_route(impl, q, kv_seq_len=k.shape[2]):
        b = k_block or flash_pallas._DEF_BLOCK
        return flash_pallas.flash_attention(q, k, v, causal=causal,
                                            sm_scale=sm_scale,
                                            q_offset=q_offset,
                                            block_q=b, block_k=b,
                                            key_bias=key_bias)
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           k_block=k_block, q_offset=q_offset,
                           key_bias=key_bias)


def gathered_attention(q, k, v, axis_name: str, *, causal=True,
                       sm_scale=None, k_block: Optional[int] = 512,
                       impl: str = "auto"):
    """Sequence-parallel attention via KV all-gather: queries stay
    sequence-sharded, keys/values gather once over `axis_name`, and the
    local attention is `flash_attention_remat` with the shard's position as
    its `q_offset`, so peak score memory stays one block — only the
    gathered K/V buffers are O(S_global).

    Why it exists next to ring_attention: the 1F1B schedulers run the
    attention inside stage-divergent `lax.cond` branches, and a
    collective-PERMUTE there is unsound — its source-target pairs span
    the whole mesh, so every device must execute it, while replica-
    GROUPED collectives (psum / all_gather / all_to_all) rendezvous per
    subgroup and only need the sp group, which does share one pp stage
    and one branch.  (Empirically: a ppermute inside a half-mesh cond
    crashes the CPU runtime outright; the sp-sharded 1F1B llama silently
    produced a 4% wrong loss.)  Numerics: identical online-softmax
    accumulation to ring_attention up to f32 summation order (both are
    exact attention).  Reference analogue: none — the reference has no
    attention; this is the standard all-gather sequence-parallel form.

    impl: as `flash_attention_remat`'s; the (replica-grouped, cond-safe)
    all_gather stays on either backend.
    """
    kf = lax.all_gather(k, axis_name, axis=2, tiled=True)
    vf = lax.all_gather(v, axis_name, axis=2, tiled=True)
    return flash_attention_remat(
        q, kf, vf, causal=causal, sm_scale=sm_scale, k_block=k_block,
        impl=impl, q_offset=lax.axis_index(axis_name) * q.shape[2])


# the name the route's residuals `out` and `lse` carry: a caller whose
# jax.checkpoint keeps it (checkpoint_policies.save_only_these_names) does
# not run the attention forward again in its recompute
SAVED = "ainic.attn.out_lse"


def _join(x):
    """Stacked chunks [n, B, H, rows, .] -> [B, H, n * rows, .]."""
    n, B, H, rows, d = x.shape
    return jnp.moveaxis(x, 0, 2).reshape(B, H, n * rows, d)


def _blocking(q, k, off, block, causal):
    """Rows per query chunk and per k block, the number of chunks, and the
    number of k blocks chunk i reads: under a causal mask those whose first
    key is at or before the chunk's last row, so the blocks wholly above
    the diagonal are never visited."""
    qb, kb = _fit_block(q.shape[2], block), _fit_block(k.shape[2], block)
    nk = k.shape[2] // kb

    def visible(i):
        if not causal:
            return nk
        return jnp.minimum(nk, (off + (i + 1) * qb - 1) // kb + 1)

    return qb, kb, q.shape[2] // qb, visible


# The most float32 scores one block [b, H, qb, kb] may hold, in bytes: what
# XLA keeps in the v5e's fast memory (`S(1)` in the compiled step) with the
# products, the mask, exp and the row sums fused around it.  A batch whose
# block would be larger goes through in equal groups of sequences, one after
# the other.  Measured on the chip (PERF.md, PR 31 and 33): the GLM cell's
# f32[2,20,512,512], 40 MiB, is one group; BERT-Base at S = 512 (12 MiB a
# sequence) took 1.29 / 1.21 / 2.02 / 4.22 ms a layer, forward and
# backward, in blocks of 24 / 48 / 96 / 192 MiB against 5.62 ms with the
# scores of all 32 sequences at once (403 MB).
SCORE_BLOCK_BYTES = 48 * 2 ** 20


def _group(B, H, qb, kb):
    """Sequences a group: the largest divisor of B whose float32 score
    block [b, H, qb, kb] stays within SCORE_BLOCK_BYTES (one sequence where
    none does)."""
    return _fit_block(B, max(1, SCORE_BLOCK_BYTES // (4 * H * qb * kb)))


def _in_groups(fn, b, *xs):
    """fn(*xs) over the leading (batch) axis in groups of b sequences, the
    results joined again; one group is one plain call.  An x may be None."""
    B = xs[0].shape[0]
    if b == B:
        return fn(*xs)
    out = lax.map(lambda g: fn(*g), jax.tree_util.tree_map(
        lambda x: x.reshape(B // b, b, *x.shape[1:]), xs))
    return jax.tree_util.tree_map(
        lambda y: y.reshape(B, *y.shape[2:]), out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _blocked(q, k, v, off, key_bias, sm_scale, causal, block):
    return _blocked_fwd(q, k, v, off, key_bias, sm_scale, causal, block)[0]


def _vma(*xs):
    """The manual mesh axes any of xs (None: no array) varies over."""
    return set().union(*(jax.typeof(x).vma for x in xs if x is not None))


# The forward and the backward below are jitted on their own: a model that
# calls the route once a layer from a Python loop (BERT: 12 times) then
# traces and lowers each once for all its layers, and XLA inlines the calls
# (traced per layer, BERT's warm set-up took 9 s longer: PERF.md, PR 33).
# `b`, the sequences a group, is static with the rest of the blocking.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _fwd_groups(q, k, v, off, key_bias, sm_scale, causal, block, b):
    qb, kb, nq, visible = _blocking(q, k, off, block, causal)
    vma = _vma(q, k, v, off, key_bias)

    def group(q, k, v, key_bias):
        B, H, _, dh = q.shape
        top = None if key_bias is None else jnp.max(key_bias, axis=1)

        def chunk(i):
            qf = _rows(q, i, qb).astype(jnp.float32)
            q_pos = off + i * qb + _iota(qb)

            def attend(j, mlo):
                return _block_attend(
                    qf, _rows(k, j, kb).astype(jnp.float32), _rows(v, j, kb),
                    q_pos, j * kb + _iota(kb), *mlo, sm_scale, causal,
                    _bias_block(key_bias, top, j, kb))

            m, l, o = lax.fori_loop(0, visible(i), attend,
                                    _init_acc(B, H, qb, dh, vma))
            return (_finish(o, l, q.dtype),
                    m + jnp.log(jnp.where(l == 0, 1.0, l)))

        # the chunks stacked and joined: written into the whole in place,
        # the output cost 1.2 ms a layer more on the v5e (PERF.md, PR 31)
        return tuple(map(_join, lax.map(chunk, jnp.arange(nq))))

    return _in_groups(group, b, q, k, v, key_bias)


def _blocked_fwd(q, k, v, off, key_bias, sm_scale, causal, block):
    qb, kb, _, _ = _blocking(q, k, off, block, causal)
    with scope("ainic.attn.fwd"):
        out, lse = _fwd_groups(q, k, v, off, key_bias, sm_scale, causal,
                               block, _group(*q.shape[:2], qb, kb))
    out, lse = checkpoint_name(out, SAVED), checkpoint_name(lse, SAVED)
    return out, (q, k, v, off, key_bias, out, lse)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _bwd_groups(q, k, v, off, key_bias, out, lse, do, sm_scale, causal,
                block, b):
    """The flash-attention backward: with p = exp(s - lse) recomputed a
    block at a time, ds = p * (dp - delta) * scale where dp = dO v^T and
    delta = rowsum(dO * O); one float32 dK and one dV for all the keys of a
    group, each block's rows added in place.  The bias has no gradient."""
    qb, kb, nq, visible = _blocking(q, k, off, block, causal)
    vma = _vma(q, k, v, off, key_bias, do)

    def add_rows(acc, j, part):
        return lax.dynamic_update_slice_in_dim(
            acc, _rows(acc, j, kb) + part, j * kb, axis=2)

    def group(q, k, v, key_bias, out, lse, do):
        B, H, _, dh = q.shape
        top = None if key_bias is None else jnp.max(key_bias, axis=1)

        def chunk(dkv, i):
            qf = _rows(q, i, qb).astype(jnp.float32)
            dof = _rows(do, i, qb).astype(jnp.float32)
            lse_i, delta_i = _rows(lse, i, qb), _rows(delta, i, qb)
            q_pos = off + i * qb + _iota(qb)

            def block_grads(j, acc):
                dq, dk, dv = acc
                kf = _rows(k, j, kb).astype(jnp.float32)
                vf = _rows(v, j, kb).astype(jnp.float32)
                p = jnp.exp(_scores(
                    qf, kf, q_pos, j * kb + _iota(kb), sm_scale, causal,
                    _bias_block(key_bias, top, j, kb)) - lse_i)
                dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf,
                                preferred_element_type=jnp.float32)
                ds = p * (dp - delta_i) * sm_scale
                dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf,
                                     preferred_element_type=jnp.float32)
                dk = add_rows(dk, j, jnp.einsum(
                    "bhqk,bhqd->bhkd", ds, qf,
                    preferred_element_type=jnp.float32))
                dv = add_rows(dv, j, jnp.einsum(
                    "bhqk,bhqd->bhkd", p, dof,
                    preferred_element_type=jnp.float32))
                return dq, dk, dv

            dq0 = _varying(jnp.zeros((B, H, qb, dh), jnp.float32), vma)
            dq, dk, dv = lax.fori_loop(0, visible(i), block_grads,
                                       (dq0, *dkv))
            return (dk, dv), dq.astype(q.dtype)

        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        zeros = _varying(jnp.zeros(k.shape, jnp.float32), vma)
        (dk, dv), dq = lax.scan(chunk, (zeros, zeros), jnp.arange(nq))
        return _join(dq), dk.astype(k.dtype), dv.astype(v.dtype)

    return _in_groups(group, b, q, k, v, key_bias, out, lse, do)


def _blocked_bwd(sm_scale, causal, block, res, do):
    q, k, v, off, key_bias, out, lse = res
    qb, kb, _, _ = _blocking(q, k, off, block, causal)
    with scope("ainic.attn.bwd"):
        dq, dk, dv = _bwd_groups(q, k, v, off, key_bias, out, lse, do,
                                 sm_scale, causal, block,
                                 _group(*q.shape[:2], qb, kb))
    return dq, dk, dv, None, None


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def flash_attention(q, k, v, *, causal=True, sm_scale=None,
                    k_block: Optional[int] = 512, q_offset=0, key_bias=None):
    """Single-device flash-blocked exact attention, the XLA route: queries
    and keys in blocks of `k_block` rows (its largest divisor of each
    length; None: whole), the same `_block_attend` online softmax the
    ring/gathered variants use, over exactly the blocks a causal mask
    leaves — peak score memory one [b, H, k_block, k_block] block instead of
    full_attention's O(S^2) float32 score matrix, forward and backward.
    b follows from the shape (`_group`): the whole batch where its block
    fits SCORE_BLOCK_BYTES, else the largest divisor of B that does, the
    groups of b sequences one after the other.
    Differences from full_attention are float32 summation order only; both
    are exact softmax attention.  q may be a shard of the sequence's
    queries: its first row is key position `q_offset` (traced or not).

    key_bias [B, Sk] float32 is added to the scores over the keys, each k
    block's slice to that block's, forward and in the backward's recomputed
    p; the answer is softmax(s + key_bias)'s, also for a sequence whose
    keys are all masked (-1e30: that softmax is uniform).  It has no
    gradient, and None adds nothing to the program."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if key_bias is not None:
        key_bias = key_bias.astype(jnp.float32)
    return _blocked(q, k, v, jnp.asarray(q_offset, jnp.int32), key_bias,
                    sm_scale, causal, k_block)
