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

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# "minus infinity" that survives exp() safely.  A plain float, NOT a
# jnp scalar: creating a device array at import time initializes the XLA
# backend, which breaks jax.distributed.initialize() in every process
# that imports this package before calling it (multihost.initialize must
# come first)
_NEG = -1e30


def _init_acc(B, H, S, dh, vma=()):
    """Fresh online-softmax accumulators (running max / normalizer /
    output), widened to `vma` when the caller sits inside shard_map (scan
    carries must enter with the vma type the body produces)."""
    accs = (jnp.full((B, H, S, 1), _NEG, jnp.float32),
            jnp.zeros((B, H, S, 1), jnp.float32),
            jnp.zeros((B, H, S, dh), jnp.float32))
    vma = tuple(sorted(vma))
    return tuple(lax.pcast(z, vma, to="varying") if vma else z
                 for z in accs)


def _finish(o, l, out_dtype):
    """Normalize the accumulated output; rows with no visible keys keep a
    zero output (cannot happen causally: a token always sees itself)."""
    return (o / jnp.where(l == 0, 1.0, l)).astype(out_dtype)


def _block_attend(q, k, v, q_pos, k_pos, m, l, o, sm_scale, causal):
    """One online-softmax accumulation step against a visiting K/V block.

    q: [B,H,Sq,dh]; k,v: [B,H,Sk,dh]; positions: [Sq]/[Sk];
    m,l: [B,H,Sq,1] running max / normalizer; o: [B,H,Sq,dh] running output.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = k_pos[None, :] > q_pos[:, None]           # [Sq, Sk]
        s = jnp.where(mask[None, None], _NEG, s)
    m_blk = jnp.max(s, axis=-1, keepdims=True)           # [B,H,Sq,1]
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)                           # rescale old state
    p = jnp.exp(s - m_new)                               # [B,H,Sq,Sk]
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                   v.astype(jnp.float32),
                                   preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


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
    if k_block is not None and S % k_block:
        # keep the memory bound for any S: largest divisor of S <= k_block
        # (smaller blocks cost iterations, never memory)
        k_block = next(d for d in range(min(k_block, S), 0, -1) if S % d == 0)
    if k_block is None or k_block >= S:
        k_pos = k_pos0 + lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]
        return _block_attend(qf, k.astype(jnp.float32), v, q_pos, k_pos,
                             m, l, o, sm_scale, causal)

    def step(carry, j):
        m, l, o = carry
        ks = lax.dynamic_slice_in_dim(k, j * k_block, k_block, axis=2)
        vs = lax.dynamic_slice_in_dim(v, j * k_block, k_block, axis=2)
        kp = (k_pos0 + j * k_block
              + lax.broadcasted_iota(jnp.int32, (k_block, 1), 0)[:, 0])
        m, l, o = _block_attend(qf, ks.astype(jnp.float32), vs, q_pos, kp,
                                m, l, o, sm_scale, causal)
        return (m, l, o), None

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
    q_pos = idx * S + lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]

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


def full_attention(q, k, v, *, causal=True, sm_scale=None):
    """Unsharded reference implementation (the golden model for tests)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    S = q.shape[2]
    if causal:
        pos = lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]
        s = jnp.where((pos[None, :] > pos[:, None])[None, None], _NEG, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def flash_attention_remat(q, k, v, *, causal=True, sm_scale=None,
                          k_block: Optional[int] = 512, impl: str = "auto",
                          q_offset: int = 0):
    """Memory-bounded exact attention for model code — picks the best
    backward story available:

    - ``pallas`` (auto on TPU when shapes tile): the fused
      ops.flash_pallas kernels; the custom-vjp backward recomputes p
      from the saved logsumexp, so no ``jax.checkpoint`` wrapper is
      needed (wrapping one would only re-run the forward kernel).
    - ``xla`` (auto off-TPU / odd shapes): the k-block-scanned
      ``flash_attention`` under attention-only ``jax.checkpoint`` —
      without it the scan's per-block residuals reconstitute O(S^2)
      backward memory (measured 22 GB at S=16,384; models/llama.py
      carried this wrapper before round 5 moved the choice here).

    `q_offset` is the position of q's first row among the keys: a caller
    that takes the queries of a causal sequence in chunks hands each chunk
    only the keys at or before its last row, and skips the rest of the
    square."""
    from . import flash_pallas
    if pallas_route(impl, q, kv_seq_len=k.shape[2]):
        b = k_block or flash_pallas._DEF_BLOCK
        return flash_pallas.flash_attention(q, k, v, causal=causal,
                                            sm_scale=sm_scale,
                                            q_offset=q_offset,
                                            block_q=b, block_k=b)
    return jax.checkpoint(
        lambda q2, k2, v2: flash_attention(q2, k2, v2, causal=causal,
                                           sm_scale=sm_scale,
                                           k_block=k_block,
                                           q_offset=q_offset))(q, k, v)


def gathered_attention(q, k, v, axis_name: str, *, causal=True,
                       sm_scale=None, k_block: Optional[int] = 512,
                       impl: str = "auto"):
    """Sequence-parallel attention via KV all-gather: queries stay
    sequence-sharded, keys/values gather once over `axis_name`, and the
    local attention runs the same flash-style k-blocked online softmax as
    ring_attention (`_attend_chunk`), so peak score memory stays
    O(S_local * k_block) — only the gathered K/V buffers are O(S_global).

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

    impl: "auto" keeps the (replica-grouped, cond-safe) all_gather and
    runs the LOCAL attention through the fused Pallas kernel with
    q_offset = idx*S_local (global-position causality); "xla"/"pallas"
    pin a backend.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, Sl, dh = q.shape
    if sm_scale is None:
        sm_scale = dh ** -0.5
    kf = lax.all_gather(k, axis_name, axis=2, tiled=True)
    vf = lax.all_gather(v, axis_name, axis=2, tiled=True)
    if pallas_route(impl, q, kv_seq_len=kf.shape[2]):
        from . import flash_pallas
        b = k_block or flash_pallas._DEF_BLOCK
        return flash_pallas.flash_attention(
            q, kf, vf, causal=causal, sm_scale=sm_scale,
            q_offset=idx * Sl, block_q=b, block_k=b)
    qf = q.astype(jnp.float32)
    q_pos = idx * Sl + lax.broadcasted_iota(jnp.int32, (Sl, 1), 0)[:, 0]
    m0, l0, o0 = _init_acc(B, H, Sl, dh,
                           {axis_name} | set(jax.typeof(qf).vma))
    m, l, o = _attend_chunk(qf, kf, vf, q_pos, 0, m0, l0, o0,
                            sm_scale, causal, k_block)
    return _finish(o, l, q.dtype)


def flash_attention(q, k, v, *, causal=True, sm_scale=None,
                    k_block: Optional[int] = 512, q_offset: int = 0):
    """Single-device flash-blocked exact attention: the same
    `_attend_chunk` online-softmax accumulation the ring/gathered
    variants use, with no collectives — peak score memory
    O(S * k_block) instead of full_attention's O(S^2) f32 score matrix
    (which XLA also saves for the backward, forcing remat on long
    sequences).  Bit-differences vs full_attention are f32 summation
    order only; both are exact softmax attention.  q may be a chunk of the
    sequence's queries: its first row is key position `q_offset`."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, S, dh = q.shape
    qf = q.astype(jnp.float32)
    pos = q_offset + lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]
    # q may be batch-sharded under an outer shard_map even though this
    # attention itself is collective-free
    vma = (set(jax.typeof(qf).vma) | set(jax.typeof(k).vma)
           | set(jax.typeof(v).vma))
    m0, l0, o0 = _init_acc(B, H, S, dh, vma)
    m, l, o = _attend_chunk(qf, k, v, pos, 0, m0, l0, o0,
                            sm_scale, causal, k_block)
    return _finish(o, l, q.dtype)
