"""Pallas TPU paged gather-attend: the serving plane's decode fast path.

`models.llama_decode.forward_paged` (the reference path, and the bitwise
oracle for this kernel) gathers each request's K/V pages into a
materialized ``[R, kv, P*page_size, hd]`` view every layer of every
decode step — bytes/token therefore scale with the ALLOCATED page span
of the table, not the live KV, and the gather write+readback doubles the
traffic on top.  This kernel walks the int32 page table and DMAs each
LIVE page HBM->VMEM inside the kernel instead, so the gathered view is
never formed: dead table slots move zero bytes, and a page's K/V tile is
read exactly once per (request, kv-head) cell.

One definition discipline (PR 14): the per-page DMA schedule — prologue
launch, depth-deep double buffer over dedicated VMEM spans with
semaphores cycling mod depth, wait-before-relaunch hazard order, dead
slot handling — is NOT written here.  It is emitted by
`verify.opstream.PagedAttendEmitter` through `_PagedSink`, the same
stream `verify.mc.build_gather` model-checks exhaustively (semaphore
slot aliasing under every landing interleaving) and
`verify.opstream.check_gather_coverage` pins statically (every live
(page, offset) covered exactly once, zero overlap, zero dead-page
bytes).

Kernel layout (one cell per (request slot, kv head)):

  grid (R, n_kv)   q arrives as the cell's [G*T, hd] f32 query group
                   (G = n_heads/n_kv — GQA and the kv_rep branch both
                   reduce to head-group mapping; MHA is G == 1); the
                   K/V pools stay un-blocked in HBM (memory_space ANY)
                   and are touched only by the emitter's DMAs.
  epilogue         ONE [G*T, hd] x [P*page_size, hd] score dot over the
                   whole landed K row, the exact masked softmax (into
                   the scores scratch, the softmax->PV handoff), then
                   one PV contraction — deliberately NOT the
                   online-rescale flash accumulation, and deliberately
                   not per-page score tiles either: full-row is the
                   reference einsum's per-(r, kv) gemm shape, which is
                   what makes the kernel BITWISE equal to
                   `forward_paged`'s `_cached_attend` on the same
                   backend (per-page tiles drift by an ulp at G*T == 1,
                   where XLA lowers the matvec differently;
                   tests/test_paged_attend.py pins parity across
                   GQA/MHA, ragged occupancy, dirty pools and tp).

Parity at the dead/live boundary rides the same mask-parity rule the
reference path documents: masked positions score exactly -1e30 in both
paths, their softmax weights underflow to exactly +0.0, and a +-0 term
never moves an f32 sum — so skipping a dead page's bytes (this kernel)
and attending its garbage behind the mask (the reference gather) agree
bit-for-bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.names import kernel
from ..verify import opstream as _opstream
from .bfp_pallas import _is_tpu

LANES = 128
_NEG = -1e30
_DEF_DEPTH = 2


def _vma(*arrs):
    vma = frozenset()
    for a in arrs:
        vma = vma | jax.typeof(a).vma
    return vma


class _PagedSink(_opstream.OpSink):
    """Maps `PagedAttendEmitter`'s abstract ops onto one grid cell's
    DMA/semaphore/VPU resources.  The emitter owns the FULL schedule
    (launch depth, wait order, dead-slot handling); this sink only binds
    each abstract op to a real call and lowers ``when`` to `pl.when` —
    the liveness predicate is a traced bound here (n_live comes from the
    cell's SMEM position), so the rolled lowering is the only one.
    Hazard-predecessor annotations on dma_start are checker evidence
    (`check_dma_discipline`), not schedule — ignored, as in
    `ring_pallas._KernelSink`."""

    def __init__(self, *, dma_start, dma_wait, local):
        self._dma_start = dma_start
        self._dma_wait = dma_wait
        self._local = local

    def when(self, cond):
        return pl.when(cond)

    def dma_start(self, chan, i, *conf):
        self._dma_start(chan, i)

    def dma_wait(self, chan, i):
        self._dma_wait(chan, i)

    def local(self, name, *args):
        self._local(name, *args)


def _paged_kernel(table_ref, pos_ref, qg_ref, kp_ref, vp_ref, out_ref,
                  kbuf, vbuf, scores, sem, *, n_pages, page_size, n_t,
                  depth, sm_scale):
    """One (request, kv-head) cell: drive the shared emitter, then the
    exact epilogue.  n_pages/page_size/n_t(=T)/depth are static; the
    liveness bound is the cell's traced position."""
    r = pl.program_id(0)
    kh = pl.program_id(1)
    ps = page_size
    pos_r = pos_ref[r]
    # pages holding any visible position j <= pos + T - 1 (clamped to
    # the table width; inactive slots sit at pos 0 -> one live page)
    n_live = jnp.minimum((pos_r + n_t - 1) // ps + 1, n_pages)
    gt = qg_ref.shape[2]
    k_chan = _opstream.PagedAttendEmitter.K_CHAN

    def page_dma(chan, i):
        """THE transfer of table slot i's K or V page tile: HBM page
        [page, kh] -> this slot's dedicated VMEM span, on the slot's
        mod-depth semaphore.  Built identically by start and wait (the
        descriptor must match for the wait to pair)."""
        page = table_ref[r, i]
        if chan == k_chan:
            return pltpu.make_async_copy(
                kp_ref.at[page, kh], kbuf.at[pl.ds(i * ps, ps)],
                sem.at[i % depth, 0])
        return pltpu.make_async_copy(
            vp_ref.at[page, kh], vbuf.at[pl.ds(i * ps, ps)],
            sem.at[i % depth, 1])

    def local(name, *args):
        if name == "attend_tile":
            # page i's K/V tiles are landed (the emitter ordered this
            # marker after their waits); consumption is deferred to the
            # fused epilogue, which runs after EVERY wait — a sound
            # refinement of the abstract consume-here marker, and the
            # only lowering that stays bitwise: per-page score tiles
            # drift by an ulp at G*T == 1, where XLA lowers the matvec
            # differently than the reference's full-row contraction.
            pass
        elif name == "dead_fill":
            # a dead slot's V span must be FINITE zeros: its softmax
            # weights are exact +0 and +0 * 0 == +0, the same +-0
            # equivalence class as the reference's +0 * garbage.  Its
            # score span is never written — the mask overwrites it.
            i = args[0]
            vbuf[pl.ds(i * ps, ps), :] = jnp.zeros(
                (ps, vbuf.shape[1]), vbuf.dtype)
        elif name == "softmax":
            # the reference's exact contraction shape — ONE [G*T, hd] x
            # [P*ps, hd] score dot over the whole landed row (dead K
            # spans are read as garbage and land behind the mask) —
            # then its exact mask + softmax: row g*T + t sees key j iff
            # j <= pos + t
            kk = kbuf[...].astype(jnp.float32)
            s = lax.dot_general(qg_ref[0, 0], kk,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = s * sm_scale
            jj = lax.broadcasted_iota(jnp.int32, (gt, n_pages * ps), 1)
            tt = lax.broadcasted_iota(jnp.int32, (gt, n_pages * ps),
                                      0) % n_t
            visible = jj <= pos_r + tt
            s = jnp.where(visible, s, jnp.float32(_NEG))
            scores[...] = jax.nn.softmax(s, axis=-1)
        else:                                        # "pv"
            p = scores[...]
            vv = vbuf[...].astype(jnp.float32)
            out_ref[0, 0] = lax.dot_general(
                p, vv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    sink = _PagedSink(dma_start=lambda chan, i: page_dma(chan, i).start(),
                      dma_wait=lambda chan, i: page_dma(chan, i).wait(),
                      local=local)
    _opstream.PagedAttendEmitter(n_pages, depth).stream(
        sink, lambda i: i < n_live)


def supported(page_size: int, head_dim: int, *,
              interpret: Optional[bool] = None) -> bool:
    """Can the paged kernel take this pool geometry?  The hardware path
    needs lane-tileable page tiles (see `_validate`); interpret mode
    takes anything (how the CPU parity battery runs)."""
    if interpret is None:
        interpret = not _is_tpu()
    return bool(interpret) or (page_size % LANES == 0
                               and head_dim % LANES == 0)


def _validate(q, pool_k, pool_v, page_table, pos, page_size, depth,
              interpret) -> None:
    if q.ndim != 4:
        raise ValueError(f"paged_gather_attend: q must be [R, H, T, hd], "
                         f"got {q.shape}")
    R, H, _T, hd = q.shape
    if pool_k.shape != pool_v.shape or pool_k.ndim != 4:
        raise ValueError(
            "paged_gather_attend: K/V pools must share one "
            f"[n_pages, kv, page_size, hd] shape, got k={pool_k.shape} "
            f"v={pool_v.shape}")
    n_kv = pool_k.shape[1]
    if pool_k.shape[2] != page_size or pool_k.shape[3] != hd:
        raise ValueError(
            f"paged_gather_attend: pool pages {pool_k.shape} do not "
            f"match page_size={page_size}, head_dim={hd}")
    if n_kv == 0 or H % n_kv != 0:
        raise ValueError(
            f"paged_gather_attend: n_heads={H} must be a multiple of "
            f"the pool's kv heads={n_kv} (GQA head-group mapping)")
    if page_table.ndim != 2 or page_table.shape[0] != R:
        raise ValueError(
            f"paged_gather_attend: page_table must be [R={R}, P], got "
            f"{page_table.shape}")
    if page_table.dtype != jnp.int32:
        raise ValueError(
            "paged_gather_attend: page_table must be int32 (the walked "
            f"table), got {page_table.dtype}")
    if pos.shape != (R,):
        raise ValueError(
            f"paged_gather_attend: pos must be [R={R}], got {pos.shape}")
    if depth < 1:
        raise ValueError(f"paged_gather_attend: depth must be >= 1, "
                         f"got {depth}")
    if not interpret and (page_size % LANES or hd % LANES):
        # same contract as flash_pallas's Sk check: fail HERE with a
        # real error naming the config, not later as an opaque Mosaic
        # layout error — the page tile [page_size, hd] is the unit every
        # DMA, score column span and PV contraction tiles by
        bad = [f"page_size={page_size}"] if page_size % LANES else []
        bad += [f"head_dim={hd}"] if hd % LANES else []
        raise ValueError(
            "paged_gather_attend needs lane-tileable page tiles on "
            f"hardware: {' and '.join(bad)} not a multiple of {LANES} "
            f"(pool shape {pool_k.shape}); repack the pool geometry or "
            "use attend_impl='reference' (the XLA gathered-view path)")


def paged_gather_attend(q, pool_k, pool_v, page_table, pos, *,
                        page_size: int, sm_scale: Optional[float] = None,
                        depth: int = _DEF_DEPTH,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Paged-KV decode attention without the gathered view.

    q: [R, H, T, hd] (post-rope, any float dtype — scored in f32 like
    the reference); pool_k/pool_v: [n_pages, kv, page_size, hd] (the
    serve pool AFTER this call's K/V scatter); page_table: [R, P] int32;
    pos: [R] int32, each slot's global position of its first token this
    call.  Returns f32 [R, H, T, hd], bitwise equal to
    `_cached_attend(q, gathered_k, gathered_v, pos, ...)` on the same
    backend — `forward_paged(..., attend_impl="pallas")` is the seam
    that slots it in, with the reference path staying the default-on
    oracle.
    """
    if interpret is None:
        interpret = not _is_tpu()
    pos = jnp.asarray(pos, jnp.int32)
    _validate(q, pool_k, pool_v, page_table, pos, page_size, depth,
              interpret)
    R, H, T, hd = q.shape
    n_kv = pool_k.shape[1]
    P = page_table.shape[1]
    G = H // n_kv
    if sm_scale is None:
        sm_scale = hd ** -0.5
    qg = q.astype(jnp.float32).reshape(R, n_kv, G * T, hd)
    kern = functools.partial(_paged_kernel, n_pages=P,
                             page_size=page_size, n_t=T, depth=depth,
                             sm_scale=sm_scale)
    vma = _vma(qg, pool_k, pool_v, page_table, pos)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kern,
        grid=(R, n_kv),
        in_specs=[smem, smem,
                  pl.BlockSpec((1, 1, G * T, hd),
                               lambda r, k: (r, k, 0, 0)),
                  hbm, hbm],
        out_specs=pl.BlockSpec((1, 1, G * T, hd),
                               lambda r, k: (r, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, n_kv, G * T, hd),
                                            jnp.float32, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((P * page_size, hd), pool_k.dtype),   # K tiles
            pltpu.VMEM((P * page_size, hd), pool_v.dtype),   # V tiles
            pltpu.VMEM((G * T, P * page_size), jnp.float32),  # scores
            pltpu.SemaphoreType.DMA((max(depth, 1), 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            has_side_effects=True),
        interpret=bool(interpret),
        **kernel("attention.paged"),
    )(page_table, pos, qg, pool_k, pool_v)
    return out.reshape(R, H, T, hd)
