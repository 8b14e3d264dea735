"""Pallas TPU kernels for the BFP codec.

The reference implements the codec as a fully-pipelined RTL datapath:
exponent max-tree (hw/max_u.sv), per-lane barrel shift
(hw/barrel_shifter.sv), two's-complement pack (hw/bf16_to_bfp_core.sv:109),
and an LZC-based renormalizing decoder (hw/bfp_to_bf16_core.sv).  On TPU the
same dataflow maps onto the VPU: the kernel views the flat vector as
(tiles, block_size, 128) so each *lane column* of a (block_size, 128) tile
is one BFP block — the block max is a sublane reduction, and shift/round
becomes a scale-multiply (the "sublane" layout of ops.bfp_golden, which is
the bit-level spec these kernels must match; see tests/test_bfp_pallas.py).

Fusing encode (exponent extract -> block max -> scale -> round -> int8) into
one VMEM pass matters because the codec sits on the collective's critical
path: at HBM-bandwidth ~1 byte/flop there is no headroom for the 4+
materialized intermediates the XLA version produces.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.names import kernel

LANES = 128
_DEF_TILES = 64  # (64, 16, 128) f32 tiles = 512 KiB per grid step in VMEM


def _is_tpu() -> bool:
    """THE 'kernels compile for hardware here' predicate: every Pallas
    module's ``interpret=None`` default and the fused-ring routing read
    this one definition."""
    return jax.devices()[0].platform == "tpu"


def _bcast_blocks(small, block_size, broadcast):
    """(T, 128) -> (T*B, 128) with each row repeated B times consecutively.

    "repeat": jnp.repeat on sublanes.  "reshape": broadcast through a 3D
    register view — (T,1,128) -> (T,B,128) -> (T*B,128); whether Mosaic
    lowers one better than the other is an on-hardware question
    (tools/codec_kernel_probe.py A/Bs them); both are bit-identical
    (tests/test_bfp_pallas.py)."""
    assert broadcast in ("repeat", "reshape"), broadcast
    T = small.shape[0]
    if broadcast == "reshape":
        return jnp.broadcast_to(small[:, None, :], (T, block_size, LANES)
                                ).reshape(T * block_size, LANES)
    return jnp.repeat(small, block_size, axis=0)


def _encode_kernel(x_ref, mant_ref, scale_ref, *, block_size, mantissa_bits,
                   rounding, broadcast="repeat"):
    # refs are 2D (T*B, 128) so every operand/result sits in NATIVE tiles —
    # f32 (8,128), int8 (32,128); a 3D (T, B=16, 128) int8 block would leave
    # each row-group half a native int8 tile and force packed relayouts on
    # every store.  The block view exists only on registers.
    x = x_ref[:]                                   # (T*B, 128) f32
    T = x.shape[0] // block_size
    bits = pltpu.bitcast(x, jnp.uint32)
    e = jnp.right_shift(bits, 23).astype(jnp.int32) & 0xFF
    emax = jnp.max(e.reshape(T, block_size, LANES), axis=1)   # (T, 128)
    scale_e = jnp.clip(emax - 127 - (mantissa_bits - 2), -126, 126)
    inv = pltpu.bitcast(((127 - scale_e) << 23).astype(jnp.uint32),
                        jnp.float32)               # 2.0**-scale_e, exact
    q = x * _bcast_blocks(inv, block_size, broadcast)
    q = jnp.round(q) if rounding == "nearest" else jnp.trunc(q)
    lim = float(2 ** (mantissa_bits - 1) - 1)
    mant_ref[:] = jnp.clip(q, -lim, lim).astype(jnp.int8)
    scale_ref[:] = scale_e.astype(jnp.int8)


def _decode_kernel(mant_ref, scale_ref, out_ref, *, block_size,
                   broadcast="repeat"):
    m = mant_ref[:].astype(jnp.float32)            # (T*B, 128)
    se = scale_ref[:].astype(jnp.int32)            # (T, 128)
    scale = pltpu.bitcast(((se + 127) << 23).astype(jnp.uint32), jnp.float32)
    out_ref[:] = m * _bcast_blocks(scale, block_size, broadcast)


# the scale block of one grid step is (t, 128) int8 (bf16 in compress.int8):
# Mosaic takes it only when t is a whole number of native sublane tiles —
# (32, 128) for int8, which also covers bf16's (16, 128) — or the whole array
_SCALE_SUBLANES = 32


def _grid(n_tiles: int, tiles_per_step: int):
    """(tiles per grid step, steps) for any tile count: one whole-array
    step when it fits, else tiles_per_step rounded down to the sublane
    tile with a ragged last step (Pallas drops its out-of-bounds rows).
    Tiles are independent — a BFP block never leaves its tile — so the
    grid is a schedule choice and never changes the bits."""
    if n_tiles <= tiles_per_step:
        return n_tiles, 1
    t = max(tiles_per_step - tiles_per_step % _SCALE_SUBLANES,
            _SCALE_SUBLANES)
    return t, pl.cdiv(n_tiles, t)


def bfp_encode_inline(x: jax.Array, block_size: int = 16,
                      mantissa_bits: int = 8, rounding: str = "nearest",
                      interpret: Optional[bool] = None,
                      tiles_per_step: int = _DEF_TILES,
                      broadcast: str = "repeat"
                      ) -> Tuple[jax.Array, jax.Array]:
    """Flat f32/bf16 [N] (N % (block*128) == 0) -> (int8 [N], int8 [N/block])
    in the "sublane" layout (bit-identical to
    ``bfp_golden.bfp_encode(..., layout="sublane")``).

    Un-jitted entry for callers already inside jit/shard_map (a nested
    closed_call trips the vma checker); ``bfp_encode`` is the jitted
    public wrapper."""
    if interpret is None:
        interpret = not _is_tpu()
    n = x.shape[0]
    assert n % (block_size * LANES) == 0, (n, block_size * LANES)
    x2 = x.astype(jnp.float32).reshape(-1, LANES)       # (tiles*B, 128)
    n_tiles = x2.shape[0] // block_size
    t, steps = _grid(n_tiles, tiles_per_step)
    kern = functools.partial(_encode_kernel, block_size=block_size,
                             mantissa_bits=mantissa_bits, rounding=rounding,
                             broadcast=broadcast)
    mant, scale = pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[pl.BlockSpec((t * block_size, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((t * block_size, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((t, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.int8, vma=jax.typeof(x2).vma),
            jax.ShapeDtypeStruct((n_tiles, LANES), jnp.int8,
                                 vma=jax.typeof(x2).vma),
        ],
        interpret=interpret,
        **kernel("codec.bfp_encode"),
    )(x2)
    return mant.reshape(n), scale.reshape(n // block_size)


bfp_encode = functools.partial(jax.jit, static_argnames=(
    "block_size", "mantissa_bits", "rounding", "interpret",
    "tiles_per_step", "broadcast"))(bfp_encode_inline)


def bfp_decode_inline(mant: jax.Array, scale: jax.Array,
                      block_size: int = 16, dtype=jnp.float32,
                      interpret: Optional[bool] = None,
                      tiles_per_step: int = _DEF_TILES,
                      broadcast: str = "repeat") -> jax.Array:
    if interpret is None:
        interpret = not _is_tpu()
    n = mant.shape[0]
    m2 = mant.reshape(-1, LANES)
    s2 = scale.reshape(-1, LANES)
    t, steps = _grid(s2.shape[0], tiles_per_step)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size,
                          broadcast=broadcast),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((t * block_size, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((t, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((t * block_size, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            m2.shape, jnp.float32,
            vma=jax.typeof(m2).vma | jax.typeof(s2).vma),
        interpret=interpret,
        **kernel("codec.bfp_decode"),
    )(m2, s2)
    return out.reshape(n).astype(dtype)


bfp_decode = functools.partial(jax.jit, static_argnames=(
    "block_size", "dtype", "interpret", "tiles_per_step", "broadcast"))(
        bfp_decode_inline)
