"""Per-block-scaled int8 quantization with stochastic rounding — the
EQuARX-style (arXiv:2506.17615) low-bit quantized all-reduce codec.

Wire format per block of ``block_size`` f32 values: int8 quantized values
plus ONE bf16 linear scale (``scale = bf16(max|x| / 127)``; all-zero
blocks get scale 1.0 so decode is exact).  Unlike BFP's power-of-two
shared exponent, the linear scale uses the full int8 range on every block
— tighter error per bit at the cost of a 2-byte (not 1-byte) scale:
4B/(B+2) vs f32, 3.56x at the default B=16.

The scale is bf16 (EQuARX's own choice) for a reason beyond rate: the
decode product ``q * scale`` then has <= 15 significand bits — EXACTLY
representable in f32 — so the multiply never rounds, which makes it
FMA-IMMUNE: XLA:CPU freely contracts a*b+c into fused multiply-adds
(even across lax.optimization_barrier), and an inexact decode multiply
fused with the ring's accumulate would change bits vs the numpy golden
and make sliced/whole hops diverge.  Exact multiplies are the same
immunity BFP gets from power-of-two scales; any future codec whose
decode ends in an INEXACT op will hit this wall (measured here first on
the f32-scale draft of this codec).

Rounding:
  - "stochastic" (default; EQuARX §3): ``q = floor(x/scale + u)`` with
    u ~ U[0,1), which is UNBIASED — E[decode] = x — so quantization noise
    averages out across devices and steps instead of accumulating as bias.
  - "nearest": deterministic round-to-nearest; half the worst-case error,
    but biased on the wire's repeated-requantization path.

Determinism (the golden-compare contract): u is NOT drawn from a stateful
PRNG — it is a counter-free hash of each value's own f32 BIT PATTERN mixed
with the codec seed (murmur3 finalizer).  That keeps every pass
reproducible, makes the numpy golden (`compress.golden.int8_encode`) bit-
exact against both backends, and — because u depends on the value, not on
the element's position — makes ring slicing a pure schedule change: a
sliced hop sees the same values, hence the same u, hence the same bits
(`Codec.sliceable`).

Backends, mirroring `ops.bfp` / `ops.bfp_pallas`:
  - "xla" (default): consecutive-element blocks ("flat" layout) — golden
    bit-exact on every platform.
  - "pallas": fused VMEM encode/decode kernels with LANE-COLUMN blocks
    (the "sublane" layout — block max is a sublane reduction on the VPU),
    golden bit-exact vs layout="sublane".
  - "auto": pallas on TPU when the payload tiles onto (block, 128) lanes.
Same rate and error bound either way; the block PARTITION differs, so the
two backends are distinct bit streams (exactly BFP's xla/pallas story).

Not idempotent: decode lands off the next pass's grid (the re-quantized
block max shifts the scale), so repeated requantization adds bounded noise
per pass rather than being a projection.  The ring all-gather is unaffected
(one encode, payload forwarded verbatim); the reduce-scatter's per-hop
requantization noise is covered by ``error_bound`` and measured end-to-end
by evals/codec_convergence.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import Codec, DTypeLike, register
from ..obs.names import kernel
from ..ops import bfp_pallas as _bfp_pl
from ..ops.bfp_pallas import LANES


def _hash_u01(bits: jax.Array, seed: int) -> jax.Array:
    """uint32 value bits -> deterministic pseudo-uniform f32 in [0, 1).

    murmur3 finalizer over (bits ^ seed-stamp); the top 24 bits scale to
    [0, 1 - 2^-24] exactly in f32.  The numpy golden twin is
    compress.golden.hash_u01 — constants are the bit spec."""
    z = bits ^ jnp.uint32((seed * 0x9E3779B9) & 0xFFFFFFFF)
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> 13)
    z = z * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> 16)
    return (z >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# XLA backend ("flat" layout: consecutive elements per block)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_size", "rounding",
                                             "seed"))
def int8_encode(x: jax.Array, block_size: int = 16,
                rounding: str = "stochastic",
                seed: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Flat f32/bf16 [n] (n % block == 0) -> (int8 q [n], bf16 scale
    [n/block])."""
    x = x.astype(jnp.float32)
    xb = x.reshape(-1, block_size)
    maxabs = jnp.max(jnp.abs(xb), axis=-1)
    # multiply-by-reciprocal IS the spec (not a /127 the compiler may or
    # may not strength-reduce), and the bf16-ROUNDED scale is what both
    # sides use (encode divides by it, decode multiplies by it) — the
    # golden must match bit-for-bit
    scale = jnp.where(maxabs > 0, maxabs * jnp.float32(1.0 / 127.0),
                      jnp.float32(1.0)).astype(jnp.bfloat16)
    v = xb / scale.astype(jnp.float32)[:, None]
    if rounding == "stochastic":
        bits = lax.bitcast_convert_type(x, jnp.uint32).reshape(xb.shape)
        v = jnp.floor(v + _hash_u01(bits, seed))
    else:
        v = jnp.round(v)
    q = jnp.clip(v, -127.0, 127.0).astype(jnp.int8)
    return q.reshape(x.shape), scale


@functools.partial(jax.jit, static_argnames=("block_size", "dtype"))
def int8_decode(q: jax.Array, scale: jax.Array, block_size: int = 16,
                dtype: DTypeLike = jnp.float32) -> jax.Array:
    qb = q.reshape(-1, block_size).astype(jnp.float32)
    # int8 x bf16 -> <= 15 significand bits: this multiply is EXACT in
    # f32 (never rounds), hence FMA-safe — see module docstring
    return (qb * scale.astype(jnp.float32)[:, None]).reshape(q.shape).astype(
        dtype)


# ---------------------------------------------------------------------------
# Pallas backend ("sublane" layout: lane-column blocks, as bfp_pallas)
# ---------------------------------------------------------------------------

def _encode_kernel(x_ref: Any, q_ref: Any, scale_ref: Any, *,
                   block_size: int, rounding: str,
                   seed: int) -> None:
    from jax.experimental.pallas import tpu as pltpu
    x = x_ref[:]                                   # (T*B, 128) f32
    T = x.shape[0] // block_size
    maxabs = jnp.max(jnp.abs(x).reshape(T, block_size, LANES), axis=1)
    scale = jnp.where(maxabs > 0, maxabs * jnp.float32(1.0 / 127.0),
                      jnp.float32(1.0)).astype(jnp.bfloat16)  # (T, 128)
    sf = scale.astype(jnp.float32)
    v = x / _bfp_pl._bcast_blocks(sf, block_size, "repeat")
    if rounding == "stochastic":
        v = jnp.floor(v + _hash_u01(pltpu.bitcast(x, jnp.uint32), seed))
    else:
        v = jnp.round(v)
    q_ref[:] = jnp.clip(v, -127.0, 127.0).astype(jnp.int8)
    scale_ref[:] = scale


def _decode_kernel(q_ref: Any, scale_ref: Any, out_ref: Any, *,
                   block_size: int) -> None:
    q = q_ref[:].astype(jnp.float32)
    sf = scale_ref[:].astype(jnp.float32)
    out_ref[:] = q * _bfp_pl._bcast_blocks(sf, block_size, "repeat")


def int8_encode_pallas(x: jax.Array, block_size: int = 16,
                       rounding: str = "stochastic", seed: int = 0,
                       interpret: Optional[bool] = None,
                       tiles_per_step: int = _bfp_pl._DEF_TILES
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sublane-layout fused encode (bit spec: golden.int8_encode with
    layout="sublane").  Un-jitted, callable inside vma-checked shard_maps
    — same contract as bfp_pallas.bfp_encode_inline."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if interpret is None:
        interpret = not _bfp_pl._is_tpu()
    n = x.shape[0]
    assert n % (block_size * LANES) == 0, (n, block_size * LANES)
    x2 = x.astype(jnp.float32).reshape(-1, LANES)
    n_tiles = x2.shape[0] // block_size
    t, steps = _bfp_pl._grid(n_tiles, tiles_per_step)
    q, scale = pl.pallas_call(
        functools.partial(_encode_kernel, block_size=block_size,
                          rounding=rounding, seed=seed),
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
            jax.ShapeDtypeStruct(x2.shape, jnp.int8,
                                      vma=jax.typeof(x2).vma),
            jax.ShapeDtypeStruct((n_tiles, LANES), jnp.bfloat16,
                                      vma=jax.typeof(x2).vma),
        ],
        interpret=interpret,
        **kernel("codec.int8_encode"),
    )(x2)
    return q.reshape(n), scale.reshape(n // block_size)


def int8_decode_pallas(q: jax.Array, scale: jax.Array, block_size: int = 16,
                       dtype: DTypeLike = jnp.float32,
                       interpret: Optional[bool] = None,
                       tiles_per_step: int = _bfp_pl._DEF_TILES) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if interpret is None:
        interpret = not _bfp_pl._is_tpu()
    n = q.shape[0]
    q2 = q.reshape(-1, LANES)
    s2 = scale.reshape(-1, LANES)
    t, steps = _bfp_pl._grid(s2.shape[0], tiles_per_step)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size),
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
            q2.shape, jnp.float32,
            vma=jax.typeof(q2).vma | jax.typeof(s2).vma),
        interpret=interpret,
        **kernel("codec.int8_decode"),
    )(q2, s2)
    return out.reshape(n).astype(dtype)


# ---------------------------------------------------------------------------
# the codec
# ---------------------------------------------------------------------------

@register
class Int8Codec(Codec):
    """Per-block linear int8, stochastic rounding (see module docstring)."""

    name = "int8"
    idempotent = False
    supports_fused = False     # fused ring frames carry int8 SCALES (BFP)

    def __init__(self, block_size: int = 16, rounding: str = "stochastic",
                 seed: int = 0, backend: str = "xla",
                 error_feedback: bool = False) -> None:
        assert rounding in ("stochastic", "nearest"), rounding
        assert backend in ("xla", "pallas", "auto"), backend
        assert block_size >= 2
        self.block_size = int(block_size)
        self.rounding = rounding
        self.seed = int(seed)
        self.backend = backend
        self.error_feedback = bool(error_feedback)

    def _use_pallas(self, n_elems: int) -> bool:
        return self.backend == "pallas" or (
            self.backend == "auto" and _bfp_pl._is_tpu()
            and n_elems % (self.block_size * LANES) == 0)

    # -- wire transform -----------------------------------------------------

    def encode(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        if self._use_pallas(x.shape[0]):
            return tuple(int8_encode_pallas(x, self.block_size,
                                            self.rounding, self.seed))
        return tuple(int8_encode(x, self.block_size, self.rounding,
                                 self.seed))

    def decode(self, payload: Tuple[jax.Array, ...], n_elems: int,
               dtype: DTypeLike = jnp.float32) -> jax.Array:
        q, scale = payload
        if self._use_pallas(n_elems):
            return int8_decode_pallas(q, scale, self.block_size, dtype)
        return int8_decode(q, scale, self.block_size, dtype)

    # -- structure ----------------------------------------------------------

    @property
    def pad_elems(self) -> int:
        return self.block_size

    def sliceable(self, chunk_elems: int,
                  slice_elems: Optional[int]) -> bool:
        return (super().sliceable(chunk_elems, slice_elems)
                # same backend-consistency rules as BFPCodec: the block
                # partition must not depend on how the chunk is sliced
                and self._use_pallas(slice_elems) == self._use_pallas(
                    chunk_elems)
                and not (self._use_pallas(slice_elems)
                         and slice_elems % (self.block_size * LANES)))

    # -- declared accuracy / rate ------------------------------------------

    @property
    def error_bound(self) -> float:
        # grid step = bf16(blockmax/127) <= (1 + 2^-8) * blockmax/127;
        # stochastic floor can land a full step away, nearest half a step
        step = (1.0 + 2.0 ** -8) / 127.0
        return step if self.rounding == "stochastic" else step / 2

    def wire_bytes(self, n_elems: int) -> int:
        assert n_elems % self.block_size == 0
        return n_elems + 2 * (n_elems // self.block_size)

    def describe(self) -> Dict[str, Any]:
        d = super().describe()
        d.update(block_size=self.block_size, rounding=self.rounding,
                 seed=self.seed, backend=self.backend)
        return d
