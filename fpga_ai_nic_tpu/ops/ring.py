"""Explicit ring collectives over ``lax.ppermute`` — the TPU-native analogue
of the reference's ring dataflow FSM (hw/all_reduce.sv st_eth_t:
SEND_LOCAL → REDUCE ×(N-2) → REDUCE_OUTPUT → FORWARD_OUTPUT/OUTPUT,
lines 691-1183).

Why these exist when ``lax.psum_scatter`` does: the XLA collectives cannot
compress on the wire.  The reference's headline trick is BFP-compressing
every ring hop (hw/bfp_adapter.sv); here each hop's payload is whatever
tuple of arrays the configured `compress.Codec` emits — BFP's (int8
mantissa, int8 scale) pair cutting ICI bytes 3.76x vs f32, top-k's
(values, indices), int8's (q, scale) — the codec seam generalizing the
reference's single hard-wired trick.  ``compression=`` accepts a Codec or
(back-compat) a bare BFPConfig.  Uncompressed mode exists for parity
testing and as the building block the fused-update engine selects per
config (`CollectiveConfig.impl`).

Chunk ownership is *natural order* — device i ends with chunk i — unlike
the reference's rotated slice order (hw/all_reduce.sv:361), which existed
only to keep its host-write FSM streaming; on TPU natural order keeps
ZeRO-1 shard <-> device mapping stable across collective impls.

Slicing (the reference's BUF_SIZE=512-CL / 32 KiB streaming granularity,
hw/all_reduce.sv:101-103,330): a compressed hop whose chunk exceeds
``slice_elems`` is streamed slice-by-slice, double-buffered so slice k+1's
encode runs while slice k's ppermute is on the wire — the TPU analogue of
the bfp_adapter sitting *inside* the ring stream (hw/bfp_adapter.sv).
Because compression units (BFP blocks / top-k buckets / int8 blocks) are
independent and ``slice_elems`` is a unit multiple (`Codec.sliceable`),
sliced and whole-chunk hops are bit-identical; slicing changes the
schedule, never the numerics.  Uncompressed hops always send the whole
chunk in one ppermute: with no codec work to overlap, slicing would only
serialize the DMA that XLA already streams.

All functions must run inside ``jax.shard_map`` with `axis_name` a mesh
axis; per-device inputs must vary over that axis (JAX >= 0.8 VMA rules).
Bit-exactness vs `ops.ring_golden` (same add order, same per-hop
quantization) is enforced by tests/test_ring.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import integrity as _integrity
from ..utils.config import BFPConfig  # noqa: F401 — legacy compression= type


def _next_neighbor_perm(n: int):
    # unidirectional ring, node n sends to (n+1) % N — the IKL topology
    # (sw/setup_route.sh:12-40, readme.pdf §2.2)
    return [(i, (i + 1) % n) for i in range(n)]


# -- chaos tap (runtime.chaos) ----------------------------------------------
# When installed (fault-injection runs only), both explicit-ring
# collectives (reduce-scatter and all-gather; ring_all_reduce composes
# them) route their payload through the tap at trace time, so the chaos
# harness can straggle or corrupt the wire INSIDE the compiled step — the
# boundary the reference's bfp_adapter sits on.  None (the default) is
# zero-cost: the collectives are traced exactly as before.

_FAULT_TAP = None


def set_fault_tap(tap) -> None:
    """Install/remove (None) the trace-time payload tap.  Must be set
    before the consuming step function is first traced; installed taps are
    compiled into the program."""
    global _FAULT_TAP
    _FAULT_TAP = tap


def _tap(x: jax.Array, point: str) -> jax.Array:
    return x if _FAULT_TAP is None else _FAULT_TAP(x, point)


# -- wire tap (runtime.chaos, encoded-frame plane) ---------------------------
# The value tap above perturbs the collective's INPUT (pre-encode) — the
# surface the value-space integrity layer guards.  The wire tap sits on
# the ENCODED payload between ppermute and decode: exactly the boundary
# the reference's bfp_adapter owns, and exactly where a finite bit flip
# becomes invisible to any value-space guard (it decodes to a plausible
# number).  The exact frame checksums (ops.integrity) are computed on the
# send side BEFORE the wire and on the receive side AFTER this tap, so a
# tapped corruption must trip them.  None (default) is zero-cost.

_WIRE_TAP = None


def set_wire_tap(tap) -> None:
    """Install/remove (None) the trace-time ENCODED-payload tap.  Same
    contract as set_fault_tap: install before the consuming program is
    first traced."""
    global _WIRE_TAP
    _WIRE_TAP = tap


def _tap_wire(payload, point: str, consumed=None):
    """``consumed`` (traced bool, default True) tells the tap whether
    THIS device's received payload is actually consumed by the program —
    single-pair ppermutes (reshard segments, the KV handoff) execute the
    callback on every SPMD participant but deliver real bytes only to
    the destination, and a corruption spec must fire on a frame that
    matters, not on a bystander's zeros."""
    if _WIRE_TAP is None:
        return payload
    return tuple(_WIRE_TAP(p, point, consumed) for p in payload)


def _use_pallas(cfg: BFPConfig, n_elems: int) -> bool:
    # kept as a public-ish seam (bench_collective.py keys its consumption
    # strategy off it); the implementation moved to compress.bfp with the
    # codec subsystem
    from ..compress.bfp import use_pallas
    return use_pallas(cfg, n_elems)


def _codec(cfg: BFPConfig, n_elems: int):
    """(encode, decode) pair for a flat [n_elems] BFP payload — moved to
    compress.bfp.codec_pair (this delegate keeps the bench drivers' entry
    point stable)."""
    from ..compress.bfp import codec_pair
    return codec_pair(cfg, n_elems)


def _as_codec(compression):
    """Normalize ``compression=``: None | compress.Codec | bare BFPConfig
    (the pre-subsystem spelling, still honored everywhere)."""
    from ..compress import as_codec
    return as_codec(compression)


def _varying(x: jax.Array, axis_name: str) -> jax.Array:
    """x as a ``fori_loop`` over the ring's hops must enter with it: what a
    hop returns is varying over the ring's axis, so what goes in must be
    too, or the loop's carry types differ."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, axis_name, to="varying")


def _zero_carry(axis_name: str):
    """The integrity checksums' (send, recv) carry, varying like the sums
    every hop adds to it."""
    return tuple(_varying(z, axis_name) for z in _integrity.zero_carry())


def _send_n_messages(codec, length: int,
                     slice_elems: Optional[int]) -> int:
    """How many distinct wire messages one ``_send`` call emits — the
    static message-counter stride callers use to give every (hop,
    slice) its own ``msg_base`` range, so every message in a collective
    carries a DISTINCT odd conservation weight (a product of two odd
    per-axis weights would collide across hops — the aliasing class
    the reshard transfer's per-segment counter also rules out)."""
    if codec is None or not codec.sliceable(length, slice_elems):
        return 1
    return length // slice_elems


def _send(payload: jax.Array, axis_name: str, n: int,
          codec, slice_elems: Optional[int] = None,
          perm=None, chk=None, msg_base=None):
    """One ring hop, optionally codec-compressed on the wire.  ``codec``
    is an already-normalized compress.Codec (or None).  ``perm``
    overrides the next-neighbor permutation — the seam `ops.ring_hier`
    drives its intra/inter SUBRING hops through, so the sliced
    double-buffered codec stream below is written exactly once.

    ``chk`` (None = integrity off) is a (send_acc, recv_acc) uint32
    carry: every payload element that crosses the wire is checksummed
    once on the send side (pre-ppermute) and once on the receive side
    (post-ppermute, post-wire-tap) with the SAME odd message weight
    ``integrity.hop_weight(msg_base + slice)`` — ``msg_base`` is this
    hop's offset into the collective's single message counter (stride
    ``_send_n_messages``), so no two messages in one conservation sum
    share a weight (messages at the same (hop, slice) on DIFFERENT
    devices still do — part of the conceded multi-corruption algebraic
    class, docs/KNOWN_FAILURES.md).  The collective closes the carry
    with ``integrity.conservation_ok``.  Returns ``received`` or
    ``(received, chk')``.  The checksums never ride the wire: ppermute
    operand bytes are IDENTICAL with integrity on or off (the J4/J9
    accounting is untouched)."""
    if perm is None:
        perm = _next_neighbor_perm(n)
    if codec is None:
        if chk is None and _WIRE_TAP is None:
            return lax.ppermute(payload, axis_name, perm)
        pay = (payload,)
        if chk is not None:
            w = _integrity.hop_weight(msg_base)
            sa = chk[0] + w * _integrity.payload_checksum(pay)
        pay = tuple(lax.ppermute(p, axis_name, perm) for p in pay)
        pay = _tap_wire(pay, "ring.wire")
        if chk is None:
            return pay[0]
        ra = chk[1] + w * _integrity.payload_checksum(pay)
        return pay[0], (sa, ra)
    C = payload.shape[0]
    if not codec.sliceable(C, slice_elems):
        # whole-chunk hop (also the fallback when slicing would change the
        # codec's unit partition — sliced and whole-chunk hops must be
        # bit-identical, so an incompatible slice_elems degrades to this)
        pay = codec.encode(payload)
        if chk is not None:
            w = _integrity.hop_weight(msg_base)
            sa = chk[0] + w * _integrity.payload_checksum(pay)
        pay = tuple(lax.ppermute(p, axis_name, perm) for p in pay)
        pay = _tap_wire(pay, "ring.wire")
        out = codec.decode(pay, C, payload.dtype)
        if chk is None:
            return out
        ra = chk[1] + w * _integrity.payload_checksum(pay)
        return out, (sa, ra)

    # Sliced, double-buffered stream: while slice k's compressed payload is
    # on the wire, encode slice k+1 (they are independent, so XLA's
    # latency-hiding scheduler overlaps codec compute with the permute DMA).
    # The final iteration's look-ahead encode (slice 0 again) is dead work
    # worth 1/S of one codec pass — the price of a uniform scan body.
    S = C // slice_elems
    slices = payload.reshape(S, slice_elems)

    if chk is None:
        def step(carry, k):
            received = tuple(lax.ppermute(p, axis_name, perm)
                             for p in carry)
            received = _tap_wire(received, "ring.wire")
            nxt = codec.encode(slices[(k + 1) % S])
            return nxt, codec.decode(received, slice_elems, payload.dtype)

        _, received = lax.scan(step, codec.encode(slices[0]),
                               jnp.arange(S))
        return received.reshape(C)

    def step(carry, k):
        pay, sa, ra = carry
        # slice k of this hop is message msg_base + k of the collective:
        # the same index on sender and receiver (the conservation sum
        # telescopes to zero when clean), distinct from every other
        # (hop, slice) in the same carry
        w = _integrity.hop_weight(msg_base + k)
        sa = sa + w * _integrity.payload_checksum(pay)
        received = tuple(lax.ppermute(p, axis_name, perm) for p in pay)
        received = _tap_wire(received, "ring.wire")
        ra = ra + w * _integrity.payload_checksum(received)
        nxt = codec.encode(slices[(k + 1) % S])
        return (nxt, sa, ra), codec.decode(received, slice_elems,
                                           payload.dtype)

    (_, sa, ra), received = lax.scan(
        step, (codec.encode(slices[0]), chk[0], chk[1]), jnp.arange(S))
    return received.reshape(C), (sa, ra)


def ring_reduce_scatter(x: jax.Array, axis_name: str, *,
                        compression=None,        # compress.Codec | BFPConfig | None
                        slice_elems: Optional[int] = None,
                        unroll: bool = False,
                        integrity: bool = False):
    """Sliced ring reduce-scatter of a flat per-device vector.

    x: [L] with L % n == 0 (pad upstream; the reference pads to slice
    multiples the same way, hw/all_reduce.sv:403-409).  Returns [L//n]:
    this device's fully-reduced chunk, chunk index == device index.

    Schedule (n-1 hops): at hop s device i sends partial chunk
    (i - s - 1) mod n and accumulates the received partial into chunk
    (i - s - 2) mod n; the last accumulation lands on chunk i.

    ``integrity=True`` additionally checksums every hop's ENCODED wire
    payload on both sides (ops.integrity) and returns ``(owned,
    wire_ok)`` with ``wire_ok`` a replicated bool: every frame arrived
    bit-identical.  The result bits are unchanged and no checksum rides
    the wire (ppermute bytes identical either way).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    codec = _as_codec(compression)
    if x.ndim != 1 or x.shape[0] % n != 0:
        raise ValueError(f"need flat length divisible by {n}, got {x.shape}")
    if n == 1:
        return (x, jnp.bool_(True)) if integrity else x
    x = _tap(x, "ring.reduce_scatter")
    chunks = x.reshape(n, -1)

    if not integrity:
        def hop(s, ch):
            send = jnp.take(ch, ((idx - s - 1) % n)[None], axis=0)[0]
            recv = _send(send, axis_name, n, codec, slice_elems)
            return ch.at[(idx - s - 2) % n].add(recv)

        chunks = lax.fori_loop(0, n - 1, hop, chunks, unroll=unroll)
        return jnp.take(chunks, idx[None], axis=0)[0]

    stride = _send_n_messages(codec, x.shape[0] // n, slice_elems)

    def hop_i(s, carry):
        ch, chk = carry
        send = jnp.take(ch, ((idx - s - 1) % n)[None], axis=0)[0]
        recv, chk = _send(send, axis_name, n, codec, slice_elems,
                          chk=chk, msg_base=s * stride)
        return ch.at[(idx - s - 2) % n].add(recv), chk

    chunks, (sa, ra) = lax.fori_loop(
        0, n - 1, hop_i,
        (_varying(chunks, axis_name), _zero_carry(axis_name)),
        unroll=unroll)
    ok = _integrity.conservation_ok(sa, ra, axis_name)
    return jnp.take(chunks, idx[None], axis=0)[0], ok


def ring_all_gather(owned: jax.Array, axis_name: str, *,
                    compression=None,        # compress.Codec | BFPConfig | None
                    unroll: bool = False,
                    integrity: bool = False):
    """Ring all-gather: device i contributes chunk i, returns [n * C].

    This is the phase that distributes *updated weights* in the fused
    collective (hw/all_reduce.sv FORWARD_OUTPUT/OUTPUT_SEND, lines
    996-1086).  Under compression the chunk is encoded once at first
    send and the compressed payload is forwarded VERBATIM thereafter
    (decoding the same payload is deterministic even for non-idempotent
    codecs like stochastic int8), so every replica sees identical bytes.
    No per-hop slicing here: the payload is encoded exactly once, so there
    is no codec work to overlap with the forwarding permutes.

    ``integrity=True`` returns ``(gathered, wire_ok)`` — every forwarded
    frame checksummed on both sides of every hop (ops.integrity); a
    corrupted forward trips every downstream replica's receive sum.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    codec = _as_codec(compression)
    owned = _tap(owned, "ring.all_gather")
    if n == 1:
        # still quantize: replicas must see wire-identical bytes at any n,
        # and the golden model quantizes the owned chunk unconditionally
        out1 = (codec.roundtrip(owned).astype(owned.dtype)
                if codec is not None else owned)
        return (out1, jnp.bool_(True)) if integrity else out1
    C = owned.shape[0]
    out = jnp.zeros((n, C), owned.dtype).at[idx].set(owned)
    perm = _next_neighbor_perm(n)

    if codec is None:
        pay = (owned,)
        store = owned
    else:
        pay = codec.encode(owned)
        # the local replica stores the same quantized bytes it sends,
        # keeping replicas identical across devices
        store = codec.decode(pay, C, owned.dtype)
    out = out.at[idx].set(store)

    def _landed(pay_):
        return pay_[0] if codec is None else codec.decode(pay_, C,
                                                          owned.dtype)

    if not integrity:
        if codec is None and _WIRE_TAP is None:
            def hop(s, carry):
                out_, p = carry
                p = lax.ppermute(p, axis_name, perm)
                return out_.at[(idx - s - 1) % n].set(p), p

            out, _ = lax.fori_loop(0, n - 1, hop, (out, owned),
                                   unroll=unroll)
        else:
            def hop(s, carry):
                out_, p = carry
                p = tuple(lax.ppermute(q, axis_name, perm) for q in p)
                p = _tap_wire(p, "ring.wire")
                return out_.at[(idx - s - 1) % n].set(_landed(p)), p

            out, _ = lax.fori_loop(0, n - 1, hop, (out, pay),
                                   unroll=unroll)
        return out.reshape(n * C)

    def hop_i(s, carry):
        out_, p, (sa, ra) = carry
        w = _integrity.hop_weight(s)
        sa = sa + w * _integrity.payload_checksum(p)
        p = tuple(lax.ppermute(q, axis_name, perm) for q in p)
        p = _tap_wire(p, "ring.wire")
        ra = ra + w * _integrity.payload_checksum(p)
        return out_.at[(idx - s - 1) % n].set(_landed(p)), p, (sa, ra)

    out, _, (sa, ra) = lax.fori_loop(
        0, n - 1, hop_i, (out, pay, _zero_carry(axis_name)),
        unroll=unroll)
    ok = _integrity.conservation_ok(sa, ra, axis_name)
    return out.reshape(n * C), ok


def ring_all_reduce(x: jax.Array, axis_name: str, *,
                    compression=None,        # compress.Codec | BFPConfig | None
                    slice_elems: Optional[int] = None,
                    unroll: bool = False,
                    integrity: bool = False):
    """Full all-reduce (sum) = reduce-scatter + all-gather.  With
    ``integrity=True`` returns ``(reduced, wire_ok)`` — the AND of both
    phases' frame-conservation verdicts."""
    if integrity:
        owned, ok_rs = ring_reduce_scatter(
            x, axis_name, compression=compression,
            slice_elems=slice_elems, unroll=unroll, integrity=True)
        full, ok_ag = ring_all_gather(owned, axis_name,
                                      compression=compression,
                                      unroll=unroll, integrity=True)
        return full, ok_rs & ok_ag
    owned = ring_reduce_scatter(x, axis_name, compression=compression,
                                slice_elems=slice_elems, unroll=unroll)
    return ring_all_gather(owned, axis_name, compression=compression,
                           unroll=unroll)


def wire_bytes_per_device(L: int, n: int,
                          compression=None,
                          dtype_bytes: int = 4) -> int:
    """Bytes each device puts on the ring for one all-reduce of L elements
    (observability parity with the reference's flit counters,
    hw/bfp_adapter.sv:705-729).  ``compression`` is a Codec or (legacy)
    a BFPConfig."""
    elems = 2 * (n - 1) * (L // n)
    codec = _as_codec(compression)
    if codec is None:
        return elems * dtype_bytes
    return codec.wire_bytes(elems)
