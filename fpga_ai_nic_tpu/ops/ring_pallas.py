"""Fused BFP-compressed ring reduce-scatter — ONE Pallas kernel.

The reference's bfp_adapter sits *inside* the wire datapath: the engine
streams 512b groups through compress -> Ethernet -> decompress without ever
materializing the compressed frame in host-visible memory
(hw/bfp_adapter.sv:33-741 between hw/all_reduce.sv's engine and the IKL
shell).  `ops.ring` approximates that with separate XLA ops (encode /
ppermute / decode) and leaves the overlap to XLA's scheduler; THIS module
is the real analogue: a single kernel that, per 32 KiB-class slice, runs
a depth-D pipeline —

    encodes slice g+D into a send buffer        (VPU compute)
  while
    slices g+1 .. g+D-1 fly as RDMAs on the ICI (DMA engines)
  while
    decode + accumulate of slice g retires      (VPU compute)

over a (D+1)-slot comm window with explicit credit-based flow control —
the same producer/consumer discipline the reference implements with its
dual-clock FIFOs and valid/ready handshakes (hw/fifo.v,
hw/bfp_adapter.sv:57-98), generalized from the reference's fixed
double-buffer to a credit window sized by the pipeline depth (_rs_plan
states and proves the three schedule invariants; simulate_rs_protocol
race-checks them at model level up to n=8, and ops.ring_cost turns the
`ablate=` stage timings into a predicted pipeline time and a
pipeline_efficiency the loopback bench reports per row).

Wire format: one int8 frame per slice packing `R` mantissa rows followed
by `R/B` shared-exponent rows (B = block_size) — the live rows carry the
reference's exact 17-flit rate (16 mantissa flits : 1 exponent flit,
hw/bfp_adapter.sv:30,63-77), and the RDMA'd frame rounds up to the int8
8-row tile (_frame_rows; 72/68 of the live bytes at the default R=64
plan).  One RDMA moves the whole compressed slice.

Numerics are bit-identical to `ops.ring.ring_reduce_scatter` with
codec="pallas" and the same slice_elems (same add order, same per-hop
lane-layout quantization): slicing and fusion change the schedule, never
the bits (tests/test_ring_pallas.py enforces this on the CPU interpreter).

Residency: two reduce-scatter kernels share the schedule.  The
VMEM-resident one holds the whole per-device vector on-chip (fastest for
payloads up to a few MiB); `_rs_stream_kernel` keeps the vector in HBM
(aliased with the input) and streams two slices of working f32 through
VMEM with load/writeback DMAs — the reference's memory shape exactly:
arbitrarily long vectors through a fixed 32 KiB-class working set
(hw/all_reduce.sv:101-103,246-253).  `ring_reduce_scatter_fused` picks by
payload size; both are bit-identical.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bfp_pallas import LANES, _is_tpu
from .. import optim as _optim
from ..obs.names import kernel
from ..utils.config import BFPConfig, OptimizerSpec
# the shared protocol IR: the kernels below CONSUME its emitters — the
# schedule they execute and the stream graftmc explores are one
# definition (no jax inside verify.opstream; importing it here is free)
from ..verify import opstream as _opstream


def _encode_rows(x, block_size: int, mantissa_bits: int, rounding: str):
    """(R, 128) f32 -> ((R, 128) int8 mantissas, (R/B, 128) int8 scales).
    Register-level port of bfp_pallas._encode_kernel (the bit spec is
    bfp_golden layout="sublane"; hw/bf16_to_bfp_core.sv:30-132)."""
    R = x.shape[0]
    T = R // block_size
    bits = pltpu.bitcast(x, jnp.uint32)
    e = jnp.right_shift(bits, 23).astype(jnp.int32) & 0xFF
    emax = jnp.max(e.reshape(T, block_size, LANES), axis=1)
    scale_e = jnp.clip(emax - 127 - (mantissa_bits - 2), -126, 126)
    inv = pltpu.bitcast(((127 - scale_e) << 23).astype(jnp.uint32),
                        jnp.float32)                 # 2.0**-scale_e, exact
    q = x * jnp.repeat(inv, block_size, axis=0)
    q = jnp.round(q) if rounding == "nearest" else jnp.trunc(q)
    lim = float(2 ** (mantissa_bits - 1) - 1)
    return (jnp.clip(q, -lim, lim).astype(jnp.int8),
            scale_e.astype(jnp.int8))


def _decode_rows(mant, scale, block_size: int):
    """Inverse of _encode_rows (hw/bfp_to_bf16_core.sv:30-125)."""
    se = scale.astype(jnp.int32)
    s = pltpu.bitcast(((se + 127) << 23).astype(jnp.uint32), jnp.float32)
    return mant.astype(jnp.float32) * jnp.repeat(s, block_size, axis=0)


_FRAME_ALIGN = 8     # int8 VMEM sublane tile: DMA slice row extents align


def _frame_rows(R: int, block_size: int) -> int:
    """Rows of one RDMA'd wire frame: R mantissa rows + R/B scale rows,
    padded up to the int8 (8,128) sublane tile — the Mosaic compiler
    rejects DMA slices whose row extent is not tile-aligned (first
    hardware contact, v5e: "Slice shape along dimension 1 must be aligned
    to tiling (8), but is 17").  Pad rows ride the wire but are never
    written or decoded; at the default slice plan (R=64, B=16: 68 -> 72
    rows) the overhead is 5.9%, and the live rows keep the reference's
    exact 16:1 mantissa:exponent rate (hw/bfp_adapter.sv:30,63-77)."""
    live = R + R // block_size
    return -(-live // _FRAME_ALIGN) * _FRAME_ALIGN


def _neighbor_barrier(left, right):
    """All ring members must have entered the kernel before the first RDMA
    lands in a neighbor's scratch (the analogue of ikl_setup's reset
    barrier, sw/mlp_mpi_example_f32.cpp:50-63)."""
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def _interp_args(interpret):
    """Map the public tri-state ``interpret`` flag to (pallas interpret
    argument, flow_control, unrolled).

    False       hardware: compiled kernel, rolled schedule, flow control ON
    True        discharge interpreter (fast lockstep emulation; copies
                materialize at dma_start in SPMD program order): flow
                control OFF — it cannot execute remote semaphore signals —
                and safety rests on the static schedule's program-order
                properties (_ag_schedule P1/P2)
    "threaded"  pltpu.InterpretParams: one thread per device, BLOCKING
                semaphores, remote signals, race detection — the real
                flow-control protocol (neighbor barrier + credit window)
                executes end-to-end; a protocol deadlock hangs the test
                and a data race is reported by the interpreter.  This is
                the strongest off-hardware evidence the credit protocol
                admits (tests/test_ring_pallas.py::TestFlowControl).
    """
    if interpret == "threaded":
        return pltpu.InterpretParams(detect_races=True), True, True
    return bool(interpret), not interpret, bool(interpret)


def _when(cond, static: bool):
    """pl.when for the rolled (compiled) schedule; a plain python ``if``
    for the statically-unrolled schedule the interpreter runs — the
    vma-checked interpreter rejects lax.cond branch joins inside kernels
    (invariant vs varying branch outputs), and every schedule decision is
    a static counter comparison anyway."""
    if static:
        def deco(f):
            if cond:
                f()
        return deco
    return pl.when(cond)


class _KernelSink(_opstream.OpSink):
    """Maps the shared emitters' abstract ops (`verify.opstream`) onto
    one Pallas kernel's DMA/semaphore/VPU resources.  The emitter owns
    the FULL schedule — every wait/signal/transfer order decision; this
    sink only (a) binds each abstract op to a real call, (b) filters op
    classes for stage ablation (`do_*`) and the interpreter's
    flow-control limitation, and (c) lowers `when` to `pl.when` on the
    rolled path / a python ``if`` on the unrolled path (`_when`).  The
    kernels therefore carry no schedule text of their own to drift from
    the checked model — the PR-9 flat-route discipline, applied to every
    route."""

    def __init__(self, *, unrolled, flow_control, do_rdma=True,
                 do_enc=True, do_dec=True, do_upd=True, do_chk=False,
                 barrier=None, send=None, wait_send=None, wait_recv=None,
                 credit_wait=None, credit_signal=None, credit_drain=None,
                 encode=None, decode=None, update=None, chk_emit=None,
                 chk_arrive=None, dma_start=None, dma_wait=None,
                 local=None):
        self._unrolled = unrolled
        self._flow = flow_control
        self._do_rdma = do_rdma
        self._do_enc = do_enc
        self._do_dec = do_dec
        self._do_upd = do_upd
        self._do_chk = do_chk
        self._barrier = barrier
        self._send = send
        self._wait_send = wait_send
        self._wait_recv = wait_recv
        self._credit_wait = credit_wait
        self._credit_signal = credit_signal
        self._credit_drain = credit_drain
        self._encode = encode
        self._decode = decode
        self._update = update
        self._chk_emit = chk_emit
        self._chk_arrive = chk_arrive
        self._dma_start = dma_start
        self._dma_wait = dma_wait
        self._local = local

    def when(self, cond):
        return _when(cond, self._unrolled)

    def barrier(self):
        if self._flow and self._do_rdma:
            self._barrier()

    def send(self, q, src=None):
        if self._do_rdma:
            self._send(q, src)

    def wait_send(self, j):
        if self._do_rdma:
            self._wait_send(j)

    def wait_recv(self, g):
        if self._do_rdma:
            self._wait_recv(g)

    def credit_wait(self):
        if self._flow and self._do_rdma:
            self._credit_wait()

    def credit_signal(self):
        if self._flow and self._do_rdma:
            self._credit_signal()

    def credit_drain(self, k):
        if self._flow and self._do_rdma:
            self._credit_drain(k)

    def encode(self, q, src=None):
        if self._do_enc:
            self._encode(q, src)

    def decode(self, g):
        if self._do_dec:
            self._decode(g)

    def update(self, g):
        if self._do_upd:
            self._update(g)

    def chk_emit(self, msg, carry="wire", weight=None):
        if self._do_chk:
            self._chk_emit(msg)

    def chk_arrive(self, msg, carry="wire", weight=None):
        if self._do_chk:
            self._chk_arrive(msg)

    def local(self, name, *args):
        self._local(name, *args)

    def dma_start(self, chan, i, *conf):
        # conf (the checker's hazard-predecessor annotations) is
        # evidence for `check_dma_discipline`, not schedule — ignored
        self._dma_start(chan, i)

    def dma_wait(self, chan, i):
        self._dma_wait(chan, i)


# Default pipeline depth D of the reduce-scatter schedule: at steady
# state encode(g+D), RDMA(g+D-1 .. g+1), and decode+accumulate(g) are all
# in flight — the reference's keep-every-beat-busy discipline
# (hw/all_reduce.sv:891-1183) expressed as a comm-slot window of D+1
# frames.  D is capped by the slice plan (launch-ahead must not outrun the
# cross-hop RAW: send q reads what consume q-S accumulated), so deep
# pipelines need S >= D slices per chunk — which is what the sub-slice
# split below buys on big payloads.
_PIPE_DEPTH = 2

# Encode/decode VPU work is issued in sub-slice chunks of at most this
# many rows, so no single VPU op serializes against a whole slice's DMA;
# boundaries stay BFP-block-aligned, so the chunking is invisible to the
# bits (the blocks and the add order are unchanged).
_SUB_ROWS = 128


def _sub_rows(R: int, block_size: int) -> int:
    """Largest divisor of R that is <= _SUB_ROWS and a whole number of
    BFP blocks (rows group into blocks of block_size consecutive rows, so
    a sub-chunk boundary must never straddle a block)."""
    if R <= _SUB_ROWS:
        return R
    for d in range(_SUB_ROWS, block_size - 1, -1):
        if R % d == 0 and d % block_size == 0:
            return d
    return block_size                 # R % block_size == 0 by construction


def _rs_plan(n: int, S: int, depth: Optional[int]):
    """(D, n_slots, launch_first) for the deep-pipelined RS schedule —
    a delegate to THE plan definition in `verify.opstream.rs_plan`, so
    the emitted kernels and the graftmc model checker derive from one
    source (the three schedule invariants — RAW, SLOT, CAP — are stated
    there and exhaustively verified per plan by `make modelcheck`)."""
    from ..verify import opstream as _opstream
    return _opstream.rs_plan(n, S, depth, default_depth=_PIPE_DEPTH)


def _rs_offsets(ids, n: int, S: int, slice_rows: int):
    """(2, total) int32 schedule table — row 0: send-side acc row offset
    of emission q; row 1: recv-side offset of arrival g.  Hop s sends
    partial chunk idx-s-1 and accumulates into chunk idx-s-2 (the ring
    rotation of hw/all_reduce.sv's slice schedule).  Computed at trace
    time from the launch-data ring index, so the kernel's inner loop does
    one SMEM load per schedule decision instead of div/mod chains."""
    import numpy as np
    total = (n - 1) * S
    q = np.arange(total, dtype=np.int32)
    s, k = q // S, q % S
    idx = ids[0]
    chunk_rows = S * slice_rows
    send = ((idx - s - 1) % n) * chunk_rows + k * slice_rows
    recv = ((idx - s - 2) % n) * chunk_rows + k * slice_rows
    return jnp.stack([send, recv]).astype(jnp.int32)


def _rs_parse_refs(opt_kind: Optional[str], refs,
                   integrity: bool = False):
    """Split a fused-opt (or plain) RS kernel's positional refs into the
    named slots shared by both kernels: pallas passes inputs, then
    outputs, then scratch, and the fused variants add (hyper, w, *state)
    inputs and (w_new, *state_new) outputs.  With ``integrity`` the LAST
    output is the SMEM [2] uint32 (send_acc, recv_acc) checksum pair.
    Returns (hyper, x, w, st_in, out, w_out, st_out, chk, *scratch6)."""
    if opt_kind is None:
        x_ref, out_ref = refs[0], refs[1]
        rest = refs[2:]
        chk = None
        if integrity:
            chk, rest = rest[0], rest[1:]
        return (None, x_ref, None, (), out_ref, None, (), chk) \
            + tuple(rest)
    ns = OptimizerSpec(kind=opt_kind).n_state
    hyper_ref, x_ref, w_ref = refs[:3]
    st_in = tuple(refs[3:3 + ns])
    out_ref, w_out = refs[3 + ns], refs[4 + ns]
    st_out = tuple(refs[5 + ns:5 + 2 * ns])
    rest = refs[5 + 2 * ns:]
    chk = None
    if integrity:
        chk, rest = rest[0], rest[1:]
    return (hyper_ref, x_ref, w_ref, st_in, out_ref, w_out,
            st_out, chk) + tuple(rest)


def _frame_checksum(frame) -> jax.Array:
    """uint32 scalar: the ops.integrity odd-weighted word sum over one
    int8 wire frame, zero-extended byte-per-word — computed over the
    FULL (tile-padded) frame, which is exactly what the RDMA moves, so
    both ends of a hop sum identical bytes (pad rows are stale slot
    garbage, but the SAME stale garbage on both sides: the checksum is
    taken after encode on the send side and after wait_recv on the
    receive side, and nothing touches the slot in between)."""
    words = (frame[:].astype(jnp.int32) & 0xFF).astype(jnp.uint32)
    r, l = words.shape
    pos = (lax.broadcasted_iota(jnp.uint32, (r, l), 0) * jnp.uint32(l)
           + lax.broadcasted_iota(jnp.uint32, (r, l), 1))
    return jnp.sum(words * ((pos << 1) | jnp.uint32(1)),
                   dtype=jnp.uint32)


def _emission_weight(q) -> jax.Array:
    """Odd per-emission weight: my emission q is my right neighbor's
    arrival q, so sender and receiver weight the same message
    identically and the global conservation sum telescopes to zero iff
    every frame arrived bit-identical.  Delegates to
    ops.integrity.hop_weight — the kernel-side and host-side weight
    schemes MUST be one definition or conservation silently breaks."""
    from . import integrity
    return integrity.hop_weight(q)


def _rs_kernel(ids_ref, sched_ref, *refs, n: int, n_slices: int,
               slice_rows: int, block_size: int, mantissa_bits: int,
               rounding: str, flow_control: bool, unrolled: bool,
               depth: int, n_slots: int, launch_first: bool,
               ablate: Optional[str] = None,
               opt_kind: Optional[str] = None,
               integrity: bool = False):
    """The whole sliced ring reduce-scatter, one kernel invocation, as a
    depth-D pipeline: encode(g+D), RDMA(g+D-1 .. g+1), and
    decode+accumulate(g) proceed concurrently over an (D+1)-slot comm
    window with credit-based flow control (schedule invariants and their
    proof: _rs_plan).

    ids_ref:   SMEM [3] int32 — (my index, right neighbor, left neighbor),
               computed OUTSIDE the kernel: in-kernel axis_index arithmetic
               trips vma typing under the checked interpreter, and the ring
               position is launch-time data anyway
    sched_ref: SMEM (2, total) int32 — per-step acc row offsets
               (_rs_offsets), hoisting the div/mod bookkeeping out of the
               inner loop
    acc:       (L_rows, 128) f32 — running partials (starts as x)
    send_pkt:  (n_slots, R + R/B, 128) int8 — packed frames, slot-cycled
    recv_pkt:  (n_slots, R + R/B, 128) int8
    send/recv_sem: DMA (n_slots,) — one per comm slot
    credit_sem: REGULAR — downstream-consumed-slot credits (flow control)

    ablate (STAGE-ATTRIBUTION ONLY, compile-time): None runs the full
    pipeline; "encode" / "rdma" / "decode" run exactly one stage of the
    same schedule (the other stages compile away) and "skeleton" runs
    none of them — the bare loop + slot bookkeeping, the control-flow
    floor the cost model subtracts (ops.ring_cost).  Timing the variants
    answers which stage binds the pipelined hop — the per-stage breakdown
    the round-4 verdict ordered for the loopback microbench (the
    reference reads the same split from its stall counters,
    hw/all_reduce.sv:94-97).  Ablated outputs are garbage by design:
    "rdma" sends whatever is in the frames, "decode" decodes stale
    frames — timing is data-independent on the VPU/DMA so rates are
    unaffected.  Loopback/bench use only; never a collective.

    opt_kind (STATIC): None runs the plain reduce-scatter; "sgd" /
    "momentum" / "adamw" fuse the ZeRO-1 optimizer update into the
    final-hop decode — the reference's weight_update.sv sitting inside
    the decode datapath (SURVEY.md §3.2), generalized to pluggable
    formulas.  The refs then grow (hyper SMEM f32[HYPER_LEN], w shard,
    state shards) on the input side and (w_new, state_new) outputs
    aliased onto the shards; each owned sub-slice chunk updates in the
    same block-aligned `_sub_rows` pieces its decode retires, while the
    ring's remaining hops are still in flight.  The GRADIENT path (acc,
    out_ref) is bit-identical to the unfused kernel at every depth (same
    slices, same add order); the update formula is
    optim.fused_apply_blocks, bit-specified by optim.golden_fused_apply.
    ablate gains "update": ONLY the update stage of the same schedule
    (its VPU cost + nothing else), for ring_cost's fused-opt term."""
    assert ablate in (None, "encode", "rdma", "decode", "skeleton",
                      "update"), ablate
    assert ablate != "update" or opt_kind is not None, \
        "ablate='update' needs a fused optimizer"
    do_enc = ablate in (None, "encode")
    do_rdma = ablate in (None, "rdma")
    do_dec = ablate in (None, "decode")
    do_upd = opt_kind is not None and ablate in (None, "update")
    refs = _rs_parse_refs(opt_kind, refs, integrity)
    (hyper_ref, x_ref, w_ref, st_in, out_ref, w_out, st_out, chk_ref,
     acc, send_pkt, recv_pkt, send_sem, recv_sem, credit_sem) = refs
    # the integrity accumulators live in the SMEM output itself: pl.when
    # blocks mutate refs, never loop-carried values, and the wraparound
    # u32 sums are order-insensitive (addition mod 2^32 commutes)
    do_chk = integrity and ablate is None
    if integrity:
        # zero the SMEM output whenever it EXISTS (it is appended for
        # integrity=True regardless of ablate): an ablated kernel must
        # report a clean 0==0 conservation, never uninitialized SMEM
        chk_ref[0] = jnp.uint32(0)
        chk_ref[1] = jnp.uint32(0)
    idx = ids_ref[0]
    right = ids_ref[1]               # we send downstream (IKL ring order,
    left = ids_ref[2]                # sw/setup_route.sh:12-40)
    S = n_slices
    R = slice_rows
    B = block_size
    sub = _sub_rows(R, B)
    chunk_rows = S * R
    total = (n - 1) * S              # global send/consume count
    D = depth
    final_g0 = (n - 2) * S           # consumes >= this land in OUR chunk

    acc[:] = x_ref[:]

    def rdma(g):
        slot = g % n_slots
        return pltpu.make_async_remote_copy(
            src_ref=send_pkt.at[slot], dst_ref=recv_pkt.at[slot],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)

    def encode_to_slot(g, _src=None):
        # rolled path: g = loop index + D can exceed the table under the
        # pl.when(q < total) guard — clamp the (guarded-dead) SMEM load
        # like the AG kernel's is_own_j does
        off = sched_ref[0, g if unrolled else jnp.clip(g, 0, total - 1)]
        slot = g % n_slots
        for c in range(0, R, sub):   # sub-slice chunks, block-aligned
            mant, scale = _encode_rows(acc[pl.ds(off + c, sub)], B,
                                       mantissa_bits, rounding)
            send_pkt[slot, pl.ds(c, sub)] = mant
            send_pkt[slot, pl.ds(R + c // B, sub // B)] = scale

    def chk_emit(q):
        # checksum the frame exactly as the RDMA will move it
        chk_ref[0] = chk_ref[0] + _emission_weight(q) \
            * _frame_checksum(send_pkt[q % n_slots])

    def chk_arrive(g):
        chk_ref[1] = chk_ref[1] + _emission_weight(g) \
            * _frame_checksum(recv_pkt[g % n_slots])

    def decode_slice(g):
        # decode slice g + accumulate into the chunk this hop owns
        off = sched_ref[1, g]
        slot = g % n_slots
        for c in range(0, R, sub):
            dec = _decode_rows(recv_pkt[slot, pl.ds(c, sub)],
                               recv_pkt[slot, pl.ds(R + c // B, sub // B)],
                               B)
            acc[pl.ds(off + c, sub)] = acc[pl.ds(off + c, sub)] + dec

    def update_slice(g):
        # fused ZeRO-1 optimizer update of the owned chunk this final-
        # hop decode just retired: the mean gradient is read straight
        # out of the accumulator rows, the master/state shards update in
        # place (aliased outputs) — the decode feeds weight_update with
        # no HBM round-trip in between, and the remaining ring hops
        # still overlap this VPU work.  Formula/bit contract:
        # optim.fused_apply_blocks.
        off = sched_ref[1, g]
        loc = off - idx * chunk_rows    # owned-shard row offset
        for c in range(0, R, sub):
            gblk = acc[pl.ds(off + c, sub)] / jnp.float32(n)
            wblk = w_ref[pl.ds(loc + c, sub)]
            stblks = tuple(s[pl.ds(loc + c, sub)] for s in st_in)
            w2, st2 = _optim.fused_apply_blocks(
                opt_kind, wblk, gblk, stblks, lambda i: hyper_ref[i])
            w_out[pl.ds(loc + c, sub)] = w2
            for so, sv in zip(st_out, st2):
                so[pl.ds(loc + c, sub)] = sv

    # The schedule itself — prologue pipe-fill, launch/consume order,
    # wait/credit placement, drain — is NOT written here: the kernel
    # consumes the shared emitter (`verify.opstream.RsEmitter`), the
    # same object graftmc explores exhaustively, through the sink
    # below.  flow_control=False only under the discharge interpreter,
    # whose lockstep emulation cannot execute remote semaphore signals;
    # the threaded interpreter and hardware run barrier + credits for
    # real (see _interp_args).
    emitter = _opstream.RsEmitter(n, S, depth, opt_kind=opt_kind,
                                  integrity=do_chk,
                                  default_depth=_PIPE_DEPTH)
    assert (emitter.n_slots, emitter.launch_first) == \
        (n_slots, launch_first), (emitter.n_slots, n_slots)
    sink = _KernelSink(
        unrolled=unrolled, flow_control=flow_control, do_rdma=do_rdma,
        do_enc=do_enc, do_dec=do_dec, do_upd=do_upd, do_chk=do_chk,
        barrier=lambda: _neighbor_barrier(left, right),
        send=lambda q, src: rdma(q).start(),
        wait_send=lambda j: rdma(j).wait_send(),
        wait_recv=lambda g: rdma(g).wait_recv(),
        credit_wait=lambda: pltpu.semaphore_wait(credit_sem, 1),
        credit_signal=lambda: pltpu.semaphore_signal(
            credit_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL),
        credit_drain=lambda k: pltpu.semaphore_wait(credit_sem, k),
        encode=encode_to_slot, decode=decode_slice, update=update_slice,
        chk_emit=chk_emit, chk_arrive=chk_arrive)

    emitter.prologue(sink)
    if unrolled:
        # static schedule (the interpreter path): every counter decision
        # is a python bool, no lax.cond joins for the vma checker to fight
        for g in range(total):
            emitter.step(sink, g)
    else:
        def body(g, _):
            emitter.step(sink, g)
            return 0
        lax.fori_loop(0, total, body, 0)
    emitter.epilogue(sink)

    out_ref[:] = acc[pl.ds(idx * chunk_rows, chunk_rows)]


def _ring_ids(axis_name: Optional[str]) -> jax.Array:
    """[my, right, left] int32 — ring coordinates as kernel data; all-self
    when axis_name is None (single-chip loopback mode).

    The values feed make_async_remote_copy's LOGICAL device id, which is
    the FLAT index into the whole mesh — equal to the ring-axis index only
    when every other manual axis has extent 1.  Guard that here at trace
    time: a silent mismatch would RDMA to the wrong chip."""
    if axis_name is None:
        return jnp.zeros((3,), jnp.int32)
    sizes = dict(jax.sharding.get_abstract_mesh().shape)
    other = {a: s for a, s in sizes.items()
             if a != axis_name and s != 1}
    if other:
        raise ValueError(
            f"fused ring collectives need '{axis_name}' to be the only "
            f"nontrivial mesh axis (LOGICAL RDMA ids are flat mesh "
            f"indices); other axes with extent > 1: {other} — use the "
            f"XLA-op ring (ops.ring) on multi-axis meshes")
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    return jnp.stack([idx, (idx + 1) % n, (idx - 1) % n]).astype(jnp.int32)


@functools.partial(jax.jit, donate_argnames=("w2", "opt_st"),
                   static_argnames=(
    "axis_name", "block_size", "mantissa_bits", "rounding", "slice_elems",
    "interpret", "collective_id", "loopback_n", "ablate", "depth",
    "opt_kind", "integrity"))
def _rs_call(x2, axis_name: Optional[str], block_size: int,
             mantissa_bits: int, rounding: str, slice_elems: int,
             interpret: bool, collective_id: int,
             loopback_n: Optional[int] = None,
             ablate: Optional[str] = None,
             depth: Optional[int] = None,
             opt_kind: Optional[str] = None,
             w2: Optional[jax.Array] = None,
             opt_st: Tuple[jax.Array, ...] = (),
             hyper: Optional[jax.Array] = None,
             integrity: bool = False):
    n = loopback_n if axis_name is None else lax.axis_size(axis_name)
    L_rows = x2.shape[0]
    chunk_rows = L_rows // n
    R = slice_elems // LANES
    S = chunk_rows // R
    pkt_rows = _frame_rows(R, block_size)
    ids = _ring_ids(axis_name)
    sched = _rs_offsets(ids, n, S, R)
    D, n_slots, launch_first = _rs_plan(n, S, depth)
    _interp, _flow, _unrolled = _interp_args(interpret)
    kern = functools.partial(
        _rs_kernel, n=n, n_slices=S, slice_rows=R,
        block_size=block_size, mantissa_bits=mantissa_bits,
        rounding=rounding, flow_control=_flow, unrolled=_unrolled,
        depth=D, n_slots=n_slots, launch_first=launch_first,
        ablate=ablate, opt_kind=opt_kind, integrity=integrity)
    vma = jax.typeof(x2).vma | jax.typeof(ids).vma
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    def chk_sds():
        return jax.ShapeDtypeStruct((2,), jnp.uint32, vma=vma)

    if opt_kind is None:
        out_shape = [sds((chunk_rows, LANES))]
        out_specs = [vmem]
        in_specs = [smem, smem, vmem]
        args = (ids, sched, x2)
        io_alias = {}
    else:
        ns = OptimizerSpec(kind=opt_kind).n_state
        assert w2 is not None and hyper is not None and len(opt_st) == ns
        # outputs: g_own (raw SUM — the gradient path stays bit-identical
        # to the unfused kernel), then w_new + state_new aliased onto the
        # donated shard operands (ZeRO-1: each replica owns 1/n of the
        # master + moments, updated in place)
        out_shape = [sds((chunk_rows, LANES))] * (2 + ns)
        out_specs = [vmem] * (2 + ns)
        in_specs = [smem, smem, smem] + [vmem] * (2 + ns)
        args = (ids, sched, hyper, x2, w2) + tuple(opt_st)
        io_alias = {4: 1, **{5 + i: 2 + i for i in range(ns)}}
    if integrity:
        # (send_acc, recv_acc) u32 pair — SMEM scalars, psum'd into the
        # conservation verdict OUTSIDE the kernel
        out_shape = out_shape + [chk_sds()]
        out_specs = out_specs + [smem]
    out = pl.pallas_call(
        kern,
        out_shape=(out_shape[0] if len(out_shape) == 1 else out_shape),
        in_specs=in_specs,
        out_specs=(out_specs[0] if len(out_specs) == 1 else out_specs),
        input_output_aliases=io_alias,
        scratch_shapes=[
            pltpu.VMEM((L_rows, LANES), jnp.float32),          # acc
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # send frames
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # recv frames
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp,
        **kernel("ring.rs" if opt_kind is None else "ring.rs_update",
                 opt=opt_kind, ablate=ablate),
    )(*args)
    if opt_kind is None:
        if integrity:
            return out[0], (out[1][0], out[1][1])
        return out
    if integrity:
        return (out[0], out[1], tuple(out[2:-1]),
                (out[-1][0], out[-1][1]))
    return (out[0], out[1], tuple(out[2:]))


# above this per-device payload, the whole-vector VMEM-resident kernel
# (input + acc copies) stops fitting on-chip; the streaming kernel keeps
# only two slices + frames in VMEM
_VMEM_RESIDENT_MAX_BYTES = 4 << 20


def ring_reduce_scatter_fused(x: jax.Array, axis_name: str, *,
                              compression: Optional[BFPConfig] = None,
                              slice_elems: int = 8192,
                              streaming: Optional[bool] = None,
                              interpret: Optional[bool] = None,
                              pipeline_depth: Optional[int] = None,
                              collective_id: int = 7,
                              integrity: bool = False):
    """Fused compress-into-hop ring reduce-scatter of a flat f32 [L].

    Drop-in for `ops.ring.ring_reduce_scatter(..., codec="pallas")` where
    the payload meets the tiling constraints below; bit-identical result.

    streaming=None picks by size: payloads over ~4 MiB/device stream
    HBM->VMEM slice by slice (the vector never lives on-chip, matching
    the reference's fixed 32 KiB working set over arbitrarily long
    vectors); smaller payloads use the VMEM-resident kernel.  Both are
    bit-identical — the choice is residency, not numerics.

    pipeline_depth picks the launch-ahead D of the slice schedule
    (default _PIPE_DEPTH, capped by the slice plan — _rs_plan): at
    steady state encode(g+D), D RDMAs, and decode(g) run concurrently.
    A schedule choice, never a numerics choice: every depth is
    bit-identical (the slice partition and add order are unchanged).

    integrity=True returns ``(owned, wire_ok)``: the kernel accumulates
    the ops.integrity exact frame checksums of every emission (at
    encode) and every arrival (at wait_recv) into its SMEM output, and
    the conservation psum OUTSIDE the kernel yields the replicated
    verdict — the gradient path is bit-identical to integrity=False at
    every depth (checksums only READ the frames), no checksum rides the
    wire, and the RDMA'd bytes are unchanged.  Validated under the
    interpreters like the rest of the kernel contract (the hardware
    canary discipline of CollectiveConfig.fused_kernel applies).

    Constraints (assert, don't silently repartition — changing the block
    partition would change the bits):
      - L % n == 0, chunk C = L/n
      - C % slice_elems == 0, slice_elems % (block_size * 128) == 0
    """
    cfg = compression or BFPConfig()
    n = lax.axis_size(axis_name)
    L = x.shape[0]
    if interpret is None:
        interpret = not _is_tpu()
    assert L % n == 0, (L, n)
    C = L // n
    if C % slice_elems or slice_elems % (cfg.block_size * LANES):
        raise ValueError(
            f"fused ring needs chunk {C} % slice_elems {slice_elems} == 0 "
            f"and slice_elems % {cfg.block_size * LANES} == 0")
    if n == 1:
        return (x, jnp.bool_(True)) if integrity else x
    if streaming is None:
        streaming = L * 4 > _VMEM_RESIDENT_MAX_BYTES
    x2 = x.astype(jnp.float32).reshape(-1, LANES)
    call = _rs_stream_call if streaming else _rs_call
    out = call(x2, axis_name, cfg.block_size, cfg.mantissa_bits,
               cfg.rounding, slice_elems, interpret, collective_id,
               depth=pipeline_depth, integrity=integrity)
    if not integrity:
        return out.reshape(C)
    out, (sa, ra) = out
    from . import integrity as _integrity
    return out.reshape(C), _integrity.conservation_ok(sa, ra, axis_name)


def _rs_stream_kernel(ids_ref, sched_ref, *refs, n: int, n_slices: int,
                      slice_rows: int, block_size: int, mantissa_bits: int,
                      rounding: str, flow_control: bool, unrolled: bool,
                      depth: int, n_slots: int, launch_first: bool,
                      ablate: Optional[str] = None,
                      opt_kind: Optional[str] = None,
                      integrity: bool = False):
    """HBM-streaming variant of _rs_kernel: the vector stays in HBM (acc
    aliases the input buffer) and only two slices of working f32 plus the
    int8 frames live in VMEM — the reference's exact memory shape, which
    streams arbitrarily long vectors through fixed 32 KiB slices and a
    handful of FIFOs (hw/all_reduce.sv:101-103,246-253) instead of
    buffering the vector on-chip.  The same depth-D comm window as
    _rs_kernel (invariants: _rs_plan) plus two streaming-only overlaps:
    the send-side slice load is prefetched ONE emission ahead (ld(q+1)
    starts before encode(q), hiding the HBM read behind the codec), and
    the recv-side load starts before the wire wait.  The cross-hop RAW
    hazard (hop s sends what hop s-1 wrote back) is guarded by the
    writeback wait discipline below.

    del x_hbm: the aliased acc ref IS the input buffer (same for the
    fused-opt w/state shards: their aliased OUTPUT refs are the buffers).

    opt_kind (STATIC): as in _rs_kernel — fuse the ZeRO-1 optimizer
    update into the final-hop decode.  Streaming adds the reference's
    memory shape to the update too: the owned master/state slice streams
    HBM->VMEM while the wire wait is in flight, updates in VMEM in the
    same `_sub_rows` chunks the decode retires, and writes back on its
    own DMA pair — so the optimizer's entire HBM traffic (read+write of
    w and moments, 1/n of the model per replica) hides under the ring's
    remaining hops instead of running as a separate exposed pass.
    """
    # Stage ablation (loopback attribution only — see _rs_kernel): each
    # variant keeps exactly one pipeline resource class of the SAME
    # schedule: "hbm" = slice load + store-load + writeback streaming,
    # "encode" = load + codec-in, "rdma" = the wire chain alone,
    # "decode" = store-load + codec-out+add + writeback, "update" = the
    # fused-optimizer stage alone (its state-slice DMAs + VPU update),
    # "skeleton" = none of them (the control-flow floor, ops.ring_cost).
    assert ablate in (None, "encode", "rdma", "decode", "hbm",
                      "skeleton", "update"), ablate
    assert ablate != "update" or opt_kind is not None, \
        "ablate='update' needs a fused optimizer"
    do_ld = ablate in (None, "encode", "hbm")
    do_enc = ablate in (None, "encode")
    do_rdma = ablate in (None, "rdma")
    do_stld = ablate in (None, "hbm", "decode")
    do_dec = ablate in (None, "decode")
    do_wb = ablate in (None, "hbm", "decode")
    do_upd = opt_kind is not None and ablate in (None, "update")
    do_chk = integrity and ablate is None
    ns = 0 if opt_kind is None else OptimizerSpec(kind=opt_kind).n_state
    n_t = 1 + ns                     # fused-opt tensors: w + state shards
    chk_ref = None
    if opt_kind is None:
        x_hbm = refs[0]
        hyper_ref = None
        acc = refs[1]
        opt_out = ()
        rest = refs[2:]
        if integrity:
            chk_ref, rest = rest[0], rest[1:]
        (ld, st, send_pkt, recv_pkt, ld_sem, st_ld_sem, wb_sem, send_sem,
         recv_sem, credit_sem) = rest
        opt_buf = opt_ld_sem = opt_wb_sem = None
    else:
        hyper_ref, x_hbm = refs[0], refs[1]
        # inputs w_hbm/st_hbm are aliased onto the outputs right after
        # acc — the out refs ARE the buffers (del the input handles)
        acc = refs[2 + n_t]
        opt_out = tuple(refs[3 + n_t:3 + 2 * n_t])
        rest = refs[3 + 2 * n_t:]
        if integrity:
            chk_ref, rest = rest[0], rest[1:]
        (ld, st, send_pkt, recv_pkt, opt_buf, ld_sem, st_ld_sem, wb_sem,
         opt_ld_sem, opt_wb_sem, send_sem, recv_sem,
         credit_sem) = rest
    del refs, x_hbm
    if integrity:
        # zeroed whenever the SMEM output exists (see _rs_kernel): an
        # ablated kernel reports clean 0==0 conservation, never garbage
        chk_ref[0] = jnp.uint32(0)
        chk_ref[1] = jnp.uint32(0)
    idx = ids_ref[0]
    right = ids_ref[1]
    left = ids_ref[2]
    S = n_slices
    R = slice_rows
    B = block_size
    sub = _sub_rows(R, B)
    chunk_rows = S * R
    total = (n - 1) * S
    D = depth
    final_g0 = (n - 2) * S           # consumes >= this land in OUR chunk

    def send_off(q):
        # clamp guarded-dead loads past the table (see _rs_kernel's
        # encode_to_slot): rolled-path q can exceed total under pl.when
        return sched_ref[0, q if unrolled else jnp.clip(q, 0, total - 1)]

    def recv_off(g):
        return sched_ref[1, g]

    def ld_dma(q):
        return pltpu.make_async_copy(acc.at[pl.ds(send_off(q), R)],
                                     ld.at[q % 2], ld_sem.at[q % 2])

    def stld_dma(g):
        return pltpu.make_async_copy(acc.at[pl.ds(recv_off(g), R)],
                                     st.at[g % 2], st_ld_sem.at[g % 2])

    def wb_dma(g):
        return pltpu.make_async_copy(st.at[g % 2],
                                     acc.at[pl.ds(recv_off(g), R)],
                                     wb_sem.at[g % 2])

    def rdma(g):
        slot = g % n_slots
        return pltpu.make_async_remote_copy(
            src_ref=send_pkt.at[slot], dst_ref=recv_pkt.at[slot],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)

    def encode_from_ld(q, _src=None):
        slot = q % n_slots
        for c in range(0, R, sub):   # sub-slice chunks, block-aligned
            mant, scale = _encode_rows(ld[q % 2, pl.ds(c, sub)], B,
                                       mantissa_bits, rounding)
            send_pkt[slot, pl.ds(c, sub)] = mant
            send_pkt[slot, pl.ds(R + c // B, sub // B)] = scale

    def chk_emit(q):
        # checksum the frame exactly as the RDMA will move it
        chk_ref[0] = chk_ref[0] + _emission_weight(q) \
            * _frame_checksum(send_pkt[q % n_slots])

    def chk_arrive(g):
        chk_ref[1] = chk_ref[1] + _emission_weight(g) \
            * _frame_checksum(recv_pkt[g % n_slots])

    def decode_slice(g):
        slot = g % n_slots
        for c in range(0, R, sub):
            dec = _decode_rows(recv_pkt[slot, pl.ds(c, sub)],
                               recv_pkt[slot, pl.ds(R + c // B, sub // B)],
                               B)
            st[g % 2, pl.ds(c, sub)] = st[g % 2, pl.ds(c, sub)] + dec

    # -- fused-optimizer streaming plumbing (opt_kind only): the owned
    # master/state slice of final-hop consume g cycles through a 2-deep
    # VMEM window per tensor (opt_buf[t]), with its own ld/wb DMA pairs.
    # Each tensor's HBM rows for consume g are touched by exactly one
    # (load, update, writeback) triple, so the only hazard is VMEM slot
    # reuse: ld(g) must not overwrite a buffer wb(g-2) still drains —
    # guarded at consume entry; the last two writebacks drain at exit.
    def opt_loc(g):
        return recv_off(g) - idx * chunk_rows

    def opt_ld_dma(t, g):
        return pltpu.make_async_copy(
            opt_out[t].at[pl.ds(opt_loc(g), R)], opt_buf.at[t, g % 2],
            opt_ld_sem.at[t * 2 + g % 2])

    def opt_wb_dma(t, g):
        return pltpu.make_async_copy(
            opt_buf.at[t, g % 2], opt_out[t].at[pl.ds(opt_loc(g), R)],
            opt_wb_sem.at[t * 2 + g % 2])

    def update_slice(g):
        # mean-gradient slice straight from the decode buffer; update in
        # place in the VMEM window (formula: optim.fused_apply_blocks)
        for c in range(0, R, sub):
            gblk = st[g % 2, pl.ds(c, sub)] / jnp.float32(n)
            wblk = opt_buf[0, g % 2, pl.ds(c, sub)]
            stblks = tuple(opt_buf[1 + i, g % 2, pl.ds(c, sub)]
                           for i in range(ns))
            w2, st2 = _optim.fused_apply_blocks(
                opt_kind, wblk, gblk, stblks, lambda i: hyper_ref[i])
            opt_buf[0, g % 2, pl.ds(c, sub)] = w2
            for i, sv in enumerate(st2):
                opt_buf[1 + i, g % 2, pl.ds(c, sub)] = sv

    def dma_start(chan, i):
        # the abstract DMA channels of `RsStreamEmitter`, bound to this
        # kernel's copy descriptors (ablation filters per channel class)
        if chan == "ld":
            if do_ld:
                ld_dma(i).start()
        elif chan == "st":
            if do_stld:
                stld_dma(i).start()
        elif chan == "wb":
            if do_wb:
                wb_dma(i).start()
        elif chan.startswith("optld"):
            if do_upd:
                opt_ld_dma(int(chan[5:]), i).start()
        elif chan.startswith("optwb"):
            if do_upd:
                opt_wb_dma(int(chan[5:]), i).start()
        else:
            raise AssertionError(chan)

    def dma_wait(chan, i):
        if chan == "ld":
            if do_ld:
                ld_dma(i).wait()
        elif chan == "st":
            if do_stld:
                stld_dma(i).wait()
        elif chan == "wb":
            if do_wb:
                wb_dma(i).wait()
        elif chan.startswith("optld"):
            if do_upd:
                opt_ld_dma(int(chan[5:]), i).wait()
        elif chan.startswith("optwb"):
            if do_upd:
                opt_wb_dma(int(chan[5:]), i).wait()
        else:
            raise AssertionError(chan)

    # The schedule — prologue pipe-fill, one-ahead prefetch gate,
    # launch/consume order, the single-wait writeback discipline, the
    # fused-opt state windows, every drain — is NOT written here: the
    # kernel consumes the shared emitter (`verify.opstream.
    # RsStreamEmitter`), the same object graftmc explores exhaustively
    # and `check_dma_discipline` audits statically, through the sink
    # below.  flow_control=False only under the discharge interpreter
    # (see _interp_args).
    emitter = _opstream.RsStreamEmitter(n, S, depth, opt_kind=opt_kind,
                                        integrity=do_chk,
                                        default_depth=_PIPE_DEPTH)
    assert (emitter.n_slots, emitter.launch_first) == \
        (n_slots, launch_first), (emitter.n_slots, n_slots)
    sink = _KernelSink(
        unrolled=unrolled, flow_control=flow_control, do_rdma=do_rdma,
        do_enc=do_enc, do_dec=do_dec, do_upd=do_upd, do_chk=do_chk,
        barrier=lambda: _neighbor_barrier(left, right),
        send=lambda q, src: rdma(q).start(),
        wait_send=lambda j: rdma(j).wait_send(),
        wait_recv=lambda g: rdma(g).wait_recv(),
        credit_wait=lambda: pltpu.semaphore_wait(credit_sem, 1),
        credit_signal=lambda: pltpu.semaphore_signal(
            credit_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL),
        credit_drain=lambda k: pltpu.semaphore_wait(credit_sem, k),
        encode=encode_from_ld, decode=decode_slice, update=update_slice,
        chk_emit=chk_emit, chk_arrive=chk_arrive,
        dma_start=dma_start, dma_wait=dma_wait)

    emitter.prologue(sink)
    if unrolled:
        for g in range(total):
            emitter.step(sink, g)
    else:
        def body(g, _):
            emitter.step(sink, g)
            return 0
        lax.fori_loop(0, total, body, 0)
    emitter.epilogue(sink)


@functools.partial(jax.jit, donate_argnums=(0,),
                   donate_argnames=("w2", "opt_st"), static_argnames=(
    "axis_name", "block_size", "mantissa_bits", "rounding", "slice_elems",
    "interpret", "collective_id", "loopback_n", "ablate", "depth",
    "opt_kind", "integrity"))
def _rs_stream_call(x2, axis_name: Optional[str], block_size: int,
                    mantissa_bits: int, rounding: str, slice_elems: int,
                    interpret: bool, collective_id: int,
                    loopback_n: Optional[int] = None,
                    ablate: Optional[str] = None,
                    depth: Optional[int] = None,
                    opt_kind: Optional[str] = None,
                    w2: Optional[jax.Array] = None,
                    opt_st: Tuple[jax.Array, ...] = (),
                    hyper: Optional[jax.Array] = None,
                    integrity: bool = False):
    n = loopback_n if axis_name is None else lax.axis_size(axis_name)
    L_rows = x2.shape[0]
    chunk_rows = L_rows // n
    R = slice_elems // LANES
    S = chunk_rows // R
    pkt_rows = _frame_rows(R, block_size)
    ids = _ring_ids(axis_name)
    sched = _rs_offsets(ids, n, S, R)
    D, n_slots, launch_first = _rs_plan(n, S, depth)
    _interp, _flow, _unrolled = _interp_args(interpret)
    kern = functools.partial(
        _rs_stream_kernel, n=n, n_slices=S, slice_rows=R,
        block_size=block_size, mantissa_bits=mantissa_bits,
        rounding=rounding, flow_control=_flow, unrolled=_unrolled,
        depth=D, n_slots=n_slots, launch_first=launch_first,
        ablate=ablate, opt_kind=opt_kind, integrity=integrity)
    vma = jax.typeof(x2).vma | jax.typeof(ids).vma
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    ns = 0 if opt_kind is None else OptimizerSpec(kind=opt_kind).n_state
    n_t = 1 + ns
    if opt_kind is None:
        out_shape = [sds((L_rows, LANES))]
        out_specs = [hbm]
        in_specs = [smem, smem, hbm]
        args = (ids, sched, x2)
        io_alias = {2: 0}
    else:
        assert w2 is not None and hyper is not None and len(opt_st) == ns
        out_shape = [sds((L_rows, LANES))] + [sds((chunk_rows, LANES))] * n_t
        out_specs = [hbm] * (1 + n_t)
        in_specs = [smem, smem, smem] + [hbm] * (1 + n_t)
        args = (ids, sched, hyper, x2, w2) + tuple(opt_st)
        io_alias = {3: 0, **{4 + i: 1 + i for i in range(n_t)}}
    if integrity:
        out_shape = out_shape \
            + [jax.ShapeDtypeStruct((2,), jnp.uint32, vma=vma)]
        out_specs = out_specs + [smem]
    res = pl.pallas_call(
        kern,
        out_shape=(out_shape[0] if len(out_shape) == 1 else out_shape),
        in_specs=in_specs,
        out_specs=(out_specs[0] if len(out_specs) == 1 else out_specs),
        input_output_aliases=io_alias,
        scratch_shapes=[
            pltpu.VMEM((2, R, LANES), jnp.float32),        # send loads
            pltpu.VMEM((2, R, LANES), jnp.float32),        # recv acc
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # send frames
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # recv frames
        ] + ([] if opt_kind is None else [
            pltpu.VMEM((n_t, 2, R, LANES), jnp.float32),   # w/state window
        ]) + [
            pltpu.SemaphoreType.DMA((2,)),                 # ld
            pltpu.SemaphoreType.DMA((2,)),                 # st load
            pltpu.SemaphoreType.DMA((2,)),                 # writeback
        ] + ([] if opt_kind is None else [
            pltpu.SemaphoreType.DMA((n_t * 2,)),           # state ld
            pltpu.SemaphoreType.DMA((n_t * 2,)),           # state wb
        ]) + [
            pltpu.SemaphoreType.DMA((n_slots,)),           # rdma send
            pltpu.SemaphoreType.DMA((n_slots,)),           # rdma recv
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp,
        **kernel("ring.rs_stream" if opt_kind is None
                 else "ring.rs_update_stream", opt=opt_kind, ablate=ablate),
    )(*args)
    chk = None
    if integrity:
        chk = (res[-1][0], res[-1][1])
        res = res[:-1] if opt_kind is not None else res[0]
    acc = res if opt_kind is None else res[0]
    # the owned chunk lives at rows [idx*chunk_rows, +chunk_rows) of the
    # accumulated (aliased) vector
    idx = jnp.int32(0) if axis_name is None else lax.axis_index(axis_name)
    g_own = lax.dynamic_slice_in_dim(acc, idx * chunk_rows, chunk_rows,
                                     axis=0)
    if opt_kind is None:
        return g_own if chk is None else (g_own, chk)
    if chk is None:
        return (g_own, res[1], tuple(res[2:]))
    return (g_own, res[1], tuple(res[2:]), chk)


def _ag_kernel(ids_ref, own_ref, out_ref, send_pkt, recv_pkt, send_sem,
               recv_sem, credit_sem, *, n: int, block_size: int,
               mantissa_bits: int, rounding: str, flow_control: bool,
               unrolled: bool):
    """Fused compressed ring all-gather: encode the owned chunk ONCE, then
    forward the received frame VERBATIM each hop (BFP roundtrip is
    idempotent, so every replica sees identical bytes — the semantics of
    ops.ring.ring_all_gather and the golden model), decoding each arrival
    while its onward RDMA is in flight.  This is the phase that
    distributes updated weights in the fused collective
    (hw/all_reduce.sv FORWARD_OUTPUT/OUTPUT_SEND:996-1086)."""
    idx = ids_ref[0]
    right = ids_ref[1]
    left = ids_ref[2]
    R = own_ref.shape[0]             # chunk rows
    SB = R // block_size

    def rdma(s, src):
        slot = s % 2
        return pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=recv_pkt.at[slot],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)

    if flow_control:
        _neighbor_barrier(left, right)

    mant, scale = _encode_rows(own_ref[:], block_size, mantissa_bits,
                               rounding)
    send_pkt[pl.ds(0, R)] = mant
    send_pkt[pl.ds(R, SB)] = scale
    # the local replica stores the same quantized values it sends
    out_ref[pl.ds(idx * R, R)] = _decode_rows(mant, scale, block_size)
    rdma(0, send_pkt).start()

    def hop(s):
        p = (s - 1) % 2
        rdma(s - 1, send_pkt).wait_recv()     # frame s-1 has landed

        @_when(s < n - 1, unrolled)
        def _forward():
            @_when(s == 2, unrolled)
            def _initial_send_drained():
                # forward hop 2 reuses send_sem[0], which the INITIAL
                # owned-chunk RDMA signaled; without this wait the later
                # _done_fwd could consume that stale signal and credit the
                # slot while the forward is still reading it (every other
                # same-slot predecessor is a forward already waited in its
                # own _done_fwd)
                rdma(0, send_pkt).wait_send()
            if flow_control:
                @_when(s >= 2, unrolled)
                def _credit():                # remote slot s%2 freed?
                    pltpu.semaphore_wait(credit_sem, 1)
            rdma(s, recv_pkt.at[p]).start()

        # decode while the forward RDMA is on the wire
        chunk = (idx - s) % n
        dec = _decode_rows(recv_pkt[p, pl.ds(0, R)],
                           recv_pkt[p, pl.ds(R, SB)], block_size)
        out_ref[pl.ds(chunk * R, R)] = dec
        @_when(s < n - 1, unrolled)
        def _done_fwd():
            # our recv slot p is the upstream's NEXT delivery target; it
            # must not be freed until the onward send has drained it
            rdma(s, recv_pkt.at[p]).wait_send()
        if flow_control:
            pltpu.semaphore_signal(credit_sem, inc=1, device_id=left,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)

    if unrolled:
        for s in range(1, n):
            hop(s)
    else:
        def body(s, _):
            hop(s)
            return 0
        lax.fori_loop(1, n, body, 0)
    if n <= 3:
        # rings without a forward at hop 2 never consumed the initial
        # send's semaphore in _initial_send_drained — drain it here
        rdma(0, send_pkt).wait_send()
    if flow_control:
        pltpu.semaphore_wait(credit_sem, 2 if n > 2 else 1)


@functools.partial(jax.jit, static_argnames=(
    "axis_name", "block_size", "mantissa_bits", "rounding", "interpret",
    "collective_id", "loopback_n"))
def _ag_call(own2, axis_name: Optional[str], block_size: int,
             mantissa_bits: int, rounding: str, interpret: bool,
             collective_id: int, loopback_n: Optional[int] = None):
    n = loopback_n if axis_name is None else lax.axis_size(axis_name)
    R = own2.shape[0]
    pkt_rows = _frame_rows(R, block_size)
    ids = _ring_ids(axis_name)
    _interp, _flow, _unrolled = _interp_args(interpret)
    kern = functools.partial(
        _ag_kernel, n=n, block_size=block_size,
        mantissa_bits=mantissa_bits, rounding=rounding,
        flow_control=_flow, unrolled=_unrolled)
    vma = jax.typeof(own2).vma | jax.typeof(ids).vma
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n * R, LANES), jnp.float32,
                                       vma=vma),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((pkt_rows, LANES), jnp.int8),       # own frame
            pltpu.VMEM((2, pkt_rows, LANES), jnp.int8),    # recv frames
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp,
        **kernel("ring.ag"),
    )(ids, own2)


# THE interleaved emission schedule of the streaming gather — moved to
# the shared protocol IR (P1/P2/P3 asserted per (n, S) there; the
# exhaustive graftmc exploration of the full wait/credit protocol over
# this schedule is what retired the "statically asserted" ledger row,
# and what caught the fwd/own emission-index inversion whose one-credit
# under-wait the static sweep could not see).  tests/test_verify.py
# pins the delegation by identity.
_ag_schedule = _opstream.ag_schedule


class _SmemAgSchedule:
    """The rolled (hardware) path's schedule accessor: the same
    `ag_schedule` tables as `verify.opstream.AgSchedule`, read per
    decision from the kernel's SMEM copy (in-kernel jnp table constants
    are rejected by the Mosaic compiler).  Rows: 0 content, 1 fwd_j,
    2 own_at, 3 own-mask, 4 own_j — built in `_ag_stream_call` from the
    emitter's python tables."""

    def __init__(self, sched_ref, total):
        self._s = sched_ref
        self._total = total

    def fwd_j(self, m):
        return self._s[1, m]

    def own_at(self, m):
        return self._s[2, m]

    def own_j(self, k):
        return self._s[4, k]

    def is_own_j(self, j):
        return (j >= 0) & (self._s[3, jnp.clip(j, 0, self._total - 1)] == 1)


def _ag_stream_kernel(ids_ref, sched_ref, own_hbm, out_hbm, ld, own_st, st,
                      send_pkt, recv_pkt, ld_sem, own_wb_sem, wb_sem,
                      send_sem, recv_sem, credit_sem, *, n: int,
                      n_slices: int, n_slots: int, slice_rows: int,
                      block_size: int, mantissa_bits: int, rounding: str,
                      flow_control: bool, unrolled: bool, emitter):
    """HBM-streaming fused ring all-gather, interleaved emission order.

    Loop index m = arrival order (== upstream's emission order; wire slots
    and semaphores cycle by emission index j % n_slots on BOTH ends).
    Per m: consume arrival content(m) — wait recv, start the onward
    forward (emission j_fwd), decode into a VMEM slice, write back to the
    out vector in HBM — then emit the next own-slice send if this content
    step schedules one.  Single-wait semaphore discipline:

      send j:  forwards wait their own send right before crediting the
               recv slot; own sends are waited by the next same-slot
               emitter (pre-wait when j - n_slots is an own),
               tail-drained statically.
      wb m:    one-iteration-lag head wait + final drain.
      own_wb:  guarded at own_st slot reuse + tail drain.
      credit:  wait one before any send with j >= n_slots; signal per
               consume.

    Slot window: n_slots = S + 2 (capped at total).  The own phase emits
    two frames per consume step, so an emission index can lead its step
    by up to S (_ag_schedule property P2); S + 2 covers the lead with one
    slot of margin, which makes slot reuse safe in BOTH execution
    models — the interpreter's lockstep program order (overwrite of slot
    j % n_slots comes after the decode of arrival j - n_slots) and
    hardware's credit window (emission j waits a credit its downstream
    released at consume j - n_slots, a strictly earlier step by P2, so
    the wait-for graph is acyclic for arbitrary S and n — the proof is
    in _ag_schedule's docstring).
    """
    idx = ids_ref[0]
    right = ids_ref[1]
    left = ids_ref[2]
    S = n_slices
    R = slice_rows
    SB = R // block_size
    chunk_rows = S * R
    total = (n - 1) * S                 # arrivals == emissions

    def wslot(x):
        return x % n_slots

    # the static schedule arrives twice: as the emitter's python tables
    # (compile-time — drives the unrolled interpreter schedule and the
    # static tail-drain list) and as the sched_ref SMEM input (runtime —
    # the rolled hardware schedule reads it; in-kernel jnp table
    # constants are rejected by the Mosaic compiler: "kernel captures
    # constants ... pass them as inputs").  Both views read the SAME
    # `ag_schedule` tables; `_SmemAgSchedule` is only a reading style.
    if unrolled:
        acc_sched = emitter.sched

        def content(m):
            return emitter.sched.content_t[m]
    else:
        acc_sched = _SmemAgSchedule(sched_ref, total)

        def content(m):
            return sched_ref[0, m]

    def out_rdma(j, src):
        slot = wslot(j)
        return pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=recv_pkt.at[slot],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
            device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)

    def send(j, src):
        # src=None: own emission out of its send_pkt slot; src=m: the
        # onward forward straight out of arrival m's recv slot
        buf = send_pkt.at[wslot(j)] if src is None \
            else recv_pkt.at[wslot(src)]
        out_rdma(j, buf).start()

    def wait_send(j):
        # wait_send consumes emission j's send sem; frame shapes are
        # uniform, so any same-shape src is a valid descriptor
        out_rdma(j, send_pkt.at[wslot(j)]).wait_send()

    def wait_recv(m):
        out_rdma(m, send_pkt.at[wslot(m)]).wait_recv()

    def ld_dma(k):
        return pltpu.make_async_copy(
            own_hbm.at[pl.ds(k * R, R)], ld.at[k % 2], ld_sem.at[k % 2])

    def own_wb_dma(k):
        return pltpu.make_async_copy(
            own_st.at[k % 2],
            out_hbm.at[pl.ds(idx * chunk_rows + k * R, R)],
            own_wb_sem.at[k % 2])

    def wb_dma(m):
        t = content(m)
        s, k = t // S + 1, t % S
        off = ((idx - s) % n) * chunk_rows + k * R
        return pltpu.make_async_copy(st.at[m % 2],
                                     out_hbm.at[pl.ds(off, R)],
                                     wb_sem.at[m % 2])

    # mant/scale flow from the encode op to the own-store op of the SAME
    # send_own block (one `when` region — the emitter keeps them
    # adjacent), stashed here between the two sink calls
    last_enc = [None]

    def encode_own(j, k):
        """Encode own slice k into emission j's frame slot (the replica
        stores its own wire bytes — `own_store` below decodes the stash
        so every replica sees wire-identical values)."""
        mant, scale = _encode_rows(ld[k % 2], block_size, mantissa_bits,
                                   rounding)
        slot = wslot(j)
        send_pkt[slot, pl.ds(0, R)] = mant
        send_pkt[slot, pl.ds(R, SB)] = scale
        last_enc[0] = (mant, scale)

    def local_op(name, *args):
        assert name == "own_store", name
        k = args[0]
        mant, scale = last_enc[0]
        own_st[k % 2] = _decode_rows(mant, scale, block_size)

    def decode_arrival(m):
        # dst slot is the LOCAL st pipeline's (depth 2, cycled by
        # arrival index, drained by wb_dma(m) which reads st[m % 2]);
        # only the SRC uses the wire slot — conflating the two was a
        # real out-of-bounds bug the moment the wire window grew past
        # the st depth
        slot = wslot(m)
        st[m % 2] = _decode_rows(recv_pkt[slot, pl.ds(0, R)],
                                 recv_pkt[slot, pl.ds(R, SB)],
                                 block_size)

    def dma_start(chan, i):
        {"ld": lambda: ld_dma(i).start(),
         "ownwb": lambda: own_wb_dma(i).start(),
         "wb": lambda: wb_dma(i).start()}[chan]()

    def dma_wait(chan, i):
        {"ld": lambda: ld_dma(i).wait(),
         "ownwb": lambda: own_wb_dma(i).wait(),
         "wb": lambda: wb_dma(i).wait()}[chan]()

    # The schedule — the interleaved emission order, pre-wait rule,
    # credit placement, st/ownwb windows, tail drains — is NOT written
    # here: the kernel consumes the shared emitter
    # (`verify.opstream.AgStreamEmitter`), the same object graftmc
    # explores exhaustively with asynchronous landings (lockstep=True
    # is the interpreter primitive-lockstep ordering: all reads before
    # any same-step emission; hardware keeps forward-then-decode for
    # overlap, its slot occupancy credit-protected).
    sink = _KernelSink(
        unrolled=unrolled, flow_control=flow_control,
        barrier=lambda: _neighbor_barrier(left, right),
        send=send, wait_send=wait_send, wait_recv=wait_recv,
        credit_wait=lambda: pltpu.semaphore_wait(credit_sem, 1),
        credit_signal=lambda: pltpu.semaphore_signal(
            credit_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL),
        credit_drain=lambda k: pltpu.semaphore_wait(credit_sem, k),
        encode=lambda j, k: encode_own(j, k), decode=decode_arrival,
        dma_start=dma_start, dma_wait=dma_wait, local=local_op)

    emitter.prologue(sink, acc_sched)
    if unrolled:
        for m in range(total):
            emitter.step(sink, m, acc_sched, lockstep=True)
    else:
        def body(m, _):
            emitter.step(sink, m, acc_sched, lockstep=False)
            return 0
        lax.fori_loop(0, total, body, 0)
    emitter.epilogue(sink)


@functools.partial(jax.jit, static_argnames=(
    "axis_name", "block_size", "mantissa_bits", "rounding", "slice_elems",
    "interpret", "collective_id", "loopback_n"))
def _ag_stream_call(own2, axis_name: Optional[str], block_size: int,
                    mantissa_bits: int, rounding: str, slice_elems: int,
                    interpret: bool, collective_id: int,
                    loopback_n: Optional[int] = None):
    n = loopback_n if axis_name is None else lax.axis_size(axis_name)
    C_rows = own2.shape[0]
    R = slice_elems // LANES
    S = C_rows // R
    pkt_rows = _frame_rows(R, block_size)
    ids = _ring_ids(axis_name)
    # slot window sized to the slice plan: covers the own phase's maximum
    # emission lead (== S, ag_schedule P2) with one slot of margin — THE
    # rule lives in the IR (opstream.ag_n_slots), next to the emitter
    # graftmc explores
    n_slots = _opstream.ag_n_slots(n, S)
    _interp, _flow, _unrolled = _interp_args(interpret)
    emitter = _opstream.AgStreamEmitter(n, S)
    assert emitter.n_slots == n_slots, (emitter.n_slots, n_slots)
    sc = emitter.sched
    total = (n - 1) * S
    # SMEM copy of the emitter's schedule for the rolled (hardware)
    # path; rows: content / fwd_j / own_at / own-mask / own_j (padded
    # with -1) — read back through _SmemAgSchedule
    import numpy as np
    sched_np = np.full((5, total), -1, np.int32)
    sched_np[0] = sc.content_t
    sched_np[1] = sc.fwd_j_t
    sched_np[2] = sc.own_at_t
    sched_np[3] = [1 if j in sc.own_js else 0 for j in range(total)]
    sched_np[4, :S] = sc.own_j_t
    sched = jnp.asarray(sched_np)
    kern = functools.partial(
        _ag_stream_kernel, n=n, n_slices=S, n_slots=n_slots, slice_rows=R,
        block_size=block_size, mantissa_bits=mantissa_bits,
        rounding=rounding, flow_control=_flow, unrolled=_unrolled,
        emitter=emitter)
    vma = jax.typeof(own2).vma | jax.typeof(ids).vma
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n * C_rows, LANES), jnp.float32,
                                       vma=vma),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, R, LANES), jnp.float32),        # own loads
            pltpu.VMEM((2, R, LANES), jnp.float32),        # own decode
            pltpu.VMEM((2, R, LANES), jnp.float32),        # recv decode
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # own frames
            pltpu.VMEM((n_slots, pkt_rows, LANES), jnp.int8),  # recv frames
            pltpu.SemaphoreType.DMA((2,)),                 # ld
            pltpu.SemaphoreType.DMA((2,)),                 # own wb
            pltpu.SemaphoreType.DMA((2,)),                 # recv wb
            pltpu.SemaphoreType.DMA((n_slots,)),           # rdma send
            pltpu.SemaphoreType.DMA((n_slots,)),           # rdma recv
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=_interp,
        **kernel("ring.ag_stream"),
    )(ids, sched, own2)


# Frame VMEM for the streaming gather is ~2 * (S+2)/S * (FR/(R*4)) bytes
# per chunk f32 element (send + recv windows), where FR = _frame_rows(R, B)
# includes the 8-row tile padding — 72/68 of the live 17/16 rate at the
# default R=64 plan, but up to 24/17 (~1.4x) at R=16.  Larger chunks are
# gathered in sequential segments of at most this many elements (each
# segment is an independent all-gather — BFP blocks never straddle a
# segment boundary).
_AG_STREAM_MAX_CHUNK_ELEMS = 2 << 20      # ~4.5 MiB frame VMEM per segment


def ag_stream_segments(C: int, slice_elems: int,
                       block_size: int) -> list:
    """[(offset, size, slice_elems)] — how the streaming gather cuts an
    owned chunk of C elements into independent sequential gathers.  Two
    budgets bound a segment: its frames must fit VMEM
    (_AG_STREAM_MAX_CHUNK_ELEMS) and its slot window must fit the chip's
    semaphore memory (`opstream.AG_MAX_SLICES` — at the default 8192-element
    slice that is the binding one).  Every cut falls on a (block, 128)
    tile, so the bytes equal the whole-chunk gather's.

    Body segments use the full slice; what is left past the last whole
    slice (fewer than slice_elems/tile tiles) is one short segment with
    its own slice plan, so no chunk length can force a long plan of tiny
    slices."""
    tile = block_size * LANES
    assert C % tile == 0, (C, tile)
    slice_e = max(tile, slice_elems - slice_elems % tile)
    seg = slice_e * max(1, min(_opstream.AG_MAX_SLICES,
                               _AG_STREAM_MAX_CHUNK_ELEMS // slice_e))
    body = C - C % slice_e
    segs = [(off, min(seg, body - off), slice_e)
            for off in range(0, body, seg)]
    if C > body:
        segs.append((body, C - body,
                     pick_slice_elems(C - body, slice_e, block_size)))
    return segs


def _ag_stream_segmented(owned: jax.Array, axis_name: Optional[str],
                         cfg: BFPConfig, slice_elems: int, interpret,
                         collective_id: int,
                         loopback_n: Optional[int] = None) -> jax.Array:
    """Streaming gather of an owned chunk [C] -> [n*C], one
    `_ag_stream_call` per `ag_stream_segments` entry."""
    C = owned.shape[0]
    x = owned.astype(jnp.float32)
    outs = [_ag_stream_call(x[off:off + sz].reshape(-1, LANES), axis_name,
                            cfg.block_size, cfg.mantissa_bits, cfg.rounding,
                            slice_e, interpret, collective_id,
                            loopback_n=loopback_n).reshape(-1, sz)
            for off, sz, slice_e in ag_stream_segments(
                C, slice_elems, cfg.block_size)]
    return jnp.concatenate(outs, axis=1).reshape(-1)


def ring_all_gather_fused(owned: jax.Array, axis_name: str, *,
                          compression: Optional[BFPConfig] = None,
                          slice_elems: int = 8192,
                          streaming: Optional[bool] = None,
                          interpret: Optional[bool] = None,
                          collective_id: int = 8) -> jax.Array:
    """Fused compressed ring all-gather of an owned chunk [C] -> [n*C].
    Bit-identical to ops.ring.ring_all_gather with codec="pallas" (the
    streaming kernel slices the chunk, but frames forward verbatim and
    blocks align to slice boundaries, so the bytes are unchanged).

    Routing: payloads whose gathered output fits the VMEM-resident budget
    (~4 MiB) use the whole-chunk resident kernel; larger payloads default
    to the HBM-streaming interleaved-emission kernel (slot window S + 2,
    deadlock-free for arbitrary slice plans — _ag_schedule P1/P2), gathered
    in sequential segments past the frame-VMEM or semaphore budget
    (`ag_stream_segments`).  streaming=False opts out to the separate-op
    XLA ring with the identical codec."""
    cfg = compression or BFPConfig()
    n = lax.axis_size(axis_name)
    C = owned.shape[0]
    if interpret is None:
        interpret = not _is_tpu()
    if C % (cfg.block_size * LANES):
        raise ValueError(
            f"fused ring gather needs chunk {C} % "
            f"{cfg.block_size * LANES} == 0")
    if n == 1:
        # quantize roundtrip via the same lane-layout codec kernels
        # (matches ops.ring's n==1 semantics: replicas see wire bytes);
        # inline entries — a nested jitted closed_call trips the vma
        # checker inside checked shard_maps
        from . import bfp_pallas
        mant, se = bfp_pallas.bfp_encode_inline(
            owned.astype(jnp.float32), cfg.block_size, cfg.mantissa_bits,
            cfg.rounding, interpret=interpret)
        return bfp_pallas.bfp_decode_inline(mant, se, cfg.block_size,
                                            owned.dtype,
                                            interpret=interpret)
    big = n * C * 4 > _VMEM_RESIDENT_MAX_BYTES
    if streaming is None:
        streaming = big
    if not streaming:
        if big:
            # explicit opt-out from the streaming kernel: the separate-op
            # ring with the SAME lane-layout codec — bit-identical bytes,
            # HBM-resident via XLA
            import dataclasses
            from . import ring as _ring_ops
            return _ring_ops.ring_all_gather(
                owned, axis_name,
                compression=dataclasses.replace(cfg, codec="pallas"))
        x2 = owned.astype(jnp.float32).reshape(-1, LANES)
        out = _ag_call(x2, axis_name, cfg.block_size, cfg.mantissa_bits,
                       cfg.rounding, interpret, collective_id)
        return out.reshape(n * C)

    return _ag_stream_segmented(owned, axis_name, cfg, slice_elems,
                                interpret, collective_id)


def ring_reduce_scatter_update_fused(
        x: jax.Array, w_own: jax.Array, opt_state, hyper: jax.Array,
        axis_name: str, *, opt_kind: str,
        compression: Optional[BFPConfig] = None,
        slice_elems: int = 8192, streaming: Optional[bool] = None,
        interpret: Optional[bool] = None,
        pipeline_depth: Optional[int] = None, collective_id: int = 9,
        integrity: bool = False):
    """Fused ring reduce-scatter + in-kernel ZeRO-1 optimizer update —
    the reference's defining datapath (decode feeds weight_update.sv with
    no host round-trip, SURVEY.md §3.2) plus ZeRO-1 weight-update
    sharding: each replica's owned slice of params + optimizer state
    updates AS its final-hop decode retires, inside the same depth-D
    pipelined kernel, so the optimizer costs zero exposed time.

    x:        flat f32 [L] local gradients (the collective input)
    w_own:    [L/n] owned f32 master shard (DONATED: updated in place)
    opt_state: dict of [L/n] f32 shards per OptimizerSpec(kind).state_keys
              (DONATED)
    hyper:    optim.fused_hyperparams(cfg, step) scalar vector — SMEM
              operand, so lr/schedule/weight-decay changes never recompile

    Returns ``(g_own_sum [L/n], w_new [L/n], new_state dict)`` —
    g_own_sum is the raw reduced SUM, bit-identical to
    ring_reduce_scatter_fused at every pipeline depth; the update formula
    is optim.fused_apply_blocks (bit spec: optim.golden_fused_apply
    composed with the codec's golden ring decode).  Same slicing/
    residency constraints and routing as ring_reduce_scatter_fused.

    integrity=True appends a replicated ``wire_ok`` bool: the SAME
    in-kernel frame-checksum accumulation as ring_reduce_scatter_fused
    (every emission at encode, every arrival at wait_recv), psum'd into
    the conservation verdict outside the kernel.  This is what lifts the
    old ``fused_optimizer x integrity_check`` construction error: the
    update consumed DONATED state, so nothing is left to gate a tripped
    verdict back to in-graph — instead the verdict invalidates the STEP
    (runtime.chaos.check_step_diag raises WireIntegrityError and the
    elastic restore/reshard ladder discards the poisoned state).  The
    gradient/update bits are identical to integrity=False at every depth
    (checksums only READ the frames) and the RDMA'd bytes are
    unchanged."""
    cfg = compression or BFPConfig()
    spec = OptimizerSpec(kind=opt_kind)
    n = lax.axis_size(axis_name)
    L = x.shape[0]
    if interpret is None:
        interpret = not _is_tpu()
    assert L % n == 0, (L, n)
    C = L // n
    assert n >= 2, "n == 1 is routed by ops.fused_update (no wire)"
    if C % slice_elems or slice_elems % (cfg.block_size * LANES):
        raise ValueError(
            f"fused ring needs chunk {C} % slice_elems {slice_elems} == 0 "
            f"and slice_elems % {cfg.block_size * LANES} == 0")
    if streaming is None:
        streaming = L * 4 > _VMEM_RESIDENT_MAX_BYTES
    x2 = x.astype(jnp.float32).reshape(-1, LANES)
    w2 = w_own.astype(jnp.float32).reshape(-1, LANES)
    st = tuple(opt_state[k].astype(jnp.float32).reshape(-1, LANES)
               for k in spec.state_keys)
    call = _rs_stream_call if streaming else _rs_call
    res = call(x2, axis_name, cfg.block_size, cfg.mantissa_bits,
               cfg.rounding, slice_elems, interpret,
               collective_id, depth=pipeline_depth,
               opt_kind=opt_kind, w2=w2, opt_st=st, hyper=hyper,
               integrity=integrity)
    if integrity:
        g2, w_new2, st2, (sa, ra) = res
    else:
        g2, w_new2, st2 = res
    out = (g2.reshape(C), w_new2.reshape(C),
           {k: v.reshape(C) for k, v in zip(spec.state_keys, st2)})
    if not integrity:
        return out
    from . import integrity as _integrity
    return out + (_integrity.conservation_ok(sa, ra, axis_name),)


def ring_all_reduce_fused(x: jax.Array, axis_name: str, *,
                          compression: Optional[BFPConfig] = None,
                          slice_elems: int = 8192,
                          interpret: Optional[bool] = None,
                          pipeline_depth: Optional[int] = None) -> jax.Array:
    """Fused all-reduce = fused reduce-scatter + fused all-gather."""
    owned = ring_reduce_scatter_fused(x, axis_name,
                                      compression=compression,
                                      slice_elems=slice_elems,
                                      interpret=interpret,
                                      pipeline_depth=pipeline_depth)
    return ring_all_gather_fused(owned, axis_name, compression=compression,
                                 interpret=interpret)


def pick_slice_elems(C: int, target: int, block_size: int) -> int:
    """Largest divisor of chunk C that is a multiple of block_size*LANES
    and <= target — the fused kernel's slice plan for arbitrary
    (padded-to-tile) payloads.  Slicing at block boundaries never changes
    the block partition, so this is a schedule choice, not a numerics
    choice."""
    tile = block_size * LANES
    assert C % tile == 0, (C, tile)
    k = C // tile
    best = 1
    d = 1
    while d * d <= k:
        if k % d == 0:
            for c in (d, k // d):
                if c * tile <= target and c > best:
                    best = c
        d += 1
    return best * tile


def _rs_op_stream(n: int, S: int, depth: Optional[int]):
    """The per-node op stream of the deep-pipelined RS schedule, as data —
    the exact wait/signal/transfer order _rs_kernel executes (every node
    runs the identical program).  A delegate to the shared protocol IR
    (`verify.opstream.rs_op_stream`), so the randomized simulator below,
    the exhaustive model checker (`make modelcheck`) and this kernel's
    schedule all derive from ONE definition."""
    from ..verify import opstream as _opstream
    return _opstream.rs_op_stream(n, S, depth, default_depth=_PIPE_DEPTH)


def simulate_rs_protocol(n: int, S: int, depth: Optional[int] = None,
                         seed: int = 0, max_events: int = 2_000_000) -> int:
    """Race/deadlock check of the credit protocol at model level: execute
    the RS op stream on n simulated nodes under a randomized scheduler
    with BLOCKING semaphores and asynchronous wire transfers (a started
    RDMA lands at an arbitrary later scheduler event, exactly the freedom
    real hardware has).  Fails on

      - deadlock: no node can advance and no transfer is in flight;
      - recv-slot overwrite: a frame lands in a slot whose previous frame
        is not yet decoded (the credit window's whole job);
      - send-slot overwrite: a node encodes into a slot whose previous
        transfer has not drained (wait_send's whole job);
      - ordering corruption: a decode finds a different emission than the
        schedule expects.

    Returns the number of scheduler events on success.  This is now the
    RANDOMIZED mode of the graftmc protocol checker (`verify.mc`): the
    op stream and the small-step semantics are the shared definitions
    the exhaustive checker explores completely for n <= 6, S <= 6,
    D <= 4 (`make modelcheck`); this entry point remains the seed-sweep
    fuzz beyond that envelope (n = 8 here: the threaded TPU interpreter
    needs a jaxlib newer than this one AND convoys on 1 core at n = 8 —
    the model checks the same wait-for graph without either limit)."""
    from ..verify import mc as _mc
    from ..verify import opstream as _opstream
    ops, n_slots = _rs_op_stream(n, S, depth)
    model = _opstream.RingModel(
        n, ops, n_slots,
        meta={"n": n, "S": S, "depth": depth, "seed": seed})
    # legacy fuzz semantics: no credit-bound assert and no at-exit
    # strictness (the exhaustive checker owns boundedness/leaks; a
    # mutated stream under this entry point must keep failing with the
    # overwrite/deadlock wording its callers match on)
    model.credit_bound = len(ops)
    model.strict_terminal = False
    return _mc.run_random(model, seed=seed, max_events=max_events)


def flow_control_selftest(n: int = 8, *, streaming: bool = False,
                          rng_seed: int = 0) -> None:
    """The REAL credit protocol at ring size n under the threaded TPU
    interpreter, with the codec ablated away (ablate="rdma": tiny VPU
    work, full barrier + credit + RDMA path) — the convoy-beating shape
    the round-5 diagnosis prescribed: one (16,128)-tile slice per chunk
    keeps every interpreter buffer-init copy small, so the 1-core
    allocation convoy that parked n=8 for 500+ s never forms.  With
    encode/decode compiled out the accumulator is untouched, so the
    result is exact: each device returns its own input chunk.  Raises on
    deadlock (test timeout), data race (interpreter detector), or
    mismatch."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    cfg = BFPConfig()
    C = cfg.block_size * LANES            # one native tile per chunk
    L = n * C
    x = jnp.asarray(np.random.default_rng(rng_seed).standard_normal(
        (n, L)), jnp.float32)
    call = _rs_stream_call if streaming else _rs_call
    mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))

    def rs(v):
        v2 = v.astype(jnp.float32).reshape(-1, LANES)
        out = call(v2, "dp", cfg.block_size, cfg.mantissa_bits,
                   cfg.rounding, C, "threaded", 7, ablate="rdma")
        return out.reshape(-1)

    got = jax.jit(jax.shard_map(rs, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp"),
                                check_vma=False))(x.reshape(-1))
    # ablate="rdma" never touches the accumulator: device i's owned chunk
    # is its own input rows [i*C, (i+1)*C) of the per-device vector
    want = np.stack([np.asarray(x[i, i * C:(i + 1) * C]) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(got).reshape(n, C), want)


def _loopback_shmap(fn, arg):
    """Run a self-addressed kernel call under a 1-device shard_map — the
    LOGICAL device-id space needs a mesh axis to resolve against, even
    for self-addressed copies."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:1]), ("lb",))
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)(arg)


def loopback_microbench(x: jax.Array, virtual_n: int = 4, *,
                        compression: Optional[BFPConfig] = None,
                        slice_elems: int = 8192,
                        streaming: bool = False,
                        interpret: Optional[bool] = None,
                        pipeline_depth: Optional[int] = None,
                        ablate: Optional[str] = None) -> jax.Array:
    """Single-chip exercise of the fused reduce-scatter pipeline: the same
    kernel with every RDMA addressed to this device (virtual ring of
    `virtual_n`); streaming=True runs the HBM-streaming variant.

    The numerics are a self-accumulation (not a real reduce-scatter), but
    the DATAFLOW — encode slice g+1 on the VPU while slice g's DMA is in
    flight, decode+accumulate on arrival, credit flow control — is
    identical, so its sustained GB/s bounds the compressed ring's per-hop
    rate on real multi-chip ICI (where the DMA engine drives the
    interconnect instead of a local loopback).  This exists because the
    bench surface has ONE chip (BASELINE.md); the multi-chip bit-exactness
    story runs on the CPU interpreter (tests/test_ring_pallas.py).
    """
    cfg = compression or BFPConfig()
    if interpret is None:
        interpret = not _is_tpu()
    L = x.shape[0]
    assert L % virtual_n == 0, (L, virtual_n)
    C = L // virtual_n
    if C % slice_elems or slice_elems % (cfg.block_size * LANES):
        raise ValueError((C, slice_elems, cfg.block_size * LANES))
    x2 = x.astype(jnp.float32).reshape(-1, LANES)
    if ablate == "hbm" and not streaming:
        raise ValueError("'hbm' ablates the streaming kernel's slice "
                         "load/store stages; the resident kernel has none")
    call = _rs_stream_call if streaming else _rs_call
    out = _loopback_shmap(
        lambda v: call(v, None, cfg.block_size, cfg.mantissa_bits,
                       cfg.rounding, slice_elems, interpret, 7,
                       loopback_n=virtual_n, ablate=ablate,
                       depth=pipeline_depth), x2)
    return out.reshape(C)


def loopback_update_microbench(x: jax.Array, virtual_n: int = 4, *,
                               opt_kind: str = "adamw",
                               hyper: Optional[jax.Array] = None,
                               compression: Optional[BFPConfig] = None,
                               slice_elems: int = 8192,
                               streaming: bool = False,
                               interpret: Optional[bool] = None,
                               pipeline_depth: Optional[int] = None,
                               ablate: Optional[str] = None) -> jax.Array:
    """Single-chip exercise of the fused reduce-scatter + IN-KERNEL
    optimizer pipeline (`loopback_microbench` with opt_kind): the same
    self-addressed virtual ring, plus chunk-sized master/state shards
    updated on the final-hop decodes.  Returns the updated w chunk
    (consuming any output runs the whole opaque kernel, so O(1)
    consumption is exact for slope timing).  ablate adds "update" — the
    optimizer stage alone on the same schedule — feeding ring_cost's
    fused-optimizer decomposition."""
    cfg = compression or BFPConfig()
    spec = OptimizerSpec(kind=opt_kind)
    if interpret is None:
        interpret = not _is_tpu()
    L = x.shape[0]
    assert L % virtual_n == 0, (L, virtual_n)
    C = L // virtual_n
    if C % slice_elems or slice_elems % (cfg.block_size * LANES):
        raise ValueError((C, slice_elems, cfg.block_size * LANES))
    if hyper is None:
        from ..utils.config import OptimizerConfig
        hyper = _optim.fused_hyperparams(
            OptimizerConfig(kind=opt_kind, learning_rate=1e-3),
            jnp.zeros((), jnp.int32))
    x2 = x.astype(jnp.float32).reshape(-1, LANES)
    w2 = jnp.zeros((C // LANES, LANES), jnp.float32)
    st = tuple(jnp.zeros((C // LANES, LANES), jnp.float32)
               for _ in spec.state_keys)
    call = _rs_stream_call if streaming else _rs_call
    if ablate == "hbm" and not streaming:
        raise ValueError("'hbm' ablates the streaming kernel's slice "
                         "load/store stages; the resident kernel has none")

    def run(v):
        res = call(v, None, cfg.block_size, cfg.mantissa_bits,
                   cfg.rounding, slice_elems, interpret, 9,
                   loopback_n=virtual_n, ablate=ablate,
                   depth=pipeline_depth, opt_kind=opt_kind,
                   w2=w2, opt_st=st, hyper=hyper)
        return res[1]
    return _loopback_shmap(run, x2).reshape(C)


def loopback_gather_microbench(owned: jax.Array, virtual_n: int = 4, *,
                               compression: Optional[BFPConfig] = None,
                               slice_elems: int = 8192,
                               streaming: bool = False,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Single-chip exercise of the fused all-gather pipeline (resident or
    streaming), self-addressed like `loopback_microbench` — on one chip a
    node's arrival stream is its own emission stream, so the interleaved
    schedule, slot window, credits, and the encode/forward/decode overlap
    all execute exactly as on a real ring.  Output is [virtual_n * C]
    (deterministic; not a real gather)."""
    cfg = compression or BFPConfig()
    if interpret is None:
        interpret = not _is_tpu()
    C = owned.shape[0]
    if C % slice_elems or slice_elems % (cfg.block_size * LANES):
        raise ValueError((C, slice_elems, cfg.block_size * LANES))
    if streaming:
        return _loopback_shmap(
            lambda v: _ag_stream_segmented(v, None, cfg, slice_elems,
                                           interpret, 8,
                                           loopback_n=virtual_n), owned)
    x2 = owned.astype(jnp.float32).reshape(-1, LANES)
    out = _loopback_shmap(
        lambda v: _ag_call(v, None, cfg.block_size, cfg.mantissa_bits,
                           cfg.rounding, interpret, 8,
                           loopback_n=virtual_n), x2)
    return out.reshape(virtual_n * C)
