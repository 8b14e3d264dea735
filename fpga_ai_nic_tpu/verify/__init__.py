"""graftmc — exhaustive protocol model checking for the collectives.

The repo now carries SIX hand-built wire protocols (the reference
carried one, hw/all_reduce.sv): the depth-D flat ring reduce-scatter,
the HBM-streaming variant with its slice-prefetch DMA windows and the
fused-optimizer w/m/v state window, the streaming all-gather's
interleaved emission schedule, the hierarchical intra x inter two-hop
schedule (ops.ring_hier), the reshard single-pair ppermute program
(parallel.reshard), and the serving KV-handoff pair program
(serve.handoff).  Until this package the strongest protocol evidence
was a *randomized* interleaving simulator
(`ops.ring_pallas.simulate_rs_protocol`) — a fuzzer, not a proof.

graftmc closes that gap in three layers (docs/MODELCHECK.md):

  opstream   ONE op-stream EMITTER per protocol — consumed by the real
             kernels/lowerings for their schedule AND by the checker
             for its stream, so transcription drift is structurally
             impossible — plus the small-step execution models
             (`RingModel`, `PairModel`), a static DMA single-wait/RAW
             discipline check, and the M2 static checksum-weight pass
             (paired odd program-distinct conservation weights: the
             PR-12 collision class as a tool).
  mc         an exhaustive explicit-state checker with state hashing
             and a persistent-set/sleep-style partial-order reduction
             over commuting wire-landing events; checks deadlock
             freedom, recv/send-slot overwrite, decode ordering, credit
             non-negativity/boundedness and termination across the
             (route x n x S x depth) grid — exhaustive for n<=6, S<=6,
             D<=4 per route (integrity variants included), randomized
             seed-sweep fuzz beyond.  The randomized mode IS
             `simulate_rs_protocol`'s backend now.  Every corpus run
             records its envelope (per-route cells/states/wall time)
             for MC_ENVELOPE_r*.json and the obs-gate mc.* keys.
  replay     a violating interleaving pretty-prints as a per-node op
             trace and exports through obs.timeline as Perfetto JSON.
  lockset    the happens-before/lockset AST pass (rule H1): watchdog vs
             trainer vs queue writes classified by acquired-lock sets
             over the call graph; unordered cross-thread writes are
             findings.

Entry points: ``tools/graftlint.py --mc`` / ``make modelcheck``.
No module IN this package imports jax or touches a device — the corpus
is plain-Python state exploration.  (Importing it still executes the
parent package's ``__init__``, which pulls jax; the CLI pins
``JAX_PLATFORMS=cpu`` before any import — the same guard as
tests/conftest.py — so `make modelcheck` never reaches for a chip.)
"""

from .opstream import (
    RingModel, PairModel, ProtocolError, rs_plan, rs_op_stream,
    rs_stream_op_stream, ag_schedule, ag_op_stream, hier_program,
    hier_op_stream, reshard_op_stream, reshard_segments,
    handoff_program, handoff_op_stream, check_dma_discipline,
    check_weight_conservation, SchedEmitter, SCHED_RULES,
)
from .sched import SchedModel, build_sched, sched_cells
from .mc import Violation, CheckResult, check, run_random, run_corpus

__all__ = [
    "RingModel", "PairModel", "ProtocolError", "rs_plan", "rs_op_stream",
    "rs_stream_op_stream", "ag_schedule", "ag_op_stream", "hier_program",
    "hier_op_stream", "reshard_op_stream", "reshard_segments",
    "handoff_program", "handoff_op_stream", "check_dma_discipline",
    "check_weight_conservation", "SchedEmitter", "SCHED_RULES",
    "SchedModel", "build_sched", "sched_cells",
    "Violation", "CheckResult", "check", "run_random", "run_corpus",
]
