"""Deterministic fault injection + collective integrity checking.

The reference's defining failure mode is a nondeterministic infinite hang
with no recovery path: OPAE reads/writes to on-board memory never complete
(hw/README:3-5), the `kill_syn_e0` kill CSR is declared but never wired
(hw/all_reduce.sv:83), and the only remedy is a full shell reset
(sw/mlp_mpi_example_f32.cpp:54-57).  `runtime.watchdog` ships the
*detection* half; this module ships the half that makes detection
testable: a seeded, deterministic fault plan that can provoke every
failure class on demand, at the three device-touching boundaries —

  - ``queue.issue`` / ``queue.wait``  (runtime/queue.py host issue loop)
  - ``staging``                       (runtime/staging.py host batch gather)
  - ``collective``                    (the explicit-ring reduce-scatter AND
                                       all-gather in ops/ring.py, via a
                                       pure_callback tap that executes
                                       INSIDE the jitted program; the
                                       TPU-only fused ring_pallas kernel
                                       path is NOT tapped — off-TPU it
                                       falls back onto the tapped ring)

plus the collective-integrity layer the compressed wire path needs:
per-chunk checksums across the all-reduce (input contribution sums vs the
reduced output), a NaN/inf guard, and a host-side gradient-norm drift
guard — BFP quantization is *bounded* error, so anything outside the bound
is corruption, caught before the optimizer consumes it.

Fault classes (``FAULT_KINDS``):

  hang        sleep far past the watchdog limit — the reference's OPAE
              poll-forever, provoked on purpose.
  slowdown    sleep below the limit — a straggler hop/host; must be
              survived WITHOUT recovery.
  exception   raise InjectedFault — a transient driver error.
  corruption  silently damage the payload (NaN / high-bit flip / scale) —
              the failure a compressed wire adds and checksums must catch.
  preemption  raise InjectedPreemption — the process lost its device slice
              (TPU preemption); recovery must re-init + restore.

Sites are host boundaries except ``collective``, whose faults run inside
the compiled step via `jax.pure_callback` (sleep or corrupt only — raising
inside an XLA callback aborts the runtime rather than unwinding the step,
so transient-exception faults belong to the host sites).

Everything is deterministic under a fixed seed: the plan's spec list, the
corrupted indices, and the flipped bits all derive from
``numpy.random.default_rng(seed)`` — a failing chaos run replays exactly.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "FAULT_KINDS", "DURABILITY_KINDS", "SITES", "TRAIN_SITES",
    "SERVE_SITES", "WIRE_SITES", "CKPT_SITES",
    "CORRUPTION_MODES",
    "InjectedFault", "InjectedPreemption", "IntegrityError",
    "WireIntegrityError",
    "FaultSpec", "FaultPlan", "NormDriftGuard",
    "chunk_checksums", "collective_integrity", "integrity_tol",
    "check_step_diag", "install_collective_tap", "uninstall_collective_tap",
    "install_wire_tap", "uninstall_wire_tap",
    "activate", "state_buffers_alive",
]

FAULT_KINDS = ("hang", "slowdown", "exception", "corruption", "preemption")
# "serve.step" is the serving plane's tick boundary (serve.engine): a
# host site like queue.*, fired once per engine tick inside the
# watchdog-bounded device work.  "serve.handoff" fires at the fleet's
# KV-migration boundary (serve.fleet._handoff — an exception there must
# degrade to replay, never lose the request) and "fleet.membership" at
# the fleet tick boundary (a preemption there IS a replica kill: the
# victim's in-flight requests must migrate to survivors).  The TRAINING
# matrix/soak in tools/chaos_bench.py iterates TRAIN_SITES — a serving
# spec never fires in a training run.
#
# "reshard.transfer" is the live-reshard transfer program's WIRE (the
# per-segment ppermute payloads of parallel/reshard.lower_apply): like
# "collective" it executes inside an XLA callback (corruption only), via
# the ENCODED-payload wire tap below — the boundary the exact frame
# checksums (ops.integrity) guard.  It is not in TRAIN_SITES: it can
# only fire while a reshard transfer is actually running, so the
# generic matrix/soak would plan unfireable specs; the dedicated
# integrity corruption cells in tools/chaos_bench.py own it.
# chaos FIRE point (the code boundary that actually calls
# ``FaultPlan.fire`` / arms a tap) -> the chaos SITE its specs target.
# The exported ``*_SITES`` tuples are DERIVED from these maps — never
# hand-written — so a new fire point that lands here is automatically
# part of the matrix/soak sweep, and one that doesn't is a one-line
# review catch.  This is the PR-12 drift class ("serve.handoff" missing
# from WIRE_SITES, caught by review) frozen structurally; graftlint R6
# fails any module-level ``*_SITES`` tuple built from string literals
# instead of a derivation like the ones below.
_TRAIN_POINT_SITES = {
    "runtime.queue.TicketQueue.issue": "queue.issue",
    "runtime.queue.TicketQueue.wait": "queue.wait",
    "runtime.staging.StagingPipeline.put": "staging",
    "runtime.chaos.collective_tap": "collective",   # XLA callback tap
}
_SERVE_POINT_SITES = {
    "serve.engine.ServeEngine.tick": "serve.step",
    "serve.fleet.ServeFleet._handoff": "serve.handoff",
    "serve.fleet.ServeFleet.tick": "fleet.membership",
}
TRAIN_SITES = tuple(dict.fromkeys(_TRAIN_POINT_SITES.values()))
SERVE_SITES = tuple(dict.fromkeys(_SERVE_POINT_SITES.values()))
# "ckpt.save" / "ckpt.restore" are the DURABILITY sites
# (utils.checkpoint): the save file-op sequence and the restore audit
# boundary.  Their fault kinds model what disks and processes actually
# do to checkpoints — kill-during-save (the op stream truncated at a
# planned prefix), disk-full (ENOSPC mid-sequence), file bit-flip at
# rest (corruption mode="wirebit" through damage_checkpoint) and a
# stale manifest (mode="stale_manifest": a previous step's manifest
# copied over the new one).  Not in TRAIN_SITES: they can only fire
# while a Checkpointer armed with the plan is saving/restoring, so the
# generic matrix/soak would plan unfireable specs; the dedicated
# durability cells in tools/chaos_bench.py own them.
_CKPT_POINT_SITES = {
    "utils.checkpoint.Checkpointer.save": "ckpt.save",
    "utils.checkpoint.Checkpointer.restore": "ckpt.restore",
}
CKPT_SITES = tuple(dict.fromkeys(_CKPT_POINT_SITES.values()))
SITES = TRAIN_SITES + SERVE_SITES + ("reshard.transfer",) + CKPT_SITES
# "wirebit" is the FINITE corruption class the wire checksums exist for
# (the blind spot of every value-space guard): a low bit flipped in the
# ENCODED frame (int8 mantissa / int16 index / f32 low-mantissa word)
# decodes to a plausible, in-band, wrong value — no NaN, no magnitude
# excursion.  At WIRE_SITES it fires through the encoded-payload wire
# tap; at host sites (serve.step payloads, staging) it flips low
# mantissa bits of the float tree in place.
CORRUPTION_MODES = ("nan", "bitflip", "scale", "wirebit", "stale_manifest")

# durability-only fault kinds (ckpt.save): "kill" truncates the save's
# file-op sequence at a planned prefix (``fraction`` of the op count) —
# the simulated mid-save crash the commit protocol must absorb;
# "diskfull" raises ENOSPC at the same point.  Neither is legal at any
# other site (a host boundary has no op stream to truncate).
DURABILITY_KINDS = ("kill", "diskfull")

# faults that can run inside an XLA callback (no raising in there)
_CALLBACK_KINDS = ("hang", "slowdown", "corruption")
# sites that ONLY exist inside an XLA callback
_CALLBACK_ONLY_SITES = ("collective", "reshard.transfer")
# corruption modes consumed by the VALUE taps (collective input, host
# payload trees); "wirebit" belongs to the encoded-payload wire tap
_VALUE_MODES = ("nan", "bitflip", "scale")
# wire-tap point (the string the transfer programs tap with) -> the
# chaos SITE whose wirebit specs fire there
_WIRE_POINT_SITES = {
    "ring.wire": "collective",          # ops.ring / ops.ring_hier hops
    "reshard.wire": "reshard.transfer",  # parallel.reshard segments
    "handoff.wire": "serve.handoff",     # serve.handoff page blocks
}
# the sites wirebit specs reach through the wire tap — DERIVED from the
# point map above so the exported constant can never drift from the
# real routing
WIRE_SITES = tuple(dict.fromkeys(_WIRE_POINT_SITES.values()))


class InjectedFault(RuntimeError):
    """A fault raised on purpose by a FaultPlan (transient by contract)."""

    def __init__(self, spec: "FaultSpec"):
        super().__init__(f"injected {spec.kind} at {spec.site} "
                         f"(step {spec.step})")
        self.spec = spec
        self.kind = spec.kind
        self.site = spec.site


class InjectedPreemption(InjectedFault):
    """The process 'lost its device slice' — recovery requires control-plane
    re-init + checkpoint restore, not a plain retry."""


class IntegrityError(RuntimeError):
    """A collective/loss integrity guard tripped: the step's numbers cannot
    be trusted and must not reach (or have been gated out of) the
    optimizer."""


class WireIntegrityError(IntegrityError):
    """The EXACT tier tripped: an encoded wire frame / KV page failed its
    bit-exact checksum (ops.integrity).  Distinguished from the
    value-space IntegrityError so recovery stats and chaos verdicts can
    prove WHICH tier caught a finite corruption — the class the
    value-space guards are provably blind to."""


def state_buffers_alive(state: Any) -> bool:
    """True when every device buffer in a state pytree is still live —
    the gate between the two recovery tiers (parallel.elastic): a
    preemption detected BEFORE the step dispatched leaves the in-memory
    state intact, so it can be migrated to the surviving mesh shape by
    collective redistribution (parallel.reshard); one detected at the
    wait boundary may have DONATED the state's buffers into the failed
    attempt, and only a checkpoint restore can reconstruct it."""
    import jax
    for leaf in jax.tree_util.tree_leaves(state):
        if isinstance(leaf, jax.Array) and leaf.is_deleted():
            return False
    return True


@dataclass(frozen=True)
class FaultSpec:
    """One planned fault: fire ``kind`` at ``site`` on trainer step
    ``step``.  ``duration_s`` is the sleep for hang/slowdown (a hang is a
    sleep chosen to exceed the watchdog limit; the daemon worker thread
    absorbs it).  ``mode``/``fraction`` shape corruption."""

    kind: str
    site: str
    step: int
    duration_s: float = 0.25
    mode: str = "nan"             # corruption: "nan" | "bitflip" | "scale"
    fraction: float = 0.01        # corrupted element fraction (>= 1 elem)

    def __post_init__(self):
        assert self.kind in FAULT_KINDS + DURABILITY_KINDS, self.kind
        assert self.site in SITES, self.site
        assert self.mode in CORRUPTION_MODES, self.mode
        if self.kind in DURABILITY_KINDS and self.site != "ckpt.save":
            raise ValueError(
                f"{self.kind!r} only exists at the 'ckpt.save' site: it "
                "truncates/fails the save file-op sequence at a planned "
                "prefix (fraction of the op count) — no other site has "
                "an op stream to interrupt")
        if self.site in CKPT_SITES and self.kind not in \
                DURABILITY_KINDS + ("corruption",):
            raise ValueError(
                f"{self.kind!r} cannot fire at the {self.site!r} site: "
                "durability sites take kill/diskfull (save only) and "
                "corruption (mode='wirebit' file bit-flip at rest, "
                "mode='stale_manifest') — hang/exception belong to the "
                "host boundaries around the checkpoint call")
        if self.site in CKPT_SITES and self.kind == "corruption" \
                and self.mode not in ("wirebit", "stale_manifest"):
            raise ValueError(
                f"corruption mode {self.mode!r} cannot fire at "
                f"{self.site!r}: stored-file damage is 'wirebit' (a low "
                "stored bit flips at rest) or 'stale_manifest' — the "
                "value modes corrupt live payload trees, not files")
        if self.mode == "stale_manifest" and self.site not in CKPT_SITES:
            raise ValueError(
                "mode='stale_manifest' only exists at the durability "
                "sites (ckpt.save / ckpt.restore): it swaps a step's "
                "manifest for a previous step's")
        if self.site in _CALLBACK_ONLY_SITES \
                and self.kind not in _CALLBACK_KINDS:
            raise ValueError(
                f"{self.kind!r} cannot fire at the {self.site!r} site: it "
                "executes inside an XLA callback, where raising aborts the "
                "runtime instead of unwinding the step — plan it at a host "
                "site (queue.*/staging) instead")
        if self.site == "reshard.transfer" and (
                self.kind != "corruption" or self.mode != "wirebit"):
            raise ValueError(
                "the 'reshard.transfer' site is the transfer program's "
                "wire tap: only corruption mode='wirebit' specs can fire "
                "there (the tap pops wirebit alone — any other spec "
                "would stay armed forever; hang/slowdown belong to the "
                "host boundaries around the transfer)")


class FaultPlan:
    """A deterministic schedule of FaultSpecs plus the machinery that fires
    them.  Thread-safe: host hooks and the in-program collective tap may
    run concurrently (queue issue thread vs XLA callback threads).

    Protocol with the hook sites::

        plan.begin_step(i)          # trainer loop, before dispatching step i
        plan.fire(site)             # host boundary: may sleep or raise
        x = plan.corrupt(site, x)   # host boundary carrying a payload
        y = plan.collective_payload(y)   # inside jit, via the ring tap

    Each spec fires at most once (``fired``) so a recovery retry of the
    same step re-runs clean — the injected fault is transient by
    construction, like the reference's nondeterministic hang."""

    def __init__(self, faults: Iterable[FaultSpec] = (), seed: int = 0):
        self.faults: Tuple[FaultSpec, ...] = tuple(faults)
        self.seed = seed
        self.fired: List[FaultSpec] = []
        self._step = -1
        self._lock = threading.RLock()
        # optional obs.events.EventStream: every fired spec lands as an
        # instant event so the Perfetto timeline shows the injected fault
        # on the same axis as the spans/tickets it perturbs (ElasticTrainer
        # attaches its profiler's stream automatically)
        self.events = None

    # -- construction -------------------------------------------------------

    @classmethod
    def random(cls, seed: int, n_steps: int, *, rate: float = 0.25,
               kinds: Sequence[str] = FAULT_KINDS,
               sites: Sequence[str] = TRAIN_SITES,
               duration_s: float = 0.25) -> "FaultPlan":
        """Seeded random plan: each step draws one fault with probability
        ``rate``; kind/site/mode are drawn uniformly from the legal
        combinations.  Same seed -> identical plan, always."""
        rng = np.random.default_rng(seed)
        specs = []
        for step in range(n_steps):
            if rng.random() >= rate:
                continue
            site = str(rng.choice(list(sites)))
            legal = [k for k in kinds
                     if site != "collective" or k in _CALLBACK_KINDS]
            if not legal:
                continue
            kind = str(rng.choice(legal))
            specs.append(FaultSpec(
                kind=kind, site=site, step=step, duration_s=duration_s,
                # value modes only: a random wirebit spec would need the
                # wire tap installed to fire at all — the dedicated
                # integrity cells own that mode deterministically
                mode=str(rng.choice(list(_VALUE_MODES)))))
        return cls(specs, seed=seed)

    @classmethod
    def sustained(cls, kind: str, site: str, *, start_step: int,
                  n_steps: int, duration_s: float = 0.25,
                  mode: str = "nan", fraction: float = 0.01,
                  seed: int = 0) -> "FaultPlan":
        """A REGIME SHIFT, not a glitch: one identical spec per step for
        ``n_steps`` consecutive steps from ``start_step``.  Single specs
        fire at most once (transient by contract), so a sustained
        condition — the straggling link whose codec break-even has moved
        (SparCML), the forced `slowdown@collective` cell that proves the
        drift observatory's detection→switch path end to end — is
        modeled as one spec per step, each firing exactly once."""
        assert n_steps >= 1, n_steps
        return cls([FaultSpec(kind, site, step=start_step + i,
                              duration_s=duration_s, mode=mode,
                              fraction=fraction)
                    for i in range(n_steps)], seed=seed)

    # -- stepping -----------------------------------------------------------

    def begin_step(self, step: int) -> None:
        with self._lock:
            self._step = int(step)

    @property
    def step(self) -> int:
        return self._step

    def _take(self, site: str, kinds: Sequence[str],
              limit: Optional[int] = None,
              modes: Optional[Sequence[str]] = None) -> List[FaultSpec]:
        """Pop (mark fired) the unfired specs matching (site, current step,
        kinds).  Fired-ness is per spec INSTANCE (identity, not dataclass
        equality): a plan may deliberately schedule several equal specs —
        e.g. one per expected retry — and each must fire exactly once.
        ``limit`` caps how many are popped per call: raising hooks take one
        at a time, so sibling specs stay armed for the retry.  ``modes``
        (corruption only) restricts which corruption modes this hook
        consumes: the VALUE tap must leave "wirebit" specs armed for the
        ENCODED-payload wire tap (and vice versa) — the two taps model
        different fault locations and must not steal each other's specs."""
        with self._lock:
            fired_ids = {id(f) for f in self.fired}
            out = [s for s in self.faults
                   if s.site == site and s.step == self._step
                   and s.kind in kinds and id(s) not in fired_ids
                   and (modes is None or s.kind != "corruption"
                        or s.mode in modes)]
            if limit is not None:
                out = out[:limit]
            self.fired.extend(out)
        ev = self.events
        if ev is not None:
            for s in out:
                ev.instant("chaos.fire", kind=s.kind, site=s.site,
                           step=s.step)
        return out

    # -- host-side firing ---------------------------------------------------

    def fire(self, site: str) -> None:
        """Host boundary hook: sleeps for hang/slowdown, raises for
        exception/preemption.  Corruption specs are left for corrupt()."""
        for spec in self._take(site, ("hang", "slowdown")):
            time.sleep(spec.duration_s)
        for spec in self._take(site, ("preemption",), limit=1):
            raise InjectedPreemption(spec)
        for spec in self._take(site, ("exception",), limit=1):
            raise InjectedFault(spec)

    def corrupt(self, site: str, tree: Any) -> Any:
        """Apply any pending corruption specs at ``site`` to a pytree of
        arrays; returns the tree unchanged (same objects, zero copies) when
        nothing fires."""
        specs = self._take(site, ("corruption",))
        if not specs:
            return tree
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for spec in specs:
            # corrupt the largest float leaf: for a batch that is the
            # payload (not e.g. int labels); for a (state, batch) tree —
            # the queue.issue boundary — whichever of the master shard
            # and the batch is bigger, so the guard layer that catches
            # it depends on model-vs-batch size (both layers are pinned
            # down by the dedicated queue.wait / staging cells)
            fl = [i for i, l in enumerate(leaves)
                  if np.issubdtype(np.asarray(l).dtype, np.floating)]
            if not fl:
                continue
            i = max(fl, key=lambda j: np.asarray(leaves[j]).size)
            leaves[i] = self._corrupt_array(np.array(leaves[i]), spec)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _corrupt_array(self, arr: np.ndarray, spec: FaultSpec) -> np.ndarray:
        """Deterministic damage: indices and bits derive from
        (plan seed, spec step) only."""
        if spec.mode == "wirebit":
            # the FINITE class: a low STORED bit flips in the array's
            # native width (_corrupt_wire_array, its own rng — an f32
            # round-trip would round the flip away below bf16/f16
            # resolution and silently corrupt NOTHING), so the damaged
            # value stays plausible and in-band — invisible to NaN/
            # norm/magnitude guards by construction; only an exact
            # checksum (ops.integrity) can prove it
            return self._corrupt_wire_array(arr, spec)
        rng = np.random.default_rng((self.seed, spec.step, 0xC0FFEE))
        flat = arr.reshape(-1)
        k = max(1, int(flat.size * spec.fraction))
        idx = rng.choice(flat.size, size=min(k, flat.size), replace=False)
        if spec.mode == "nan":
            flat[idx] = np.nan
        elif spec.mode == "scale":
            flat[idx] = flat[idx] * np.float32(1e8) + np.float32(1e8)
        else:                                   # bitflip: exponent-high bit
            f32 = flat.astype(np.float32, copy=True)
            bits = f32.view(np.uint32)
            bits[idx] ^= np.uint32(1 << 30)
            flat[:] = f32.astype(flat.dtype)
        return arr

    def stage(self, batch: Any) -> Any:
        """The host staging boundary as one call (fire, then corrupt):
        what ``runtime.staging.Stager`` does internally when constructed
        with ``chaos=plan``, for callers staging batches without the
        native gather library (the elastic loop's ``stage_fn``)."""
        self.fire("staging")
        return self.corrupt("staging", batch)

    # -- in-program (collective) path --------------------------------------

    def collective_payload(self, arr: np.ndarray) -> np.ndarray:
        """The host half of the collective tap: called from inside the
        compiled step (one call per shard).  Sleeps for a pending
        hang/slowdown ON THE FIRST SHARD TO ARRIVE (a straggler device);
        corrupts the first arriving shard's payload for corruption specs."""
        for spec in self._take("collective", ("hang", "slowdown")):
            time.sleep(spec.duration_s)
        for spec in self._take("collective", ("corruption",),
                               modes=_VALUE_MODES):
            arr = self._corrupt_array(np.array(arr), spec)
        return arr

    # -- in-program (encoded wire) path -------------------------------------

    def wire_payload(self, arr: np.ndarray, point: str) -> np.ndarray:
        """The host half of the ENCODED-payload wire tap: called from
        inside a transfer program, once per payload array per hop, with
        the bytes exactly as they ride the wire (int8 mantissa/scale
        tiles, int16 top-k indices, raw f32 words).  Only "wirebit"
        corruption specs fire here — the finite low-bit class the exact
        frame checksums (ops.integrity) exist for; a flipped encoded bit
        decodes to a plausible in-band value no value-space guard can
        see."""
        site = _WIRE_POINT_SITES.get(point)
        if site is None:
            return arr
        # limit=1: ONE corruption event per wire crossing.  A transfer
        # program taps once per payload array, so sibling specs at the
        # same step stay armed for LATER payloads/attempts (the
        # bounded-retry cells need the retry to trip too) — and two
        # identical deterministic flips can never land on one array and
        # XOR-cancel each other
        for spec in self._take(site, ("corruption",), limit=1,
                               modes=("wirebit",)):
            arr = self._corrupt_wire_array(np.array(arr), spec)
        return arr

    # -- durability (checkpoint file) path ----------------------------------

    def take_save_interrupts(self) -> List[FaultSpec]:
        """Pop the pending kill/diskfull spec at ``ckpt.save`` for the
        save whose file-op sequence is about to execute
        (utils.checkpoint._exec_ops maps the spec's ``fraction`` to an
        op index and stops there — the simulated mid-save crash).
        ``limit=1``: ONE interrupt per save — a save dies once, so
        sibling specs at the same step stay armed for LATER saves
        instead of being popped-as-fired without ever firing (the
        wire tap's one-event-per-crossing discipline)."""
        return self._take("ckpt.save", DURABILITY_KINDS, limit=1)

    def damage_checkpoint(self, site: str, step_dir: str,
                          prev_manifest: Optional[str] = None) -> None:
        """Fire pending corruption specs at a durability site against a
        COMMITTED step directory — damage at rest, applied after the
        save commit (``ckpt.save``) or just before the restore audit
        (``ckpt.restore``).

        ``mode="wirebit"``: the lowest stored bit of one word in the
        data region of a deterministically chosen PRIMARY leaf file
        flips — a plausible, in-band value (f32 low-mantissa byte /
        int8 LSB) that no magnitude or finiteness guard can see; only
        the manifest's exact checksum audit proves it.
        ``mode="stale_manifest"``: the step's manifest is replaced with
        the PREVIOUS step's (operator error / misdirected copy) — the
        audit must reject it as describing other bytes (the step-field
        and self-checksum validation), never validate against it."""
        import os
        import shutil
        # lazy: runtime.chaos must stay importable without the utils
        # layer; utils.checkpoint only imports chaos lazily too
        from ..utils.checkpoint import (MANIFEST_FILE, flip_stored_bit,
                                        npy_data_offset)
        for spec in self._take(site, ("corruption",),
                               modes=("wirebit", "stale_manifest")):
            if spec.mode == "stale_manifest":
                if prev_manifest is not None and \
                        os.path.exists(prev_manifest):
                    shutil.copyfile(
                        prev_manifest,
                        os.path.join(step_dir, MANIFEST_FILE))
                continue
            # primary npy files only (mirror copies end ".m.npy"): the
            # repair tier exists exactly for a damaged primary
            try:
                names = sorted(
                    f for f in os.listdir(step_dir)
                    if f.endswith(".npy") and not f.endswith(".m.npy"))
            except FileNotFoundError:
                continue
            if not names:
                continue
            big = [f for f in names
                   if os.path.getsize(os.path.join(step_dir, f)) >= 1024]
            pool = big or names
            rng = np.random.default_rng((self.seed, spec.step, 0xD15C0))
            p = os.path.join(step_dir, str(rng.choice(pool)))
            with open(p, "rb") as f:
                header = f.read(16)
            # flip bit 0 of a 4-byte-aligned data byte (f32 low-mantissa
            # byte / int8 LSB — always finite, always in-band)
            n_words = max(1, (os.path.getsize(p)
                              - npy_data_offset(header)) // 4)
            flip_stored_bit(p, byte_off=4 * int(rng.integers(n_words)))

    def _corrupt_wire_array(self, arr: np.ndarray,
                            spec: FaultSpec) -> np.ndarray:
        """Deterministic low-bit damage to an ENCODED frame: the lowest
        stored bit of ``fraction`` of the words flips — int frames flip
        mantissa/index LSBs, f32 frames flip mantissa bit 1.  Always
        finite, always in-band, always a changed wire byte."""
        rng = np.random.default_rng((self.seed, spec.step, 0xB17F11B))
        flat = arr.reshape(-1)
        k = max(1, int(flat.size * spec.fraction))
        idx = rng.choice(flat.size, size=min(k, flat.size), replace=False)
        if flat.dtype == np.float32:
            flat.view(np.uint32)[idx] ^= np.uint32(1 << 1)
        elif flat.dtype.kind in "iu":
            flat[idx] ^= flat.dtype.type(1)
        else:   # other float widths: flip the lowest mantissa bit
            w = flat.view(np.uint16 if flat.dtype.itemsize == 2
                          else np.uint32)
            w[idx] ^= w.dtype.type(1)
        return arr


# ---------------------------------------------------------------------------
# the collective tap (ops.ring / ops.ring_pallas boundary)
# ---------------------------------------------------------------------------

_ACTIVE_PLAN: Optional[FaultPlan] = None


def _tap_fn(x, point: str):
    """Trace-time tap body installed into ops.ring: routes the payload
    through the ACTIVE plan on the host.  The callback executes on every
    step of the compiled program; with no active plan (or no pending spec)
    it is an identity copy."""
    import jax

    def host(v):
        plan = _ACTIVE_PLAN
        a = np.asarray(v)
        if plan is None:
            return a
        return np.asarray(plan.collective_payload(a), dtype=a.dtype)

    return jax.pure_callback(host, jax.ShapeDtypeStruct(x.shape, x.dtype), x)


def install_collective_tap() -> None:
    """Install the chaos tap into the explicit-ring collectives.  Must run
    BEFORE the trainer's step is first traced (the tap is compiled into the
    program); per-run plans are then switched via activate()."""
    from ..ops import ring
    ring.set_fault_tap(_tap_fn)


def uninstall_collective_tap() -> None:
    from ..ops import ring
    ring.set_fault_tap(None)


def _wire_tap_fn(x, point: str, consumed=None):
    """Trace-time ENCODED-payload tap body installed into ops.ring (and
    through it every ppermute-bearing transfer program: flat/hier rings,
    the reshard segments, the KV handoff): routes each wire payload
    through the ACTIVE plan's wirebit hook on the host.  Identity copy
    when no plan / no pending spec.  ``consumed`` (traced bool) gates
    the hook to devices whose received bytes the program actually uses
    (ops.ring._tap_wire docstring) — a spec must never be spent on a
    bystander's zero payload."""
    import jax
    import jax.numpy as jnp

    def host(v, c):
        plan = _ACTIVE_PLAN
        a = np.asarray(v)
        if plan is None or not bool(np.asarray(c)):
            return a
        return np.asarray(plan.wire_payload(a, point), dtype=a.dtype)

    c = jnp.bool_(True) if consumed is None else consumed
    return jax.pure_callback(host, jax.ShapeDtypeStruct(x.shape, x.dtype),
                             x, c)


def install_wire_tap() -> None:
    """Install the encoded-payload wire tap (the boundary the exact frame
    checksums guard — ops.integrity).  Must run BEFORE the consuming
    transfer program is first traced, same contract as
    install_collective_tap; per-run plans switch via activate()."""
    from ..ops import ring
    ring.set_wire_tap(_wire_tap_fn)


def uninstall_wire_tap() -> None:
    from ..ops import ring
    ring.set_wire_tap(None)


class activate:
    """Context manager binding a plan as the ambient target of the
    collective tap (and a convenience holder for host hooks).

    Dispatch is async: the tap's callback reads the ambient plan from XLA
    callback threads while the program runs, so any step that should see
    the plan must COMPLETE (``jax.block_until_ready`` on its outputs, or a
    blocking ``queue.wait``) before this context exits — the elastic loop
    already blocks per step inside ``_check``."""

    def __init__(self, plan: Optional[FaultPlan]):
        self.plan = plan

    def __enter__(self):
        global _ACTIVE_PLAN
        self._prev = _ACTIVE_PLAN
        _ACTIVE_PLAN = self.plan
        return self.plan

    def __exit__(self, *exc):
        global _ACTIVE_PLAN
        _ACTIVE_PLAN = self._prev
        return False


# ---------------------------------------------------------------------------
# collective integrity (pure JAX — runs inside shard_map)
# ---------------------------------------------------------------------------

def integrity_tol(coll, n: int) -> float:
    """Checksum tolerance for an n-way all-reduce under the configured wire
    format — derived from the codec's DECLARED error bound
    (compress.Codec.error_bound), not from a BFP special case, so the
    integrity layer works unmodified under any registered codec.

    Uncompressed rings/psum differ from the input sums only by f32
    reassociation.  A bounded codec adds per-hop quantization error
    (<= error_bound of the unit max per element per hop: 2^(1-m) for BFP's
    m-bit mantissa — the pre-subsystem hard-wired formula — 1/127 for
    stochastic int8), so the chunk-sum discrepancy is bounded by
    ~(n-1) * error_bound * (unitmax/mean) of the chunk L1.
    Unbounded-by-design codecs (top-k declares error_bound=1.0) saturate
    at the 0.5 cap: the checksum then only trips on the failures it can
    still prove — NaN/inf and runaway scale — with no false trips on
    intentional compression loss (the error-feedback carry, not a per-pass
    bound, is top-k's accuracy story).  Either way the tolerance is a
    GROSS-corruption tripwire (NaN, flipped exponent bits, runaway scale),
    not a bit-exactness check — in-bound quantization noise must pass."""
    from ..ops.fused_update import resolve_codec
    codec = resolve_codec(coll)
    if codec is None:
        return 1e-3
    return min(0.5, (n - 1) * float(codec.error_bound) * 8.0)


def chunk_checksums(flat: "Any", axis_name: str, n: int):
    """Inside shard_map: per-chunk input checksums of a local flat [L]
    contribution, reduced across the axis.  Returns (expect[n], l1[n]):
    expect[b] is the true sum of reduced chunk b; l1[b] the matching scale
    for a relative comparison."""
    import jax.numpy as jnp
    from jax import lax
    sums = flat.reshape(n, -1).sum(axis=1)
    l1 = jnp.abs(flat).reshape(n, -1).sum(axis=1)
    return lax.psum(sums, axis_name), lax.psum(l1, axis_name)


def collective_integrity(expect, l1, g_red, axis_name: str, n: int,
                         tol: float) -> Dict[str, Any]:
    """Inside shard_map, after ``g_red = reduce_scatter(flat)`` (pre-mean):
    compares this device's reduced-chunk sum against the input checksum
    and counts non-finites.  Returns replicated scalar diagnostics::

        integrity_ok   bool  — all chunks within tol AND fully finite
        integrity_err  f32   — worst relative chunk-sum discrepancy
        nonfinite      i32   — NaN/inf count across the reduced vector

    ``integrity_ok`` is safe to gate the optimizer with (NaN comparisons
    come out False, so a poisoned checksum fails closed)."""
    import jax.numpy as jnp
    from jax import lax
    idx = lax.axis_index(axis_name)
    mine = jnp.sum(g_red.astype(jnp.float32))
    onehot = (jnp.arange(n) == idx).astype(jnp.float32)
    # psum of masked per-device sums -> replicated [n] vector of the
    # actual reduced-chunk sums (all-gather without relying on tiling)
    got = lax.psum(onehot * mine, axis_name)
    nonfinite = lax.psum(jnp.sum(~jnp.isfinite(g_red)), axis_name)
    err = jnp.max(jnp.abs(expect - got) / (l1 + 1e-20))
    ok = (nonfinite == 0) & (err <= tol)
    return {"integrity_ok": ok, "integrity_err": err,
            "nonfinite": nonfinite}


def check_step_diag(diag: Dict[str, Any], step: int) -> None:
    """Host-side verdict on a step's integrity diagnostics (raises
    IntegrityError / WireIntegrityError).  Call AFTER the step's outputs
    are materialized.  The EXACT tier (``wire_ok``: bit-conservation of
    the encoded ring frames, ops.integrity) is checked FIRST — a wire
    trip is a different fact than a value-band excursion (it proves the
    bytes changed in flight, with no tolerance involved), and on the
    in-kernel fused-optimizer route this raise is the ONLY recovery path
    (the donated state cannot be gated in-graph; the elastic ladder
    discards the invalidated step)."""
    if not bool(diag.get("wire_ok", True)):
        raise WireIntegrityError(
            f"exact wire checksum tripped at step {step}: an encoded "
            "frame changed between send and receive (finite corruption "
            "class — invisible to the value band; gated/invalidated "
            "before the masters could absorb it)")
    nonfinite = int(diag.get("nonfinite", 0))
    ok = bool(diag.get("integrity_ok", True))
    if nonfinite or not ok:
        raise IntegrityError(
            f"collective integrity tripped at step {step}: "
            f"nonfinite={nonfinite}, "
            f"rel_err={float(diag.get('integrity_err', float('nan'))):.3g} "
            "(update was gated out before the optimizer)")


@dataclass
class NormDriftGuard:
    """Cheap host-side drift guard over a scalar series (gradient norm or
    loss): trips when the value is non-finite, or after ``warmup`` clean
    samples jumps ``factor``x above the running median."""

    factor: float = 1e3
    warmup: int = 3
    window: int = 32
    history: List[float] = field(default_factory=list)

    def check(self, value: float, what: str = "grad_norm") -> None:
        v = float(value)
        if not np.isfinite(v):
            raise IntegrityError(f"{what} is non-finite ({v})")
        h = self.history
        if len(h) >= self.warmup:
            med = float(np.median(h[-self.window:]))
            if med > 0 and v > self.factor * med:
                raise IntegrityError(
                    f"{what} drift: {v:.3g} is {v / med:.1f}x the running "
                    f"median {med:.3g} (factor limit {self.factor:g})")
        h.append(v)
        del h[:-self.window]
