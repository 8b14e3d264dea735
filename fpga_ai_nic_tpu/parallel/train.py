"""Data-parallel training loop with the fused scatter-update-gather
collective — the TPU rebuild of the reference's MPI training driver
(sw/mlp_mpi_example_f32.cpp:682-827).

Reference structure: each rank computes fwd/bwd on its batch shard; per-layer
gradients are handed to the NIC (async all-reduce + fused SGD); the host
never runs the optimizer (its calls are commented out, :765,780,787) and the
canonical weights live device-resident (FPGA DDR).  Here:

- the batch is sharded over the ``dp`` mesh axis (MPI_Scatter equivalent,
  :452-460);
- ``jax.grad`` replaces the hand-written bwd GEMM chain;
- the fused collective (`ops.fused_update`) reduce-scatters gradients,
  applies the optimizer on the owned f32 master shard, and all-gathers
  updated working weights — ZeRO-1 semantics, matching the reference's
  "gather phase distributes updated weights" design;
- issue/wait overlap (:752-764) is XLA's latency-hiding scheduler's job;
  the async-queue API for explicit overlap lives in `runtime.queue`.

Everything is one jitted step with donated state: the "updated weights
written over the gradient buffer" aliasing trick of the reference
(hw/all_reduce.sv:240,1286-1311) becomes XLA buffer donation — same memory
win, no aliasing confusion.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import accum
from . import mesh as mesh_lib
from .. import optim
from ..obs import metrics as obs_metrics
from ..obs.names import scope
from ..ops import fused_update
from ..runtime import chaos
from ..utils.config import TrainConfig


class TrainState(NamedTuple):
    params: Any            # replicated working weights (model dtype)
    w_own: jax.Array       # this device's f32 master shard [L/n] (ZeRO-1)
    opt_state: Any         # sharded optimizer state (ZeRO-1)
    step: jax.Array
    # error-feedback residual of the configured compression codec: each
    # device's locally-dropped gradient mass [L_pad], re-added next step
    # (compress.Codec.state_init; None when the codec carries no state).
    # Checkpoint restore re-zeros it — EF is self-healing, the residual
    # is a bounded accumulator, not part of the optimization state proper.
    codec_state: Any = None


class DPTrainer:
    """Builds jitted init/step functions for a loss_fn over a 1-D dp mesh.

    loss_fn(params, batch) -> scalar; batch leaves have a leading
    global-batch axis that is sharded over dp.
    """

    def __init__(self, loss_fn: Callable, mesh: Mesh, cfg: TrainConfig,
                 axis_name: str = "dp"):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.cfg = cfg
        self.ax = axis_name
        self.n = mesh.shape[axis_name]
        self._meta = None
        # codec="auto": codec / pipeline_depth / bucket_elems / topology
        # resolve ONCE at the first _ensure_meta (the payload size is
        # known there), from the ring_cost model under calibrated rates
        # (fpga_ai_nic_tpu.tune) — static thereafter, R2-clean, and the
        # plan lands in obs_static_metrics() for obs-gate to diff
        self._tuned_plan = None
        self._tune_calib = None
        # trace counters: the traced Python bodies below bump these once
        # per TRACE (cache miss), so the adaptation plane (tune.adapt)
        # and graftlint J13 can hold "a plan switch causes zero new
        # traces" as a counted fact, the J10 discipline applied to
        # training
        self.step_traces = 0
        self.gather_traces = 0
        self._set_codec_flags()
        if cfg.collective.fused_optimizer \
                and cfg.optimizer.clip_norm is not None:
            raise ValueError(
                "fused_optimizer cannot honor clip_norm: a global-norm "
                "clip needs a cross-replica barrier BETWEEN the "
                "reduce-scatter and the update — exactly the exposed "
                "optimizer time the fused path removes; clip before the "
                "collective or run unfused")

    def _set_codec_flags(self) -> None:
        """(Re)derive the codec object + error-feedback flag from the
        CURRENT collective config — called at construction and again
        after autotune resolution replaces the config."""
        coll = self.cfg.collective
        from .. import tune as tune_lib
        if tune_lib.needs_autotune(coll):
            # unresolved "auto": no codec to instantiate yet (resolution
            # happens at _ensure_meta, where the payload size is known)
            self._codec, self._ef = None, False
            return
        # error-feedback residual carry (compress codecs that declare it,
        # e.g. top-k): threaded through TrainState.codec_state
        codec = fused_update.resolve_codec(coll)
        self._codec = codec
        self._ef = (coll.impl == "ring" and codec is not None
                    and codec.error_feedback)

    def _resolve_auto(self, params_like) -> None:
        """One-shot autotune resolution of a codec='auto' template (no-op
        otherwise): deterministic in the banked artifacts, done in plain
        Python before any tracing.  The calibration is kept so the
        padded-length rescore prices with the SAME artifacts.  With
        ``cfg.adapt`` armed for live calibration, the banked rates are
        first upgraded by the startup mesh microbenches
        (tune.adapt.live_calibrate) — the `live` provenance tier: the
        plan is priced for the mesh the job actually landed on, not the
        mesh some artifact was banked on."""
        from .. import tune as tune_lib
        calibration = None
        acfg = getattr(self.cfg, "adapt", None)
        if (acfg is not None and acfg.enabled and acfg.live_calibration
                and tune_lib.needs_autotune(self.cfg.collective)):
            from ..tune import adapt as adapt_lib
            calibration = adapt_lib.live_calibrate(self.mesh, self.ax)
        cfg, plan, calib = tune_lib.resolve_train_config(
            self.cfg, self.n, params_like, calibration=calibration)
        if plan is None:
            return
        self.cfg = cfg
        self._tuned_plan, self._tune_calib = plan, calib
        self._set_codec_flags()

    # -- init ---------------------------------------------------------------

    def _ensure_meta(self, params_like) -> None:
        """Flat-master layout from a params tree or ShapeDtypeStructs —
        meta is static, derived without touching device memory; invalidate
        any step_fn cached against a previous model's meta."""
        self._resolve_auto(params_like)
        self._meta = fused_update.flat_meta(params_like,
                                            self.cfg.collective, self.n)
        if self._tuned_plan is not None \
                and self._tuned_plan.payload_elems != self._meta.padded_len:
            # re-price the chosen plan at the PADDED length (padding
            # depends on the resolved codec) so the banked wire-byte
            # declaration matches the collective bit for bit — under the
            # SAME calibration and slice plan the argmin scored with
            from .. import tune as tune_lib
            self._tuned_plan = tune_lib.rescore(
                self._tuned_plan, self._meta.padded_len,
                calibration=self._tune_calib,
                slice_elems=self.cfg.collective.slice_elems)
        self.__dict__.pop("step_fn", None)
        self.__dict__.pop("_gather_fn", None)

    def init_state(self, params) -> TrainState:
        """Split replicated params into ZeRO-1 master shards (the analogue
        of the first-iteration weight download to FPGA DDR, flags=1 path,
        sw/mlp_mpi_example_f32.cpp:700; hw/weight_update.sv MEM_INIT)."""
        # _ensure_meta FIRST: it resolves a codec='auto' template into
        # the concrete config _init must close over
        self._ensure_meta(params)
        coll, opt_cfg = self.cfg.collective, self.cfg.optimizer

        def _init(params):
            w_own, opt_state, meta = fused_update.init_master_shard(
                params, self.ax, coll, opt_cfg)
            return w_own, opt_state

        w_own, opt_state = jax.jit(jax.shard_map(
            _init, mesh=self.mesh, in_specs=P(),
            out_specs=P(self.ax), check_vma=False))(params)
        return TrainState(params=params, w_own=w_own, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32),
                          codec_state=self._init_codec_state())

    def _init_codec_state(self):
        """Zeroed per-device error-feedback residuals ([n * L_pad] global,
        sharded over the axis so each device carries its own [L_pad])."""
        if not self._ef:
            return None
        return jax.device_put(
            jnp.zeros((self.n * self._meta.padded_len,), jnp.float32),
            NamedSharding(self.mesh, P(self.ax)))

    # -- step ---------------------------------------------------------------

    @functools.cached_property
    def step_fn(self):
        coll, opt_cfg = self.cfg.collective, self.cfg.optimizer
        meta = self._meta
        assert meta is not None, "call init_state first"
        ax = self.ax

        codec, ef = self._codec, self._ef
        # trace-time metrics gate: False adds NOTHING to the jaxpr (the
        # obs.metrics compiled-out contract, asserted by tests/test_obs.py)
        obs_on = self.cfg.obs_metrics

        # Phase 1 (check_vma=True): gradients + reduce-scatter + optimizer.
        # Variance tracking must stay ON anywhere jax.grad runs inside
        # shard_map — with check_vma=False the transposes of collectives
        # inside the loss are silently wrong.
        def shard_update(params, w_own, opt_state, step, batch,
                         *maybe_resid):
            # Cast params dp-varying BEFORE grad: otherwise vma-typed
            # autodiff auto-inserts a full psum over dp for every gradient
            # (params are dp-invariant), which both double-counts once we
            # reduce-scatter and forfeits the fused-ring/BFP wire path.
            params_v = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, ax, to="varying"), params)
            with scope("ainic.fwd_bwd"):
                loss, grads = accum.accumulated_value_and_grad(
                    self.loss_fn, self.cfg.accum_steps)(params_v, batch)
            with scope("ainic.flatten"):
                flat_g, _ = fused_update.flatten_tree(grads, coll, self.n)
            m = {}      # in-graph metrics (obs_on only; else stays empty)
            if ef:
                # compensate-then-compress: the wire sees the locally
                # quantized gradient; what it dropped carries to the next
                # step (TrainState.codec_state)
                resid = maybe_resid[0]
                flat_raw = flat_g
                with scope("ainic.flatten"):
                    flat_g, new_resid = fused_update.error_feedback_encode(
                        codec, flat_g, resid)
                if obs_on:
                    # flat_g IS roundtrip(flat_raw + resid) here, so the
                    # declared-vs-observed check costs no extra roundtrip
                    m["codec_obs_rel_err"] = lax.pmax(
                        obs_metrics.codec_observed_error(
                            codec, flat_raw + resid, quantized=flat_g), ax)
                    m["ef_resid_norm"] = obs_metrics.l2_norm(new_resid, ax)
            elif obs_on and codec is not None:
                m["codec_obs_rel_err"] = lax.pmax(
                    obs_metrics.codec_observed_error(codec, flat_g), ax)
            diag = {}
            icheck = coll.integrity_check
            if icheck:
                # checksums guard the COLLECTIVE (what actually rides the
                # wire), so under EF they see the post-compression vector
                # — local compression is intentional, not corruption
                expect, l1 = chaos.chunk_checksums(flat_g, ax, self.n)
                tol = (coll.integrity_tol if coll.integrity_tol is not None
                       else chaos.integrity_tol(coll, self.n))
            if coll.fused_optimizer:
                # decode+accumulate+update in one pass (in-kernel on the
                # TPU fused-ring path; the same formula fused after the
                # reduce elsewhere — ops.fused_update.reduce_scatter_
                # update): the optimizer runs on zero exposed time, and
                # the EF residual carry above is untouched by the fusion
                # (it compensates the LOCAL encode, before the wire)
                with scope("ainic.collective_update"):
                    res = fused_update.reduce_scatter_update(
                        flat_g, w_own, opt_state, step, ax, coll, opt_cfg,
                        integrity=icheck)
                if icheck:
                    g_sum, w_new, opt_state2, wire_ok = res
                    # BOTH tiers ride the fused path since PR 12: the
                    # value band compares the returned raw sum shard, the
                    # exact tier is the in-graph/in-kernel frame verdict
                    diag = chaos.collective_integrity(
                        expect, l1, g_sum, ax, self.n, tol)
                    diag["wire_ok"] = wire_ok
                    if fused_update.update_route_gatable(coll, self.n):
                        # pre-step state still materialized on this
                        # route: a tripped verdict gates the update to a
                        # no-op (the in-kernel route cannot — its state
                        # is donated; check_step_diag invalidates the
                        # step instead)
                        ok = diag["integrity_ok"] & wire_ok
                        w_new = jnp.where(ok, w_new, w_own)
                        opt_state2 = jax.tree_util.tree_map(
                            lambda new, old: jnp.where(ok, new, old),
                            opt_state2, opt_state)
                        if ef:
                            new_resid = jnp.where(ok, new_resid,
                                                  maybe_resid[0])
                else:
                    g_sum, w_new, opt_state2 = res
                g_own = g_sum / self.n
                if icheck:
                    diag["grad_norm"] = jnp.sqrt(lax.psum(
                        jnp.sum(g_own.astype(jnp.float32) ** 2), ax))
                if obs_on:
                    # same definition as the diag norm — reuse it (as
                    # the unfused path below does) instead of paying a
                    # second psum on the hot fused path
                    m["grad_norm"] = (diag["grad_norm"] if icheck
                                      else obs_metrics.l2_norm(g_own, ax))
                loss_m = lax.pmean(loss, ax)
                if obs_on:
                    m["loss"] = loss_m
                out = (w_new, opt_state2, loss_m, diag)
                return out + ((new_resid,) if ef else ()) + (
                    (m,) if obs_on else ())
            if icheck:
                with scope("ainic.collective_update"):
                    g_red, wire_ok = fused_update.reduce_scatter(
                        flat_g, ax, coll, integrity=True)
                diag = chaos.collective_integrity(expect, l1, g_red, ax,
                                                  self.n, tol)
                # the EXACT tier (ops.integrity): bit-conservation of the
                # encoded frames — the finite wrong-value class the value
                # band above is provably blind to
                diag["wire_ok"] = wire_ok
            else:
                with scope("ainic.collective_update"):
                    g_red = fused_update.reduce_scatter(flat_g, ax, coll)
            g_own = g_red / self.n
            if icheck:
                diag["grad_norm"] = jnp.sqrt(
                    lax.psum(jnp.sum(g_own.astype(jnp.float32) ** 2), ax))
            if obs_on:
                # captured HERE, pre-clip (the documented definition):
                # below this point g_own may be rescaled by clipping
                m["grad_norm"] = diag["grad_norm"] if "grad_norm" in diag \
                    else jnp.sqrt(lax.psum(
                        jnp.sum(g_own.astype(jnp.float32) ** 2), ax))
            with scope("ainic.optimizer"):
                g_own = optim.clip_by_global_norm(opt_cfg, g_own, (ax,))
                w_new, opt_state2 = optim.apply(opt_cfg, w_own, g_own,
                                                opt_state, step)
            if icheck:
                # gate the update: a corrupted reduce-scatter must not
                # reach the master weights — the step becomes a no-op and
                # the host decides (retry / restore) from the diag verdict
                ok = diag["integrity_ok"] & diag["wire_ok"]
                w_new = jnp.where(ok, w_new, w_own)
                opt_state2 = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(ok, new, old),
                    opt_state2, opt_state)
                if ef:
                    # a gated (replayed) step must not mutate the residual
                    # either, or the retry would double-count this step's
                    # dropped mass
                    new_resid = jnp.where(ok, new_resid, maybe_resid[0])
            loss_m = lax.pmean(loss, ax)
            if obs_on:
                if coll.integrity_check:
                    m["integrity_err"] = diag["integrity_err"]
                m["loss"] = loss_m
            out = (w_new, opt_state2, loss_m, diag)
            return out + ((new_resid,) if ef else ()) + ((m,) if obs_on
                                                         else ())

        # Phase 2 (no autodiff): all-gather updated weights -> replicated
        # working params (the reference's host write-back of w_new,
        # hw/all_reduce.sv:1286-1311).  With integrity on, this wire is
        # checksummed too: a corrupted weight gather poisons the
        # REPLICATED params (the masters are safe), so the verdict is
        # surfaced for check_step_diag — the elastic ladder rebuilds the
        # params from the still-clean masters.
        @scope("ainic.gather")
        def shard_gather(w_new):
            if coll.integrity_check:
                flat_w, ag_ok = fused_update.all_gather_flat(
                    w_new, ax, coll, integrity=True)
                return fused_update.unflatten_tree(flat_w, meta), ag_ok
            flat_w = fused_update.all_gather_flat(w_new, ax, coll)
            return fused_update.unflatten_tree(flat_w, meta)

        def _step(state: TrainState, batch):
            self.step_traces += 1           # trace-count bookkeeping only
            in_specs = (P(), P(ax), P(ax), P(), P(ax)) + (
                (P(ax),) if ef else ())
            out_specs = (P(ax), P(ax), P(), P()) + (
                (P(ax),) if ef else ()) + ((P(),) if obs_on else ())
            args = (state.params, state.w_own, state.opt_state, state.step,
                    batch) + ((state.codec_state,) if ef else ())
            res = jax.shard_map(
                shard_update, mesh=self.mesh,
                in_specs=in_specs, out_specs=out_specs)(*args)
            w_own, opt_state, loss, diag = res[:4]
            codec_state = res[4] if ef else state.codec_state
            if obs_on:
                # route the loss through the metrics tap: the callback
                # delivers the step's metric scalars to the ambient
                # MetricsSink; consuming the tapped loss keeps it alive
                loss = obs_metrics.tap(loss, res[-1])
            if coll.integrity_check:
                new_params, ag_ok = jax.shard_map(
                    shard_gather, mesh=self.mesh, in_specs=P(ax),
                    out_specs=(P(), P()), check_vma=False)(w_own)
                diag = dict(diag, wire_ok=diag["wire_ok"] & ag_ok)
            else:
                new_params = jax.shard_map(
                    shard_gather, mesh=self.mesh, in_specs=P(ax),
                    out_specs=P(), check_vma=False)(w_own)
            new_state = TrainState(new_params, w_own, opt_state,
                                   state.step + 1, codec_state)
            if coll.integrity_check:
                # metrics dict instead of the bare loss: the elastic loop
                # (parallel.elastic) reads the integrity verdict from here
                return new_state, dict(diag, loss=loss)
            return new_state, loss

        return jax.jit(_step, donate_argnums=(0,))

    def step(self, state: TrainState, batch) -> Tuple[TrainState, jax.Array]:
        return self.step_fn(state, batch)

    # -- telemetry ----------------------------------------------------------

    def obs_static_metrics(self) -> dict:
        """Trace-time-constant telemetry facts for ``MetricsSink(static=)``:
        flat layout, declared codec properties, wire bytes per all-reduce
        (the flit-counter arithmetic of hw/bfp_adapter.sv:705-729)."""
        meta = self._meta
        assert meta is not None, "call init_state first"
        coll = self.cfg.collective
        d = {"padded_len": meta.padded_len, "n_devices": self.n,
             "impl": coll.impl, "topology": coll.topology}
        d.update(obs_metrics.codec_static_metrics(self._codec,
                                                  meta.padded_len))
        d["wire_bytes_per_allreduce"] = fused_update.wire_bytes_for(
            coll, meta.padded_len, self.n)
        d["raw_bytes_per_allreduce"] = fused_update.wire_bytes_for(
            coll, meta.padded_len, self.n, codec=None)
        if coll.topology == "hier":
            from ..ops import ring_hier
            d["hier_plan"] = ring_hier.plan_hier(
                meta.padded_len, self.n, coll.intra_size,
                self._codec).describe()
        if self._tuned_plan is not None:
            # the banked tuning decision: obs-gate diffs the declared
            # wire bytes (tune.* keys) across PRs, so a silent change of
            # plan or accounting fails CI, not a doc
            d["tune"] = self._tuned_plan.describe()
        return d

    # -- restore ------------------------------------------------------------

    @functools.cached_property
    def _gather_fn(self):
        """The jitted master->params gather, built ONCE per layout: a
        fresh closure per call would re-enter jax's jit cache (and
        recompile) on every restore/reshard — recovery-path time that is
        pure waste.  Invalidated with step_fn by _ensure_meta."""
        meta = self._meta
        assert meta is not None, "call init_state first (defines the layout)"
        coll, ax = self.cfg.collective, self.ax

        def _gather(w):
            self.gather_traces += 1         # trace-count bookkeeping only
            flat = fused_update.all_gather_flat(w, ax, coll)
            return fused_update.unflatten_tree(flat, meta)

        return jax.jit(jax.shard_map(
            _gather, mesh=self.mesh, in_specs=P(self.ax), out_specs=P(),
            check_vma=False))

    def params_from_master(self, w_own: jax.Array):
        """Rebuild the replicated working params from the sharded f32 master
        vector — the checkpoint-restore analogue of the fused step's gather
        phase.  Needed because checkpoints persist only the master shards."""
        return self._gather_fn(w_own)

    def restore_state(self, restored: dict,
                      params_like=None) -> TrainState:
        """TrainState from a Checkpointer.restore() payload.  Layout must
        be known: call init_state first or pass params_like (a params tree
        or jax.eval_shape output — zero device work)."""
        if params_like is not None:
            self._ensure_meta(params_like)
        assert self._meta is not None, (
            "flat layout unknown: call init_state first or pass params_like")
        # re-pad onto THIS mesh's flat layout: the checkpoint may have
        # been written at a different dp width (fused_update.repad_flat),
        # so restore re-gathers the same live elements under new padding
        sh = NamedSharding(self.mesh, P(self.ax))
        w_own = jax.device_put(
            fused_update.repad_flat(restored["w_own"], self._meta), sh)
        opt_state = {
            k: jax.device_put(fused_update.repad_flat(v, self._meta), sh)
            for k, v in restored["opt_state"].items()}
        return TrainState(
            params=self.params_from_master(w_own), w_own=w_own,
            opt_state=opt_state, step=jnp.asarray(restored["step"]),
            # EF residual restarts at zero: it is a bounded local
            # accumulator, and checkpoints persist only the masters
            codec_state=self._init_codec_state())

    # -- live resharding (parallel.reshard) ---------------------------------

    def reshard_leaves(self, state: TrainState) -> dict:
        """The state's flat-vector leaves in the shared transfer naming
        (reshard.pack_state_leaves) — what a live mesh move must
        transport (masters + optimizer moments; the replicated working
        params are REBUILT from the landed masters, not moved, and the
        EF residual rides its own per-device plan)."""
        from . import reshard as reshard_lib
        return reshard_lib.pack_state_leaves(state.w_own, state.opt_state)

    def state_from_reshard(self, leaves: dict, step,
                           codec_state) -> TrainState:
        """Assemble this trainer's state from landed reshard leaves (the
        inverse of ``reshard_leaves`` on the TARGET mesh): params are
        rematerialized by the same gather phase a checkpoint restore
        uses, so a resharded state and a restored one are constructed
        identically — the bit-parity contract."""
        from . import reshard as reshard_lib
        w_own, opt_state = reshard_lib.split_state_leaves(leaves)
        return TrainState(params=self.params_from_master(w_own),
                          w_own=w_own, opt_state=opt_state,
                          step=jnp.asarray(step), codec_state=codec_state)

    # -- data ---------------------------------------------------------------

    @property
    def batch_spec(self):
        """PartitionSpec for batch leaves (loaders pass this to
        ShardedLoader) — same public handle as ShardedTrainer."""
        return P(self.ax)

    def shard_batch(self, batch):
        """Place a host batch with sharding over dp (MPI_Scatter analogue)."""
        return mesh_lib.shard_host_batch(batch, self.mesh, self.batch_spec)
