"""Multi-axis sharded trainer: dp x tp x sp with ZeRO-1 over dp.

Generalizes `parallel.train.DPTrainer` (the reference's shape: pure DP,
SURVEY.md §2) to the full mesh the BASELINE configs demand:

- tp: params arrive tp-sharded per the model's ``param_specs``; the model
  itself closes its row-parallel sums with ``psum(tp)``.
- sp: batch sequence axis sharded; gradients are partial per sequence shard
  and are summed over sp before the weight update.
- dp: batch axis sharded; the fused ZeRO-1 collective (reduce-scatter ->
  optimizer on owned f32 master shard -> all-gather of updated weights)
  runs over dp, per tp shard.

Master/optimizer state layout: one flat f32 vector per tp shard, sharded
over dp — a global 1-D array of length tp * padded_len with spec
P(("tp", "dp")).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import accum
from . import mesh as mesh_lib
from .. import optim
from ..ops import fused_update
from ..utils.config import TrainConfig


class ShardedState(NamedTuple):
    params: Any            # tp-sharded working weights (model dtype)
    w_own: jax.Array       # [tp * padded_len] f32, spec P(("tp","dp"))
    opt_state: Any
    step: jax.Array


def _axis_factor(spec_entry, mesh: Mesh) -> int:
    if spec_entry is None:
        return 1
    names = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
    f = 1
    for nm in names:
        f *= mesh.shape[nm]
    return f


def local_shape_tree(tree, specs, mesh: Mesh):
    """ShapeDtypeStructs of the per-device shards given PartitionSpecs."""
    def one(leaf, spec):
        shape = list(leaf.shape)
        for d, entry in enumerate(spec):
            shape[d] //= _axis_factor(entry, mesh)
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return jax.tree_util.tree_map(one, tree, specs,
                                  is_leaf=lambda x: isinstance(x, P))


class ShardedTrainer:
    """loss_fn(params_local, batch_local) -> scalar, already closed over the
    model's tp/sp axis names.  batch leaves are [global_batch, global_seq]
    and shard as P(dp, sp)."""

    def __init__(self, loss_fn: Callable, mesh: Mesh, cfg: TrainConfig,
                 param_specs, *, dp_axis: str = "dp", tp_axis: str = "tp",
                 sp_axis: str = "sp", pp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None,
                 loss_and_grads_fn: Optional[Callable] = None):
        """loss_and_grads_fn(params_local, batch_local) -> (loss, grads):
        an explicit-gradient alternative to jax.grad(loss_fn) — the hook
        for schedules that produce gradients themselves, e.g. the 1F1B
        pipeline (llama.loss_and_grads_pp_1f1b).  The contract matches
        what vma autodiff would produce: dp-varying per-shard grads (the
        trainer's manual dp reduction follows), tp/pp-replicated leaves
        already psum'd.  Mutually exclusive with accum_steps > 1 (1F1B
        already microbatches inside the schedule)."""
        self.loss_fn = loss_fn
        self.loss_and_grads_fn = loss_and_grads_fn
        if loss_and_grads_fn is not None and cfg.accum_steps > 1:
            raise ValueError(
                "loss_and_grads_fn (explicit-gradient schedule) does not "
                "compose with accum_steps > 1 — fold accumulation into "
                "the schedule's num_microbatches instead")
        if cfg.collective.integrity_check:
            raise ValueError(
                "integrity_check is implemented on DPTrainer only (both "
                "value and exact wire tiers ride its step diag); "
                "ShardedTrainer's dp reduce/gather do not thread the "
                "verdicts yet, and a silently ignored flag would be "
                "claimed-but-absent coverage — construct with "
                "integrity_check=False (docs/CHAOS.md 'Exact wire "
                "integrity')")
        self.mesh = mesh
        self.cfg = cfg
        self.param_specs = param_specs
        self.dp, self.tp, self.sp = dp_axis, tp_axis, sp_axis
        self.pp, self.ep = pp_axis, ep_axis
        # flat-master sharding: one distinct f32 shard per (tp[, pp, ep])
        # model shard, split over dp for ZeRO-1
        self._waxes = ((tp_axis,) + ((pp_axis,) if pp_axis else ())
                       + ((ep_axis,) if ep_axis else ()) + (dp_axis,))
        # token/batch sharding: ep splits the batch alongside dp (experts
        # exchange tokens within the ep group via all_to_all)
        self._bspec = P((dp_axis, ep_axis) if ep_axis else dp_axis, sp_axis)
        self.n_dp = mesh.shape[dp_axis]
        self._meta = None

    @property
    def batch_spec(self):
        """PartitionSpec batch leaves are sharded with — the public handle
        for data loaders (`data.ShardedLoader(..., tr.batch_spec)`)."""
        return self._bspec

    # -- init ---------------------------------------------------------------

    def shard_params(self, params):
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params, self.param_specs,
            is_leaf=lambda x: isinstance(x, P))

    def _ensure_meta(self, params_like) -> None:
        """Derive the flat-master layout from a params tree OR a tree of
        ShapeDtypeStructs (e.g. ``jax.eval_shape(model.init, ...)``) — no
        device work, so a restoring process never materializes throwaway
        params."""
        local = local_shape_tree(params_like, self.param_specs, self.mesh)
        self._meta = fused_update.flat_meta(local, self.cfg.collective,
                                            self.n_dp)
        self.__dict__.pop("step_fn", None)
        self.__dict__.pop("_gather_fn", None)

    def _norm_weight_tables(self):
        """Segment tables for per-element global-norm weights over the
        LOCAL flat master layout: leaves replicated across the non-dp
        master axes get weight 1/replication so the cross-axis psum counts
        each parameter once; sharded leaves (disjoint across ranks) get 1;
        padding gets 0.  Returned as (bounds [n+1], values [n]) so each
        device materializes only ITS dp-chunk of weights (a searchsorted
        over ~n_leaves boundaries), never the full flat vector."""
        meta = self._meta
        assert meta is not None, "call init_state/_ensure_meta first"
        spec_leaves = jax.tree_util.tree_leaves(
            self.param_specs, is_leaf=lambda x: isinstance(x, P))
        assert len(spec_leaves) == len(meta.sizes), (
            len(spec_leaves), len(meta.sizes))
        non_dp = [a for a in self._waxes if a != self.dp]
        bounds, values = [0], []
        for spec, size in zip(spec_leaves, meta.sizes):
            used = set()
            for entry in tuple(spec):
                if entry is None:
                    continue
                used.update(entry if isinstance(entry, tuple) else (entry,))
            rep = 1
            for a in non_dp:
                if a not in used:
                    rep *= self.mesh.shape[a]
            bounds.append(bounds[-1] + size)
            values.append(1.0 / rep)
        if bounds[-1] < meta.padded_len:       # padding segment
            bounds.append(meta.padded_len)
            values.append(0.0)
        return (np.asarray(bounds, np.int32),
                np.asarray(values, np.float32))

    def init_state(self, params) -> ShardedState:
        coll, opt_cfg = self.cfg.collective, self.cfg.optimizer
        params = self.shard_params(params)
        self._ensure_meta(params)
        meta, dp = self._meta, self.dp

        def _init(p):
            w_own, opt_state, _ = fused_update.init_master_shard(
                p, dp, coll, opt_cfg)
            return w_own, opt_state

        w_own, opt_state = jax.jit(jax.shard_map(
            _init, mesh=self.mesh, in_specs=(self.param_specs,),
            out_specs=P(self._waxes), check_vma=False))(params)
        return ShardedState(params=params, w_own=w_own, opt_state=opt_state,
                            step=jnp.zeros((), jnp.int32))

    # -- step ---------------------------------------------------------------

    @functools.cached_property
    def step_fn(self):
        coll, opt_cfg = self.cfg.collective, self.cfg.optimizer
        meta = self._meta
        assert meta is not None, "call init_state first"
        dp, tp, sp, pp, ep = self.dp, self.tp, self.sp, self.pp, self.ep
        n_sp = self.mesh.shape[sp]
        w_spec = P(self._waxes)
        b_spec = self._bspec
        clip_tables = (self._norm_weight_tables()
                       if opt_cfg.clip_norm is not None else None)

        # Phase 1 runs with check_vma=True: differentiating THROUGH
        # collectives (tp psum, sp loss reduction, ring-attention ppermute)
        # is only sound with variance tracking on — with it, the transposes
        # of auto-inserted pvary ops ARE the tp/sp gradient reductions.
        # (check_vma=False silently corrupts those gradients.)
        def shard_update(params, w_own, opt_state, step, batch):
            # dp goes varying BEFORE grad so the dp reduction stays manual
            # (reduce-scatter, fusible, compressible); sp and tp stay as-is
            # so vma-typed autodiff inserts exactly the right psums for
            # sequence shards and tp-replicated params.
            params_v = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, dp, to="varying"), params)
            if self.loss_and_grads_fn is not None:
                loss, grads = self.loss_and_grads_fn(params_v, batch)
            else:
                loss, grads = accum.accumulated_value_and_grad(
                    self.loss_fn, self.cfg.accum_steps)(params_v, batch)
            flat_g, _ = fused_update.flatten_tree(grads, coll, self.n_dp)
            g_own = fused_update.reduce_scatter(flat_g, dp, coll) / self.n_dp
            if opt_cfg.clip_norm is not None:
                # per-element weights de-duplicate tp/pp/ep-REPLICATED
                # leaves in the cross-axis psum (sharded leaves are
                # disjoint, weight 1); built per-device from the tiny
                # segment tables so no full-length constant is embedded
                bounds, values = clip_tables
                c = g_own.shape[0]
                pos = (lax.axis_index(dp) * c
                       + lax.broadcasted_iota(jnp.int32, (c, 1), 0)[:, 0])
                seg = jnp.searchsorted(jnp.asarray(bounds), pos,
                                       side="right") - 1
                w_chunk = jnp.asarray(values)[seg]
                g_own = optim.clip_by_global_norm(
                    opt_cfg, g_own, self._waxes, weights=w_chunk)
            w_new, opt_state2 = optim.apply(opt_cfg, w_own, g_own,
                                            opt_state, step)
            loss = lax.pmean(loss, dp)
            loss = lax.pmean(loss, tp)     # numerically identity; clears vma
            if n_sp == 1:
                loss = lax.pmean(loss, sp)  # loss_fn psums sp when n_sp > 1
            if pp is not None:
                loss = lax.pmean(loss, pp)  # identity: loss_fn psums pp
            if ep is not None:
                loss = lax.pmean(loss, ep)  # identity: loss_fn psums ep
            return w_new, opt_state2, loss

        gather = self._gather_fn       # phase 2: weights back to working copy

        def _step(state: ShardedState, batch):
            w_own, opt_state, loss = jax.shard_map(
                shard_update, mesh=self.mesh,
                in_specs=(self.param_specs, w_spec, w_spec, P(),
                          b_spec),
                out_specs=(w_spec, w_spec, P()),
            )(state.params, state.w_own, state.opt_state, state.step, batch)
            return ShardedState(gather(w_own), w_own, opt_state,
                                state.step + 1), loss

        return jax.jit(_step, donate_argnums=(0,))

    @functools.cached_property
    def _gather_fn(self):
        """Jitted gather of the flat masters into the working params tree —
        phase 2 of the fused step AND the checkpoint-restore
        rematerialization (one definition so they cannot drift; cached so
        repeated params_from_master calls hit jit's cache, invalidated by
        _ensure_meta)."""
        meta, coll, dp = self._meta, self.cfg.collective, self.dp
        assert meta is not None, "call init_state/_ensure_meta first"

        def shard_gather(w_new):
            flat_w = fused_update.all_gather_flat(w_new, dp, coll)
            return fused_update.unflatten_tree(flat_w, meta)

        return jax.jit(jax.shard_map(shard_gather, mesh=self.mesh,
                                     in_specs=P(self._waxes),
                                     out_specs=self.param_specs,
                                     check_vma=False))

    def step(self, state: ShardedState, batch) -> Tuple[ShardedState, jax.Array]:
        return self.step_fn(state, batch)

    # -- restore ------------------------------------------------------------

    def params_from_master(self, w_own: jax.Array):
        """Rematerialize the working params tree from the flat master shards
        (the fused step's gather phase, run standalone — checkpoint-restore
        needs it because checkpoints persist only the masters)."""
        return self._gather_fn(w_own)

    def restore_state(self, restored: dict,
                      params_like=None) -> ShardedState:
        """ShardedState from a Checkpointer.restore() payload.

        The flat layout must be known: either call init_state first, or
        pass ``params_like`` — a params tree or ShapeDtypeStructs (e.g.
        ``jax.eval_shape(functools.partial(model.init, key), cfg)``), which
        sets it with zero device work."""
        if params_like is not None:
            self._ensure_meta(params_like)
        assert self._meta is not None, (
            "flat layout unknown: call init_state first or pass params_like")
        sh = NamedSharding(self.mesh, P(self._waxes))
        w_own = jax.device_put(jnp.asarray(restored["w_own"]), sh)
        opt_state = {k: jax.device_put(jnp.asarray(v), sh)
                     for k, v in restored["opt_state"].items()}
        return ShardedState(
            params=self.params_from_master(w_own), w_own=w_own,
            opt_state=opt_state, step=jnp.asarray(restored["step"]))

    def shard_batch(self, batch):
        return mesh_lib.shard_host_batch(batch, self.mesh, self._bspec)
