"""The fused all-reduce + weight-update engine — the reference's defining
capability, rebuilt TPU-first.

Reference semantics (SURVEY.md §3.2): gradients stream through a ring
reduce-scatter; the *reduced* gradient shard feeds a fused SGD unit holding
the canonical weights (hw/weight_update.sv); the all-gather phase then
distributes **updated weights**, not gradients (hw/all_reduce.sv:996-1086).
That is exactly ZeRO-1: sharded optimizer + master weights, gather of the
updated parameters.  On TPU we express it as

    g_own   = reduce_scatter(flat_grads)        # XLA psum_scatter or BFP ring
    w_own'  = opt(w_own, g_own / n)             # owned f32 master shard
    params' = all_gather(cast(w_own'))          # replicated working copy

inside ``shard_map``; XLA overlaps the collectives with surrounding compute
the way the FPGA overlapped its ring with the host's backward GEMMs
(sw/mlp_mpi_example_f32.cpp:735-787).

Pytrees are flattened into one contiguous f32 vector (padded to a
lcm(n, bfp_block) multiple) before the collective, mirroring the reference's
treatment of the model as one long gradient stream sliced into 32 KiB
blocks (hw/all_reduce.sv:101-103,246-253).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import ring as ring_ops
from .. import optim
from ..obs.names import scope
from ..utils.config import CollectiveConfig, OptimizerConfig


class FlatMeta(NamedTuple):
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    padded_len: int


def resolve_codec(coll: CollectiveConfig):
    """The compress.Codec this config asks for (None = uncompressed) —
    one definition so every consumer (ring routing, padding, trainers,
    integrity tolerance) resolves identically."""
    from ..compress import resolve
    return resolve(coll)


def pad_multiple(coll: CollectiveConfig, n: int) -> int:
    """Padding multiple for flat vectors fed to the n-way collective: the
    per-device chunk (len / n) must be a whole number of codec units (BFP
    block / top-k bucket / int8 block) — and of (block, 128)-lane tiles
    when the fused Pallas kernel carries the wire (its frames are native
    int8 tiles)."""
    codec = resolve_codec(coll)
    if codec is not None:
        if getattr(coll, "fused_kernel", False):
            from . import ring_pallas
            return n * codec.pad_elems * ring_pallas.LANES
        return n * codec.pad_elems
    return n


def wire_bytes_for(coll: CollectiveConfig, L: int, n: int,
                   codec="__resolve__") -> int:
    """Topology-aware per-device wire bytes for one all-reduce of an
    [L]-element flat f32 vector under this config — the flit-counter
    arithmetic every consumer (obs statics, queued telemetry, bucket
    accounting) must share so the declaration can never drift from the
    routing.  ``codec`` defaults to the config's own resolution; pass
    None explicitly for the raw-f32 accounting."""
    if codec == "__resolve__":
        codec = resolve_codec(coll)
    if getattr(coll, "topology", "flat") == "hier":
        from . import ring_hier
        return ring_hier.wire_bytes_per_device(L, n, coll.intra_size,
                                               codec)
    return ring_ops.wire_bytes_per_device(L, n, codec)


def flat_meta(tree, coll: CollectiveConfig, n: int) -> FlatMeta:
    """Static flattening metadata from a pytree of arrays (or shape structs)
    without touching device memory."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(l.shape for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    total = sum(sizes)
    m = pad_multiple(coll, n)
    padded = total + ((-total) % m)
    return FlatMeta(treedef, shapes, dtypes, sizes, padded)


def flatten_tree(tree, coll: CollectiveConfig, n: int) -> Tuple[jax.Array, FlatMeta]:
    """Concatenate a pytree into one flat f32 vector, zero-padded so the
    per-device chunk is a whole number of BFP blocks."""
    meta = flat_meta(tree, coll, n)
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
    pad = meta.padded_len - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, meta


def repad_flat(v, meta: FlatMeta) -> jax.Array:
    """Re-fit a saved flat master/optimizer vector to THIS layout's
    padded length.  The padding multiple depends on the collective's
    device count (``pad_multiple(coll, n)``), so a checkpoint written on
    one mesh shape carries a different tail padding than the mesh it
    restores onto (dp8 -> dp4 after a preemption, or a codec change);
    the LIVE elements (``sum(meta.sizes)``) are mesh-invariant, and every
    pad element is zero by construction (flatten_tree zero-pads, and the
    optimizers keep zero-gradient pad lanes at zero), so the re-fit is
    value-exact.  A vector with fewer than the live elements is a
    different model's checkpoint — loud error, never a truncation."""
    v = jnp.asarray(v)
    total = sum(meta.sizes)
    if v.shape[0] < total:
        raise ValueError(
            f"flat state of length {v.shape[0]} cannot hold this "
            f"layout's {total} live elements — wrong checkpoint/model")
    if v.shape[0] == meta.padded_len:
        return v
    # the stripped tail must be the zero padding — a NONZERO tail means
    # the vector belongs to a different model/layout whose live elements
    # extend past this layout's, and stripping it would silently corrupt
    # the restore (eager-only check: restore paths run outside jit)
    tail = v[total:]
    if tail.size and float(jnp.abs(tail).max()) != 0.0:
        raise ValueError(
            f"flat state of length {v.shape[0]} carries nonzero data "
            f"past this layout's {total} live elements — wrong "
            "checkpoint/model (refusing to truncate)")
    return jnp.pad(v[:total], (0, meta.padded_len - total))


def params_like_from_meta(meta: FlatMeta):
    """Rebuild a zero-device-work params pytree (ShapeDtypeStructs) from
    flattening metadata — the handle a TARGET trainer needs to derive its
    own layout (``_ensure_meta``) when the live state arrives from another
    mesh shape (parallel.reshard) instead of from ``init_state``."""
    leaves = [jax.ShapeDtypeStruct(s, d)
              for s, d in zip(meta.shapes, meta.dtypes)]
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def unflatten_tree(flat: jax.Array, meta: FlatMeta):
    leaves, off = [], 0
    for shape, dtype, size in zip(meta.shapes, meta.dtypes, meta.sizes):
        leaves.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def shard_slice(flat: jax.Array, axis_name: str) -> jax.Array:
    """This device's chunk of a replicated flat vector (natural order)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    c = flat.shape[0] // n
    return lax.dynamic_slice_in_dim(flat, idx * c, c)


_warned_fused_fallback = False


def _warn_fused_fallback() -> None:
    """fused_kernel=True off TPU falls back to the separate-op ring with
    the CONFIGURED codec (default "xla": contiguous block grouping) — the
    pallas interpret codec cannot run inside vma-checked shard_maps — so
    the quantization bits differ from the TPU kernel's lane-layout
    partition.  Same wire rate and error bound, but training runs are not
    bit-reproducible across platforms; surface that once instead of
    silently diverging (round-3 advisor finding)."""
    global _warned_fused_fallback
    if not _warned_fused_fallback:
        _warned_fused_fallback = True
        import warnings
        warnings.warn(
            "CollectiveConfig.fused_kernel=True on a non-TPU backend: "
            "routing to the separate-op ring with the configured codec. "
            "Quantization block grouping (and therefore the exact bits) "
            "differs from the TPU fused kernel's lane layout; numerics "
            "are equivalent in rate/error but not bit-reproducible "
            "across platforms.", stacklevel=3)


def _fused_bfp_cfg(coll: CollectiveConfig):
    """The BFPConfig driving the fused Pallas kernels (config validation
    guarantees the resolved codec supports_fused, i.e. is BFP)."""
    return resolve_codec(coll).cfg


def ring_all_reduce_routed(flat: jax.Array, axis_name: str,
                           coll: CollectiveConfig,
                           chunk_len: int):
    """Explicit-ring all-reduce respecting the fused_kernel AND topology
    routing (one definition shared by all_reduce_mean and ops.bucketed so
    the fallback/slice/topology policy cannot drift between call sites).

    Carries no ``integrity=`` seam on purpose: every caller is a
    bucketed/queued DDP reduce, and those trainers reject
    integrity_check at construction until they thread the verdicts —
    an untestable flag here would be claimed-but-unverified coverage."""
    codec = resolve_codec(coll)
    if getattr(coll, "topology", "flat") == "hier":
        from . import ring_hier
        return ring_hier.hier_all_reduce(
            flat, axis_name, coll.intra_size, compression=codec,
            slice_elems=coll.slice_elems, unroll=coll.unroll_hops)
    if coll.fused_kernel:
        from . import ring_pallas
        bcfg = _fused_bfp_cfg(coll)
        slice_e = ring_pallas.pick_slice_elems(
            chunk_len, coll.slice_elems, bcfg.block_size)
        if ring_pallas._is_tpu():
            return ring_pallas.ring_all_reduce_fused(
                flat, axis_name, compression=bcfg,
                slice_elems=slice_e,
                pipeline_depth=coll.pipeline_depth)
        _warn_fused_fallback()
        return ring_ops.ring_all_reduce(
            flat, axis_name, compression=codec,
            slice_elems=slice_e, unroll=coll.unroll_hops)
    return ring_ops.ring_all_reduce(flat, axis_name,
                                    compression=codec,
                                    slice_elems=coll.slice_elems,
                                    unroll=coll.unroll_hops)


def reduce_scatter(flat_g: jax.Array, axis_name: str,
                   coll: CollectiveConfig, integrity: bool = False):
    """``integrity=True`` returns ``(owned, wire_ok)``; wire_ok is the
    exact frame-conservation verdict of the routed collective
    (ops.integrity).  impl='xla' owns its own wire (no explicit frames
    to checksum), so its verdict is constant True — the exact tier is a
    property of the explicit-ring routes."""
    if coll.impl == "xla":
        out = lax.psum_scatter(flat_g, axis_name, scatter_dimension=0,
                               tiled=True)
        return (out, jnp.bool_(True)) if integrity else out
    codec = resolve_codec(coll)
    if getattr(coll, "topology", "flat") == "hier":
        from . import ring_hier
        return ring_hier.hier_reduce_scatter(
            flat_g, axis_name, coll.intra_size, compression=codec,
            slice_elems=coll.slice_elems, unroll=coll.unroll_hops,
            integrity=integrity)
    if coll.fused_kernel:
        from . import ring_pallas
        n = lax.axis_size(axis_name)
        bcfg = _fused_bfp_cfg(coll)
        slice_e = ring_pallas.pick_slice_elems(
            flat_g.shape[0] // n, coll.slice_elems, bcfg.block_size)
        if ring_pallas._is_tpu():
            return ring_pallas.ring_reduce_scatter_fused(
                flat_g, axis_name, compression=bcfg,
                slice_elems=slice_e,
                pipeline_depth=coll.pipeline_depth,
                integrity=integrity)
        # off-TPU: the separate-op ring with the CONFIGURED codec (see
        # _warn_fused_fallback); the kernel's own bit-exactness story
        # lives in tests/test_ring_pallas.py
        _warn_fused_fallback()
        return ring_ops.ring_reduce_scatter(
            flat_g, axis_name, compression=codec,
            slice_elems=slice_e, unroll=coll.unroll_hops,
            integrity=integrity)
    return ring_ops.ring_reduce_scatter(flat_g, axis_name,
                                        compression=codec,
                                        slice_elems=coll.slice_elems,
                                        unroll=coll.unroll_hops,
                                        integrity=integrity)


def reduce_scatter_update(flat_g: jax.Array, w_own: jax.Array, opt_state,
                          step, axis_name: str, coll: CollectiveConfig,
                          opt_cfg: OptimizerConfig,
                          integrity: bool = False):
    """Fused gradient reduce + ZeRO-1 optimizer update: the reference's
    whole point (decode feeds hw/weight_update.sv, no separate optimizer
    pass over HBM) + cross-replica weight-update sharding (ZeRO-1).

    Routing (one definition so trainers cannot drift):
      - fused_kernel on TPU: the in-kernel path —
        ops.ring_pallas.ring_reduce_scatter_update_fused updates the
        owned shard as each final-hop slice decodes, inside the depth-D
        pipeline; w/state shards are donated kernel operands.
      - everything else (xla psum_scatter, separate-op ring with any
        codec, the off-TPU fallback, n == 1): the identical update
        formula (optim.fused_apply_flat) fused into the step right after
        the reduce — same hyper vector, same golden twin, so the
        numerics contract is uniform across routes.

    Returns ``(g_own_sum, w_new, opt_state_new)``; g_own_sum is the raw
    reduced SUM shard (callers /n for metrics), bit-identical to
    ``reduce_scatter`` on the same route.

    ``integrity=True`` appends the exact wire verdict: ``(g_own_sum,
    w_new, opt_state_new, wire_ok)``.  On the in-kernel TPU route the
    kernel accumulates the frame checksums itself (the update retires
    with the final-hop decode and the state is DONATED — a tripped
    verdict invalidates the STEP via the elastic ladder, see
    runtime.chaos.check_step_diag); every other route still holds the
    pre-step state, so callers can gate the update in-graph
    (``update_route_gatable`` tells them which situation they are
    in)."""
    from ..utils.config import OptimizerSpec
    spec = OptimizerSpec.from_optimizer(opt_cfg)
    n = lax.axis_size(axis_name)
    hyper = optim.fused_hyperparams(opt_cfg, step)
    # topology='hier' always takes the shared-formula route below: the
    # hierarchical reduce_scatter carries the codec only on the slow
    # inter hop and the update fuses right after the reduce — identical
    # golden contract, zero exposed optimizer pass either way
    if coll.fused_kernel and n > 1 \
            and getattr(coll, "topology", "flat") == "flat":
        from . import ring_pallas
        if ring_pallas._is_tpu():
            bcfg = _fused_bfp_cfg(coll)
            slice_e = ring_pallas.pick_slice_elems(
                flat_g.shape[0] // n, coll.slice_elems, bcfg.block_size)
            return ring_pallas.ring_reduce_scatter_update_fused(
                flat_g, w_own, opt_state, hyper, axis_name,
                opt_kind=spec.kind, compression=bcfg, slice_elems=slice_e,
                pipeline_depth=coll.pipeline_depth, integrity=integrity)
        # off-TPU: reduce_scatter itself warns and routes to the
        # separate-op ring; the update below stays the shared formula
    res = reduce_scatter(flat_g, axis_name, coll, integrity=integrity)
    g_own, wire_ok = res if integrity else (res, None)
    with scope("ainic.optimizer"):
        w_new, st2 = optim.fused_apply_flat(spec, w_own, g_own, opt_state,
                                            hyper, n)
    if integrity:
        return g_own, w_new, st2, wire_ok
    return g_own, w_new, st2


def update_route_gatable(coll: CollectiveConfig, n: int = 0) -> bool:
    """True when ``reduce_scatter_update`` takes a route that still
    materializes the pre-step state — i.e. a tripped integrity verdict
    can be gated IN-GRAPH (``jnp.where(ok, new, old)``).  False only on
    the in-kernel TPU route, where the master/moment shards are donated
    kernel operands updated in place: referencing the old value after
    the call would read the aliased (already-updated) buffer, so the
    only safe recovery is invalidating the step on the host
    (check_step_diag -> elastic restore/reshard).  ``n`` is the axis
    size when the caller knows it (``reduce_scatter_update`` only takes
    the in-kernel route for n > 1 — a single-device mesh always runs
    the shared formula, hence gatable); 0 = unknown, assume the
    in-kernel route is reachable."""
    from . import ring_pallas
    return not (coll.fused_kernel and n != 1
                and getattr(coll, "topology", "flat") == "flat"
                and ring_pallas._is_tpu())


def all_gather_flat(owned: jax.Array, axis_name: str,
                    coll: CollectiveConfig, integrity: bool = False):
    """``integrity=True`` returns ``(gathered, wire_ok)`` — per-hop
    frame conservation on the explicit rings; the replica-agreement
    exact check on the fused TPU kernel (its wire lives inside the
    kernel); constant True on impl='xla' (no explicit frames)."""
    if coll.impl == "xla":
        out = lax.all_gather(owned, axis_name, tiled=True)
        return (out, jnp.bool_(True)) if integrity else out
    codec = resolve_codec(coll)
    if getattr(coll, "topology", "flat") == "hier":
        from . import ring_hier
        return ring_hier.hier_all_gather(
            owned, axis_name, coll.intra_size, compression=codec,
            unroll=coll.unroll_hops, integrity=integrity)
    if coll.fused_kernel:
        from . import ring_pallas
        if ring_pallas._is_tpu():
            out = ring_pallas.ring_all_gather_fused(
                owned, axis_name, compression=_fused_bfp_cfg(coll))
            if not integrity:
                return out
            from . import integrity as integrity_lib
            return out, integrity_lib.replica_consistent(out, axis_name)
        _warn_fused_fallback()
        return ring_ops.ring_all_gather(owned, axis_name,
                                        compression=codec,
                                        unroll=coll.unroll_hops,
                                        integrity=integrity)
    return ring_ops.ring_all_gather(owned, axis_name,
                                    compression=codec,
                                    unroll=coll.unroll_hops,
                                    integrity=integrity)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def all_gather_flat_vjp(owned: jax.Array, axis_name: str,
                        coll: CollectiveConfig) -> jax.Array:
    """`all_gather_flat` with an explicit VJP: differentiable ring/BFP path.

    ZeRO-3's gather-on-use sits INSIDE autodiff, where the explicit ring is
    a dead end for jax's automatic transpose: the rolled ppermute fori_loop
    has no reverse-mode rule and the BFP codec's int8 casts have no
    gradient.  But the *mathematical* transpose of an all-gather is simply
    the reduce-scatter — so this custom VJP declares it directly:

      forward:  ring all-gather of the (optionally BFP-encoded-once)
                master shards — replicas see wire-identical quantized bytes
                (hw/bfp_adapter.sv compressing the weight-output stream,
                hw/all_reduce.sv FORWARD_OUTPUT:996-1086);
      backward: the per-hop-compressed ring reduce-scatter of the full
                gradient cotangent (the adapter on the gradient stream).

    Quantized-forward semantics: with compression, the loss/grad are
    evaluated at the BFP-rounded parameters while the optimizer updates the
    exact f32 master — straight-through estimation, the same contract as
    the ZeRO-1 trainers' compressed weight gather.
    """
    return all_gather_flat(owned, axis_name, coll)


def _gather_vjp_fwd(owned, axis_name, coll):
    return all_gather_flat(owned, axis_name, coll), None


def _gather_vjp_bwd(axis_name, coll, _res, ct):
    # same routing as the forward collectives (incl. the fused-kernel
    # path and its slice plan) — the gradient stream is where most of the
    # wire bytes are
    return (reduce_scatter(ct, axis_name, coll),)


all_gather_flat_vjp.defvjp(_gather_vjp_fwd, _gather_vjp_bwd)


def error_feedback_encode(codec, flat_g: jax.Array,
                          residual: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Compensate-then-compress (SparCML §3 / EF-SGD): returns
    ``(g_wire, new_residual)`` where ``g_wire = roundtrip(flat_g +
    residual)`` is the locally-quantized gradient handed to the collective
    and ``new_residual`` is what this pass dropped — carried to the next
    step in the train state, so every coordinate is eventually
    transmitted.

    The residual compensates the LOCAL quantization (the first wire pass
    of this device's contribution); per-hop requantization of partial sums
    inside the ring stays bounded by the codec's declared error_bound and
    is measured end-to-end by evals/codec_convergence.  For idempotent
    codecs (bfp, topk) the ring's first re-encode of ``g_wire`` is exact,
    so the local roundtrip costs no extra wire error at all."""
    g_comp = flat_g + residual
    g_wire = codec.roundtrip(g_comp)
    return g_wire, g_comp - g_wire


def all_reduce_mean(tree, axis_name: str, coll: CollectiveConfig):
    """Plain (unfused) mean all-reduce of a gradient pytree — for training
    loops that keep a separate optimizer.  Uses psum or the BFP ring."""
    n = lax.axis_size(axis_name)
    if coll.impl == "xla":
        return jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name) / n, tree)
    flat, meta = flatten_tree(tree, coll, n)
    red = ring_all_reduce_routed(flat, axis_name, coll, flat.shape[0] // n)
    return unflatten_tree(red / n, meta)


def init_master_shard(params_tree, axis_name: str, coll: CollectiveConfig,
                      opt_cfg: OptimizerConfig):
    """Build (w_own, opt_state, meta) from a replicated params pytree.
    Run inside shard_map once at startup — the analogue of the reference's
    first-iteration weight download into FPGA-local DDR (flags=1 path,
    hw/weight_update.sv MEM_INIT, sw/mlp_mpi_example_f32.cpp:700)."""
    n = lax.axis_size(axis_name)
    flat_w, meta = flatten_tree(params_tree, coll, n)
    w_own = shard_slice(flat_w, axis_name)
    opt_state = optim.init_state(opt_cfg, w_own.shape[0])
    return w_own, opt_state, meta
