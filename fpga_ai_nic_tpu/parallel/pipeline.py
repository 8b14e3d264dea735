"""Pipeline parallelism (pp): GPipe microbatch schedule over a mesh axis.

The reference has no pipeline axis (its only strategy is DP over a ring of
FPGAs, SURVEY.md §2 "Parallelism strategies"), but its defining mechanism —
a static ring whose stages each own a slice of state and forward partial
results to the next hop (hw/all_reduce.sv st_eth_t, SEND_LOCAL/REDUCE/
FORWARD) — is exactly what a TPU pipeline stage does with activations.  We
reuse that shape: each device owns a contiguous slice of the layer stack,
processes one microbatch per tick, and `lax.ppermute`s its activation to the
next stage, keeping the ring full (1 bubble of pp-1 ticks per batch, the
GPipe schedule).

Everything is a single `lax.scan` inside `shard_map`, so XLA sees static
control flow; autodiff through ppermute gives the reverse-ring backward
schedule for free.

Layout contract:
- stage params: any pytree whose leaves are stacked [n_local_layers, ...]
  slices of the global [n_layers, ...] stack, sharded P(pp_axis, ...).
- activations: replicated over pp on entry; microbatching is temporal
  (B is split into num_microbatches chunks), so batch specs never mention pp.
- output: valid on the LAST stage; use `from_last_stage` (scalar-cheap psum
  mask) to make it pp-invariant.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
from jax import lax



def _pcast_to(x: jax.Array, vma) -> jax.Array:
    """Widen x's varying-manual-axes set to `vma` (scan carries must enter
    with the vma type their loop body produces)."""
    missing = tuple(sorted(set(vma) - set(jax.typeof(x).vma)))
    return lax.pcast(x, missing, to="varying") if missing else x


def _tree_vma(*trees) -> set:
    vma = set()
    for t in trees:
        for leaf in jax.tree_util.tree_leaves(t):
            vma |= set(jax.typeof(leaf).vma)
    return vma


def stack_layers(layers: List[Any]):
    """[{w: [..]}, ...] -> {w: [L, ..]}: stack a homogeneous list-of-pytrees
    along a new leading layer axis (shardable over pp)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked) -> List[Any]:
    leaves = jax.tree_util.tree_leaves(stacked)
    n = leaves[0].shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], stacked)
            for i in range(n)]


def scan_layers(block_fn: Callable, stacked_params, x, *,
                remat: bool = False):
    """Apply block_fn(layer_params, x) -> x over a stacked [L, ...] slice."""
    def fn(lyr, h):
        return block_fn(lyr, h), jnp.float32(0.0)

    out, _ = scan_layers_aux(fn, stacked_params, x, remat=remat)
    return out


def scan_layers_aux(block_fn: Callable, stacked_params, x, *,
                    remat: bool = False):
    """Apply block_fn(layer_params, x) -> (x, aux) over a stacked [L, ...]
    slice, summing the per-layer aux scalars (MoE load-balance loss)."""
    fn = jax.checkpoint(block_fn) if remat else block_fn

    # carry must enter varying over every axis the block output varies over
    # (block_fn is assumed vma-monotone, e.g. residual-style)
    vma = _tree_vma(x, stacked_params)

    def body(carry, lyr):
        h, acc = carry
        h, aux = fn(lyr, h)
        return (h, acc + aux.astype(jnp.float32)), None

    (out, aux), _ = lax.scan(
        body, (_pcast_to(x, vma), _pcast_to(jnp.float32(0.0), vma)),
        stacked_params)
    return out, aux


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   num_microbatches: int, pp_axis: str) -> jax.Array:
    """`pipeline_apply_aux` for aux-free stage_fn(stage_params, mb) -> mb."""
    out, _ = pipeline_apply_aux(
        lambda p, mb: (stage_fn(p, mb), jnp.float32(0.0)),
        stage_params, x, num_microbatches, pp_axis)
    return out


def pipeline_apply_aux(stage_fn: Callable, stage_params, x: jax.Array,
                       num_microbatches: int, pp_axis: str):
    """Run x through the full pipeline; call inside shard_map.

    stage_fn(stage_params, mb) -> (mb, aux) applies this device's layer
    slice to one microbatch, returning an auxiliary scalar (MoE
    load-balance loss; 0.0 for dense stacks).  x: [B, ...] replicated over
    pp, B % num_microbatches == 0.  Returns (out [B, ...], aux scalar) —
    out valid ONLY on the last stage (mask with `from_last_stage`); aux is
    already pp-invariant (psum over stages) and averaged over microbatches,
    matching the unpipelined path's one-full-batch aux up to the
    per-microbatch routing granularity.

    Schedule (per tick t of num_microbatches + pp - 1):
      stage 0 injects microbatch t; every stage applies its slice; the
      result rotates one hop down the ring (ppermute), exactly the
      reference's SEND_LOCAL -> REDUCE -> FORWARD slice rotation
      (hw/all_reduce.sv:891-1086) with layers in place of partial sums.
    Ticks where a stage holds no real microbatch compute on ring garbage;
    those results land in output slots that a later tick overwrites, and
    their aux contributions are masked out (stage s holds real microbatch
    t - s only when 0 <= t - s < num_microbatches).
    """
    n = lax.axis_size(pp_axis)
    stage = lax.axis_index(pp_axis)
    B = x.shape[0]
    assert B % num_microbatches == 0, (B, num_microbatches)
    mb = B // num_microbatches
    x_mb = x.reshape((num_microbatches, mb) + x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]

    # scan carries enter with the vma type the tick body produces: varying
    # over pp (stage index / ppermute) plus everything x or the params carry
    vma = _tree_vma(x, stage_params) | {pp_axis}
    state = _pcast_to(jnp.zeros_like(x_mb[0]), vma)
    outputs = _pcast_to(jnp.zeros_like(x_mb), vma)
    aux0 = _pcast_to(jnp.float32(0.0), vma)

    def tick(carry, t):
        state, outputs, aux_acc = carry
        inject = lax.dynamic_index_in_dim(x_mb, t % num_microbatches, 0,
                                          keepdims=False)
        cur = jnp.where(stage == 0, inject, state)
        out, aux = stage_fn(stage_params, cur)
        real = ((t >= stage) & (t - stage < num_microbatches))
        aux_acc = aux_acc + jnp.where(real, aux.astype(jnp.float32), 0.0)
        # Last stage finished microbatch t-(n-1); earlier ticks write garbage
        # at wrapped indices that tick t+num_microbatches overwrites.
        outputs = lax.dynamic_update_index_in_dim(
            outputs, out, (t - (n - 1)) % num_microbatches, 0)
        state = lax.ppermute(out, pp_axis, perm)
        return (state, outputs, aux_acc), None

    ticks = jnp.arange(num_microbatches + n - 1)
    (_, outputs, aux_acc), _ = lax.scan(tick, (state, outputs, aux0), ticks)
    aux = lax.psum(aux_acc, pp_axis) / num_microbatches
    return outputs.reshape(x.shape), aux


def _widen(tree, vma):
    """Widen every leaf to the full varying set, RECORDING the widened
    axes per leaf — the 1F1B schedulers' entry pcast whose manual
    transpose is the exit psum in ``_unwiden_grads`` (the reason is
    documented in pipeline_train_1f1b: a vjp-inserted psum inside a
    stage-divergent cond deadlocks the mesh).  The leaf types carry the
    exact varying set (including extra axes like dp)."""
    tmap = jax.tree_util.tree_map
    axes = tmap(lambda v: tuple(sorted(set(vma) - set(jax.typeof(v).vma))),
                tree)
    return tmap(lambda v: _pcast_to(v, vma), tree), axes


def _unwiden_grads(grads, axes):
    """Transpose of ``_widen``: psum each gradient leaf over exactly the
    axes it was widened over on entry."""
    return jax.tree_util.tree_map(
        lambda d, a: lax.psum(d, a) if a else d, grads, axes)


def _unit_fn(stage_fn, loss_head_fn, R: int):
    """The per-unit primal shared by both 1F1B schedulers: stage slice
    (+ its own loss contribution), then the loss head when `is_last`
    says this unit produces the final activations (the v=1 scheduler
    passes its stage==pp-1 flag; the interleaved one its per-tick
    virtual-stage-P-1 table flag).  The false branch derives its
    (varying) type from h with a zero-gradient sum, NOT a pcast — a
    pcast's transpose is a psum, which must not exist inside the
    schedulers' divergent conds.  The report channel rides along
    stop-gradiented (display only, never differentiated)."""
    def g(sp, hp, x_in, c_in, is_last):
        if R:
            h, stage_loss, rep_s = stage_fn(sp, hp, x_in, c_in)
            head_loss, head_rep = lax.cond(
                is_last,
                lambda: [o.astype(jnp.float32) for o in
                         loss_head_fn(hp, h, c_in)],
                lambda: [jnp.sum(h).astype(jnp.float32) * 0.0,
                         jnp.zeros((R,), jnp.float32)
                         + jnp.sum(h).astype(jnp.float32) * 0.0])
            rep = lax.stop_gradient(rep_s.astype(jnp.float32) + head_rep)
        else:
            h, stage_loss = stage_fn(sp, hp, x_in, c_in)
            head_loss = lax.cond(
                is_last,
                lambda: loss_head_fn(hp, h, c_in).astype(jnp.float32),
                lambda: jnp.sum(h).astype(jnp.float32) * 0.0)
            rep = jnp.zeros((0,), jnp.float32)
        return h, (stage_loss.astype(jnp.float32) + head_loss, rep)
    return g


def pipeline_train_1f1b(stage_fn: Callable, loss_head_fn: Callable,
                        stage_params, head_params, x: jax.Array,
                        ctx, num_microbatches: int,
                        pp_axis: str, report_len: int = 0):
    """One fused forward+backward pass under the 1F1B schedule — explicit
    per-tick scheduling of forwards, backwards, and both ring directions,
    returning gradients directly (no outer jax.grad).

    Why it exists: differentiating ``pipeline_apply`` (GPipe) makes jax
    save the forward scan's carries — O(num_microbatches) live
    activations per stage.  1F1B caps the in-flight window at the ring
    depth: stage s never holds more than pp - s microbatch activations,
    so the buffer here is a static [pp, ...] ring regardless of
    num_microbatches (the standard perf-grade schedule for deep stacks
    at large microbatch counts; beyond-reference — the reference has no
    pipeline axis at all).

    Schedule (derived; all stages lockstep, one work unit per tick):
      fwd of microbatch m at stage s:  tick  s + 2m
      bwd of microbatch m at stage s:  tick  2*pp - 1 - s + 2m
    Forward ticks have parity s, backward ticks parity s + 1 — each
    stage strictly alternates F,B,F,B with no same-tick collision, the
    activation arrives exactly one tick after the upstream forward, and
    the cotangent one tick after the downstream backward.  Total ticks
    2*(M + pp) - 3 vs GPipe's 2*(M + pp - 1) forward+backward units —
    same bubble, O(pp) memory.

    Backward recompute: at a backward tick the stage re-runs its forward
    under jax.vjp from the SAVED INPUT activation (stage-granular
    rematerialization, like GPipe-with-remat) — the ring buffer then
    stores one known-shape activation per in-flight microbatch instead
    of arbitrary vjp residuals.

    Contracts (call inside shard_map):
      stage_fn(stage_params, head_params, x_in, ctx_mb)
          -> (x_out, stage_loss)
        this stage's layer slice on one microbatch plus the stage's OWN
        per-microbatch scalar loss contribution (MoE load-balance aux —
        every stage's loss channel is seeded in its backward, not just
        the last).  stage_loss must carry the same varying type as
        x_out; plain stacks return the zero-gradient
        ``jnp.sum(x_out) * 0.0``, NOT an invariant literal (mixing an
        invariant scalar into the varying loss channel inserts a pvary
        whose transpose is a psum inside the divergent cond).  head_params carries
        replicated leaves stages may need, e.g. stage 0's embedding —
        gate stage-specific work on lax.axis_index(pp_axis), keeping any
        collectives over OTHER axes, never over pp_axis.
      loss_head_fn(head_params, x_out, ctx_mb) -> scalar per-microbatch
        loss (applied on the LAST stage only, ADDED to that stage's
        contribution)
      x:   [B, ...] initial activations, replicated over pp, B % M == 0
      ctx: pytree of [B, ...] arrays (tokens/labels/masks), microbatched
        alongside x and handed to every stage + the head

    report_len > 0 switches both callables to a three-output contract —
    stage_fn -> (x_out, stage_loss, report [report_len]) and
    loss_head_fn -> (loss, report [report_len]) — where `report` is a
    NON-differentiated f32 vector accumulated across stages and
    microbatches (summed, psum'd over pp, NOT divided by M) and returned
    as a fifth output.  This is the display channel: a wrapper can fold
    per-term gradient scales into the differentiated loss channel while
    reconstructing exact unscaled values (e.g. raw token-NLL sum and raw
    MoE aux) from the report.

    Returns (loss, d_stage_params, d_head_params, d_x[, report]):
      loss   microbatch-mean of the summed per-stage contributions +
             head losses (pp-invariant: psum over stages — identical to
             the last stage's value for plain stacks)
      d_*    gradient trees matching the params; each leaf is psum'd over
             EXACTLY the axes it was widened over on entry (an
             already-varying leaf — dp-varying grads for a manual dp
             reduce-scatter, tp-sharded weights — keeps its per-shard
             cotangent, so this composes with any outer mesh)
      d_x    [B, ...] cotangent of the initial activations (for an
             embedding vjp outside), invariantized the same way
    The per-stage loss channel + report channel carry MoE: every
    stage's load-balance aux differentiates locally with its gradient
    scale folded into the objective, and the raw values ride the report
    for exact display (llama.loss_and_grads_pp_1f1b).
    """
    n = lax.axis_size(pp_axis)
    stage = lax.axis_index(pp_axis)
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    tmap = jax.tree_util.tree_map

    def to_mb(v):
        return v.reshape((M, mb) + v.shape[1:])

    x_mb = to_mb(x)
    ctx_mb = tmap(to_mb, ctx)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]
    is_last = stage == n - 1
    act_shape = (mb,) + x.shape[1:]
    vma = _tree_vma(x, ctx, stage_params, head_params) | {pp_axis}

    # Widen EVERY input to the full varying set BEFORE the schedule runs,
    # RECORDING the widened axes per leaf.  The scheduling conds are
    # stage-divergent, and jax.vjp transposes an invariant-used-in-
    # varying-math widening into a psum — a collective inside a divergent
    # branch deadlocks the whole mesh (observed as an XLA rendezvous
    # abort: 3 devices in collective-permute, 1 in all-reduce).  With all
    # inputs varying, every vjp inside the conds is collective-free;
    # invariantization happens exactly once after the scan — each
    # gradient leaf psum'd over precisely its recorded widened axes (the
    # manual transpose of the entry pcast).
    sp_v, sp_axes = _widen(stage_params, vma)
    hp_v, hp_axes = _widen(head_params, vma)
    x_axes = tuple(sorted(set(vma) - set(jax.typeof(x).vma)))
    x_mb = _pcast_to(x_mb, vma)
    ctx_mb = tmap(lambda v: _pcast_to(v, vma), ctx_mb)

    R = report_len

    g5 = _unit_fn(stage_fn, loss_head_fn, R)

    def g(sp, hp, x_in, c_in):
        return g5(sp, hp, x_in, c_in, is_last)

    f32 = functools.partial(tmap, lambda p: jnp.zeros(p.shape, jnp.float32))

    def pc(v):
        return _pcast_to(v, vma)

    carry0 = (
        pc(jnp.zeros(act_shape, x.dtype)),            # act in flight (down)
        pc(jnp.zeros(act_shape, jnp.float32)),        # ct in flight (up)
        pc(jnp.zeros((n,) + act_shape, x.dtype)),     # saved inputs ring
        tmap(pc, f32(stage_params)),
        tmap(pc, f32(head_params)),
        pc(jnp.zeros((M,) + act_shape, jnp.float32)),  # d_x per microbatch
        pc(jnp.float32(0.0)),                         # loss accumulator
        pc(jnp.zeros((report_len,), jnp.float32)),    # report accumulator
    )

    def ctx_at(mi):
        return tmap(lambda v: lax.dynamic_index_in_dim(v, mi, 0, False),
                    ctx_mb)

    def tick(carry, t):
        act_in, ct_in, saved, d_sp, d_hp, d_x, loss_acc, rep_acc = carry

        m_f = (t - stage) // 2
        fwd_work = ((t - stage) % 2 == 0) & (m_f >= 0) & (m_f < M)
        m_b = (t - (2 * n - 1 - stage)) // 2
        bwd_work = (((t - (2 * n - 1 - stage)) % 2 == 0)
                    & (m_b >= 0) & (m_b < M))

        # ---- forward unit (parity-s ticks) ----
        def do_fwd(op):
            act_in, saved, loss_acc, rep_acc = op
            mi = jnp.clip(m_f, 0, M - 1)
            x_in = jnp.where(stage == 0,
                             lax.dynamic_index_in_dim(x_mb, mi, 0, False),
                             act_in.astype(x.dtype))
            h, (loss, rep) = g(sp_v, hp_v, x_in, ctx_at(mi))
            saved = lax.dynamic_update_index_in_dim(
                saved, x_in, mi % n, 0)
            return h, saved, loss_acc + loss / M, rep_acc + rep

        def skip_fwd(op):
            act_in, saved, loss_acc, rep_acc = op
            return act_in.astype(x.dtype), saved, loss_acc, rep_acc

        act_out, saved, loss_acc, rep_acc = lax.cond(
            fwd_work, do_fwd, skip_fwd, (act_in, saved, loss_acc, rep_acc))

        # ---- backward unit (parity-(s+1) ticks) ----
        def do_bwd(op):
            ct_in, d_sp, d_hp, d_x = op
            mi = jnp.clip(m_b, 0, M - 1)
            x_in = lax.dynamic_index_in_dim(saved, mi % n, 0, False)
            _, pull = jax.vjp(g, sp_v, hp_v, x_in, ctx_at(mi))
            # seeds must carry g's full output vma type; the pcast here
            # feeds a cotangent INTO pull (it is never itself transposed,
            # so no psum materializes inside this divergent branch)
            ct_h = pc(jnp.where(is_last,
                                jnp.zeros(act_shape, jnp.float32),
                                ct_in).astype(x.dtype))
            # EVERY stage seeds its loss channel (its own per-stage
            # contribution differentiates locally; the head rides the
            # last stage's channel)
            ct_loss = pc(jnp.full((), 1.0 / M, jnp.float32))
            # report: no grad; the R=0 dummy channel is an invariant
            # empty array, so its seed must be too
            ct_rep = (pc(jnp.zeros((R,), jnp.float32)) if R
                      else jnp.zeros((0,), jnp.float32))
            g_sp, g_hp, g_x, _ = pull((ct_h, (ct_loss, ct_rep)))
            d_sp = tmap(lambda a, b: a + b.astype(jnp.float32), d_sp, g_sp)
            d_hp = tmap(lambda a, b: a + b.astype(jnp.float32), d_hp, g_hp)
            # d_x is meaningful on stage 0 only (its x_in came from x_mb,
            # not the ring); other stages contribute zeros
            d_x = lax.dynamic_update_index_in_dim(
                d_x, jnp.where(stage == 0, g_x.astype(jnp.float32), 0.0),
                mi, 0)
            return g_x.astype(jnp.float32), d_sp, d_hp, d_x

        def skip_bwd(op):
            ct_in, d_sp, d_hp, d_x = op
            return ct_in, d_sp, d_hp, d_x

        ct_out, d_sp, d_hp, d_x = lax.cond(
            bwd_work, do_bwd, skip_bwd, (ct_in, d_sp, d_hp, d_x))

        # both ring directions rotate every tick (collectives must stay
        # outside the conds: every stage participates every tick)
        act_next = lax.ppermute(act_out, pp_axis, fwd_perm)
        ct_next = lax.ppermute(ct_out, pp_axis, bwd_perm)
        return (act_next, ct_next, saved, d_sp, d_hp, d_x, loss_acc,
                rep_acc), None

    ticks = jnp.arange(2 * (M + n) - 2)     # last: stage-0 bwd of M-1
    (_, _, _, d_sp, d_hp, d_x, loss_acc, rep_acc), _ = lax.scan(
        tick, carry0, ticks)
    loss = lax.psum(loss_acc, pp_axis)      # per-stage contributions + head
    # transpose of the entry widening: psum each grad leaf over exactly
    # the axes it was widened over (head/replicated leaves got per-stage
    # partials; stage-sharded and dp-varying leaves stay per-shard)
    d_sp = _unwiden_grads(d_sp, sp_axes)
    d_hp = _unwiden_grads(d_hp, hp_axes)
    # d_x: stage-0 rows + zeros elsewhere; pp-psum selects stage 0's and
    # the recorded widening handles any other axes
    d_x = lax.psum(d_x, tuple(sorted(set(x_axes) | {pp_axis})))
    if report_len:
        report = lax.psum(rep_acc, pp_axis)
        return loss, d_sp, d_hp, d_x.reshape(x.shape), report
    return loss, d_sp, d_hp, d_x.reshape(x.shape)


def cost_model(num_microbatches: int, pp: int,
               schedule: str = "gpipe", virtual_stages: int = 1) -> dict:
    """Pipeline schedule cost report — the bubble/memory arithmetic users
    need to size num_microbatches.

    schedule="gpipe" (forward pass of `pipeline_apply`; this
    implementation computes on ring garbage during bubble ticks, so
    `bubble_fraction` IS the wasted-compute fraction):
      ticks            M + pp - 1 forward ticks
      bubble_ticks     pp - 1
      live_activations M per stage once differentiated (jax saves every
                       forward carry for the backward)

    schedule="1f1b" (`pipeline_train_1f1b`, fused fwd+bwd):
      ticks            2*(M + pp) - 2 work units (fwd and bwd counted 1)
      bubble_ticks     2*pp - 2 per stage
      live_activations <= pp per stage — the whole point: the in-flight
                       window is the ring depth, independent of M

    Design note — zero-bubble (ZB-H1) schedules: splitting the backward
    into input-grad (B) and weight-grad (W) units lets W units fill
    bubble ticks.  Considered and NOT implemented here: this module's
    lockstep execution model (every device, one unit per tick, two
    ppermutes per tick) synchronizes each tick on the SLOWEST unit, and
    F/B/W have unequal costs (~1x/2x/1x of a forward), so the bubble
    ticks ZB reclaims are largely returned as per-tick stalls.  Getting
    ZB's real win needs per-edge asynchronous p2p sends, which the
    shard_map + ppermute paradigm deliberately does not use (static
    lockstep is what makes the schedules verifiable at trace time).
    """
    if num_microbatches < 1 or pp < 1:
        raise ValueError((num_microbatches, pp))
    M = num_microbatches
    if schedule == "gpipe":
        ticks = M + pp - 1
        return {
            "schedule": "gpipe",
            "num_microbatches": M,
            "pp": pp,
            "ticks": ticks,
            "bubble_ticks": pp - 1,
            "bubble_fraction": (pp - 1) / ticks,
            "utilization": M / ticks,
            "live_activations_per_stage": M,
        }
    if schedule == "1f1b":
        ticks = 2 * (M + pp) - 2
        return {
            "schedule": "1f1b",
            "num_microbatches": M,
            "pp": pp,
            "ticks": ticks,
            "bubble_ticks": 2 * pp - 2,
            "bubble_fraction": (2 * pp - 2) / ticks,
            "utilization": 2 * M / ticks,
            "live_activations_per_stage": min(M, pp),
        }
    if schedule == "1f1b-interleaved":
        # measured from the verified static schedule, not a formula —
        # each tick is 1/v of a full stage, so compare bubble in
        # FULL-STAGE units against plain 1f1b
        v = virtual_stages
        t = _interleaved_tables(pp, v, M)
        ticks = t["T"]
        ideal = 2 * v * M
        return {
            "schedule": "1f1b-interleaved",
            "num_microbatches": M,
            "pp": pp,
            "virtual_stages": v,
            "ticks": ticks,
            "bubble_ticks": ticks - ideal,
            "bubble_fraction": (ticks - ideal) / ticks,
            "bubble_full_stage_units": (ticks - ideal) / v,
            "utilization": ideal / ticks,
            "live_activations_per_stage": t["n_aslots"],
        }
    raise ValueError(f"unknown schedule {schedule!r}")


def from_last_stage(val: jax.Array, pp_axis: str) -> jax.Array:
    """psum-broadcast a value that is only valid on the last pp stage.
    Cheap for scalars (per-microbatch losses); use sparingly on big tensors.

    The psum sits on the gradient path, so differentiating through this
    inherits the psum-transpose convention.  That is the correct pairing
    when the grad is taken OUTSIDE shard_map (the boundary hands each
    replica ct/n for a replicated output, and the psum transpose restores
    the factor); losses differentiated INSIDE shard_map must use
    ``from_last_stage_local_grad`` instead — with the psum on their
    gradient path every pipeline gradient came out scaled by n_pp
    (docs/KNOWN_FAILURES.md #5-16 family, frozen as graftlint rule J7)."""
    n = lax.axis_size(pp_axis)
    is_last = (lax.axis_index(pp_axis) == n - 1).astype(val.dtype)
    return lax.psum(val * is_last, pp_axis)


def from_last_stage_local_grad(val: jax.Array, pp_axis: str) -> jax.Array:
    """``from_last_stage`` for losses differentiated INSIDE shard_map: the
    psum carries the VALUE only, the gradient path rides the local masked
    value — so the cotangent reaching ``val`` is exactly ct * is_last,
    independent of the psum-transpose convention (the J7 gradient-scale
    class).  Per-stage gradients of pp-replicated leaves then come out
    as clean per-stage PARTIALS; vma autodiff inserts the cross-stage
    psum (the transpose of its pvary)."""
    n = lax.axis_size(pp_axis)
    is_last = (lax.axis_index(pp_axis) == n - 1).astype(val.dtype)
    masked = val * is_last
    return lax.stop_gradient(lax.psum(masked, pp_axis)) + (
        masked - lax.stop_gradient(masked))


# -- interleaved (virtual-stage) 1F1B ----------------------------------------


def _alloc_slots(intervals):
    """Greedy interval-graph coloring: intervals = [(start, end, key)]
    inclusive; returns ({key: slot}, n_slots).  Used to map each in-flight
    activation/cotangent to a static buffer slot with disjoint lifetimes."""
    import heapq
    assign, free, n = {}, [], 0
    for start, end, key in sorted(intervals):
        # pop every slot freed strictly before `start`, reuse the lowest
        ready = []
        while free and free[0][0] < start:
            ready.append(heapq.heappop(free)[1])
        if ready:
            slot = min(ready)
            for r in ready:
                if r != slot:
                    heapq.heappush(free, (start - 1, r))
        else:
            slot = n
            n += 1
        assign[key] = slot
        heapq.heappush(free, (end, slot))
    # verify disjointness per slot — allocation is load-bearing for the
    # scheduler's correctness, so check, don't trust
    by_slot = {}
    for start, end, key in intervals:
        by_slot.setdefault(assign[key], []).append((start, end))
    for sl, ivs in by_slot.items():
        ivs.sort()
        for (s1, e1), (s2, e2) in zip(ivs, ivs[1:]):
            assert e1 < s2, ("slot lifetime overlap", sl, (s1, e1), (s2, e2))
    return assign, n


def _interleaved_tables(pp: int, v: int, M: int):
    """Static lockstep schedule for interleaved 1F1B (Megatron order).

    Virtual stage u in [0, v*pp) holds layer chunk u of the model; device
    of u is u % pp, so EVERY virtual hop u -> u+1 is the uniform ring
    step s -> s+1 (including chunk transitions pp-1 -> 0) and the two
    ppermute rings of the non-interleaved scheduler carry the traffic
    unchanged.  Per device the unit ORDER is Megatron's: W(s) warmup
    forwards (W = 2*(pp-s-1) + (v-1)*pp, capped), then strict 1F1B
    alternation, then cooldown backwards; chunk index cycles every pp
    consecutive microbatch slots.  Ticks are assigned by earliest-feasible
    list scheduling under the ring dependencies (fwd(m,u) strictly after
    fwd(m,u-1); bwd(m,u) strictly after bwd(m,u+1); bwd(m,P-1) strictly
    after fwd(m,P-1)) and one-unit-per-device-per-tick; the result is
    VERIFIED here (every unit scheduled once, strict orderings, slot
    lifetimes disjoint), not trusted.

    Phase changes (1-spaced warmup vs 2-spaced steady state) mean an
    arriving activation is not always consumed on its arrival tick, so
    unlike the closed-form v=1 scheduler, arrivals land in statically
    allocated SLOTS: one act buffer doubles as arrival buffer and saved
    input (lifetime: arrival -> that unit's backward), one ct buffer for
    in-flight cotangents.  Returns numpy tables [T, pp] driving the scan:
    KIND (0 idle / 1 fwd / 2 bwd), MB, CH, ASLOT (the unit's act slot),
    CTSLOT (bwd cotangent slot; -1 = loss-head seed), ISU0 (input from
    x_mb), ISHEAD (unit is virtual stage P-1), RA / RC (slot to store the
    act / ct arriving this tick; -1 none), plus (T, n_aslots, n_cslots).
    """
    import numpy as np
    P = v * pp
    if M % pp:
        raise ValueError(
            f"interleaved 1F1B needs num_microbatches {M} % pp {pp} == 0 "
            f"(the chunk rotation covers pp microbatches per segment)")
    vM = v * M

    def chunk_of(vmid, fwd):
        c = (vmid % (v * pp)) // pp
        return c if fwd else v - 1 - c

    def mb_of(vmid):
        return (vmid // (v * pp)) * pp + vmid % pp

    orders = []
    for s in range(pp):
        W = min(pp - s - 1 if v == 1
                else 2 * (pp - s - 1) + (v - 1) * pp, vM)
        seq, fi, bi = [], 0, 0
        for _ in range(W):
            seq.append(("F", mb_of(fi), chunk_of(fi, True))); fi += 1
        while fi < vM:
            seq.append(("F", mb_of(fi), chunk_of(fi, True))); fi += 1
            seq.append(("B", mb_of(bi), chunk_of(bi, False))); bi += 1
        while bi < vM:
            seq.append(("B", mb_of(bi), chunk_of(bi, False))); bi += 1
        orders.append(seq)

    tick_f, tick_b = {}, {}
    ptr = [0] * pp
    rows = []
    t = 0
    while any(p < 2 * vM for p in ptr):
        row = {}
        for s in range(pp):
            if ptr[s] >= 2 * vM:
                continue
            kind, m, c = orders[s][ptr[s]]
            u = c * pp + s
            if kind == "F":
                ok = u == 0 or tick_f.get((m, u - 1), t) < t
            elif u == P - 1:
                ok = tick_f.get((m, u), t) < t
            else:
                ok = tick_b.get((m, u + 1), t) < t
            if ok:
                row[s] = (kind, m, c)
                (tick_f if kind == "F" else tick_b)[(m, u)] = t
                ptr[s] += 1
        rows.append(row)
        t += 1
        if t > 100 * vM + 100:
            raise AssertionError(f"schedule non-convergence pp={pp} v={v}")
    T = t

    for m in range(M):                       # verify, don't trust
        for u in range(P):
            assert (m, u) in tick_f and (m, u) in tick_b, (m, u)
            if u > 0:
                assert tick_f[(m, u)] > tick_f[(m, u - 1)]
                assert tick_b[(m, u)] < tick_b[(m, u - 1)]
            assert tick_b[(m, u)] > tick_f[(m, u)]

    # slot allocation per device (all devices share the buffer SIZES)
    aslot, cslot = {}, {}
    n_as = n_cs = 0
    for s in range(pp):
        a_iv, c_iv = [], []
        for c in range(v):
            u = c * pp + s
            for m in range(M):
                a0 = tick_f[(m, u - 1)] + 1 if u > 0 else tick_f[(m, u)]
                a_iv.append((a0, tick_b[(m, u)], (m, u)))
                if u < P - 1:
                    c_iv.append((tick_b[(m, u + 1)] + 1,
                                 tick_b[(m, u)], (m, u)))
        amap, na = _alloc_slots(a_iv)
        cmap, nc = _alloc_slots(c_iv)
        aslot.update({(s,) + k: sl for k, sl in amap.items()})
        cslot.update({(s,) + k: sl for k, sl in cmap.items()})
        n_as, n_cs = max(n_as, na), max(n_cs, nc)

    shape = (T, pp)
    KIND = np.zeros(shape, np.int32)
    MB = np.zeros(shape, np.int32)
    CH = np.zeros(shape, np.int32)
    ASLOT = np.zeros(shape, np.int32)
    CTSLOT = np.full(shape, -1, np.int32)
    ISU0 = np.zeros(shape, np.int32)
    ISHEAD = np.zeros(shape, np.int32)
    RA = np.full(shape, -1, np.int32)
    RC = np.full(shape, -1, np.int32)
    for t2, row in enumerate(rows):
        for s, (kind, m, c) in row.items():
            u = c * pp + s
            KIND[t2, s] = 1 if kind == "F" else 2
            MB[t2, s] = m
            CH[t2, s] = c
            ASLOT[t2, s] = aslot[(s, m, u)]
            ISU0[t2, s] = int(u == 0)
            ISHEAD[t2, s] = int(u == P - 1)
            if kind == "F" and u < P - 1:
                sd = (u + 1) % pp          # arrival lands downstream next tick
                assert RA[t2 + 1, sd] == -1
                RA[t2 + 1, sd] = aslot[(sd, m, u + 1)]
            if kind == "B":
                if u < P - 1:
                    CTSLOT[t2, s] = cslot[(s, m, u)]
                if u > 0:
                    su = (u - 1) % pp      # cotangent lands upstream next tick
                    assert RC[t2 + 1, su] == -1
                    RC[t2 + 1, su] = cslot[(su, m, u - 1)]
    return dict(T=T, n_aslots=n_as, n_cslots=n_cs, KIND=KIND, MB=MB, CH=CH,
                ASLOT=ASLOT, CTSLOT=CTSLOT, ISU0=ISU0, ISHEAD=ISHEAD,
                RA=RA, RC=RC)


def pipeline_train_1f1b_interleaved(stage_fn: Callable,
                                    loss_head_fn: Callable,
                                    stage_params, head_params,
                                    x: jax.Array, ctx,
                                    num_microbatches: int, pp_axis: str,
                                    virtual_stages: int,
                                    report_len: int = 0):
    """Interleaved (virtual-stage) 1F1B: ``pipeline_train_1f1b`` with each
    device holding `virtual_stages` non-adjacent layer chunks — chunk c on
    device s is virtual stage u = c*pp + s, so a microbatch crosses every
    device v times and the warm-up/cool-down bubble costs 1/v of a full
    stage per tick: the standard Megatron bubble-cutting schedule
    (beyond-reference; the reference has no pipeline axis at all).

    Contract differences from pipeline_train_1f1b:
      stage_params   leaves carry a leading [virtual_stages] chunk axis;
                     stage_fn receives ONE chunk's params (axis dropped)
      num_microbatches must be a multiple of pp (the Megatron chunk
                     rotation covers pp microbatches per segment)
      d_stage_params returned with the same [virtual_stages] leading axis
    Everything else (loss/report channels, widening/invariantization,
    ctx microbatching, the two ppermute rings) matches — the schedule is
    a static table (_interleaved_tables), verified at trace time, driving
    which unit each device runs per tick; arrivals land in statically
    allocated slots because warm-up forwards are 1-tick spaced while
    steady state is 2-spaced, so consumption is not always on the arrival
    tick (the closed-form v=1 scheduler's single in-flight register would
    drop them)."""
    n = lax.axis_size(pp_axis)
    stage = lax.axis_index(pp_axis)
    M = num_microbatches
    v = virtual_stages
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    tmap = jax.tree_util.tree_map
    tbls = _interleaved_tables(n, v, M)
    T = tbls["T"]
    n_as, n_cs = tbls["n_aslots"], tbls["n_cslots"]
    jt = {k: jnp.asarray(tbls[k]) for k in
          ("KIND", "MB", "CH", "ASLOT", "CTSLOT", "ISU0", "ISHEAD",
           "RA", "RC")}

    def to_mb(val):
        return val.reshape((M, mb) + val.shape[1:])

    x_mb = to_mb(x)
    ctx_mb = tmap(to_mb, ctx)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]
    act_shape = (mb,) + x.shape[1:]
    vma = _tree_vma(x, ctx, stage_params, head_params) | {pp_axis}

    sp_v, sp_axes = _widen(stage_params, vma)
    hp_v, hp_axes = _widen(head_params, vma)
    x_axes = tuple(sorted(set(vma) - set(jax.typeof(x).vma)))
    x_mb = _pcast_to(x_mb, vma)
    ctx_mb = tmap(lambda val: _pcast_to(val, vma), ctx_mb)

    R = report_len

    g = _unit_fn(stage_fn, loss_head_fn, R)

    f32z = functools.partial(tmap,
                             lambda p: jnp.zeros(p.shape, jnp.float32))

    def pc(val):
        return _pcast_to(val, vma)

    carry0 = (
        pc(jnp.zeros(act_shape, x.dtype)),              # act ring register
        pc(jnp.zeros(act_shape, jnp.float32)),          # ct ring register
        pc(jnp.zeros((n_as,) + act_shape, x.dtype)),    # act slots
        pc(jnp.zeros((n_cs,) + act_shape, jnp.float32)),  # ct slots
        tmap(pc, f32z(stage_params)),
        tmap(pc, f32z(head_params)),
        pc(jnp.zeros((M,) + act_shape, jnp.float32)),   # d_x per microbatch
        pc(jnp.float32(0.0)),
        pc(jnp.zeros((report_len,), jnp.float32)),
    )

    def ctx_at(mi):
        return tmap(lambda val: lax.dynamic_index_in_dim(val, mi, 0, False),
                    ctx_mb)

    def tick(carry, t):
        act_in, ct_in, abuf, cbuf, d_sp, d_hp, d_x, loss_acc, rep_acc = carry

        def tbl(name):
            return jt[name][t, stage]

        # arrivals first: whatever landed on either ring this tick goes
        # into its statically assigned slot (-1: ring carries garbage)
        ra, rc = tbl("RA"), tbl("RC")
        a_up = lax.dynamic_update_index_in_dim(
            abuf, act_in.astype(x.dtype), jnp.clip(ra, 0, n_as - 1), 0)
        abuf = jnp.where(ra >= 0, a_up, abuf)
        c_up = lax.dynamic_update_index_in_dim(
            cbuf, ct_in, jnp.clip(rc, 0, n_cs - 1), 0)
        cbuf = jnp.where(rc >= 0, c_up, cbuf)

        kind = tbl("KIND")
        mi = tbl("MB")
        c = tbl("CH")
        sl = tbl("ASLOT")
        csl = tbl("CTSLOT")
        isu0 = tbl("ISU0") == 1
        ishead = tbl("ISHEAD") == 1
        sp_c = tmap(lambda p: lax.dynamic_index_in_dim(p, c, 0, False),
                    sp_v)
        c_in = ctx_at(mi)

        def do_fwd(op):
            abuf, loss_acc, rep_acc = op
            x_arr = lax.dynamic_index_in_dim(abuf, sl, 0, False)
            x_in = jnp.where(
                isu0, lax.dynamic_index_in_dim(x_mb, mi, 0, False),
                x_arr.astype(x.dtype))
            abuf2 = lax.dynamic_update_index_in_dim(abuf, x_in, sl, 0)
            h, (loss, rep) = g(sp_c, hp_v, x_in, c_in, ishead)
            return h, abuf2, loss_acc + loss / M, rep_acc + rep

        def skip_fwd(op):
            abuf, loss_acc, rep_acc = op
            return act_in.astype(x.dtype), abuf, loss_acc, rep_acc

        act_out, abuf, loss_acc, rep_acc = lax.cond(
            kind == 1, do_fwd, skip_fwd, (abuf, loss_acc, rep_acc))

        def do_bwd(op):
            ct_in, d_sp, d_hp, d_x = op
            x_in = lax.dynamic_index_in_dim(abuf, sl, 0, False)
            _, pull = jax.vjp(
                lambda a, b, xx: g(a, b, xx, c_in, ishead),
                sp_c, hp_v, x_in)
            ct_arr = lax.dynamic_index_in_dim(
                cbuf, jnp.clip(csl, 0, n_cs - 1), 0, False)
            ct_h = pc(jnp.where(ishead,
                                jnp.zeros(act_shape, jnp.float32),
                                ct_arr).astype(x.dtype))
            ct_loss = pc(jnp.full((), 1.0 / M, jnp.float32))
            ct_rep = (pc(jnp.zeros((R,), jnp.float32)) if R
                      else jnp.zeros((0,), jnp.float32))
            g_sp_c, g_hp, g_x = pull((ct_h, (ct_loss, ct_rep)))
            d_sp = tmap(
                lambda acc, gc: lax.dynamic_update_index_in_dim(
                    acc,
                    lax.dynamic_index_in_dim(acc, c, 0, False)
                    + gc.astype(jnp.float32), c, 0),
                d_sp, g_sp_c)
            d_hp = tmap(lambda a, b2: a + b2.astype(jnp.float32),
                        d_hp, g_hp)
            d_x = lax.dynamic_update_index_in_dim(
                d_x, jnp.where(isu0, g_x.astype(jnp.float32), 0.0), mi, 0)
            return g_x.astype(jnp.float32), d_sp, d_hp, d_x

        def skip_bwd(op):
            ct_in, d_sp, d_hp, d_x = op
            return ct_in, d_sp, d_hp, d_x

        ct_out, d_sp, d_hp, d_x = lax.cond(
            kind == 2, do_bwd, skip_bwd, (ct_in, d_sp, d_hp, d_x))

        act_next = lax.ppermute(act_out, pp_axis, fwd_perm)
        ct_next = lax.ppermute(ct_out, pp_axis, bwd_perm)
        return (act_next, ct_next, abuf, cbuf, d_sp, d_hp, d_x, loss_acc,
                rep_acc), None

    ticks = jnp.arange(T)
    (_, _, _, _, d_sp, d_hp, d_x, loss_acc, rep_acc), _ = lax.scan(
        tick, carry0, ticks)
    loss = lax.psum(loss_acc, pp_axis)
    d_sp = _unwiden_grads(d_sp, sp_axes)
    d_hp = _unwiden_grads(d_hp, hp_axes)
    d_x = lax.psum(d_x, tuple(sorted(set(x_axes) | {pp_axis})))
    if report_len:
        report = lax.psum(rep_acc, pp_axis)
        return loss, d_sp, d_hp, d_x.reshape(x.shape), report
    return loss, d_sp, d_hp, d_x.reshape(x.shape)


def interleave_layers(stacked, pp: int, v: int):
    """Permute a model-order stacked [L, ...] layer tree into the
    device-major order the interleaved scheduler shards: global stack row
    s*(L/pp) + c*Lc + j  <-  model layer (c*pp + s)*Lc + j, so a plain
    P(pp) contiguous shard hands device s exactly its chunks c*pp+s.
    Apply OUTSIDE shard_map (checkpoints/exports stay in model order via
    ``deinterleave_layers``)."""
    def one(a):
        L = a.shape[0]
        Lc = L // (v * pp)
        assert L % (v * pp) == 0, (L, v, pp)
        perm = [(c * pp + s) * Lc + j
                for s in range(pp) for c in range(v) for j in range(Lc)]
        return a[jnp.asarray(perm)]
    return jax.tree_util.tree_map(one, stacked)


def deinterleave_layers(stacked, pp: int, v: int):
    """Inverse of ``interleave_layers`` (gradients/params back to model
    order)."""
    def one(a):
        L = a.shape[0]
        Lc = L // (v * pp)
        assert L % (v * pp) == 0, (L, v, pp)
        perm = [(c * pp + s) * Lc + j
                for s in range(pp) for c in range(v) for j in range(Lc)]
        inv = [0] * L
        for newp, oldp in enumerate(perm):
            inv[oldp] = newp
        return a[jnp.asarray(inv)]
    return jax.tree_util.tree_map(one, stacked)
