"""Mixture-of-experts FFN with expert parallelism (ep) over all-to-all.

The reference has no MoE (SURVEY.md §2 "Absent: ... EP, MoE"); this is the
north-star generalization of its core move — shard state across a ring and
move *data* to the state's owner instead of replicating state — applied to
FFN experts: expert weights shard over the ep mesh axis, and tokens travel
to their expert's owner via `lax.all_to_all` (ICI), the TPU analogue of the
reference streaming gradient slices to the slice's reducing node
(hw/all_reduce.sv slice rotation).

Design (GShard/Switch-style, static shapes for XLA):
- top-k routing with renormalized gates;
- fixed per-expert capacity C = ceil(T*k/E * capacity_factor); overflow
  tokens are dropped deterministically in token-major priority order (their
  residual path still carries them).  NOTE: under ep/sp sharding, capacity
  and drop priority are computed over each rank's LOCAL tokens (T = local
  token count), so once capacity binds, sharded and unsharded runs drop
  different tokens and diverge numerically — by design, matching how every
  capacity-based MoE shards; parity tests use generous capacity;
- dispatch/combine via scatter-add / gather, not [T,E,C] one-hot einsums —
  O(T*k*D) memory;
- load-balance aux loss computed over the *global* batch (psum over the
  batch axes) so sharded and unsharded training see the same regularizer.

Beside the capacity path, and sharing nothing with it, the DROPLESS path of
the sigmoid-routed expert models (`sigmoid_route`, `dropless_experts`,
`held_experts_ffn`): scores are a sigmoid over every expert of the layer,
the top-k of score + bias are selected, the gates are the selected scores
normalised over the selection and scaled; assignments are sorted by expert
and multiplied with `lax.ragged_dot`, whose cost follows the rows routed, so
no expert has a capacity and no assignment is dropped.  `held` names the
experts this chip holds of an expert-parallel layer: the router still scores
all of them and the gates are normalised over all that were selected, the
chip computes the part of the result its own experts give, and what the
absent experts would add is left out — there is no stand-in for the absent
chips or for their exchange.  The sort puts the rows of the experts held in
front, and a chip that holds H of E experts works on those: THE COMPACT
PROGRAM gathers the first C sorted rows, C = `compact_capacity` = `SLACK`
times the rows a uniform router sends to the experts held, in blocks of 512
and never more than T*k — a function of shapes, so it does the same work
whatever a seed's router sends — runs the experts' products (SwiGLU's
three, or the two of relu(x w1)**2 w2 where the weights hold no `w3`), their
elementwise part and the gates on `[C, .]`, and brings the C result rows back
to their tokens.  THE FULL PROGRAM over all T*k sorted rows is the other
branch of a `lax.cond` on the plan's `fits`, for a layer whose rows on the
experts held exceed C: a collapsed router costs speed and never a token.
The conditional sits inside one `jax.custom_vjp` whose residuals are its
inputs (`_compact_or_full`), so the backward takes the same branch; where
every expert is held C is T*k and the full program is called alone.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.names import scope


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0   # C = ceil(T*k/E * cf) per rank
    aux_weight: float = 0.01       # load-balance loss weight

    def __post_init__(self):
        assert 1 <= self.top_k <= self.num_experts

    def capacity(self, tokens: int) -> int:
        return max(1, math.ceil(tokens * self.top_k / self.num_experts
                                * self.capacity_factor))


def init_ffn(key: jax.Array, dim: int, ffn_dim: int, cfg: MoEConfig,
             dtype=jnp.float32) -> Dict:
    """Router + E SwiGLU experts.  wr stays f32 (routing logits are
    precision-sensitive); expert weights use the model dtype."""
    kr, k1, k3, k2 = jax.random.split(key, 4)
    E, D, F = cfg.num_experts, dim, ffn_dim

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(1.0 / fan_in)).astype(dtype)

    return {"wr": jax.random.normal(kr, (D, E), jnp.float32)
                  * jnp.sqrt(1.0 / D),
            "w1": dense(k1, D, (E, D, F)),
            "w3": dense(k3, D, (E, D, F)),
            "w2": dense(k2, F, (E, F, D))}


def param_specs(cfg: MoEConfig, ep_axis: Optional[str] = None,
                tp_axis: Optional[str] = None) -> Dict:
    """Experts shard over ep on their leading axis; the router replicates.

    With tp_axis, each expert's SwiGLU additionally Megatron-shards its
    hidden dim over tp (w1/w3 column, w2 row) — the same col/row split the
    dense FFN uses, applied per expert.  Every rank then computes a
    *partial* expert output over its hidden slice, and the model's existing
    row-parallel ``psum(tp)`` closes it; dispatch/routing run identically
    on every tp rank (tokens are tp-replicated), so tp composes with ep
    without touching the all_to_all."""
    return {"wr": P(), "w1": P(ep_axis, None, tp_axis),
            "w3": P(ep_axis, None, tp_axis), "w2": P(ep_axis, tp_axis, None)}


def _expert_ffn(params: Dict, h: jax.Array) -> jax.Array:
    """h: [E_local, C', D] -> [E_local, C', D], SwiGLU per expert."""
    g = jnp.einsum("ecd,edf->ecf", h, params["w1"])
    u = jnp.einsum("ecd,edf->ecf", h, params["w3"])
    g = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return jnp.einsum("ecf,efd->ecd", g * u, params["w2"])


def _route(params: Dict, xf: jax.Array, cfg: MoEConfig, C: int):
    """Top-k routing + token-major capacity assignment for local tokens
    xf [T, D].  Returns (gates [T,k], e_flat [T*k], onehot [T*k,E],
    keep [T*k] bool, slot [T*k], probs [T,E])."""
    E, k = cfg.num_experts, cfg.top_k
    logits = (xf.astype(jnp.float32) @ params["wr"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)                         # [T, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # deterministic token-major priority: earlier tokens win capacity slots
    # (the reference drops nothing but orders everything by stream position;
    # same discipline here)
    e_flat = eidx.reshape(-1)                                 # [T*k]
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # [T*k, E]
    prio = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(prio * onehot, axis=-1)                     # [T*k]
    keep = (pos < C)
    slot = jnp.where(keep, pos, 0)
    return gates, e_flat, onehot, keep, slot, probs


def expert_stats(params: Dict, x: jax.Array, cfg: MoEConfig, *,
                 batch_axes: Sequence[str] = ()) -> Dict[str, jax.Array]:
    """Expert-utilization observability (the reference's flit/stall-counter
    discipline, hw/bfp_adapter.sv:705-729, applied to routing): per-expert
    load fractions, dropped-assignment fraction, and capacity occupancy for
    one batch.  Jit-safe; call inside the same shard_map/batch_axes setup as
    the training loss, or unsharded on a debug batch.  Standalone entry —
    reruns the router; inside a forward pass use
    ``moe_ffn(..., with_stats=True)``, which reuses the routing it already
    computed.

    Returns (E = num_experts):
      load_frac      [E]  fraction of kept assignments per expert (sums ~1)
      capacity_frac  [E]  kept assignments / capacity slots per expert
      drop_frac      []   fraction of routed assignments dropped
      capacity       []   per-expert capacity C used
    """
    B, S, D = x.shape
    T = B * S
    C = cfg.capacity(T)
    _, _, onehot, keep, _, _ = _route(params, x.reshape(T, D), cfg, C)
    return _stats_from_routing(onehot, keep, C, batch_axes)


def _stats_from_routing(onehot: jax.Array, keep: jax.Array, C: int,
                        batch_axes: Sequence[str] = ()
                        ) -> Dict[str, jax.Array]:
    kept = jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                   axis=0).astype(jnp.float32)                # [E]
    total = jnp.float32(keep.size)                            # T*k local
    kept_total = jnp.sum(kept)
    n_ranks = jnp.float32(1.0)
    if batch_axes:
        axes = tuple(batch_axes)
        kept = lax.psum(kept, axes)
        total = lax.psum(total, axes)
        kept_total = lax.psum(kept_total, axes)
        n_ranks = lax.psum(n_ranks, axes)    # slots scale with rank count
    return {
        "load_frac": kept / jnp.maximum(kept_total, 1.0),
        "capacity_frac": kept / (C * n_ranks),
        "drop_frac": 1.0 - kept_total / total,
        "capacity": jnp.int32(C),
    }


def moe_ffn(params: Dict, x: jax.Array, cfg: MoEConfig, *,
            ep_axis: Optional[str] = None,
            batch_axes: Sequence[str] = (),
            with_stats: bool = False):
    """x: [B, S, D] local tokens -> (y [B, S, D], aux scalar)
    [, stats dict when with_stats — see `expert_stats`; reuses this pass's
    routing rather than rerunning the router].

    With ep_axis set (inside shard_map), expert leaves are the local
    [E/ep, ...] shards and tokens are exchanged with two all_to_alls
    (dispatch + return).  batch_axes: every mesh axis that shards tokens
    (dp/sp/ep) — used only for the global aux statistics.
    """
    B, S, D = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.top_k
    C = cfg.capacity(T)
    xf = x.reshape(T, D)
    gates, e_flat, onehot, keep, slot, probs = _route(params, xf, cfg, C)

    toks = jnp.repeat(xf, k, axis=0)                          # [T*k, D]
    buf = jnp.zeros((E, C, D), x.dtype).at[e_flat, slot].add(
        toks * keep[:, None].astype(x.dtype))

    if ep_axis is not None:
        ep = lax.axis_size(ep_axis)
        assert E % ep == 0, (E, ep)
        El = E // ep
        buf = buf.reshape(ep, El, C, D)
        buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0)
        h = buf.transpose(1, 0, 2, 3).reshape(El, ep * C, D)
        out = _expert_ffn(params, h)
        out = out.reshape(El, ep, C, D).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0)
        ybuf = out.reshape(E, C, D)
    else:
        ybuf = _expert_ffn(params, buf)

    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x.dtype)
    ytok = ybuf[e_flat, slot] * w[:, None]                    # [T*k, D]
    y = ytok.reshape(T, k, D).sum(axis=1).reshape(B, S, D)

    # load-balance aux (GShard): E * sum_i f_i * p_i over the GLOBAL batch.
    # f from hard assignments (zero grad), p from mean router probs.
    counts = jnp.sum(onehot, axis=0).astype(jnp.float32)      # [E]
    psum_p = jnp.sum(probs, axis=0)                           # [E]
    n_tok = jnp.float32(T)
    if batch_axes:
        axes = tuple(batch_axes)
        counts = lax.psum(counts, axes)
        psum_p = lax.psum(psum_p, axes)
        n_tok = lax.psum(n_tok, axes)
    f = counts / (n_tok * k)
    p = psum_p / n_tok
    aux = cfg.aux_weight * E * jnp.dot(f, p)
    if with_stats:
        return y, aux, _stats_from_routing(onehot, keep, C, batch_axes)
    return y, aux


# -- dropless, sigmoid-routed, some experts held -----------------------------


def router_scores(wr: jax.Array, xf: jax.Array) -> jax.Array:
    """sigmoid(xf @ wr) [T, E] in float32, the matmul at full precision."""
    return jax.nn.sigmoid(jnp.dot(
        xf.astype(jnp.float32), wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))


# `balanced_bias` moves a bias by u = BALANCE_STEP * BALANCE_SHRINK**i in its
# i-th iteration (a total travel of 1.0, scores lying in (0, 1)) and gives up
# after BALANCE_ITERS
BALANCE_STEP, BALANCE_SHRINK, BALANCE_ITERS = 0.02, 0.98, 1000


def balanced_bias(scores: jax.Array, top_k: int, tol: float) -> jax.Array:
    """A selection bias [E] under which the `top_k` largest of
    `scores` [T, E] + bias load the experts evenly: the aux-loss-free rule of
    arXiv:2408.15664, b_e += u * sign(mean load - load_e), iterated from
    zero on this one batch of scores with a shrinking u, until max load /
    mean load <= `tol` over ALL E experts (or BALANCE_ITERS); the most even
    bias seen is returned.  The bias steers the selection only,
    so no gate changes under it (`sigmoid_route`).  It stands for what a
    deployed router's bias holds after training; nothing here runs in a
    step."""
    tokens, num_experts = scores.shape
    mean = tokens * top_k / num_experts

    def body(state):
        bias, i, best, best_ratio = state
        _, chosen = lax.top_k(scores + bias, top_k)
        load = jnp.sum(chosen[..., None] == jnp.arange(num_experts),
                       axis=(0, 1), dtype=jnp.float32)
        ratio = jnp.max(load) / mean
        best = jnp.where(ratio < best_ratio, bias, best)
        move = BALANCE_STEP * BALANCE_SHRINK ** i * jnp.sign(mean - load)
        return bias + move, i + 1, best, jnp.minimum(ratio, best_ratio)

    zero = jnp.zeros((num_experts,), jnp.float32)
    return lax.while_loop(
        lambda s: (s[3] > tol) & (s[1] < BALANCE_ITERS), body,
        (zero, jnp.float32(0), zero, jnp.float32(jnp.inf)))[2]


def sigmoid_route(wr: jax.Array, xf: jax.Array, *, top_k: int,
                  bias: Optional[jax.Array] = None, scale: float = 1.0,
                  norm_topk: bool = True) -> Tuple[jax.Array, jax.Array]:
    """xf [T, D], wr [D, E] -> (gates [T, k] float32, experts [T, k] int32).

    Scores are sigmoid(xf @ wr) in float32, the matmul at full precision
    (a near tie between the k-th and the next score decides an expert).
    The k largest of score + `bias` [E] are selected (the bias steers the
    selection only: the aux-loss-free balancing of `noaux_tc`); the gates are
    the selected SCORES, divided by their sum where `norm_topk`, times
    `scale`.  The gradient reaches `wr` through the gates."""
    with scope("ainic.moe.route"):
        scores = router_scores(wr, xf)
        _, experts = lax.top_k(scores if bias is None else scores + bias,
                               top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates * scale, experts


# The compact program of `dropless_experts` holds this many times the rows a
# uniform router sends to the experts held (`compact_capacity`).
SLACK = 2.0


def compact_capacity(assignments: int, n_held: int, num_experts: int) -> int:
    """C, the sorted rows the compact program of `dropless_experts` works
    on: min(A, SLACK * A * H / E rounded up to whole blocks of 512 rows) for
    A assignments and H of E experts held — all A where every expert is
    held.  A function of shapes alone."""
    blocks = math.ceil(SLACK * assignments * n_held / (num_experts * 512))
    return min(assignments, 512 * blocks)


def _zero_cotangent(ints: jax.Array):
    return np.zeros(ints.shape, jax.dtypes.float0)


@jax.custom_vjp
def _rows_to_experts(xf, order, inv):
    """xf [T, D] -> [T*k, D]: the row of assignment `order[p]` (token
    `order[p] // k`) at sorted position p.  The transpose is a gather by
    the inverse permutation and a sum over a token's k assignments, where
    autodiff would scatter-add T*k rows."""
    return xf[order // (order.shape[0] // xf.shape[0])]


def _rows_to_experts_fwd(xf, order, inv):
    return _rows_to_experts(xf, order, inv), (order, inv, xf.shape[0])


def _rows_to_experts_bwd(res, g):
    order, inv, tokens = res
    return (g[inv].reshape(tokens, -1, g.shape[-1]).sum(axis=1),
            _zero_cotangent(order), _zero_cotangent(inv))


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _rows_to_tokens(ys, order, inv):
    """ys [T*k, D] in sorted order -> the same rows in assignment order
    (token-major); the transpose is the gather by `order`."""
    return ys[inv]


def _rows_to_tokens_fwd(ys, order, inv):
    return ys[inv], (order, inv)


def _rows_to_tokens_bwd(res, g):
    order, inv = res
    return g[order], _zero_cotangent(order), _zero_cotangent(inv)


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


def _grouped_dot(x: jax.Array, w: jax.Array, sizes: jax.Array,
                 live: jax.Array) -> jax.Array:
    """Rows of x [A, K], sorted by group, times their group's w [G, K, N].
    Rows past sum(sizes) belong to no group held here: `lax.ragged_dot`
    leaves them undefined, so they are zeroed going in and coming out —
    which also zeroes both cotangents of such a row."""
    x = jnp.where(live, x, jnp.zeros((), x.dtype))
    y = lax.ragged_dot(x, w, sizes)
    return jnp.where(live, y, jnp.zeros((), y.dtype))


def _held_lookup(num_experts: int, held: Optional[Sequence[int]]):
    """(local index of every expert, H where it is absent; H)."""
    held = tuple(range(num_experts)) if held is None else tuple(held)
    if len(set(held)) != len(held) or not all(
            0 <= e < num_experts for e in held):
        raise ValueError(f"held experts {held} are not distinct ids below "
                         f"{num_experts}")
    lookup = np.full((num_experts,), len(held), np.int32)
    lookup[list(held)] = np.arange(len(held), dtype=np.int32)
    return lookup, len(held)


def dispatch_plan(experts: jax.Array, num_experts: int,
                  held: Optional[Sequence[int]] = None) -> Dict[str, jax.Array]:
    """Where every assignment of `experts` [T, k] goes: `order` [T*k] (the
    assignments sorted by the local index of their expert, absent experts
    last; stable, so token-major inside an expert), its inverse `inv`,
    `sizes` [H] rows per expert held, `live` [T*k, 1] (sorted positions
    that hold a row of a held expert), `head` [C] (the first
    `compact_capacity` of `order`) and `fits` (every row of a held expert
    is among them)."""
    lookup, n_held = _held_lookup(num_experts, held)
    local = jnp.asarray(lookup)[experts.reshape(-1)]            # [T*k]
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :],
                    axis=0, dtype=jnp.int32)
    held_rows = jnp.sum(sizes)
    live = (jnp.arange(order.shape[0]) < held_rows)[:, None]
    head = order[:compact_capacity(order.shape[0], n_held, num_experts)]
    return {"order": order, "inv": inv, "sizes": sizes, "live": live,
            "local": local, "head": head, "fits": held_rows <= head.shape[0]}


def _relu2(g: jax.Array) -> jax.Array:
    """relu(g)**2, squared in float32 and rounded once."""
    return jnp.square(jax.nn.relu(g.astype(jnp.float32))).astype(g.dtype)


def _expert_rows(xs: jax.Array, w: Dict, sizes: jax.Array,
                 live: jax.Array) -> jax.Array:
    """Every sorted row through its own expert's matrices.  The expert's
    function is a property of the weights: with a `w3` it is SwiGLU,
    (silu(x w1) * (x w3)) w2; without, the two-matrix relu(x w1)**2 w2."""
    g = _grouped_dot(xs, w["w1"], sizes, live)
    if "w3" in w:
        u = _grouped_dot(xs, w["w3"], sizes, live)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    else:
        h = _relu2(g)
    return _grouped_dot(h, w["w2"], sizes, live)


def _full(xf, gates, w, plan):
    """The program over all T*k sorted rows, whatever share of them a held
    expert takes."""
    T, D = xf.shape
    order, inv = plan["order"], plan["inv"]
    xs = _rows_to_experts(xf, order, inv)                       # [T*k, D]
    ys = _rows_to_tokens(_expert_rows(xs, w, plan["sizes"], plan["live"]),
                         order, inv).reshape(T, -1, D)
    return jnp.sum(ys.astype(jnp.float32) * gates[..., None],
                   axis=1).astype(xf.dtype)


def _full_bwd(xf, gates, w, plan, gy):
    return jax.vjp(lambda *a: _full(*a, plan), xf, gates, w)[1](gy)


def _rows_by_token(plan: Dict, tokens: int, k: int):
    """The first C sorted rows the other way round, for a plan that fits:
    `perm` [C] (their positions in token-major order, rows of no held
    expert last), `ids` [C] (the assignment t*k + j of each, T*k for a row
    of no held expert), `first` [T] (where a token's rows start among
    them) and `held` [T] (how many it has: at most k).  One sort of C
    keys."""
    head = plan["head"]
    ids = jnp.where(plan["live"][:head.shape[0], 0], head,
                    plan["order"].shape[0])
    perm = jnp.argsort(ids).astype(jnp.int32)
    held = jnp.sum(plan["inv"].reshape(tokens, k) < jnp.sum(plan["sizes"]),
                   axis=1, dtype=jnp.int32)
    return perm, ids[perm], jnp.cumsum(held) - held, held


def _sum_to_tokens(rows: jax.Array, plan: Dict, gates: jax.Array,
                   weighted: bool, dtype) -> jax.Array:
    """rows [C, D] of the first C sorted rows -> [T, D]: a token's rows
    summed in float32, each times its gate where `weighted`, for a plan
    that fits.  C rows are touched, not the T*k slots: one gather puts the
    rows in token-major order, where a token's at most k rows are
    neighbours and k - 1 shifted adds sum them onto the first, and one
    gather of T rows reads each token's sum (zeros for a token with no
    row here).  (One layer of the LFM2 cell alone on the v5e, forward and
    backward: 35.6 ms so, 40.2 with k gathers of T rows through the slots,
    37.3 with a scatter-add of the C rows; at the GLM cell's 8,192 rows
    8.78, 8.89 and 10.2.)"""
    tokens, k = gates.shape
    perm, ids, first, held = _rows_by_token(plan, tokens, k)
    count = perm.shape[0]

    def padded(a, fill):
        return jnp.concatenate(
            [a, jnp.full((k - 1,) + a.shape[1:], fill, a.dtype)])

    rows, token = padded(rows[perm], 0), padded(ids // k, -1)
    if weighted:
        gate = padded(gates.reshape(-1)[jnp.minimum(ids, gates.size - 1)], 0)
    total = 0.0
    for j in range(k):
        part = rows[j:j + count].astype(jnp.float32)
        if weighted:
            part = part * gate[j:j + count, None]
        same = (token[j:j + count] == token[:count])[:, None]
        total = total + jnp.where(same, part, 0.0)
    # rounded here as the result is, so the T rows are read at its width
    total = total.astype(dtype)
    return jnp.where((held > 0)[:, None],
                     total[jnp.minimum(first, count - 1)],
                     jnp.zeros((), dtype))


def _compact(xf, gates, w, plan):
    """The compact program, for a plan that `fits`: the same sums over the
    first C sorted rows, which hold every row of a held expert."""
    head = plan["head"]
    ys = _expert_rows(xf[head // gates.shape[1]], w, plan["sizes"],
                      plan["live"][:head.shape[0]])             # [C, D]
    return _sum_to_tokens(ys, plan, gates, True, xf.dtype)


def _compact_bwd(xf, gates, w, plan, gy):
    """`_compact` transposed by hand, gathers only: the cotangent's rows
    and the gates are gathered to the sorted side and multiplied there in
    float32; what flows back to the tokens takes `_sum_to_tokens`' way and
    what flows to the gates is read through the slots (an assignment
    sorted behind the C rows reads zero), where autodiff would scatter
    rows."""
    head = plan["head"]
    count, token = head.shape[0], head // gates.shape[1]
    ys, pull = jax.vjp(
        lambda xs, w: _expert_rows(xs, w, plan["sizes"],
                                   plan["live"][:count]), xf[token], w)
    d_rows = gy[token].astype(jnp.float32)                      # [C, D]
    d_gs = jnp.sum(d_rows * ys.astype(jnp.float32), axis=-1)
    d_xs, d_w = pull((d_rows * gates.reshape(-1)[head][:, None]
                      ).astype(ys.dtype))
    slot = plan["inv"].reshape(gates.shape)
    return (_sum_to_tokens(d_xs, plan, gates, False, xf.dtype),
            jnp.where(slot < count, d_gs[jnp.minimum(slot, count - 1)], 0.0),
            d_w)


@jax.custom_vjp
def _compact_or_full(xf, gates, w, plan):
    """`_compact` where the plan fits, else `_full`.  One differentiation
    rule around the conditional, its residuals the inputs: differentiated
    through, a `lax.cond` keeps both branches' residuals, the branch not
    taken zero-filled at full size, so the compact branch would still
    write the other's [T*k, F] arrays.  Each branch of the backward
    recomputes its own forward."""
    return lax.cond(plan["fits"], _compact, _full, xf, gates, w, plan)


def _compact_or_full_fwd(xf, gates, w, plan):
    return _compact_or_full(xf, gates, w, plan), (xf, gates, w, plan)


def _compact_or_full_bwd(res, gy):
    plan = res[-1]
    return lax.cond(plan["fits"], _compact_bwd, _full_bwd, *res, gy) + (
        jax.tree_util.tree_map(_zero_cotangent, plan),)


_compact_or_full.defvjp(_compact_or_full_fwd, _compact_or_full_bwd)


def dropless_experts(params: Dict, xf: jax.Array, gates: jax.Array,
                     plan: Dict[str, jax.Array]) -> jax.Array:
    """sum over the selected experts HELD HERE of gate * E_e(x), for
    xf [T, D] and gates [T, k]: w1, w3 [H, D, F] and w2 [H, F, D] are the
    held experts' weights in the order of `held` (E_e is SwiGLU; without a
    `w3`, relu(x w1)**2 w2: `_expert_rows`), `plan` the `dispatch_plan` of
    the selection.  Every assignment to a held expert is
    computed, whatever the routing: the rows are sorted by expert and one
    grouped product a matrix runs over as many rows as were routed.  The
    work around the products is done on the first C sorted rows
    (`compact_capacity`) where those hold every row of a held expert, and
    on all T*k otherwise; where C is T*k there is one program and no
    conditional."""
    w = {k: params[k] for k in ("w1", "w3", "w2") if k in params}
    plan = {k: plan[k] for k in ("order", "inv", "sizes", "live", "head",
                                 "fits")}
    with scope("ainic.moe.experts"):
        if plan["head"].shape[0] == plan["order"].shape[0]:
            return _full(xf, gates, w, plan)
        return _compact_or_full(xf, gates, w, plan)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    """(silu(x w1) * (x w3)) w2, the gate's silu in float32."""
    g = jax.nn.silu((x @ w1).astype(jnp.float32)).astype(x.dtype)
    return (g * (x @ w3)) @ w2


def shared_expert(params: Dict, xf: jax.Array) -> jax.Array:
    """The expert every token passes through, beside the routed ones:
    sw1, sw3 [D, F], sw2 [F, D], SwiGLU; without an `sw3`,
    relu(x sw1)**2 sw2, as the routed experts (`_expert_rows`)."""
    with scope("ainic.moe.shared"):
        if "sw3" in params:
            return swiglu(xf, params["sw1"], params["sw3"], params["sw2"])
        return _relu2(xf @ params["sw1"]) @ params["sw2"]


def routing_counts(plan: Dict, experts: jax.Array) -> Dict[str, jax.Array]:
    """What one layer's dropless dispatch did, from its own plan: `rows`
    [H] per expert held, `held_share` of all assignments that landed on a
    held expert, `max_over_mean` of the rows over the held experts, and
    `dropped`: assignments to a held expert that have no live row in the
    grouped product (0 by construction); `selected` is `experts` itself;
    `capacity` is C (`compact_capacity`) and `fit` 1.0 where the layer's
    rows lay within it, so that the compact program ran."""
    n_held = plan["sizes"].shape[0]
    to_held = jnp.sum(plan["local"] < n_held)
    computed = jnp.sum(plan["live"][plan["inv"], 0] & (plan["local"] < n_held))
    rows = plan["sizes"].astype(jnp.float32)
    return {"rows": plan["sizes"], "selected": experts,
            "held_share": to_held / jnp.float32(experts.size),
            "max_over_mean": jnp.max(rows) / jnp.maximum(jnp.mean(rows), 1.0),
            "dropped": to_held - computed,
            "capacity": jnp.int32(plan["head"].shape[0]),
            "fit": plan["fits"].astype(jnp.float32)}


def held_experts_ffn(params: Dict, x: jax.Array, *, num_experts: int,
                     top_k: int, held: Optional[Sequence[int]] = None,
                     scale: float = 1.0, norm_topk: bool = True,
                     bias: Optional[jax.Array] = None,
                     with_counts: bool = False):
    """The expert layer of a sigmoid-routed model on a chip that holds
    `held` of its `num_experts` experts (None: all): x [B, S, D] ->
    sum_{selected & held} gate_i E_i(x) + E_shared(x) (the shared expert
    where `params` has one).  `params`: wr [D, num_experts] float32, w1/w3/w2
    of the experts held, optionally sw1/sw3/sw2; an expert without its
    third matrix is the two-matrix relu**2 one (`_expert_rows`).  No
    auxiliary loss.  With `with_counts`, also `routing_counts` of this
    pass."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    gates, experts = sigmoid_route(params["wr"], xf, top_k=top_k, bias=bias,
                                   scale=scale, norm_topk=norm_topk)
    plan = dispatch_plan(experts, num_experts, held)
    y = dropless_experts(params, xf, gates, plan)
    if "sw1" in params:
        y = y + shared_expert(params, xf)
    y = y.reshape(B, S, D)
    return (y, routing_counts(plan, experts)) if with_counts else y
