"""Nemotron-H-style decoder (`nemotron_h`): Mamba-2 mixers, sigmoid-routed
relu**2 experts beside a shared one, and a few grouped-query attention
blocks in one stack, an untied head — for the data-parallel trainers.

A BLOCK IS A MIXER OR A FEED-FORWARD PART ALONE: one pre-norm and one
residual add each, x += f(RMSNorm(x)), its kind a letter of `pattern`
(RMSNorm eps `norm_eps`; no projection has a bias):

- `M`, Mamba-2 (Dao & Gu, arXiv:2405.21060): [z | xBC | dt] = h W_in, of
  widths d_inner = `ssm_heads` x `ssm_head_dim`, d_inner + 2 x `ssm_groups`
  x `ssm_state`, `ssm_heads`; xBC = silu(conv(xBC) + conv_bias), a causal
  depthwise convolution of `conv_taps` taps (`lfm2_moe.causal_conv`,
  float32); xBC splits into x [heads, head_dim], B and C [groups, state],
  head h reading group h // (heads / groups); D_t = softplus(dt + dt_bias)
  (no clip), a_t = exp(D_t * A), A = -exp(A_log) a scalar a head;
  S_t = a_t S_{t-1} + D_t x_t (x) B_t, y_t = C_t . S_t + d_skip * x_t
  (`ssd_scan`: the recurrence in chunks of `chunk` positions, in its matrix
  form, as the Pallas kernels of `ops.ssd_pallas`); y = RMSNorm_grouped(y *
  silu(z)), over groups of d_inner / groups channels; the addend is y W_out.
- `E`: `ops.moe.held_experts_ffn` — sigmoid scores over all
  `n_routed_experts`, the `top_k` largest of score + `expert_bias` selected,
  gates the selected scores normalised and scaled; the experts this chip
  holds (`held`) are computed, dropless, each relu(x w1)**2 w2 (two
  matrices: the weights hold no `w3`), and the shared expert, the same at
  width `shared_ffn_dim`, for every token.  `expert_bias` is a float32 leaf
  that steers the selection only; it enters under `stop_gradient`, so no
  step moves it.  `balance_bias` sets it before training.
- `*`: [q | k | v] = h W_qkv, `n_heads` heads of q over `n_kv_heads` of k
  and v, width `head_dim` (n_heads x head_dim need not be `dim`); causal
  softmax of q.k / sqrt(head_dim), no rotary embedding; query head i attends
  key/value head i // (n_heads / n_kv_heads); the addend is concat(P v) W_o.
- logits = RMSNorm_final(x) W_head over the rows of the vocabulary held.

Consecutive blocks of one kind are one `lax.scan` body under
`jax.checkpoint`, their parameters stacked on a leading axis:
`params["blocks"]` is a list of such runs (`NemotronHConfig.runs`), as in
`models/lfm2_moe.py`.  In the published pattern nearly every run has one
block; a scan of one trip is the block itself once compiled, and a pattern
that repeats a kind keeps one body for the run.  Attention runs through
`ops.ring_attention.flash_attention_remat`, k and v repeated to `n_heads`
outside it.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.names import scope
from ..ops import moe as moe_ops
from ..ops import ring_attention, ssd_pallas
from .decoder import KEEP, head_nll, init_leaves, next_token_loss
from .lfm2_moe import causal_conv
from .llama import _rmsnorm

# Nemotron-Labs-TwoTower-30B-A3B's language tower (`hybrid_override_pattern`)
_PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# `balance_bias` stops where the fullest of a block's experts has this
# many times the mean load
BALANCE_TOL = 1.05


@dataclass(frozen=True)
class NemotronHConfig:
    vocab: int = 131072
    dim: int = 2688
    pattern: str = _PUBLISHED_PATTERN
    ssm_heads: int = 64             # mamba_num_heads
    ssm_head_dim: int = 64          # mamba_head_dim
    ssm_groups: int = 8             # n_groups
    ssm_state: int = 128            # ssm_state_size
    conv_taps: int = 4              # conv_kernel
    chunk: int = 128                # chunk_size
    dt_min: float = 0.001           # time_step_min
    dt_max: float = 0.1             # time_step_max
    dt_floor: float = 1e-4          # time_step_floor
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    moe_ffn_dim: int = 1856
    shared_ffn_dim: int = 3712
    n_routed_experts: int = 128     # the router's width
    # the experts this chip holds of an expert-parallel layer; None: all
    held: Optional[Tuple[int, ...]] = None
    top_k: int = 6
    routed_scale: float = 2.5
    norm_topk: bool = True
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # keys per block of scores and queries per chunk (None: the sequence)
    attn_block: Optional[int] = 512
    attn_impl: str = "xla"

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("ME*"):
            raise ValueError(f"pattern {self.pattern!r}: a block is M "
                             "(Mamba-2), E (experts) or * (attention)")
        if self.ssm_heads % self.ssm_groups or self.d_inner % self.ssm_groups:
            raise ValueError(f"{self.ssm_groups} groups do not divide "
                             f"{self.ssm_heads} heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} heads over {self.n_kv_heads} "
                             "key/value heads")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_held(self) -> int:
        return (self.n_routed_experts if self.held is None
                else len(self.held))

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """(kind, blocks) of each run of equal consecutive blocks."""
        return tuple((kind, len(list(group)))
                     for kind, group in itertools.groupby(self.pattern))

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        base = dict(vocab=128, dim=64, pattern="MEMEM*EME", ssm_heads=4,
                    ssm_head_dim=16, ssm_groups=2, ssm_state=16, chunk=8,
                    n_heads=4, n_kv_heads=2, head_dim=16, moe_ffn_dim=24,
                    shared_ffn_dim=48, n_routed_experts=16, top_k=3,
                    dtype="float32", attn_block=8)
        base.update(kw)
        return NemotronHConfig(**base)


def _block_shapes(cfg: NemotronHConfig,
                  kind: str) -> Dict[str, Tuple[int, ...]]:
    """Every leaf of one block but a mixer's per-head scalars
    (`_ssm_scalars`)."""
    D = cfg.dim
    if kind == "M":
        return {"norm": (D,),
                "w_in": (D, cfg.d_inner + cfg.conv_dim + cfg.ssm_heads),
                "conv_w": (cfg.conv_taps, cfg.conv_dim),
                "conv_bias": (cfg.conv_dim,), "gate_norm": (cfg.d_inner,),
                "w_out": (cfg.d_inner, D)}
    if kind == "E":
        F, H = cfg.moe_ffn_dim, cfg.n_held
        return {"norm": (D,), "wr": (D, cfg.n_routed_experts),
                "expert_bias": (cfg.n_routed_experts,),
                "w1": (H, D, F), "w2": (H, F, D),
                "sw1": (D, cfg.shared_ffn_dim),
                "sw2": (cfg.shared_ffn_dim, D)}
    width = cfg.n_heads * cfg.head_dim
    return {"norm": (D,),
            "wqkv": (D, width + 2 * cfg.n_kv_heads * cfg.head_dim),
            "wo": (width, D)}


def _ssm_scalars(key: jax.Array, cfg: NemotronHConfig, n: int) -> Dict:
    """A mixer's float32 scalars a head, as Mamba-2 initialises them: A
    uniform in [1, 16] (kept as its log), the step D log-uniform in
    [dt_min, dt_max] and no smaller than dt_floor (kept as the inverse
    softplus), the skip at one."""
    ka, kd = jax.random.split(key)
    shape = (n, cfg.ssm_heads)
    a = jax.random.uniform(ka, shape, jnp.float32, 1.0, 16.0)
    step = jnp.maximum(cfg.dt_floor, jnp.exp(jax.random.uniform(
        kd, shape, jnp.float32, math.log(cfg.dt_min), math.log(cfg.dt_max))))
    return {"A_log": jnp.log(a), "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "d_skip": jnp.ones(shape, jnp.float32)}


def init(key: jax.Array, cfg: NemotronHConfig) -> Dict:
    """{"tok_emb", "final_norm", "head", "blocks": [one dict a run of
    `cfg.runs`, every leaf with a leading [blocks of the run] axis]}.
    Matrices normal with variance 1/fan_in (a filter's fan-in is its taps),
    norms at one, both biases zero and float32, the router float32."""
    dt = jnp.dtype(cfg.dtype)
    ke, kh, *keys = jax.random.split(key, 2 + len(cfg.runs))
    blocks = []
    for k, (kind, n) in zip(keys, cfg.runs):
        kl, ks = jax.random.split(k)
        leaves = init_leaves(kl, _block_shapes(cfg, kind), (n,), dt)
        if kind == "M":
            leaves.update(_ssm_scalars(ks, cfg, n))
        blocks.append(leaves)

    def matrix(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * cfg.dim ** -0.5).astype(dt)

    return {"tok_emb": matrix(ke, (cfg.vocab, cfg.dim)),
            "final_norm": jnp.ones((cfg.dim,), dt),
            "head": matrix(kh, (cfg.dim, cfg.vocab)), "blocks": blocks}


def num_params(cfg: NemotronHConfig) -> int:
    def block(kind):
        return sum(math.prod(shape)
                   for shape in _block_shapes(cfg, kind).values()) + (
            3 * cfg.ssm_heads if kind == "M" else 0)
    return (2 * cfg.vocab * cfg.dim + cfg.dim
            + sum(n * block(kind) for kind, n in cfg.runs))


def ssd_scan(x: jax.Array, step: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int) -> jax.Array:
    """y_t = C_t . S_t of S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t, S
    zero before the sequence: x [B, S, H, P], the steps D = `step`
    [B, S, H] float32, a [H] (negative), b and c [B, S, G, N], head h
    reading group h // (H / G) -> y [B, S, H, P] float32.

    The recurrence in chunks of `chunk` positions, in its matrix form
    (Mamba-2's state-space duality), as the Pallas kernels of
    `ops.ssd_pallas`: inside a chunk (C B^T * L) (D x), L the decay between
    two positions, from the cumulative sum of D a in float32, built in fast
    memory; the state carried over the chunks in float32; C . S of the
    carried state, decayed to the position.  Products take the operands'
    type and accumulate in float32.  A sequence that is no multiple of the
    chunk is padded with steps of zero, which move no state."""
    with scope("ainic.ssm.scan"):
        S = x.shape[1]
        pad = -S % chunk
        if pad:
            x, step, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
                t.ndim - 2)) for t in (x, step, b, c))
        return ssd_pallas.ssd(x, step, a, b, c, chunk)[:, :S]


def mamba_mixer(lyr: Dict, h: jax.Array, cfg: NemotronHConfig) -> jax.Array:
    """The Mamba-2 mixer's addend for h = RMSNorm(x) [B, S, D]."""
    B, S, _ = h.shape
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    f32 = jnp.float32
    with scope("ainic.ssm"):
        z, xbc, dt = jnp.split(h @ lyr["w_in"],
                               [cfg.d_inner, cfg.d_inner + cfg.conv_dim],
                               axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, lyr["conv_w"])
                          + lyr["conv_bias"]).astype(h.dtype)
        x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
        x = x.reshape(B, S, H, P)
        y = ssd_scan(x, jax.nn.softplus(dt.astype(f32) + lyr["dt_bias"]),
                     -jnp.exp(lyr["A_log"]), b.reshape(B, S, G, N),
                     c.reshape(B, S, G, N), cfg.chunk)
        y = y + lyr["d_skip"][:, None] * x.astype(f32)
        y = y.reshape(B, S, cfg.d_inner) * jax.nn.silu(z.astype(f32))
        # the gated norm: one RMSNorm a group of d_inner / G channels.  The
        # groups' sums of squares, and the factors back over the channels,
        # are products with the groups' one-hot [d_inner, G], exact at
        # HIGHEST: y keeps the scan's layout, where a reshape to
        # [B, S, G, d_inner / G] costs XLA a copy of it a pass
        width = cfg.d_inner // G
        groups = jax.nn.one_hot(jnp.arange(cfg.d_inner) // width, G,
                                dtype=f32)
        ms = jnp.dot(y * y, groups, precision=lax.Precision.HIGHEST) / width
        y = y * jnp.dot(lax.rsqrt(ms + cfg.norm_eps), groups.T,
                        precision=lax.Precision.HIGHEST)
        return (y.astype(h.dtype) * lyr["gate_norm"]) @ lyr["w_out"]


def attention(lyr: Dict, h: jax.Array, cfg: NemotronHConfig) -> jax.Array:
    """The grouped-query attention's addend for h = RMSNorm(x) [B, S, D]."""
    B, S, _ = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with scope("ainic.gqa"):
        q, k, v = (cols.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
                   for cols in jnp.split(h @ lyr["wqkv"],
                                         [H * hd, (H + KV) * hd], axis=-1))
        # query head i attends key/value head i // (H / KV)
        k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
        o = ring_attention.flash_attention_remat(
            q, k, v, causal=True, k_block=cfg.attn_block, impl=cfg.attn_impl)
        return o.transpose(0, 2, 1, 3).reshape(B, S, H * hd) @ lyr["wo"]


def _experts(lyr: Dict, h: jax.Array, cfg: NemotronHConfig,
             with_counts: bool):
    return moe_ops.held_experts_ffn(
        lyr, h, num_experts=cfg.n_routed_experts, top_k=cfg.top_k,
        held=cfg.held, scale=cfg.routed_scale, norm_topk=cfg.norm_topk,
        bias=lax.stop_gradient(lyr["expert_bias"]), with_counts=with_counts)


def _block(lyr: Dict, x: jax.Array, cfg: NemotronHConfig, kind: str,
           with_counts: bool = False):
    """One block of a run -> (x, `ops.moe.routing_counts` or None)."""
    h = _rmsnorm(x, lyr["norm"], cfg.norm_eps)
    if kind == "E":
        out = _experts(lyr, h, cfg, with_counts)
        return (x + out[0], out[1]) if with_counts else (x + out, None)
    return x + (mamba_mixer if kind == "M" else attention)(lyr, h, cfg), None


def hidden(params: Dict, tokens: jax.Array, cfg: NemotronHConfig,
           with_counts: bool = False):
    """tokens [B, S] -> the last block's residual [B, S, D] (before the
    final norm) [, `ops.moe.routing_counts` stacked over the expert
    blocks]."""
    x = params["tok_emb"][tokens]
    counts = []
    for (kind, _), stack in zip(cfg.runs, params["blocks"]):
        def body(y, lyr, kind=kind):
            return _block(lyr, y, cfg, kind, with_counts)
        x, run_counts = lax.scan(jax.checkpoint(body, policy=KEEP), x, stack)
        if run_counts is not None:
            counts.append(run_counts)
    if not with_counts:
        return x
    return x, jax.tree_util.tree_map(
        lambda *parts: jnp.concatenate(parts), *counts)


def loss_fn(params: Dict, batch, cfg: NemotronHConfig, *,
            dp_axis: Optional[str] = None) -> jax.Array:
    """Next-token cross-entropy over the rows of the vocabulary held.
    batch = (tokens, labels), both [B, S]; labels are the shifted targets,
    -100 where a position has none.  dp_axis: `decoder.next_token_loss`."""
    tokens, labels = batch
    valid = (labels >= 0).reshape(-1)
    x = hidden(params, tokens, cfg)
    nll = head_nll(params["final_norm"], params["head"],
                   x.reshape(-1, x.shape[-1]),
                   jnp.where(valid, labels.reshape(-1), 0), cfg.norm_eps)
    return next_token_loss(nll, valid, dp_axis=dp_axis)


def routing_stats(params: Dict, batch, cfg: NemotronHConfig) -> Dict:
    """What the dropless dispatch does with `batch`, per expert block
    (leading axis), as `lfm2_moe.routing_stats`.  One forward pass,
    jit-safe; call it outside a timed step."""
    _, counts = hidden(params, batch[0], cfg, with_counts=True)
    return counts


@functools.partial(jax.jit, static_argnames=("cfg",))
def balance_bias(params: Dict, tokens: jax.Array,
                 cfg: NemotronHConfig) -> Dict:
    """`params` with every expert block's `expert_bias` set so that, on
    `tokens` [B, S], the block's selection loads all its experts evenly
    (max load / mean load <= `BALANCE_TOL`): block by block in forward
    order, each on the residual the balanced blocks before it give,
    `ops.moe.balanced_bias` on the block's own scores.  It stands for what
    a trained router's bias holds (the published router carries one, and
    its update rule, arXiv:2408.15664, keeps the loads even); a zero bias
    on random weights sends one expert up to 2.3 times the mean.  Called
    once, on the weights `init` makes; no step moves the bias."""
    x = params["tok_emb"][tokens]
    blocks = []
    for (kind, _), stack in zip(cfg.runs, params["blocks"]):
        def body(y, lyr, kind=kind):
            if kind != "E":
                return _block(lyr, y, cfg, kind)
            h = _rmsnorm(y, lyr["norm"], cfg.norm_eps)
            bias = moe_ops.balanced_bias(
                moe_ops.router_scores(lyr["wr"], h.reshape(-1, cfg.dim)),
                cfg.top_k, BALANCE_TOL)
            return y + _experts(dict(lyr, expert_bias=bias), h, cfg,
                                False), bias
        x, biases = lax.scan(body, x, stack)
        blocks.append(stack if biases is None
                      else dict(stack, expert_bias=biases))
    return dict(params, blocks=blocks)
