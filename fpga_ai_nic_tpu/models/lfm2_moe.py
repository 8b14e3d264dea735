"""LFM2-MoE-style decoder (`lfm2_moe`): gated short-convolution mixers and
grouped-query attention in one stack, sigmoid-routed experts behind either,
a tied head — for the data-parallel trainers.

Layer equations, for a residual x [B, S, D] (RMSNorm eps `norm_eps`; no
projection has a bias).  Every layer: x += mixer(RMSNorm_op(x)), then
x += ffn(RMSNorm_ffn(x)); `layer_types` names each layer's mixer.

- `conv`: [B | C | x~] = h W_in (D x 3D, split in three in this order);
  u = B * x~; c_t = sum_j w[j] * u_{t - (L-1) + j}, u zero before the
  sequence's start (a depthwise causal convolution of L = `conv_taps` taps, a
  correlation: the last tap meets the present); y = C * c; the addend is
  y W_out (D x D).
- `full_attention`: [q | k | v] = h W_qkv, q of `n_heads` heads, k and v of
  `n_kv_heads`, all of width D / n_heads; q = RMSNorm_q(q), k = RMSNorm_k(k)
  over the head width (one weight each, shared by the heads); RoPE
  (rotate-half) on q and k; query head i attends key/value head
  i // (n_heads / n_kv_heads); causal softmax of q.k / sqrt(width); the
  addend is concat(P v) W_o.  W_qkv is the published q_proj, k_proj and
  v_proj side by side: one product, three column blocks.
- the first `n_dense_layers` layers: ffn = SwiGLU of width `ffn_dim`.
- every later layer: `ops.moe.held_experts_ffn` — sigmoid scores over all
  `n_routed_experts`, the `top_k` largest of score + `expert_bias` selected,
  gates the selected scores normalised and scaled; the experts this chip
  holds (`held`) are computed, dropless; no shared expert, no auxiliary
  loss.  `expert_bias` is a leaf (float32, zero at initialisation) that
  steers the selection only: it enters under `stop_gradient`, so a gradient
  step leaves it where it is (its balancing rule lies outside the step).
- logits = RMSNorm_final(x) E^T, E the token embedding (tied).

Consecutive layers of one kind (mixer and ffn alike) are one `lax.scan`
body under `jax.checkpoint`, their parameters stacked on a leading axis:
`params["layers"]` is a list of such runs (`Lfm2MoeConfig.runs`), so the
step compiles each kind of layer once and keeps one layer's activations.
Attention runs through `ops.ring_attention.flash_attention_remat`, which
takes q, k and v of equal head count: k and v are repeated to `n_heads`
outside it (ROADMAP M8).
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.names import scope
from ..ops import moe as moe_ops
from ..ops import ring_attention
from .decoder import KEEP, head_nll, init_leaves, next_token_loss
from .llama import _rmsnorm, _rope

# the published stack: two leading convolution layers, then a c c c repeated
_PUBLISHED_LAYERS = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab: int = 65536
    dim: int = 2048
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    n_dense_layers: int = 2         # num_dense_layers
    n_heads: int = 32
    n_kv_heads: int = 8
    conv_taps: int = 3              # conv_L_cache
    ffn_dim: int = 11776
    moe_ffn_dim: int = 1536
    n_routed_experts: int = 64      # the router's width
    # the experts this chip holds of an expert-parallel layer; None: all
    held: Optional[Tuple[int, ...]] = None
    top_k: int = 4
    routed_scale: float = 1.0
    norm_topk: bool = True
    rope_theta: float = 1e6
    rope_scaling: float = 1.0       # read by models.llama._rope: none
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # keys per block of scores and queries per chunk (None: the sequence)
    attn_block: Optional[int] = 512
    attn_impl: str = "xla"

    def __post_init__(self):
        if set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types}: a layer is "
                             "conv or full_attention")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} heads over {self.n_kv_heads} "
                             f"key/value heads do not divide {self.dim}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers is not in [0, n_layers]")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_held(self) -> int:
        return (self.n_routed_experts if self.held is None
                else len(self.held))

    @property
    def runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """(mixer, ffn, layers) of each run of equal consecutive layers."""
        kinds = [(mixer, "dense" if i < self.n_dense_layers else "moe")
                 for i, mixer in enumerate(self.layer_types)]
        return tuple((*kind, len(list(group)))
                     for kind, group in itertools.groupby(kinds))

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        base = dict(vocab=128, dim=32, n_dense_layers=1, n_heads=4,
                    n_kv_heads=2, ffn_dim=64, moe_ffn_dim=24,
                    n_routed_experts=8, top_k=2, dtype="float32",
                    attn_block=8, layer_types=(
                        "conv", "full_attention", "conv", "conv", "conv"))
        base.update(kw)
        return Lfm2MoeConfig(**base)


def _layer_shapes(cfg: Lfm2MoeConfig, mixer: str,
                  ffn: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.dim
    shapes = {"op_norm": (D,), "ffn_norm": (D,)}
    if mixer == "conv":
        shapes.update(w_in=(D, 3 * D), conv_w=(cfg.conv_taps, D),
                      w_out=(D, D))
    else:
        hd = cfg.head_dim
        shapes.update(wqkv=(D, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
                      q_norm=(hd,), k_norm=(hd,), wo=(D, D))
    if ffn == "dense":
        shapes.update(w1=(D, cfg.ffn_dim), w3=(D, cfg.ffn_dim),
                      w2=(cfg.ffn_dim, D))
    else:
        F, H = cfg.moe_ffn_dim, cfg.n_held
        shapes.update(wr=(D, cfg.n_routed_experts),
                      expert_bias=(cfg.n_routed_experts,),
                      w1=(H, D, F), w3=(H, D, F), w2=(H, F, D))
    return shapes


def init(key: jax.Array, cfg: Lfm2MoeConfig) -> Dict:
    """{"tok_emb", "final_norm", "layers": [one dict a run of `cfg.runs`,
    every leaf with a leading [layers of the run] axis]}.  A filter's fan-in
    is its `conv_taps` taps."""
    dt = jnp.dtype(cfg.dtype)
    ke, *keys = jax.random.split(key, 1 + len(cfg.runs))
    return {
        "tok_emb": (jax.random.normal(ke, (cfg.vocab, cfg.dim), jnp.float32)
                    * cfg.dim ** -0.5).astype(dt),
        "final_norm": jnp.ones((cfg.dim,), dt),
        "layers": [init_leaves(k, _layer_shapes(cfg, mixer, ffn), (n,), dt)
                   for k, (mixer, ffn, n) in zip(keys, cfg.runs)]}


def num_params(cfg: Lfm2MoeConfig) -> int:
    return (cfg.vocab * cfg.dim + cfg.dim + sum(
        n * sum(math.prod(shape)
                for shape in _layer_shapes(cfg, mixer, ffn).values())
        for mixer, ffn, n in cfg.runs))


def causal_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """u [B, S, D], w [L, D] -> c [B, S, D], c_t = sum_j w[j] * u_{t-(L-1)+j}
    with u zero before the sequence: one filter a channel, float32."""
    taps, seq = w.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j].astype(jnp.float32) * padded[:, j:j + seq]
               for j in range(taps))


def conv_mixer(lyr: Dict, h: jax.Array) -> jax.Array:
    """The gated short convolution's addend for h = RMSNorm(x) [B, S, D]."""
    with scope("ainic.conv"):
        b, c, xt = jnp.split(h @ lyr["w_in"], 3, axis=-1)
        u = b.astype(jnp.float32) * xt.astype(jnp.float32)
        y = c.astype(jnp.float32) * causal_conv(u, lyr["conv_w"])
        return y.astype(h.dtype) @ lyr["w_out"]


def gqa(lyr: Dict, h: jax.Array, pos: jax.Array,
        cfg: Lfm2MoeConfig) -> jax.Array:
    """The grouped-query attention's addend for h = RMSNorm(x) [B, S, D]."""
    B, S, D = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with scope("ainic.gqa"):
        def heads(cols, n):
            return cols.reshape(B, S, n, hd).transpose(0, 2, 1, 3)

        q, k, v = jnp.split(h @ lyr["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
        q = _rope(_rmsnorm(heads(q, H), lyr["q_norm"], cfg.norm_eps), pos,
                  cfg)
        k = _rope(_rmsnorm(heads(k, KV), lyr["k_norm"], cfg.norm_eps), pos,
                  cfg)
        # query head i attends key/value head i // (H / KV)
        k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, heads(v, KV)))
        o = ring_attention.flash_attention_remat(
            q, k, v, causal=True, k_block=cfg.attn_block, impl=cfg.attn_impl)
        return o.transpose(0, 2, 1, 3).reshape(B, S, D) @ lyr["wo"]


def _layer(lyr: Dict, x: jax.Array, pos: jax.Array, cfg: Lfm2MoeConfig,
           mixer: str, ffn: str, with_counts: bool = False):
    """One layer of a run -> (x, `ops.moe.routing_counts` or None)."""
    h = _rmsnorm(x, lyr["op_norm"], cfg.norm_eps)
    x = x + (conv_mixer(lyr, h) if mixer == "conv"
             else gqa(lyr, h, pos, cfg))
    h = _rmsnorm(x, lyr["ffn_norm"], cfg.norm_eps)
    if ffn == "dense":
        return x + moe_ops.swiglu(h, lyr["w1"], lyr["w3"], lyr["w2"]), None
    out = moe_ops.held_experts_ffn(
        lyr, h, num_experts=cfg.n_routed_experts, top_k=cfg.top_k,
        held=cfg.held, scale=cfg.routed_scale, norm_topk=cfg.norm_topk,
        bias=lax.stop_gradient(lyr["expert_bias"]), with_counts=with_counts)
    return (x + out[0], out[1]) if with_counts else (x + out, None)


def hidden(params: Dict, tokens: jax.Array, cfg: Lfm2MoeConfig,
           with_counts: bool = False):
    """tokens [B, S] -> the last layer's residual [B, S, D] (before the
    final norm) [, `ops.moe.routing_counts` stacked over the expert
    layers]."""
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = params["tok_emb"][tokens]
    counts = []
    for (mixer, ffn, _), stack in zip(cfg.runs, params["layers"]):
        def body(y, lyr, mixer=mixer, ffn=ffn):
            return _layer(lyr, y, pos, cfg, mixer, ffn, with_counts)
        x, run_counts = lax.scan(jax.checkpoint(body, policy=KEEP), x, stack)
        if run_counts is not None:
            counts.append(run_counts)
    if not with_counts:
        return x
    return x, jax.tree_util.tree_map(
        lambda *parts: jnp.concatenate(parts), *counts)


def loss_fn(params: Dict, batch, cfg: Lfm2MoeConfig, *,
            dp_axis: Optional[str] = None) -> jax.Array:
    """Next-token cross-entropy over the rows of the vocabulary held.
    batch = (tokens, labels), both [B, S]; labels are the shifted targets,
    -100 where a position has none.  dp_axis: `decoder.next_token_loss`."""
    tokens, labels = batch
    valid = (labels >= 0).reshape(-1)
    x = hidden(params, tokens, cfg)
    nll = head_nll(params["final_norm"], params["tok_emb"],
                   x.reshape(-1, x.shape[-1]),
                   jnp.where(valid, labels.reshape(-1), 0), cfg.norm_eps,
                   tied=True)
    return next_token_loss(nll, valid, dp_axis=dp_axis)


def routing_stats(params: Dict, batch, cfg: Lfm2MoeConfig) -> Dict:
    """What the dropless dispatch does with `batch`, per expert layer
    (leading axis): `rows` [L, H] routed to each expert held, `held_share`
    [L] of all assignments that landed on a held expert, `max_over_mean`
    [L] of the rows over the held experts, `dropped` [L] (0 by
    construction).  One forward pass, jit-safe; call it outside a timed
    step."""
    _, counts = hidden(params, batch[0], cfg, with_counts=True)
    return counts
