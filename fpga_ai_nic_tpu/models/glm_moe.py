"""GLM-4.7-Flash-style decoder (`glm4_moe_lite`): latent attention and
sigmoid-routed experts beside a shared one, for the data-parallel trainers.

Layer equations, for a token's residual x (RMSNorm eps `norm_eps`):

- attention (MLA): h = RMSNorm(x); c_q = RMSNorm(h W_qa); q = c_q W_qb, per
  head (nope | rope); [c_kv | k_r] = h W_kva; c_kv = RMSNorm(c_kv);
  [k_nope | v] = c_kv W_kvb per head; k_r is ONE rotary key shared by all
  heads; q_h = [q_nope | RoPE(q_rope)], k_h = [k_nope | RoPE(k_r)]; causal
  softmax of q_h.k_h / sqrt(nope + rope); x += concat(P v_h) W_o.
- the first `n_dense_layers` layers: x += SwiGLU(RMSNorm(x)), width `ffn_dim`.
- every later layer: `ops.moe.held_experts_ffn` — sigmoid scores over all
  `n_routed_experts`, top-k, gates normalised over the selection and scaled;
  the experts this chip holds (`held`) are computed, dropless, and the shared
  expert is added.  No auxiliary loss: the loss is the next-token
  cross-entropy alone.
- logits = RMSNorm(x) W_head (untied).

The equal expert layers are one `lax.scan` body under `jax.checkpoint`, their
parameters stacked on a leading axis (`params["moe"]`), so the step compiles
one such layer and keeps one layer's activations.  Attention runs through
`ops.ring_attention.flash_attention_remat` (scores in blocks, never
[B, H, S, S]); that route accumulates the output at the width of q, so
nope + rope must equal the value width, as it does in this family (192 + 64
= 256).  The multi-token-prediction module of the published model is not
here (ROADMAP M5).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.names import scope
from ..ops import moe as moe_ops
from ..ops import ring_attention
from .decoder import KEEP as _KEEP
from .decoder import head_nll, init_leaves as _init_leaves, next_token_loss
from .llama import _rmsnorm, _rope


@dataclass(frozen=True)
class GlmMoeConfig:
    vocab: int = 154880
    dim: int = 2048
    n_layers: int = 47
    n_dense_layers: int = 1         # first_k_dense_replace
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_dim: int = 256
    ffn_dim: int = 10240
    moe_ffn_dim: int = 1536
    n_routed_experts: int = 64      # the router's width
    # the experts this chip holds of an expert-parallel layer; None: all
    held: Optional[Tuple[int, ...]] = None
    top_k: int = 4
    routed_scale: float = 1.8
    norm_topk: bool = True
    shared_expert: bool = True
    rope_theta: float = 1e6
    rope_scaling: float = 1.0       # read by models.llama._rope: none
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # keys per block of scores and queries per chunk (None: the sequence)
    attn_block: Optional[int] = 512
    attn_impl: str = "xla"

    def __post_init__(self):
        if self.qk_nope_dim + self.qk_rope_dim != self.v_dim:
            raise ValueError(
                "the blocked attention route accumulates its output at the "
                f"width of q: {self.qk_nope_dim} + {self.qk_rope_dim} is "
                f"not the value width {self.v_dim}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers is not in [0, n_layers]")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_held(self) -> int:
        return (self.n_routed_experts if self.held is None
                else len(self.held))

    @staticmethod
    def tiny(**kw) -> "GlmMoeConfig":
        base = dict(vocab=128, dim=32, n_layers=3, n_dense_layers=1,
                    n_heads=2, q_lora_rank=16, kv_lora_rank=8,
                    qk_nope_dim=12, qk_rope_dim=4, v_dim=16, ffn_dim=64,
                    moe_ffn_dim=24, n_routed_experts=8, top_k=2,
                    dtype="float32", attn_block=8)
        base.update(kw)
        return GlmMoeConfig(**base)


def _attention_shapes(cfg: GlmMoeConfig) -> Dict[str, Tuple[int, ...]]:
    D, H = cfg.dim, cfg.n_heads
    return {
        "attn_norm": (D,), "wq_a": (D, cfg.q_lora_rank),
        "q_norm": (cfg.q_lora_rank,),
        "wq_b": (cfg.q_lora_rank, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
        "wkv_a": (D, cfg.kv_lora_rank + cfg.qk_rope_dim),
        "kv_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_dim)),
        "wo": (H * cfg.v_dim, D), "mlp_norm": (D,)}


def _dense_shapes(cfg: GlmMoeConfig) -> Dict[str, Tuple[int, ...]]:
    D, F = cfg.dim, cfg.ffn_dim
    return dict(_attention_shapes(cfg), w1=(D, F), w3=(D, F), w2=(F, D))


def _moe_shapes(cfg: GlmMoeConfig) -> Dict[str, Tuple[int, ...]]:
    D, F, H = cfg.dim, cfg.moe_ffn_dim, cfg.n_held
    shapes = dict(_attention_shapes(cfg), wr=(D, cfg.n_routed_experts),
                  w1=(H, D, F), w3=(H, D, F), w2=(H, F, D))
    if cfg.shared_expert:
        shapes.update(sw1=(D, F), sw3=(D, F), sw2=(F, D))
    return shapes


def init(key: jax.Array, cfg: GlmMoeConfig) -> Dict:
    """{"tok_emb", "final_norm", "lm_head", "dense": [a dict a leading
    dense layer], "moe": one dict for all expert layers, every leaf with a
    leading [n_moe_layers] axis}."""
    dt = jnp.dtype(cfg.dtype)
    ke, kh, kd, km = jax.random.split(key, 4)
    D = cfg.dim
    params = {
        "tok_emb": (jax.random.normal(ke, (cfg.vocab, D), jnp.float32)
                    * D ** -0.5).astype(dt),
        "final_norm": jnp.ones((D,), dt),
        "lm_head": (jax.random.normal(kh, (D, cfg.vocab), jnp.float32)
                    * D ** -0.5).astype(dt),
        "dense": [_init_leaves(k, _dense_shapes(cfg), (), dt)
                  for k in jax.random.split(kd, cfg.n_dense_layers)],
    }
    if cfg.n_moe_layers:
        params["moe"] = _init_leaves(km, _moe_shapes(cfg),
                                     (cfg.n_moe_layers,), dt)
    return params


def num_params(cfg: GlmMoeConfig) -> int:
    def count(shapes):
        return sum(math.prod(shape) for shape in shapes.values())
    return (2 * cfg.vocab * cfg.dim + cfg.dim
            + cfg.n_dense_layers * count(_dense_shapes(cfg))
            + cfg.n_moe_layers * count(_moe_shapes(cfg)))


def _causal_attention(q, k, v, cfg: GlmMoeConfig) -> jax.Array:
    """[B, H, S, d] each -> [B, H, S, d], through the memory-bounded route:
    queries and keys in blocks of `attn_block` rows, of which the route
    visits those at or below the diagonal (36 of 64 at eight blocks a
    side)."""
    return ring_attention.flash_attention_remat(
        q, k, v, causal=True, k_block=cfg.attn_block, impl=cfg.attn_impl)


def mla(lyr: Dict, x: jax.Array, pos: jax.Array,
        cfg: GlmMoeConfig) -> jax.Array:
    """The attention block's addend for x [B, S, D]."""
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    with scope("ainic.mla"):
        h = _rmsnorm(x, lyr["attn_norm"], cfg.norm_eps)
        c_q = _rmsnorm(h @ lyr["wq_a"], lyr["q_norm"], cfg.norm_eps)
        q = (c_q @ lyr["wq_b"]).reshape(B, S, H, dn + dr)
        q = q.transpose(0, 2, 1, 3)                         # [B, H, S, .]
        kv_a = h @ lyr["wkv_a"]                             # [B, S, r + dr]
        c_kv = _rmsnorm(kv_a[..., :cfg.kv_lora_rank], lyr["kv_norm"],
                        cfg.norm_eps)
        k_r = _rope(kv_a[:, None, :, cfg.kv_lora_rank:], pos, cfg)
        kv = (c_kv @ lyr["wkv_b"]).reshape(B, S, H, dn + dv)
        kv = kv.transpose(0, 2, 1, 3)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos, cfg)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, H, S, dr))], axis=-1)
        o = _causal_attention(q, k, kv[..., dn:], cfg)
        return o.transpose(0, 2, 1, 3).reshape(B, S, H * dv) @ lyr["wo"]


def _dense_block(lyr: Dict, x: jax.Array, pos: jax.Array,
                 cfg: GlmMoeConfig) -> jax.Array:
    x = x + mla(lyr, x, pos, cfg)
    return x + moe_ops.swiglu(_rmsnorm(x, lyr["mlp_norm"], cfg.norm_eps),
                              lyr["w1"], lyr["w3"], lyr["w2"])


def _moe_block(lyr: Dict, x: jax.Array, pos: jax.Array, cfg: GlmMoeConfig,
               with_counts: bool = False):
    x = x + mla(lyr, x, pos, cfg)
    out = moe_ops.held_experts_ffn(
        lyr, _rmsnorm(x, lyr["mlp_norm"], cfg.norm_eps),
        num_experts=cfg.n_routed_experts, top_k=cfg.top_k, held=cfg.held,
        scale=cfg.routed_scale, norm_topk=cfg.norm_topk,
        with_counts=with_counts)
    if with_counts:
        return x + out[0], out[1]
    return x + out


def hidden(params: Dict, tokens: jax.Array, cfg: GlmMoeConfig,
           with_counts: bool = False):
    """tokens [B, S] -> the last layer's residual [B, S, D] (before the
    final norm) [, `ops.moe.routing_counts` stacked over the expert
    layers]."""
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = params["tok_emb"][tokens]
    for lyr in params["dense"]:
        x = jax.checkpoint(
            lambda l, y: _dense_block(l, y, pos, cfg), policy=_KEEP)(lyr, x)
    counts = None
    if cfg.n_moe_layers:
        def body(y, lyr):
            out = _moe_block(lyr, y, pos, cfg, with_counts)
            return out if with_counts else (out, None)
        x, counts = lax.scan(jax.checkpoint(body, policy=_KEEP), x,
                             params["moe"])
    return (x, counts) if with_counts else x


def loss_fn(params: Dict, batch, cfg: GlmMoeConfig, *,
            dp_axis: Optional[str] = None) -> jax.Array:
    """Next-token cross-entropy.  batch = (tokens, labels), both [B, S];
    labels are the shifted targets, -100 where a position has none.
    dp_axis: `decoder.next_token_loss`."""
    tokens, labels = batch
    valid = (labels >= 0).reshape(-1)
    x = hidden(params, tokens, cfg)
    nll = head_nll(params["final_norm"], params["lm_head"],
                   x.reshape(-1, x.shape[-1]),
                   jnp.where(valid, labels.reshape(-1), 0), cfg.norm_eps)
    return next_token_loss(nll, valid, dp_axis=dp_axis)


def routing_stats(params: Dict, batch, cfg: GlmMoeConfig) -> Dict:
    """What the dropless dispatch does with `batch`, per expert layer
    (leading axis): `rows` [L, H] routed to each expert held, `held_share`
    [L] of all assignments that landed on a held expert, `max_over_mean`
    [L] of the rows over the held experts, `dropped` [L] (0 by
    construction).  One forward pass, jit-safe; call it outside a timed
    step."""
    _, counts = hidden(params, batch[0], cfg, with_counts=True)
    return counts
