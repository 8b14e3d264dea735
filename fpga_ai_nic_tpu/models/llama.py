"""Llama-3-family decoder, written for explicit mesh parallelism.

BASELINE.json config 5 ("Llama-3 8B ZeRO-1 ... BFP optimizer-state
compression") is the north-star; the reference itself has no transformer —
this model exists to exercise the framework's parallel axes at scale:

- tp: attention heads and FFN hidden are column/row sharded; row-parallel
  projections end in one ``lax.psum`` over the tp axis (Megatron-style,
  expressed directly in the model because shard_map makes collectives
  first-class, the way the reference made its ring explicit in RTL).
- sp: the sequence axis is sharded; attention runs `ops.ring_attention`
  (K/V blocks rotating the ring) and RoPE positions are offset per shard.
- dp/ZeRO-1: handled outside by the trainer (`parallel.sharded`).

Functional pytree params, like models.mlp.  GQA, RMSNorm, SwiGLU, RoPE.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import moe as moe_ops
from ..ops.ring_attention import (flash_attention_remat, full_attention,
                                  gathered_attention, pallas_route,
                                  ring_attention)


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    # Llama-3.1-style NTK rope scaling for context extension (the
    # long-context regime ring attention exists for): frequencies whose
    # wavelength exceeds old_context are stretched by rope_scaling; the
    # high-frequency band is untouched; in between interpolates smoothly.
    # rope_scaling=1.0 disables (exact parity with unscaled rope).
    rope_scaling: float = 1.0
    rope_old_context: int = 8192
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # flash-blocked single-device attention (ops.ring_attention.
    # flash_attention): score memory O(S * attn_block) instead of
    # full_attention's O(S^2); None keeps the exact direct softmax.
    # sp-sharded paths (ring/gathered) block independently of this knob.
    attn_block: "Optional[int]" = None
    # which flash implementation backs attn_block: "auto" = the fused
    # Pallas kernels on TPU (ops.flash_pallas), XLA blocks elsewhere
    # (both a custom-vjp backward); "pallas"/"xla" pin one for A/B runs
    attn_impl: str = "auto"
    # MoE: when moe_experts > 0, every FFN becomes a top-k routed expert
    # layer (ops.moe); dense SwiGLU otherwise.  Not composable with the
    # pipelined path yet (apply_pp raises).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def moe(self) -> Optional["moe_ops.MoEConfig"]:
        if self.moe_experts == 0:
            return None
        return moe_ops.MoEConfig(
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_weight=self.moe_aux_weight)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab: int = 256, dim: int = 64, n_layers: int = 2,
             n_heads: int = 4, n_kv_heads: int = 2, ffn_dim: int = 128,
             dtype: str = "float32") -> "LlamaConfig":
        return LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                           n_heads=n_heads, n_kv_heads=n_kv_heads,
                           ffn_dim=ffn_dim, dtype=dtype)


def init(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """Global (unsharded) parameter pytree; shard with param_specs."""
    dt = jnp.dtype(cfg.dtype)
    D, Hd = cfg.dim, cfg.head_dim

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(1.0 / fan_in)).astype(dt)

    keys = iter(jax.random.split(key, 4 + cfg.n_layers * 7))
    params = {
        "tok_emb": dense(next(keys), D, (cfg.vocab, D)),
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(next(keys), D, (D, cfg.vocab)),
        "layers": [],
    }
    for _ in range(cfg.n_layers):
        lyr = {
            "attn_norm": jnp.ones((D,), dt),
            "wq": dense(next(keys), D, (D, cfg.n_heads * Hd)),
            "wk": dense(next(keys), D, (D, cfg.n_kv_heads * Hd)),
            "wv": dense(next(keys), D, (D, cfg.n_kv_heads * Hd)),
            "wo": dense(next(keys), cfg.n_heads * Hd, (cfg.n_heads * Hd, D)),
            "mlp_norm": jnp.ones((D,), dt),
        }
        if cfg.moe is not None:
            lyr["moe"] = moe_ops.init_ffn(next(keys), D, cfg.ffn_dim,
                                          cfg.moe, dtype=dt)
        else:
            lyr.update({
                "w1": dense(next(keys), D, (D, cfg.ffn_dim)),
                "w3": dense(next(keys), D, (D, cfg.ffn_dim)),
                "w2": dense(next(keys), cfg.ffn_dim, (cfg.ffn_dim, D)),
            })
        params["layers"].append(lyr)
    return params


def param_specs(cfg: LlamaConfig, tp_axis: Optional[str] = "tp",
                ep_axis: Optional[str] = None,
                tp_size: Optional[int] = None) -> Dict:
    """PartitionSpecs: Megatron column/row sharding over the tp axis
    (tp_axis=None replicates — for meshes without a tp axis); MoE expert
    weights shard over ep_axis (per-expert hidden over tp, see
    moe_ops.param_specs).

    tp_size: pass the mesh's tp extent when it may exceed n_kv_heads —
    wk/wv then REPLICATE over tp and each rank slices its kv group's head
    inside the block (kv-head replication; Llama-3-8B's 8 kv heads cap
    head-sharded tp at 8, this lifts it to tp = any multiple of n_kv that
    divides n_heads)."""
    col, row, rep = P(None, tp_axis), P(tp_axis, None), P()
    kv = col
    if (tp_axis is not None and tp_size is not None
            and cfg.n_kv_heads % tp_size != 0):
        kv = rep    # kv-head replication: sliced per rank in _block
    layer = {"attn_norm": rep, "wq": col, "wk": kv, "wv": kv, "wo": row,
             "mlp_norm": rep}
    if cfg.moe is not None:
        layer["moe"] = moe_ops.param_specs(cfg.moe, ep_axis, tp_axis)
    else:
        layer.update({"w1": col, "w3": col, "w2": row})
    return {"tok_emb": rep, "final_norm": rep, "lm_head": col,
            "layers": [{k: dict(v) if isinstance(v, dict) else v
                        for k, v in layer.items()}
                       for _ in range(cfg.n_layers)]}


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def _rope_freqs(cfg: LlamaConfig, half: int) -> jax.Array:
    """Inverse frequencies, optionally NTK-scaled for context extension
    (Llama-3.1 recipe): wavelengths longer than old_context/low_factor are
    divided by rope_scaling, shorter than old_context/high_factor are
    kept, the band between interpolates linearly in 1/wavelength."""
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if cfg.rope_scaling == 1.0:
        return freqs
    wavelen = 2.0 * jnp.pi / freqs
    low = cfg.rope_old_context / cfg.rope_low_freq_factor    # long cutoff
    high = cfg.rope_old_context / cfg.rope_high_freq_factor  # short cutoff
    if cfg.rope_low_freq_factor == cfg.rope_high_freq_factor:
        smooth = jnp.zeros_like(wavelen)
    else:
        # 0 at wavelen == low cutoff (-> fully scaled), 1 at the high
        # cutoff (-> original) — the Llama-3.1 interpolation
        smooth = jnp.clip(
            (cfg.rope_old_context / wavelen - cfg.rope_low_freq_factor)
            / (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor),
            0.0, 1.0)
    scaled = freqs / cfg.rope_scaling
    mid = (1.0 - smooth) * scaled + smooth * freqs
    return jnp.where(wavelen > low, scaled,
                     jnp.where(wavelen < high, freqs, mid))


def _rope(x: jax.Array, pos: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """x: [B, H, S, dh]; pos: [S] global token positions (rotate-half)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = _rope_freqs(cfg, half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]     # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _psum_if(x: jax.Array, axis: Optional[str]) -> jax.Array:
    return lax.psum(x, axis) if axis is not None else x


def _kv_rep_slice(lyr: Dict, cfg: LlamaConfig, tp_axis: str):
    """kv-head replication (tp > n_kv): wk/wv arrive replicated; this
    rank slices the ONE kv head serving its query group (head
    g = r*n_kv//tp — rank r's n_heads/tp query heads all map to it
    because n_kv | tp).  The slice transpose scatter-adds the cotangent
    back into the replicated weight, and vma-typed autodiff inserts the
    tp-psum that ties the replicas — the same mechanism every
    tp-replicated leaf (norms, embeddings) uses.  Shared by training
    (_block) and decode (llama_decode.forward) so the mapping can never
    diverge between them.  Returns (wk, wv) sliced to ONE head."""
    Hd = cfg.head_dim
    tp = lax.axis_size(tp_axis)
    if lyr["wk"].shape[1] != cfg.n_kv_heads * Hd:
        raise ValueError(
            f"tp={tp} > n_kv_heads={cfg.n_kv_heads} needs wk/wv "
            f"REPLICATED over tp (local width {lyr['wk'].shape[1]}, "
            f"expected {cfg.n_kv_heads * Hd}) — pass tp_size to "
            f"param_specs/stacked_param_specs")
    g = (lax.axis_index(tp_axis) * cfg.n_kv_heads) // tp
    wk = lax.dynamic_slice_in_dim(lyr["wk"], g * Hd, Hd, axis=1)
    wv = lax.dynamic_slice_in_dim(lyr["wv"], g * Hd, Hd, axis=1)
    return wk, wv


def _block(lyr: Dict, x: jax.Array, pos: jax.Array, cfg: LlamaConfig,
           n_heads: int, n_kv: int, tp_axis: Optional[str],
           sp_axis: Optional[str], ep_axis: Optional[str] = None,
           batch_axes=(), sp_attn: str = "ring") -> "tuple[jax.Array, jax.Array]":
    """One decoder layer (pre-norm attention + SwiGLU or MoE FFN) on local
    shards; n_heads/n_kv are the per-tp-shard head counts.  Returns
    (x, aux) — aux is the MoE load-balance loss (0 for dense layers)."""
    B, S = x.shape[:2]
    Hd = cfg.head_dim
    h = _rmsnorm(x, lyr["attn_norm"], cfg.norm_eps)
    if n_kv == 0:
        wk, wv = _kv_rep_slice(lyr, cfg, tp_axis)
        n_kv = 1
    else:
        wk, wv = lyr["wk"], lyr["wv"]
    q = (h @ lyr["wq"]).reshape(B, S, n_heads, Hd).transpose(0, 2, 1, 3)
    k = (h @ wk).reshape(B, S, n_kv, Hd).transpose(0, 2, 1, 3)
    v = (h @ wv).reshape(B, S, n_kv, Hd).transpose(0, 2, 1, 3)
    q = _rope(q, pos, cfg)
    k = _rope(k, pos, cfg)
    if n_kv != n_heads:
        # GQA: the fused Pallas kernels take grouped K/V natively (each
        # KV head read once per group — 1/G the KV traffic/memory and,
        # on the sp ring, 1/G the rotated bytes); the XLA paths' einsum
        # math needs the repeat-expanded copy.  Grouped form is only
        # reachable through branches that can route pallas (sp, or
        # attn_block-flash) — full_attention has no kernel path — and
        # the route decision is the same pallas_route(impl, q_shape) the
        # ops make, so the two can't diverge.
        kernel_branch = sp_axis is not None or cfg.attn_block is not None
        if not (kernel_branch
                and pallas_route(cfg.attn_impl, (B, n_heads, S, Hd))):
            rep = n_heads // n_kv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
    if sp_axis is not None:
        # "gather": KV all-gather variant — the only form sound inside the
        # 1F1B schedulers' stage-divergent conds (ring's ppermute pairs
        # span the whole mesh; see ops.ring_attention.gathered_attention)
        att = (gathered_attention(q, k, v, sp_axis, causal=True,
                                  impl=cfg.attn_impl)
               if sp_attn == "gather"
               else ring_attention(q, k, v, sp_axis, causal=True,
                                   impl=cfg.attn_impl))
    elif cfg.attn_block is not None:
        # memory-bounded single-device attention; the choice of backend
        # (fused Pallas kernels or blocked XLA, one (out, lse) contract)
        # lives in ops.ring_attention.flash_attention_remat
        att = flash_attention_remat(q, k, v, causal=True,
                                    k_block=cfg.attn_block,
                                    impl=cfg.attn_impl)
    else:
        att = full_attention(q, k, v, causal=True)
    att = att.transpose(0, 2, 1, 3).reshape(B, S, n_heads * Hd)
    x = x + _psum_if(att @ lyr["wo"], tp_axis)

    h = _rmsnorm(x, lyr["mlp_norm"], cfg.norm_eps)
    if "moe" in lyr:
        ff, aux = moe_ops.moe_ffn(lyr["moe"], h, cfg.moe, ep_axis=ep_axis,
                                  batch_axes=batch_axes)
    else:
        gate = jax.nn.silu((h @ lyr["w1"]).astype(jnp.float32)).astype(x.dtype)
        ff = (gate * (h @ lyr["w3"])) @ lyr["w2"]
        aux = jnp.float32(0.0)
    return x + _psum_if(ff, tp_axis), aux


def _shard_counts(cfg: LlamaConfig, tp_axis: Optional[str]):
    """Per-rank (n_heads, n_kv) head counts; n_kv == 0 flags kv-head
    replication (tp > n_kv: wk/wv replicate and each rank slices ONE kv
    head — its query group's — inside _block)."""
    n_heads, n_kv = cfg.n_heads, cfg.n_kv_heads
    if tp_axis is not None:
        tp = lax.axis_size(tp_axis)
        if n_heads % tp:
            raise ValueError(f"tp={tp} must divide n_heads={n_heads}")
        n_heads //= tp
        if n_kv % tp == 0:
            n_kv //= tp
        elif tp % n_kv == 0:
            n_kv = 0        # replicated-kv mode: 1 sliced head per rank
        else:
            raise ValueError(
                f"tp={tp} must divide n_kv_heads={cfg.n_kv_heads}, or be a "
                f"multiple of it (kv-head replication)")
    return n_heads, n_kv


def _positions(S: int, sp_axis: Optional[str]) -> jax.Array:
    sp_off = (lax.axis_index(sp_axis) * S) if sp_axis is not None else 0
    return sp_off + lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]


def apply(params: Dict, tokens: jax.Array, cfg: LlamaConfig, *,
          tp_axis: Optional[str] = None,
          sp_axis: Optional[str] = None,
          ep_axis: Optional[str] = None,
          batch_axes=(),
          gather_logits: bool = True,
          with_aux: bool = False,
          remat: bool = False) -> jax.Array:
    """tokens [B, S_local] -> logits [B, S_local, vocab] (vocab/tp when
    gather_logits=False under tp); (logits, moe_aux) when with_aux.

    Call inside shard_map with params pre-sharded per ``param_specs`` when
    tp_axis is set; sequence shards must be contiguous when sp_axis is set;
    batch_axes lists every token-sharding axis for MoE aux statistics.
    remat rematerializes each decoder block in backward (activation memory
    O(1 block) instead of O(n_layers) at ~1/3 extra FLOPs — the standard
    long-context/deep-model trade; the pipelined path has the same knob).
    """
    B, S = tokens.shape
    n_heads, n_kv = _shard_counts(cfg, tp_axis)
    pos = _positions(S, sp_axis)

    def block(lyr, x):
        return _block(lyr, x, pos, cfg, n_heads, n_kv, tp_axis, sp_axis,
                      ep_axis, batch_axes)

    if remat:
        block = jax.checkpoint(block)

    x = params["tok_emb"][tokens]                       # [B, S, D]
    aux = jnp.float32(0.0)
    for lyr in params["layers"]:
        x, a = block(lyr, x)
        aux = aux + a

    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]                      # [B, S, V/tp]
    if tp_axis is not None and gather_logits:
        logits = lax.all_gather(logits, tp_axis, axis=2, tiled=True)
    return (logits, aux) if with_aux else logits


def _vocab_parallel_nll(logits: jax.Array, labels: jax.Array,
                        tp_axis: str) -> jax.Array:
    """Per-token NLL from vocab-sharded logits [B, S, V/tp] without
    gathering — Megatron-style distributed softmax cross-entropy.

    Every reduction over the vocab runs through psum/pmax, so the result is
    tp-invariant: each rank holds ONE copy of the loss and vma-typed
    autodiff counts each rank's logit shard exactly once.  (Computing the
    loss redundantly from all-gathered logits double-counts every gradient
    by a factor of tp — the all_gather transpose sums the identical
    per-rank cotangents.)
    """
    lf = logits.astype(jnp.float32)
    Vl = lf.shape[-1]
    off = lax.axis_index(tp_axis) * Vl
    # stability shift only — it cancels in the softmax gradient, and pmax
    # has no differentiation rule anyway
    m = lax.pmax(lax.stop_gradient(jnp.max(lf, axis=-1)), tp_axis)  # [B, S]
    z = lax.psum(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1), tp_axis)
    local = labels - off
    in_range = (local >= 0) & (local < Vl)
    safe = jnp.clip(local, 0, Vl - 1)
    tgt = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
    tgt = lax.psum(jnp.where(in_range, tgt, 0.0), tp_axis)      # [B, S]
    return jnp.log(z) + m - tgt


def _token_nll(logits: jax.Array, safe_labels: jax.Array,
               tp_axis: Optional[str]) -> jax.Array:
    """Per-token NLL [B, S]; logits vocab-sharded when tp_axis is set."""
    if tp_axis is not None:
        return _vocab_parallel_nll(logits, safe_labels, tp_axis)
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logz, safe_labels[..., None], axis=-1)[..., 0]


def _grad_scale(x: jax.Array, n: int) -> jax.Array:
    """Value-preserving gradient scale by n (cancels a trainer's uniform
    /n_dp gradient average)."""
    return lax.stop_gradient(x) + n * (x - lax.stop_gradient(x))


def _weighted_loss(local_sum: jax.Array, count: jax.Array,
                   batch_axes: Tuple[Optional[str], ...],
                   dp_axis: Optional[str]) -> jax.Array:
    """Token-weighted global mean over the token-sharding axes (sp/dp/ep).
    With dp_axis, the gradient carries an n_dp factor that cancels the
    trainer's uniform /n_dp average so the effective update is the true
    global-mean gradient (see loss_fn docstring).

    The loss VALUE is the psum'd global mean, but the gradient path rides
    the LOCAL sum only: per-replica gradient = scale * d(local_sum)/denom
    with no collective on the gradient path, so the result is invariant to
    the jaxlib's psum-transpose convention (the n_dp-scaled-gradient class
    of docs/KNOWN_FAILURES.md #1-4, frozen as graftlint rule J7)."""
    axes = tuple(a for a in batch_axes if a is not None)
    if not axes:
        return local_sum / jnp.maximum(count, 1)
    total = lax.psum(local_sum, axes)
    denom = lax.stop_gradient(
        jnp.maximum(lax.psum(count, axes), 1).astype(jnp.float32))
    loss = lax.stop_gradient(total / denom)
    scale = lax.axis_size(dp_axis) if dp_axis is not None else 1
    return loss + scale * (local_sum
                           - lax.stop_gradient(local_sum)) / denom


def loss_fn(params: Dict, batch, cfg: LlamaConfig, *,
            tp_axis: Optional[str] = None,
            sp_axis: Optional[str] = None,
            dp_axis: Optional[str] = None,
            ep_axis: Optional[str] = None,
            remat: bool = False) -> jax.Array:
    """Next-token cross-entropy.  batch = (tokens, labels), both [B, S_local]
    — labels are the globally-shifted targets (shift crosses sequence-shard
    boundaries, so the data pipeline provides them; -100 entries are
    ignored).

    Pass dp_axis when training under a dp-sharded trainer with masked
    labels: the trainers average gradients uniformly over dp
    (reduce_scatter/n), which mis-weights tokens when shards hold unequal
    valid-token counts.  With dp_axis set, the loss *value* is the exact
    global token-weighted mean and the *gradient* carries an n_dp factor
    that cancels the trainer's /n_dp — so the effective update is the true
    global-mean gradient.  (With uniformly valid labels the two coincide
    and dp_axis may be omitted.)
    """
    tokens, labels = batch
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    batch_axes = (sp_axis, dp_axis, ep_axis)
    logits, aux = apply(params, tokens, cfg, tp_axis=tp_axis,
                        sp_axis=sp_axis, ep_axis=ep_axis,
                        batch_axes=tuple(a for a in batch_axes
                                         if a is not None),
                        gather_logits=False, with_aux=True, remat=remat)
    nll = jnp.where(valid, _token_nll(logits, safe, tp_axis), 0.0)
    loss = _weighted_loss(jnp.sum(nll), jnp.sum(valid), batch_axes, dp_axis)
    if dp_axis is not None:     # same /n_dp cancellation as the ce term
        aux = _grad_scale(aux, lax.axis_size(dp_axis))
    return loss + aux


# -- pipeline-parallel path ---------------------------------------------------


def stack_params(params: Dict) -> Dict:
    """List-of-layers pytree -> stacked [n_layers, ...] leaves, shardable
    over a pp mesh axis (parallel.pipeline layout contract)."""
    from ..parallel import pipeline as pl
    out = dict(params)
    out["layers"] = pl.stack_layers(params["layers"])
    return out


def stacked_param_specs(cfg: LlamaConfig, pp_axis: str = "pp",
                        tp_axis: Optional[str] = "tp",
                        ep_axis: Optional[str] = None,
                        tp_size: Optional[int] = None) -> Dict:
    """PartitionSpecs for stack_params output: the layer stack's leading axis
    shards over pp; within a layer, Megatron col/row over tp (MoE experts
    over ep, hidden over tp); embedding and head replicated over pp (they
    run on every stage, only stage 0 / the last stage contribute
    gradients)."""
    base = param_specs(cfg, tp_axis, ep_axis, tp_size)
    layers = jax.tree_util.tree_map(lambda spec: P(pp_axis, *spec),
                                    base["layers"][0],
                                    is_leaf=lambda x: isinstance(x, P))
    return {"tok_emb": base["tok_emb"], "final_norm": base["final_norm"],
            "lm_head": base["lm_head"], "layers": layers}


def apply_pp(params: Dict, tokens: jax.Array, cfg: LlamaConfig, *,
             pp_axis: str, num_microbatches: int,
             tp_axis: Optional[str] = None,
             sp_axis: Optional[str] = None,
             ep_axis: Optional[str] = None,
             batch_axes=(),
             with_aux: bool = False,
             sp_attn: str = "ring",
             remat: bool = False) -> jax.Array:
    """Pipelined forward; call inside shard_map with stack_params params
    sharded per ``stacked_param_specs``.  Returns logits valid on the LAST
    pp stage only (loss_fn handles the mask; see parallel.pipeline);
    (logits, moe_aux) when with_aux — aux rides the microbatch scan with
    garbage ticks masked (parallel.pipeline.pipeline_apply_aux)."""
    from ..parallel import pipeline as pl

    S = tokens.shape[1]
    n_heads, n_kv = _shard_counts(cfg, tp_axis)
    pos = _positions(S, sp_axis)

    def block(lyr, x):
        return _block(lyr, x, pos, cfg, n_heads, n_kv, tp_axis, sp_axis,
                      ep_axis, batch_axes, sp_attn=sp_attn)

    def stage_fn(stacked, x):
        return pl.scan_layers_aux(block, stacked, x, remat=remat)

    x = params["tok_emb"][tokens]                       # [B, S, D]
    x, aux = pl.pipeline_apply_aux(stage_fn, params["layers"], x,
                                   num_microbatches, pp_axis)
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]                      # [B, S, V/tp]
    return (logits, aux) if with_aux else logits


def loss_fn_pp(params: Dict, batch, cfg: LlamaConfig, *,
               pp_axis: str, num_microbatches: int,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None,
               dp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None,
               sp_attn: str = "ring",
               remat: bool = False) -> jax.Array:
    """Next-token cross-entropy through the pipeline.  Every pp stage
    computes the head on its own (mostly garbage) activations — unavoidable
    under SPMD — so the token NLL sum is psum-masked from the last stage
    before the global token-weighted reduction; gradients flow only through
    real activations.  dp_axis as in loss_fn (masked-label weighting);
    the MoE aux loss rides the microbatch scan (apply_pp with_aux)."""
    from ..parallel import pipeline as pl

    tokens, labels = batch
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    batch_axes = tuple(a for a in (sp_axis, dp_axis, ep_axis)
                       if a is not None)
    logits, aux = apply_pp(params, tokens, cfg, pp_axis=pp_axis,
                           num_microbatches=num_microbatches, tp_axis=tp_axis,
                           sp_axis=sp_axis, ep_axis=ep_axis,
                           batch_axes=batch_axes, with_aux=True,
                           sp_attn=sp_attn, remat=remat)
    if batch_axes:
        # Value-preserving: the per-rank aux copies are identical over the
        # batch axes (moe_ffn psums its statistics over them), but the
        # pipeline scan carry leaves aux TYPED varying.  Without this
        # pmean, adding a varying-typed scalar to the invariant ce loss
        # makes the loss varying, and vma autodiff then seeds one cotangent
        # per rank whose pvary-transpose psum silently multiplies every ce
        # gradient by the axis size.
        aux = lax.pmean(aux, batch_axes)
    nll = jnp.where(valid, _token_nll(logits, safe, tp_axis), 0.0)
    # local-grad variant: this loss is differentiated INSIDE shard_map, so
    # the last-stage mask must not put a psum on the gradient path (J7)
    local_sum = pl.from_last_stage_local_grad(jnp.sum(nll), pp_axis)
    # ep shards the batch alongside dp (ShardedTrainer._bspec), so the
    # token-weighted reduction must span it too — matching loss_fn
    loss = _weighted_loss(local_sum, jnp.sum(valid),
                          (sp_axis, dp_axis, ep_axis), dp_axis)
    if dp_axis is not None:     # same /n_dp cancellation as the ce term
        aux = _grad_scale(aux, lax.axis_size(dp_axis))
    return loss + aux


def loss_and_grads_pp_1f1b(params: Dict, batch, cfg: LlamaConfig, *,
                           pp_axis: str, num_microbatches: int,
                           tp_axis: Optional[str] = None,
                           sp_axis: Optional[str] = None,
                           dp_axis: Optional[str] = None,
                           ep_axis: Optional[str] = None,
                           virtual_stages: int = 1,
                           remat: bool = False):
    """`loss_fn_pp`'s loss AND gradients under the 1F1B schedule
    (parallel.pipeline.pipeline_train_1f1b): O(pp) live activations per
    stage instead of GPipe's O(num_microbatches), gradients produced by
    the explicit fwd/bwd ring — no outer jax.grad.

    Exact-parity construction: the head computes the per-microbatch token
    NLL SUM; the scheduler returns the microbatch MEAN, so M * mean is
    loss_fn_pp's local_sum, fed through the same `_weighted_loss` (and
    its dp gradient-scale contract).  The scheduler seeds d(mean)=1, so
    every gradient is rescaled by d loss/d mean = M * w, where w is
    _weighted_loss's (token-count) linear coefficient.  The embedding is
    differentiated OUTSIDE the schedule via the returned d_x.

    tp composes: _block's tp psums sit inside stage-divergent schedule
    conds, but every participant of a tp group shares one pp stage (and
    therefore one branch), so the rendezvous is uniform — only pp-axis
    collectives are forbidden inside stages.  MoE composes the same way
    (dp/sp routing-stat psums are uniform per stage): each stage's aux
    differentiates through its own seeded loss channel with the
    gradient-scale folded in (aux coefficient 1/(M*w*n_rep), uniform
    post-scale M*w — reproducing loss_fn_pp's ce and _grad_scale(aux)
    gradients exactly; n_rep is the replication of the aux value over
    the non-dp batch axes, whose pmean seed GPipe's autodiff applies),
    while the scheduler's non-differentiated report channel carries the
    RAW nll and aux sums so the displayed loss is reconstructed
    unscaled.  ep composes like tp: the all_to_all expert exchange and
    routing-stat psums sit inside stage-divergent schedule conds, but
    every ep-group member shares one pp stage and therefore one branch;
    expert leaves enter ep-varying (sharded) and keep per-shard
    cotangents, ep-replicated leaves are widened on entry and psum'd
    over ep on exit.

    virtual_stages > 1 selects the INTERLEAVED schedule
    (pipeline.pipeline_train_1f1b_interleaved): each device runs v
    non-adjacent layer chunks, cutting the bubble to 1/v of a full
    stage per warm-up tick.  The stacked layer tree must then be in the
    interleaved (device-major) order — permute it with
    pipeline.interleave_layers before sharding, and map gradients back
    with pipeline.deinterleave_layers; num_microbatches must be a
    multiple of pp.  Returns (loss, grads) with grads matching the
    stack_params pytree; tp/pp-replicated leaves arrive correctly
    psum'd (the scheduler transposes its own entry widening), dp-varying
    leaves stay per-shard for the trainer's manual dp reduction.
    """
    from ..parallel import pipeline as pl

    tokens, labels = batch
    S = tokens.shape[1]
    n_heads, n_kv = _shard_counts(cfg, tp_axis)
    pos = _positions(S, sp_axis)
    M = num_microbatches
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)

    moe = cfg.moe is not None
    batch_axes = tuple(a for a in (sp_axis, dp_axis, ep_axis)
                       if a is not None)

    # the explicit schedulers run stages inside stage-divergent lax.conds,
    # where ring attention's sp ppermutes are unsound (whole-mesh
    # collective-permute pairs); the KV-all-gather variant is the
    # replica-grouped, cond-safe form
    sp_attn = ("gather" if sp_axis is not None
               and lax.axis_size(sp_axis) > 1 else "ring")

    def block(lyr, x):
        return _block(lyr, x, pos, cfg, n_heads, n_kv, tp_axis, sp_axis,
                      ep_axis, batch_axes if moe else (), sp_attn=sp_attn)

    # d loss / d (scheduler mean): _weighted_loss is linear in local_sum
    # with coefficient 1/denom (times the n_dp gradient-scale when dp is
    # on); computed BEFORE the schedule so per-term gradient scales can
    # fold into the differentiated loss channel
    count = jnp.sum(valid)
    axes = batch_axes
    if axes:
        denom = jnp.maximum(lax.psum(count, axes), 1).astype(jnp.float32)
        w = (lax.axis_size(dp_axis) if dp_axis is not None else 1.0) / denom
    else:
        w = 1.0 / jnp.maximum(count, 1).astype(jnp.float32)
    scale = M * w
    # aux's gradient contract: GPipe's aux path is
    # _grad_scale(pmean_batch(psum_pp(sum_m aux)/M), n_dp) — the pmean
    # seeds each shard with 1/(n_dp * n_rep) where n_rep is the product
    # of the NON-dp batch-axis sizes (sp, ep); the grad-scale's n_dp
    # cancels the dp factor, leaving d total/d aux_sm = 1/(M * n_rep)
    # per shard.  The uniform post-scale M*w then requires the fold
    # c = 1/(M * w * n_rep).  (The exit psums over sp/ep for replicated
    # router leaves are identical in both paths, so the SEEDS must
    # match shard-for-shard.)
    n_rep = 1
    for a in batch_axes:
        if a != dp_axis:
            n_rep *= lax.axis_size(a)
    c_aux = 1.0 / jnp.maximum(scale * n_rep, 1e-30)

    def stage_fn(sp, hp, x_in, c_in):
        def blk(lyr, h):
            return block(lyr, h)
        h, aux = pl.scan_layers_aux(blk, sp, x_in, remat=remat)
        if moe:
            return (h, c_aux * aux.astype(jnp.float32),
                    jnp.stack([jnp.sum(h).astype(jnp.float32) * 0.0,
                               aux.astype(jnp.float32)]))
        return h, jnp.sum(h).astype(jnp.float32) * 0.0

    def loss_head_fn(hp, h, c_in):
        safe_mb, valid_mb = c_in
        h = _rmsnorm(h, hp["final_norm"], cfg.norm_eps)
        logits = h @ hp["lm_head"]
        nll = jnp.where(valid_mb, _token_nll(logits, safe_mb, tp_axis), 0.0)
        nll_sum = jnp.sum(nll)              # SUM — weighting applied below
        if moe:
            return nll_sum, jnp.stack([nll_sum,
                                       nll_sum.astype(jnp.float32) * 0.0])
        return nll_sum

    x, emb_vjp = jax.vjp(lambda e: e[tokens], params["tok_emb"])
    head_params = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}
    v = virtual_stages
    if v > 1:
        # interleaved layout: the local [L/pp] shard splits into v chunks,
        # chunk c being global virtual stage c*pp + s — the GLOBAL stack
        # must be permuted with pipeline.interleave_layers OUTSIDE the
        # shard_map (gradients return in the same interleaved order)
        layer_chunks = jax.tree_util.tree_map(
            lambda a: a.reshape((v, a.shape[0] // v) + a.shape[1:]),
            params["layers"])

        def run_sched(*a, **kw2):
            return pl.pipeline_train_1f1b_interleaved(
                *a, virtual_stages=v, **kw2)
    else:
        layer_chunks = params["layers"]
        run_sched = pl.pipeline_train_1f1b
    if moe:
        obj_mean, d_layers, d_hp, d_x, report = run_sched(
            stage_fn, loss_head_fn, layer_chunks, head_params,
            x, (safe, valid), M, pp_axis, report_len=2)
        # display from the RAW report: weighted ce + aux_total (value
        # identity of _grad_scale; gradient already folded into obj)
        loss = (_weighted_loss(report[0], count, batch_axes, dp_axis)
                + report[1] / M)
    else:
        mean_nll_sum, d_layers, d_hp, d_x = run_sched(
            stage_fn, loss_head_fn, layer_chunks, head_params,
            x, (safe, valid), M, pp_axis)
        local_sum = M * mean_nll_sum
        loss = _weighted_loss(local_sum, count, batch_axes, dp_axis)
    if v > 1:
        d_layers = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), d_layers)
    d_emb, = emb_vjp(d_x.astype(x.dtype))
    # tok_emb is replicated over axes its cotangent may still vary over
    # (sp-sharded tokens feed a replicated table; GPipe's vma autodiff
    # inserts this psum automatically, the explicit path does it here)
    extra = tuple(sorted(set(jax.typeof(d_emb).vma)
                         - set(jax.typeof(params["tok_emb"]).vma)))
    if extra:
        d_emb = lax.psum(d_emb, extra)
    grads = {"tok_emb": d_emb, "final_norm": d_hp["final_norm"],
             "lm_head": d_hp["lm_head"], "layers": d_layers}
    grads = jax.tree_util.tree_map(
        lambda g2: g2.astype(jnp.float32) * scale, grads)
    return loss, grads


def num_params(cfg: LlamaConfig) -> int:
    D, Hd = cfg.dim, cfg.head_dim
    if cfg.moe is not None:
        ffn = D * cfg.moe_experts + 3 * cfg.moe_experts * D * cfg.ffn_dim
    else:
        ffn = 3 * D * cfg.ffn_dim
    per_layer = (2 * D + D * cfg.n_heads * Hd + 2 * D * cfg.n_kv_heads * Hd
                 + cfg.n_heads * Hd * D + ffn)
    return cfg.vocab * D * 2 + D + cfg.n_layers * per_layer


def active_params(cfg: LlamaConfig) -> int:
    """Parameters a TOKEN's matmuls actually touch: for MoE, only the
    top_k routed experts' FFN weights count (plus the router), so the
    6*P*tokens/s FLOP model stays honest — num_params would overstate
    MoE FLOPs by num_experts/top_k on the FFN term.  Equal to num_params
    for dense configs."""
    if cfg.moe is None:
        return num_params(cfg)
    D = cfg.dim
    all_ffn = 3 * cfg.moe_experts * D * cfg.ffn_dim
    active_ffn = 3 * cfg.moe_top_k * D * cfg.ffn_dim
    return num_params(cfg) - cfg.n_layers * (all_ffn - active_ffn)
