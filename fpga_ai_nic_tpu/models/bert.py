"""BERT-family bidirectional encoder with a masked-LM head.

BASELINE.json config 4 ("BERT-base DP bucketed ring all-reduce") is the
target: the model itself is plain data-parallel (no internal collectives);
its role is to exercise the bucketed gradient all-reduce path
(`ops.bucketed` + `parallel.ddp.DDPTrainer`) on a transformer whose layer
structure produces the many medium-sized gradient tensors that bucketing
exists for — the reference's per-layer all-reduce issue
(sw/mlp_mpi_example_f32.cpp:753-756) at transformer scale.

Architecture: post-LN encoder, learned positions, GELU FFN, tied MLM
decoder (logits through tok_emb^T), padding masked via ``pad_id``.
Functional pytree params like models.mlp / models.llama.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.ring_attention import flash_attention_remat

_NEG = jnp.float32(-1e30)


@dataclass(frozen=True)
class BertConfig:
    vocab: int = 30522
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_dim: int = 3072
    max_pos: int = 512
    pad_id: int = 0
    norm_eps: float = 1e-12
    dtype: str = "bfloat16"
    # attention backend, ops.ring_attention.flash_attention_remat's `impl`
    # as in every model: "xla" = the blocked route (a score block at a time,
    # hand-written backward), "pallas" = the fused flash kernels, "auto" =
    # those on TPU when shapes tile; the padding mask is `key_bias` in both
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def bert_base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny(vocab: int = 256, dim: int = 64, n_layers: int = 2,
             n_heads: int = 4, ffn_dim: int = 128, max_pos: int = 64,
             dtype: str = "float32") -> "BertConfig":
        return BertConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                          n_heads=n_heads, ffn_dim=ffn_dim, max_pos=max_pos,
                          dtype=dtype)


def init(key: jax.Array, cfg: BertConfig) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    D = cfg.dim

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(1.0 / fan_in)).astype(dt)

    def ln():
        return {"g": jnp.ones((D,), dt), "b": jnp.zeros((D,), dt)}

    keys = iter(jax.random.split(key, 4 + cfg.n_layers * 6))
    params = {
        "tok_emb": dense(next(keys), D, (cfg.vocab, D)),
        "pos_emb": dense(next(keys), D, (cfg.max_pos, D)),
        "emb_norm": ln(),
        "layers": [],
        "mlm_dense": dense(next(keys), D, (D, D)),
        "mlm_norm": ln(),
        "mlm_bias": jnp.zeros((cfg.vocab,), dt),
    }
    for _ in range(cfg.n_layers):
        params["layers"].append({
            "wq": dense(next(keys), D, (D, D)),
            "wk": dense(next(keys), D, (D, D)),
            "wv": dense(next(keys), D, (D, D)),
            "wo": dense(next(keys), D, (D, D)),
            "attn_norm": ln(),
            "w1": dense(next(keys), D, (D, cfg.ffn_dim)),
            "w2": dense(next(keys), cfg.ffn_dim, (cfg.ffn_dim, D)),
            "ffn_norm": ln(),
        })
    return params


def _layernorm(x: jax.Array, p: Dict, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * p["g"] + p["b"]


def encode(params: Dict, tokens: jax.Array, cfg: BertConfig,
           attention_mask: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] -> hidden states [B, S, D]: everything below the MLM
    head.

    attention_mask: [B, S] bool/int, 1 = attend; derived from
    ``tokens != pad_id`` when omitted.
    """
    B, S = tokens.shape
    if S > cfg.max_pos:
        # JAX's clamping gather would silently repeat pos_emb[max_pos-1]
        raise ValueError(f"sequence length {S} exceeds max_pos={cfg.max_pos}")
    H, Hd = cfg.n_heads, cfg.head_dim
    if attention_mask is None:
        attention_mask = tokens != cfg.pad_id
    key_bias = jnp.where(attention_mask.astype(bool), jnp.float32(0), _NEG)

    pos = lax.broadcasted_iota(jnp.int32, (S, 1), 0)[:, 0]
    x = params["tok_emb"][tokens] + params["pos_emb"][pos]
    x = _layernorm(x, params["emb_norm"], cfg.norm_eps)

    scale = Hd ** -0.5
    for lyr in params["layers"]:
        q = (x @ lyr["wq"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        k = (x @ lyr["wk"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        v = (x @ lyr["wv"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        # the padding mask rides the route's bias over the keys [B, S]
        att = flash_attention_remat(q, k, v, causal=False, sm_scale=scale,
                                    impl=cfg.attn_impl, key_bias=key_bias)
        att = att.transpose(0, 2, 1, 3).reshape(B, S, -1)
        x = _layernorm(x + att @ lyr["wo"], lyr["attn_norm"], cfg.norm_eps)

        h = jax.nn.gelu((x @ lyr["w1"]).astype(jnp.float32)).astype(x.dtype)
        x = _layernorm(x + h @ lyr["w2"], lyr["ffn_norm"], cfg.norm_eps)
    return x


def mlm_head(params: Dict, hidden: jax.Array, cfg: BertConfig) -> jax.Array:
    """hidden [..., D] -> MLM logits [..., vocab]: dense, GELU, LayerNorm,
    tied decoder (logits through tok_emb^T), bias."""
    h = jax.nn.gelu((hidden @ params["mlm_dense"]).astype(jnp.float32)
                    ).astype(hidden.dtype)
    h = _layernorm(h, params["mlm_norm"], cfg.norm_eps)
    return h @ params["tok_emb"].T + params["mlm_bias"]   # tied decoder


def apply(params: Dict, tokens: jax.Array, cfg: BertConfig,
          attention_mask: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] -> MLM logits [B, S, vocab], on every position (the
    training loss computes the head on the masked positions only)."""
    return mlm_head(params, encode(params, tokens, cfg, attention_mask), cfg)


# Rows of logits alive at one time in the loss.  The head runs block by
# block over the masked positions only, so a step holds [MLM_BLOCK, vocab]
# logits and never [tokens, vocab].  Chosen on the chip (PERF.md, PR 26):
# half a block of rows is computed for nothing on average, and every block
# adds its weight gradients to [vocab, dim] float32 sums, which is why
# smaller is not better (256 measured slower than 512 or 1,024).
MLM_BLOCK = 1024


def _mlm_blocks(n_positions: int, n_masked) -> Tuple[int, int, jax.Array]:
    """(rows a block, blocks that cover every position, blocks that hold a
    masked one).  The last is computed from the labels at run time."""
    block = min(MLM_BLOCK, n_positions)
    return block, -(-n_positions // block), -(-n_masked // block)


def mlm_head_rows(labels) -> Tuple[int, int]:
    """(rows `loss_fn` computes the MLM head on, positions): the masked
    count rounded up to whole blocks — the head's cost follows the labels."""
    labels = jnp.asarray(labels)
    block, _, live = _mlm_blocks(labels.size, jnp.sum(labels >= 0))
    return int(live) * block, labels.size


def _varying_like(x: jax.Array, *like) -> jax.Array:
    """x, varying over the manual mesh axes any leaf of `like` varies over:
    under shard_map a loop's carry must enter with the type it leaves with
    (as ops/ring.py::_varying)."""
    axes = frozenset().union(*(jax.typeof(leaf).vma for leaf in
                               jax.tree_util.tree_leaves(like)))
    axes -= jax.typeof(x).vma
    return lax.pcast(x, tuple(axes), to="varying") if axes else x


def _gather_rows(hidden: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows `idx` (each at most once) of hidden [T, D]; T itself, one past
    the end, reads a row of zeros."""
    return hidden.at[idx].get(mode="fill", fill_value=0, unique_indices=True)


def _rows_nll(head: Dict, hidden: jax.Array, labels: jax.Array,
              cfg: BertConfig) -> jax.Array:
    """Summed cross-entropy of the rows with ``labels >= 0``: hidden [R, D],
    labels [R] -> scalar.  Logits [R, vocab] in float32."""
    valid = labels >= 0
    logz = jax.nn.log_softmax(
        mlm_head(head, hidden, cfg).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logz, jnp.where(valid, labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, nll, 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _live_nll(cfg: BertConfig, head: Dict, hidden: jax.Array,
              order: jax.Array, labels: jax.Array,
              n_live: jax.Array) -> jax.Array:
    """`_rows_nll` summed over the first `n_live` blocks of rows: hidden
    [T, D]; order (row indices for `_gather_rows`) and labels, both
    [blocks, block].  The trip count is a value of the run, so a block past
    it costs nothing; reverse mode cannot differentiate such a loop itself,
    hence the backward loop below, which recomputes each live block's
    logits instead of keeping them."""
    def body(i, total):
        return total + _rows_nll(head, _gather_rows(hidden, order[i]),
                                 labels[i], cfg)
    zero = _varying_like(jnp.float32(0), head, hidden)
    return lax.fori_loop(0, n_live, body, zero)


def _live_nll_fwd(cfg, head, hidden, order, labels, n_live):
    return (_live_nll(cfg, head, hidden, order, labels, n_live),
            (head, hidden, order, labels, n_live))


def _live_nll_bwd(cfg, res, g):
    head, hidden, order, labels, n_live = res

    def zeros(x, dtype):
        return _varying_like(jnp.zeros(x.shape, dtype), head, hidden)

    def body(i, carry):
        g_head, g_hidden = carry
        _, vjp = jax.vjp(lambda p, h: _rows_nll(p, h, labels[i], cfg),
                         head, _gather_rows(hidden, order[i]))
        d_head, d_rows = vjp(g)
        g_head = jax.tree_util.tree_map(
            lambda acc, d: acc + d.astype(acc.dtype), g_head, d_head)
        return g_head, g_hidden.at[order[i]].set(d_rows, mode="drop",
                                                 unique_indices=True)

    # the weights' gradients are summed over the blocks in float32
    g_head, g_hidden = lax.fori_loop(0, n_live, body, (
        jax.tree_util.tree_map(lambda x: zeros(x, jnp.float32), head),
        zeros(hidden, hidden.dtype)))
    g_head = jax.tree_util.tree_map(lambda acc, x: acc.astype(x.dtype),
                                    g_head, head)
    return g_head, g_hidden, None, None, None


_live_nll.defvjp(_live_nll_fwd, _live_nll_bwd)


def _masked_nll(params: Dict, hidden: jax.Array, labels: jax.Array,
                cfg: BertConfig) -> jax.Array:
    """Summed cross-entropy of the masked positions: hidden [T, D], labels
    [T] -> scalar, exact for every share of masked positions.

    The positions with ``labels >= 0`` are listed first (stable) and the
    list padded to whole blocks of MLM_BLOCK with unmasked rows; the head
    and the cross-entropy run on the blocks that hold a masked position,
    one at a time (`_live_nll`), and no other row is gathered."""
    T = labels.shape[0]
    valid = labels >= 0
    block, n_blocks, n_live = _mlm_blocks(T, jnp.sum(valid))
    order = jnp.concatenate([
        jnp.argsort(jnp.logical_not(valid), stable=True),
        jnp.arange(T, n_blocks * block)])
    labels = labels.at[order].get(mode="fill", fill_value=-100)
    head = jax.tree_util.tree_map(
        lambda x: _varying_like(x, hidden),
        {k: params[k] for k in
         ("mlm_dense", "mlm_norm", "tok_emb", "mlm_bias")})
    return _live_nll(cfg, head, hidden, order.reshape(n_blocks, block),
                     labels.reshape(n_blocks, block), n_live)


def loss_fn(params: Dict, batch, cfg: BertConfig, *,
            dp_axis: Optional[str] = None) -> jax.Array:
    """Masked-LM cross-entropy.  batch = (tokens, labels), labels [B, S]
    with -100 on unmasked positions (standard MLM convention).  The head is
    computed on the masked positions only (`_masked_nll`).

    dp_axis: as in models.llama.loss_fn — under a dp trainer that averages
    gradients uniformly (mean over dp), masked-token counts differ per
    shard; with dp_axis set the loss value is the exact global
    token-weighted mean and the gradient carries the n_dp factor that
    cancels the trainer's /n_dp.
    """
    tokens, labels = batch
    valid = labels >= 0
    hidden = encode(params, tokens, cfg)
    nll = _masked_nll(params, hidden.reshape(-1, hidden.shape[-1]),
                      labels.reshape(-1), cfg)
    local_sum = jnp.sum(nll)
    count = jnp.sum(valid)
    if dp_axis is None:
        return local_sum / jnp.maximum(count, 1)
    total = lax.psum(local_sum, dp_axis)
    denom = lax.stop_gradient(
        jnp.maximum(lax.psum(count, dp_axis), 1).astype(jnp.float32))
    loss = total / denom
    n_dp = lax.axis_size(dp_axis)
    # Gradient path rides the LOCAL sum only: the per-replica gradient is
    # n_dp * d(local_sum)/denom by construction, so a trainer's uniform
    # sum/n_dp recovers the exact global token-weighted gradient — and no
    # collective sits on the gradient path, so the result cannot depend on
    # which psum-transpose convention (identity vs psum) the jaxlib uses.
    # The previous formulation differentiated through psum(local_sum) and
    # inherited exactly that convention: on jaxlibs whose transpose is a
    # psum, every replica's gradient came out n_dp x the reference (the
    # 8x-learning-rate bug of docs/KNOWN_FAILURES.md #1-2), frozen as
    # graftlint rule J7.
    return lax.stop_gradient(loss) + (
        n_dp * (local_sum - lax.stop_gradient(local_sum)) / denom)


def num_params(cfg: BertConfig) -> int:
    D = cfg.dim
    per_layer = 4 * D * D + 2 * D * cfg.ffn_dim + 4 * D
    head = D * D + 2 * D + cfg.vocab
    return (cfg.vocab * D + cfg.max_pos * D + 2 * D
            + cfg.n_layers * per_layer + head)
