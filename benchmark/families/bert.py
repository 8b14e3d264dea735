"""Family `bert`: post-LN bidirectional encoder with learned positions, a
tanh-GELU feed-forward and a masked-LM head whose decoder is the token
embedding, transposed (Devlin et al., arXiv:1810.04805).  Configuration
keys are those of the published config.json; `attn_impl` pins the
program's attention route.

Departures of the program from the published model, which the reference
below follows so that the two can be compared: no token-type embedding, no
biases on the dense layers, no next-sentence head (listed under `assumed` in
the configuration's file).

`program` is the only place that touches the system under test.
"""

import jax
import jax.numpy as jnp

ITEM = "tokens"
THROUGHPUT = "tokens_per_s_per_chip"
MASK_ID, FIRST_WORD_ID, MASK_SHARE = 3, 4, 0.15

# First step against the float32 reference below.
# Loss: a masked-LM loss of about 10.4 from bf16 logits over 30,522 classes.
LOSS_RTOL = 1e-2
# Gradient, relative L2 over the flat vector: twelve layers of bf16
# matmuls, bf16 residual stream and bf16 LayerNorm outputs.  Measured
# 6.6e-3 to 9.3e-3 on the chip at both sequence lengths over twenty seeds
# (PR 23); 2.7 times the worst is allowed, and an 8-bit float format would
# miss it by a factor of five.
GRAD_TOL = 2.5e-2


def _bert_config(config: dict):
    from fpga_ai_nic_tpu.models import bert
    return bert.BertConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        ffn_dim=config["intermediate_size"],
        max_pos=config["max_position_embeddings"],
        norm_eps=config["layer_norm_eps"], dtype=config["compute_dtype"],
        attn_impl=config["attn_impl"])


def program(config: dict, job: dict):
    """(init(key) -> params, loss_fn(params, batch)) of the system under
    test, as tools/zoo_tpu.py drives it.  Across chips the loss is the
    token-weighted mean over the `dp` axis, as models/bert.py documents."""
    from fpga_ai_nic_tpu.models import bert
    bcfg = _bert_config(config)
    dp_axis = "dp" if job["dp"] > 1 else None
    return (lambda key: bert.init(key, bcfg),
            lambda params, batch: bert.loss_fn(params, batch, bcfg,
                                               dp_axis=dp_axis))


def global_batch(config: dict, job: dict) -> int:
    return job["batch_per_chip"] * job["dp"]


def items_per_step(config: dict, job: dict) -> int:
    """Tokens a step trains on; padded positions would count, and these
    batches have none."""
    return global_batch(config, job) * job["seq_len"]


def make_batch(key, config: dict, job: dict):
    """(tokens, labels) [B, S]: uniform word ids, 15% of the positions (and
    always the first) replaced by the mask id, labels -100 elsewhere.  No
    id is the pad id, so no position is padding."""
    kt, km = jax.random.split(key)
    shape = (global_batch(config, job), job["seq_len"])
    toks = jax.random.randint(kt, shape, FIRST_WORD_ID, config["vocab_size"],
                              jnp.int32)
    mask = jax.random.uniform(km, shape) < MASK_SHARE
    mask = mask.at[:, 0].set(True)
    return jnp.where(mask, MASK_ID, toks), jnp.where(mask, toks, -100)


def matmul_weights(config: dict) -> int:
    """Weights a token is multiplied with: four attention projections and
    the two feed-forward matrices per layer, the MLM head's dense layer, and
    the tied decoder (vocab x hidden) on every position.  Embedding lookups
    are gathers and count nothing."""
    d, f = config["hidden_size"], config["intermediate_size"]
    per_layer = 4 * d * d + 2 * d * f
    return (config["num_hidden_layers"] * per_layer + d * d
            + config["vocab_size"] * d)


def flops_per_item(config: dict, job: dict) -> float:
    """Forward: 2 per weight, plus per layer 2*S*d for the scores and 2*S*d
    for the weighted sum of values.  Backward costs twice the forward.
    Softmax, LayerNorm, GELU and recomputation count nothing."""
    attention = (config["num_hidden_layers"] * 4.0 * job["seq_len"]
                 * config["hidden_size"])
    return 3.0 * (2.0 * matmul_weights(config) + attention)


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def reference_nll(params, batch, config: dict):
    """(summed masked-LM negative log-likelihood, masked positions) of a
    block of sequences, plain float32 jax.numpy, scores materialised."""
    tokens, labels = batch
    b, s = tokens.shape
    heads, eps = config["num_attention_heads"], config["layer_norm_eps"]
    hd = config["hidden_size"] // heads
    x = params["tok_emb"][tokens] + params["pos_emb"][jnp.arange(s)]
    x = _layernorm(x, params["emb_norm"], eps)

    def split(t):
        return t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)

    def layer(x, lyr):
        q, k, v = split(x @ lyr["wq"]), split(x @ lyr["wk"]), \
            split(x @ lyr["wv"])
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(hd))
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        att = (probs @ v).transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = _layernorm(x + att @ lyr["wo"], lyr["attn_norm"], eps)
        x = _layernorm(x + _gelu(x @ lyr["w1"]) @ lyr["w2"],
                       lyr["ffn_norm"], eps)
        return x, None

    # the equal layers run as one scanned body, which compiles once
    x, _ = jax.lax.scan(layer, x, jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params["layers"]))
    h = _layernorm(_gelu(x @ params["mlm_dense"]), params["mlm_norm"], eps)
    logits = h @ params["tok_emb"].T + params["mlm_bias"]
    top = jnp.max(logits, axis=-1, keepdims=True)
    logz = logits - top - jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1,
                                          keepdims=True))
    valid = labels >= 0
    nll = -jnp.take_along_axis(logz, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)), jnp.sum(valid)
