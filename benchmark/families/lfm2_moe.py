"""Family `lfm2_moe`: LFM2-24B-A2B (`lfm2_moe`) — a pre-norm decoder whose
layers mix tokens either by a gated short convolution or by grouped-query
attention (`layer_types`), with a dense SwiGLU behind the mixer in the first
`num_dense_layers` layers and sigmoid-routed experts (no shared one) in all
later ones, a head tied to the embedding.  Configuration keys are those of
the published config.json; the file adds `router_width` (the router scores
every expert of the layer, however few are held here), `ep_size` / `ep_rank`
(this chip holds experts rank*held .. rank*held+held-1 of an expert-parallel
layer; `num_experts` counts the experts held), `tie_word_embeddings`,
`compute_dtype`, `attn_impl`, `attn_block`.

The equations the reference below follows, for a residual x [B, S, D]
(RMSNorm eps `norm_eps`, no bias anywhere; the parameter tree is the
program's: `layers` a list with one dict a run of consecutive layers of one
kind, every leaf stacked on a leading axis):

- every layer: x += mixer(RMSNorm_op(x)), then x += ffn(RMSNorm_ffn(x)).
- `conv`: [B | C | x~] = h W_in (D x 3D, split in three in this order);
  u = B * x~; c_t = sum_{j<L} w[j] * u_{t-(L-1)+j}, L = `conv_L_cache`, u
  zero before the sequence's start (a depthwise causal convolution, one
  filter a channel); y = C * c; the addend is y W_out.
- `full_attention`: [q | k | v] = h W_qkv (`num_attention_heads` heads of q,
  `num_key_value_heads` of k and of v, width D / heads: the config gives no
  head_dim); q = RMSNorm_q(q), k = RMSNorm_k(k) over the head width; RoPE
  (rotate-half, theta `rope_parameters.rope_theta`) on q and k; query head i
  attends key/value head i // (heads / kv heads); causal softmax of
  q.k / sqrt(width); the addend is concat(P v) W_o.
- leading layers: ffn = W_2 (silu(W_1 h) * W_3 h), width `intermediate_size`.
- expert layers: s = sigmoid(h W_r) over all `router_width` experts, float32;
  the `num_experts_per_tok` largest of s + `expert_bias` are selected; g_i =
  `routed_scaling_factor` * s_i / sum_selected s_j; the addend is
  sum_{i selected and held} g_i E_i(h), E_i a SwiGLU of width
  `moe_intermediate_size`.  What the absent experts would add is left out,
  in the program and here alike.
- logits = RMSNorm_final(x) E^T over the rows of the vocabulary held, E the
  token embedding; the loss is the summed next-token negative
  log-likelihood, nothing beside it.

Departures from the published code, each also under the configuration's
`assumed`: the published router adds 1e-6 to the sum the gates are divided
by (from memory; under 1e-6 relative, left out here and in
`ops.moe.sigmoid_route`); `expert_bias` is zero and no gradient step moves
it (its balancing rule is a state update outside the step); W_qkv is q_proj,
k_proj and v_proj side by side (the same product); the scores are
materialised one key/value group (heads / kv heads query heads) at a time
under `jax.checkpoint`, because all heads' scores of one 8,192-token sequence
are 8.6 GB in float32: still the plain softmax over whole rows.

`program` is the only place that touches the system under test.
"""

import itertools

import jax
import jax.numpy as jnp

ITEM = "tokens"
THROUGHPUT = "tokens_per_s_per_chip"

# First step against the float32 reference below (the readings: PERF.md,
# Findings, PR 35; all through the harness at the cell's size on the chip).
# Loss: a next-token loss of 9.51 over 8,192 classes from bf16 logits;
# 5.5e-6 to 6.7e-5 over seventeen seeds.  The precision hardly moves it (float8
# operands in the reference: 1.2e-4), so it has the limit of the harness's
# accepted transformer cells — and NO UPPER READING here: neither control
# reaches it (the planted fault below reads 3.2e-5), so in this cell the
# gradient's limit alone decides, as in the glm_moe family.
LOSS_RTOL = 1e-2
# Gradient, relative L2 over the flat vector: 4.66e-2 to 5.03e-2 on the chip
# over seventeen seeds.  As in the glm_moe family it has two parts: bf16 products
# and a bf16 residual through five layers at 8,192 positions, and the
# selections — the top 4 of 64 sigmoid scores are decided by small gaps, the
# bf16 residual moves a router's logit a little, and 5.7% of the tokens pick
# another fourth expert in some layer than the float32 reference does; such
# a token's whole backward signal differs, which every earlier layer sees.
# Two upper readings: the reference with float8 (e4m3) matmul operands reads
# 0.294, and a planted fault, the program's convolution taps reversed, reads
# 1.40.  The limit is 2.4 times the worst sound reading, and the float8
# control misses it by a factor of 2.45, the planted fault by 11.7.
GRAD_TOL = 1.2e-1

# For the control that must read `correct: false` (PERF.md): the type the
# reference's matmul operands are rounded to.  None: float32, the reference.
OPERAND_DTYPE = None


def held_experts(config: dict) -> tuple:
    """Ids of the routed experts this chip holds: the `ep_rank`-th run of
    `num_experts` (the count held) among `router_width`."""
    n, first = config["num_experts"], config["ep_rank"] * config["num_experts"]
    if config["ep_size"] * n != config["router_width"]:
        raise ValueError(f"{config['ep_size']} chips of {n} experts are not "
                         f"the router's {config['router_width']}")
    return tuple(range(first, first + n))


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_runs(config: dict) -> tuple:
    """(mixer, ffn, layers) of each run of consecutive layers of one kind,
    as the parameter tree's `layers` has them."""
    kinds = [(mixer, "dense" if i < config["num_dense_layers"] else "moe")
             for i, mixer in enumerate(config["layer_types"])]
    return tuple((*kind, len(list(group)))
                 for kind, group in itertools.groupby(kinds))


def model_config(config: dict):
    from fpga_ai_nic_tpu.models import lfm2_moe
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(f"{len(config['layer_types'])} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    if not config["tie_word_embeddings"] or not config["use_expert_bias"] \
            or config["conv_bias"]:
        raise ValueError("the lfm2_moe program ties its head, carries the "
                         "selection bias and has no convolution bias")
    return lfm2_moe.Lfm2MoeConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_dense_layers=config["num_dense_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        conv_taps=config["conv_L_cache"], ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["router_width"], held=held_experts(config),
        top_k=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        norm_eps=config["norm_eps"], dtype=config["compute_dtype"],
        attn_block=config["attn_block"], attn_impl=config["attn_impl"])


def program(config: dict, job: dict):
    """(init(key) -> params, loss_fn(params, batch)) of the system under
    test.  Across chips the loss is the token-weighted mean over `dp`."""
    from fpga_ai_nic_tpu.models import lfm2_moe
    mcfg = model_config(config)
    dp_axis = "dp" if job["dp"] > 1 else None
    return (lambda key: lfm2_moe.init(key, mcfg),
            lambda params, batch: lfm2_moe.loss_fn(params, batch, mcfg,
                                                   dp_axis=dp_axis))


def global_batch(config: dict, job: dict) -> int:
    return job["batch_per_chip"] * job["dp"]


def items_per_step(config: dict, job: dict) -> int:
    """Tokens a step trains on (the last position of a sequence has no
    target and is counted all the same: it is computed)."""
    return global_batch(config, job) * job["seq_len"]


def make_batch(key, config: dict, job: dict):
    """(tokens, labels) [B, S]: uniform ids from the slice of the
    vocabulary held; the label of a position is the next token, -100 at a
    sequence's last position."""
    shape = (global_batch(config, job), job["seq_len"])
    toks = jax.random.randint(key, shape, 0, config["vocab_size"], jnp.int32)
    labels = jnp.concatenate(
        [toks[:, 1:], jnp.full((shape[0], 1), -100, jnp.int32)], axis=1)
    return toks, labels


def mixer_layers(config: dict) -> dict:
    """{"conv": n, "full_attention": m} of the layers that are run."""
    return {kind: config["layer_types"].count(kind)
            for kind in ("conv", "full_attention")}


def conv_weights(config: dict, with_out: bool = True) -> int:
    """Weights of one convolution mixer a token is multiplied with: W_in
    [D, 3D] and, `with_out`, W_out [D, D].  The filter's taps are
    elementwise work and count nothing."""
    d = config["hidden_size"]
    return 3 * d * d + (d * d if with_out else 0)


def attention_weights(config: dict) -> int:
    """Weights of one attention mixer a token is multiplied with: q and o
    [D, D], k and v [D, kv heads x width]."""
    d = config["hidden_size"]
    return 2 * d * d + 2 * d * config["num_key_value_heads"] * head_dim(config)


def expert_weights(config: dict) -> int:
    """Weights of one routed expert: three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def matmul_weights(config: dict) -> float:
    """Weights a token is multiplied with, in expectation: both mixers by
    their count in `layer_types`, the leading layers' SwiGLU, per expert
    layer the router and the routed experts at their expectation under a
    uniform router (experts per token x held / router width of an expert:
    4 x 8 / 64 = 0.5 here), and the tied head over the rows held.  The
    embedding is a gather and counts nothing."""
    d, n = config["hidden_size"], mixer_layers(config)
    dense = config["num_dense_layers"]
    routed = (config["num_experts_per_tok"] * config["num_experts"]
              / config["router_width"])
    return (n["conv"] * conv_weights(config)
            + n["full_attention"] * attention_weights(config)
            + dense * 3 * d * config["intermediate_size"]
            + (config["num_hidden_layers"] - dense) * (
                d * config["router_width"] + routed * expert_weights(config))
            + d * config["vocab_size"])


def flops_per_item(config: dict, job: dict) -> float:
    """Forward: 2 per weight, plus per attention layer 2 * S * heads * width
    for causal attention — scores and weighted values over HALF of the
    square, the half a causal mask leaves (what the route computes of the
    eight diagonal blocks' masked halves counts nothing).  Backward costs
    twice the forward.  Norms, gates, the filter's taps, softmax, sigmoid,
    RoPE, the repeated keys and recomputation count nothing."""
    attention = (mixer_layers(config)["full_attention"] * 2.0
                 * job["seq_len"] * config["hidden_size"])
    return 3.0 * (2.0 * matmul_weights(config) + attention)


def expert_flops(config: dict, rows: float) -> float:
    """Operations the routed experts held need for `rows` assignments
    (summed over the expert layers), forward and backward: 3 x 2 x the
    expert's three matrices a row.  Rows routed, not rows padded."""
    return 3.0 * 2.0 * expert_weights(config) * rows


def conv_flops(config: dict, job: dict, with_out: bool) -> float:
    """Operations a step's convolution mixers need in their matrix
    products, forward and backward: 3 x 2 x (W_in, and W_out where
    `with_out`) x tokens x convolution layers.  conv.mixer_mxu_pct passes
    `with_out` as its class's rule holds W_out's product or not."""
    return (3.0 * 2.0 * conv_weights(config, with_out)
            * items_per_step(config, job) * mixer_layers(config)["conv"])


# -- the plain reference -----------------------------------------------------

def _mm(a, b):
    """a @ b; operands rounded to OPERAND_DTYPE where the control sets it."""
    if OPERAND_DTYPE is not None:
        a = a.astype(OPERAND_DTYPE).astype(jnp.float32)
        b = b.astype(OPERAND_DTYPE).astype(jnp.float32)
    return a @ b


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [..., S, r]: rotate-half over the last axis, position = index on
    the axis before it."""
    s, half = x.shape[-2], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def _swiglu(h, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(h, w1)) * _mm(h, w3), w2)


def _conv_mixer(lyr, h, config):
    """h [b, s, D] -> the gated short convolution's addend."""
    taps, s = config["conv_L_cache"], h.shape[1]
    gate_b, gate_c, xt = jnp.split(_mm(h, lyr["w_in"]), 3, axis=-1)
    u = gate_b * xt
    c = jnp.zeros_like(u)
    for j in range(taps):               # tap j meets u_{t - (taps - 1) + j}
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :s - back]], axis=1)
        c = c + lyr["conv_w"][j] * shifted
    return _mm(gate_c * c, lyr["w_out"])


def _attention(lyr, h, config):
    """h [b, s, D] -> the grouped-query attention's addend, the scores of
    one key/value group at a time."""
    b, s, d = h.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = head_dim(config), config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    qkv = _mm(h, lyr["wqkv"])
    q = qkv[..., :heads * hd].reshape(b, s, kv, heads // kv, hd)
    k = qkv[..., heads * hd:(heads + kv) * hd].reshape(b, s, kv, hd)
    v = qkv[..., (heads + kv) * hd:].reshape(b, s, kv, hd)
    q = _rope(_rmsnorm(q, lyr["q_norm"], eps).transpose(2, 0, 3, 1, 4), theta)
    k = _rope(_rmsnorm(k, lyr["k_norm"], eps).transpose(2, 0, 1, 3), theta)
    v = v.transpose(2, 0, 1, 3)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def group(qkv_g):
        q_g, k_g, v_g = qkv_g           # [b, heads/kv, s, hd], [b, s, hd] x 2
        scores = _mm(q_g, k_g[:, None].transpose(0, 1, 3, 2)) / jnp.sqrt(
            jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm(probs, v_g[:, None])

    o = jax.lax.map(jax.checkpoint(group), (q, k, v))   # [kv, b, g, s, hd]
    o = o.transpose(1, 3, 0, 2, 4).reshape(b, s, d)     # heads kv-major
    return _mm(o, lyr["wo"])


def _selected(scores, k):
    """[T, E] bool: the k largest scores of each row."""
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    return scores >= kth


def _expert_ffn(lyr, h, config, held):
    """h [T, D] -> (sum over the selected experts held of g_i E_i(h), the
    selection [T, router width])."""
    scores = jax.nn.sigmoid(h @ lyr["wr"])      # never rounded: float32
    chosen = _selected(scores + lyr["expert_bias"],
                       config["num_experts_per_tok"])
    gates = jnp.where(chosen, scores, 0.0)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = config["routed_scaling_factor"] * gates
    y = jnp.zeros_like(h)
    for slot, expert in enumerate(held):        # the experts held, each on
        y = y + gates[:, expert, None] * _swiglu(        # every token
            h, lyr["w1"][slot], lyr["w3"][slot], lyr["w2"][slot])
    return y, chosen


def _layer(lyr, x, config, mixer, ffn, held):
    """-> (x, the selection [T, router width] or None)."""
    eps = config["norm_eps"]
    h = _rmsnorm(x, lyr["op_norm"], eps)
    x = x + (_conv_mixer if mixer == "conv" else _attention)(lyr, h, config)
    h = _rmsnorm(x, lyr["ffn_norm"], eps)
    if ffn == "dense":
        return x + _swiglu(h, lyr["w1"], lyr["w3"], lyr["w2"]), None
    b, s, d = x.shape
    y, chosen = _expert_ffn(lyr, h.reshape(-1, d), config, held)
    return x + y.reshape(b, s, d), chosen


def _hidden(params, tokens, config, with_selection=False):
    held = held_experts(config)
    x = params["tok_emb"][tokens]
    chosen = []
    for (mixer, ffn, _), stack in zip(layer_runs(config), params["layers"]):
        def body(x, lyr, mixer=mixer, ffn=ffn):
            x, sel = _layer(lyr, x, config, mixer, ffn, held)
            return x, (sel if with_selection else None)
        # the equal layers as one scanned body; checkpoint changes no
        # arithmetic, it keeps one layer's activations for the backward
        x, sel = jax.lax.scan(jax.checkpoint(body), x, stack)
        if sel is not None:
            chosen.append(sel)
    return (x, jnp.concatenate(chosen)) if with_selection else x


def reference_nll(params, batch, config: dict):
    """(summed next-token negative log-likelihood, positions with a
    target) of a block of sequences, plain float32 jax.numpy, scores
    materialised, every held expert computed on every token."""
    tokens, labels = batch
    x = _hidden(params, tokens, config)
    logits = _mm(_rmsnorm(x, params["final_norm"], config["norm_eps"]),
                 params["tok_emb"].T)
    logz = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    nll = -jnp.take_along_axis(logz, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)), jnp.sum(valid)


# -- what the per-layer readers share ----------------------------------------

def routing(run) -> dict:
    """The program's `routing_stats` on the run's resident batch and the
    seed's weights, as numpy (made once a run and kept on `run`): `rows`
    [L, H], `held_share`, `max_over_mean`, `dropped` [L].  The harness hands
    a reader no trained state, so the weights are the seed's.  Also logs
    what share of the tokens select otherwise than under the float32
    reference on the same weights and batch."""
    if getattr(run, "lfm2_routing", None) is not None:
        return run.lfm2_routing
    import numpy as np

    from fpga_ai_nic_tpu.models import lfm2_moe
    mcfg = model_config(run.config)
    params = jax.jit(lambda k: lfm2_moe.init(k, mcfg))(
        jax.random.PRNGKey(run.trainer.cfg.seed))
    stats = jax.device_get(jax.jit(
        lambda p, b: lfm2_moe.routing_stats(p, b, mcfg))(params, run.batch))

    def reference_selection(p, tokens):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        with jax.default_matmul_precision("highest"):
            return _hidden(p, tokens, run.config, with_selection=True)[1]

    block = run.job.get("reference_block", 1)
    tokens = run.batch[0]
    chosen = np.concatenate([
        np.asarray(jax.jit(reference_selection)(params, tokens[i:i + block]))
        for i in range(0, tokens.shape[0], block)], axis=1)   # [L, T, E]
    mine = np.zeros_like(chosen)
    layers, rows = np.indices(stats["selected"].shape[:2])
    for j in range(stats["selected"].shape[2]):
        mine[layers, rows, stats["selected"][:, :, j]] = True
    differ = float(np.mean(np.any(mine != chosen, axis=-1)))
    print(f"[bench] routing on the seed's weights: rows per held expert "
          f"{stats['rows'].tolist()}; held share "
          f"{[round(float(v), 4) for v in stats['held_share']]}; max over "
          f"mean {[round(float(v), 3) for v in stats['max_over_mean']]}; "
          f"dropped {stats['dropped'].tolist()}; tokens whose selection "
          f"differs from the float32 reference's: {differ:.4%}", flush=True)
    run.lfm2_routing = dict(stats, selection_differs=differ)
    return run.lfm2_routing
