"""models/glm_moe.py against the benchmark family's plain float32 reference
(benchmark/families/glm_moe.py, which imports nothing of the package): loss
and gradient with and without a chip's share of the experts, the share test
of the model-configs guide, the latent-attention block at its published head
widths, and DPTrainer steps on the CPU mesh.  Tiny widths, float32, seeded
weights."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import loader
from fpga_ai_nic_tpu.models import glm_moe
from fpga_ai_nic_tpu.ops import moe
from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
from fpga_ai_nic_tpu.utils.config import (
    BFPConfig, CollectiveConfig, MeshConfig, OptimizerConfig, TrainConfig)

FAMILY = loader.load_module("families", "glm_moe")

# the configuration file's keys at a size the CPU runs in a second
TINY = dict(
    hidden_size=32, intermediate_size=64, moe_intermediate_size=24,
    num_attention_heads=2, n_routed_experts=2, router_width=8, ep_size=4,
    ep_rank=1, n_shared_experts=1, routed_scaling_factor=1.8,
    num_experts_per_tok=2, first_k_dense_replace=1, num_hidden_layers=3,
    rms_norm_eps=1e-5, rope_theta=1000000, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, vocab_size=128,
    norm_topk_prob=True, compute_dtype="float32", attn_impl="xla",
    attn_block=8)
UNCUT = dict(TINY, n_routed_experts=8, ep_size=1, ep_rank=0)
JOB = dict(dp=1, batch_per_chip=2, seq_len=16)


def rel_l2(tree, ref):
    a, b = jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(ref)
    assert len(a) == len(b)
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(a, b))
    return (num / sum(float(jnp.sum(y ** 2)) for y in b)) ** 0.5


def reference_loss(config, batch):
    def loss(params):
        with jax.default_matmul_precision("highest"):
            total, count = FAMILY.reference_nll(params, batch, config)
        return total / count
    return loss


# -- (a) loss and gradient ---------------------------------------------------

@pytest.mark.parametrize("config", [
    TINY, UNCUT, dict(TINY, attn_block=16), dict(TINY, attn_block=6)],
    ids=["held-2-of-8", "all-held", "queries-in-one-chunk",
         "a-ragged-last-chunk"])
def test_loss_and_gradient_match_the_reference(config):
    init, loss_fn = FAMILY.program(config, JOB)
    params = init(jax.random.PRNGKey(0))
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), config, JOB)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        reference_loss(config, batch)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    assert rel_l2(grads, want_grads) < 1e-5
    # every leaf has a gradient: the router's too, through the gates
    assert all(float(jnp.sum(g ** 2)) > 0
               for g in jax.tree_util.tree_leaves(grads))


def test_parameter_count_is_the_tree():
    cfg = FAMILY.model_config(TINY)
    params = glm_moe.init(jax.random.PRNGKey(0), cfg)
    assert glm_moe.num_params(cfg) == sum(
        p.size for p in jax.tree_util.tree_leaves(params))
    assert params["moe"]["wr"].dtype == jnp.float32
    assert params["moe"]["wr"].shape == (2, 32, 8)     # the router's width
    assert params["moe"]["w1"].shape == (2, 2, 32, 24)  # the experts held


def test_published_size_has_the_issue_s_count():
    """1 dense + 4 expert layers, 8 of 64 experts, 19,360 rows: 591,294,720
    parameters (ISSUE 29's table), from shapes alone."""
    config = loader.read_json(os.path.join(
        loader.ROOT, "benchmark/configs/glm-4.7-flash-l5-e8of64.json"))
    cfg = FAMILY.model_config(config)
    assert glm_moe.num_params(cfg) == 591_294_720
    like = jax.eval_shape(lambda: glm_moe.init(jax.random.PRNGKey(0), cfg))
    assert sum(np.prod(p.shape) for p in
               jax.tree_util.tree_leaves(like)) == 591_294_720
    assert cfg.held == tuple(range(8)) and cfg.n_routed_experts == 64


# -- (b) the shares add up to the uncut layer --------------------------------

def test_all_shares_sum_to_the_uncut_layer():
    """A 64-expert layer over 8 chips: the routed part each share computes,
    summed, and the shared expert counted once, is what the uncut reference
    gives for the whole layer."""
    d, f, n, k, tokens = 16, 8, 64, 4, 48
    key = jax.random.PRNGKey(3)
    kr, k1, k2, k3, ks, kx = jax.random.split(key, 6)
    whole = {"wr": jax.random.normal(kr, (d, n)) * d ** -0.5,
             "w1": jax.random.normal(k1, (n, d, f)) * d ** -0.5,
             "w3": jax.random.normal(k3, (n, d, f)) * d ** -0.5,
             "w2": jax.random.normal(k2, (n, f, d)) * f ** -0.5}
    shared = dict(zip(("sw1", "sw3", "sw2"), (
        jax.random.normal(ks, (d, f)), jax.random.normal(ks, (d, f)) * 0.5,
        jax.random.normal(ks, (f, d)).T.reshape(f, d))))
    x = jax.random.normal(kx, (1, tokens, d))
    config = dict(num_experts_per_tok=k, routed_scaling_factor=1.8,
                  n_shared_experts=1)
    with jax.default_matmul_precision("highest"):
        want, _ = FAMILY._expert_ffn(dict(whole, **shared), x[0], config,
                                     held=range(n))
    routed = jnp.zeros_like(x)
    rows = 0
    for rank in range(8):
        held = tuple(range(rank * 8, rank * 8 + 8))
        mine = {"wr": whole["wr"], **{w: whole[w][rank * 8:rank * 8 + 8]
                                      for w in ("w1", "w3", "w2")}}
        part, counts = moe.held_experts_ffn(
            mine, x, num_experts=n, top_k=k, held=held, scale=1.8,
            with_counts=True)
        routed = routed + part
        rows += int(counts["rows"].sum())
        assert int(counts["dropped"]) == 0
    assert rows == tokens * k           # every assignment on exactly one
    got = routed[0] + moe.shared_expert(shared, x[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- (d) latent attention at its published head widths -----------------------

def test_mla_block_at_the_published_head_widths():
    config = dict(TINY, hidden_size=64, num_attention_heads=3,
                  q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=192,
                  qk_rope_head_dim=64, v_head_dim=256, attn_block=8)
    cfg = FAMILY.model_config(config)
    lyr = glm_moe.init(jax.random.PRNGKey(5), cfg)["dense"][0]
    assert lyr["wq_b"].shape == (32, 3 * 256)
    assert lyr["wkv_a"].shape == (64, 24 + 64)
    assert lyr["wkv_b"].shape == (24, 3 * (192 + 256))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    got = glm_moe.mla(lyr, x, jnp.arange(24, dtype=jnp.int32), cfg)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._attention(lyr, x, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # the rotary part matters: without positions the block reads otherwise
    flat = glm_moe.mla(lyr, x, jnp.zeros((24,), jnp.int32), cfg)
    assert float(jnp.max(jnp.abs(flat - want))) > 1e-3


@pytest.mark.parametrize("attn_block", [8, 6, 4])
def test_blocks_of_queries_equal_one_chunk(attn_block):
    """`_causal_attention` with `attn_block` < S visits the blocks at or
    below the diagonal only; which those are is the route's bookkeeping
    (ops/ring_attention._blocking).  Loss and gradients must equal the same
    call with the whole sequence as one chunk, which has no diagonal to
    keep."""
    cfg = FAMILY.model_config(dict(TINY, attn_block=attn_block))
    whole = FAMILY.model_config(dict(TINY, attn_block=None))
    q, k, v, w = jax.random.normal(jax.random.PRNGKey(7), (4, 2, 2, 24, 16))

    def run(c):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(glm_moe._causal_attention(*a, c) * w),
            argnums=(0, 1, 2)))(q, k, v)

    (got, got_grads), (want, want_grads) = run(cfg), run(whole)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert rel_l2(got_grads, want_grads) < 1e-5


def test_unequal_key_and_value_widths_are_refused():
    with pytest.raises(ValueError, match="value width"):
        glm_moe.GlmMoeConfig.tiny(v_dim=8)


# -- routing_stats -----------------------------------------------------------

def test_routing_stats_count_the_rows_of_the_batch():
    cfg = FAMILY.model_config(TINY)
    params = glm_moe.init(jax.random.PRNGKey(0), cfg)
    batch = FAMILY.make_batch(jax.random.PRNGKey(1), TINY, JOB)
    stats = jax.jit(lambda p, b: glm_moe.routing_stats(p, b, cfg))(params,
                                                                   batch)
    layers, tokens, k = 2, 32, 2
    assert stats["rows"].shape == (layers, 2)
    assert stats["selected"].shape == (layers, tokens, k)
    held = np.isin(np.asarray(stats["selected"]), cfg.held)
    np.testing.assert_array_equal(np.asarray(stats["rows"]).sum(axis=1),
                                  held.sum(axis=(1, 2)))
    np.testing.assert_allclose(np.asarray(stats["held_share"]),
                               held.mean(axis=(1, 2)), rtol=1e-6)
    assert np.asarray(stats["dropped"]).tolist() == [0, 0]


# -- (e) through DPTrainer ---------------------------------------------------

@pytest.mark.parametrize("dp", [1, 2])
def test_dp_trainer_steps(dp):
    job = dict(JOB, dp=dp)
    init, loss_fn = FAMILY.program(TINY, job)
    cfg = TrainConfig(
        global_batch=FAMILY.global_batch(TINY, job), mesh=MeshConfig(dp=dp),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=True),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=1e-2))
    tr = DPTrainer(loss_fn, make_mesh(cfg.mesh, devices=jax.devices()[:dp]),
                   cfg)
    state = tr.init_state(init(jax.random.PRNGKey(0)))
    batch = tr.shard_batch(FAMILY.make_batch(jax.random.PRNGKey(1), TINY,
                                             job))
    losses = []
    for _ in range(4):
        state, loss = tr.step(state, batch)
        losses.append(float(loss))
    assert tr.step_traces <= 2          # init_state's uncommitted state
    traces = tr.step_traces
    state, loss = tr.step(state, batch)
    assert tr.step_traces == traces     # steady: no further trace
    assert float(loss) < losses[0] and np.isfinite(losses).all()
    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert all(np.array_equal(shards[0], s) for s in shards[1:])
    # the router keeps its type through the flat master and back
    assert state.params["moe"]["wr"].dtype == jnp.float32
