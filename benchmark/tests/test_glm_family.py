"""The `glm_moe` family (PR 29): its operation counts against numbers worked
by hand from GLM-4.7-Flash's published config.json, the cut configuration
against what the source publishes, the loader's acceptance of it, and the
classes of op_classes/08-glm-moe.json on instruction texts written in the
v5e trace's own form — this model's operations get their class, the MLP's
and BERT's keep theirs."""

import os
import types

import pytest

from benchmark import loader
from benchmark import trace_reduce as tr

CELL = "glm47-flash-ep8share-s4096"
CONFIG = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/glm-4.7-flash-l5-e8of64.json"))
JOB = loader.read_json(os.path.join(loader.HERE, "jobs/dp1-b2-s4096.json"))
FAMILY = loader.load_module("families", "glm_moe")

# zai-org/GLM-4.7-Flash config.json, every key that shapes the model
PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4, first_k_dense_replace=1,
    num_hidden_layers=47, num_key_value_heads=20, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=1000000, tie_word_embeddings=False, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, vocab_size=154880)


def test_the_loader_accepts_the_cut_configuration():
    spec = loader.load_spec()
    cell = loader.load_cell(spec, CELL)
    assert cell["workload"]["chips"] == 1 and cell["job"]["dp"] == 1
    assert cell["config"]["family"] == "glm_moe"
    entry = next(c for c in spec["configs"]
                 if c["name"] == "glm-4.7-flash-l5-e8of64")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    reported = set(cell["metrics"]["per_layer"])
    assert {"moe.expert_ms_per_step", "moe.expert_mxu_pct",
            "moe.dispatch_ms_per_step",
            "moe.rows_max_over_mean", "moe.held_share_pct",
            "mla.projection_ms_per_step", "attention.kernel_ms_per_step",
            "head.ms_per_step", "codec.kernel_ms_per_step",
            "codec.hbm_roofline_pct", "model.xla_ms_per_step",
            "device.idle_pct", "routing.unnamed_kernel_ms_per_step"} \
        <= reported
    assert set(cell["metrics"]["end_to_end"]) == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_is_the_published_one_or_listed_as_reduced(key):
    if key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == PUBLISHED[key]
        assert CONFIG[key] != PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_is_the_guide_s_floors_and_no_width():
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["num_nextn_predict_layers"]) \
        == (5, 8, 19360, 0)
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["router_width"] == PUBLISHED["n_routed_experts"]
    assert FAMILY.held_experts(CONFIG) == tuple(range(8))
    assert FAMILY.held_experts(dict(CONFIG, ep_rank=7)) \
        == tuple(range(56, 64))
    with pytest.raises(ValueError, match="router"):
        FAMILY.held_experts(dict(CONFIG, ep_size=4))
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])


def test_matmul_weights_by_hand():
    """q_a 2048x768 + q_b 768x5120 + kv_a 2048x576 + kv_b 512x8960 + o
    5120x2048 = 21,757,952 a layer; the dense SwiGLU 3 x 2048 x 10240; an
    expert layer's router 2048 x 64, its shared expert 3 x 2048 x 1536 and
    half a routed expert (4 x 8 / 64); the head 2048 x 19360."""
    assert FAMILY.attention_weights(CONFIG) == 21_757_952
    assert FAMILY.expert_weights(CONFIG) == 9_437_184
    want = (5 * 21_757_952 + 62_914_560
            + 4 * (131_072 + 9_437_184 + 4_718_592) + 39_649_280)
    assert want == 268_500_992
    assert FAMILY.matmul_weights(CONFIG) == want


def test_flops_per_token_by_hand():
    """2 a weight, + 5 layers x 2 x 4096 x 5120 of causal attention (half
    the square: 209,715,200), x 3 for the backward: 2.24 GFLOP a token,
    18.35 TFLOP a step of 8,192 tokens."""
    per_token = 3 * (2 * 268_500_992 + 5 * 2 * 4096 * 5120)
    assert per_token == 2_240_151_552
    assert FAMILY.flops_per_item(CONFIG, JOB) == per_token
    assert FAMILY.items_per_step(CONFIG, JOB) == 8192
    assert round(per_token * 8192 / 1e12, 2) == 18.35
    # all of the width, whatever the share held: twice the experts, more work
    more = dict(CONFIG, n_routed_experts=16, ep_size=4)
    assert FAMILY.matmul_weights(more) - FAMILY.matmul_weights(CONFIG) \
        == 4 * 4_718_592


def test_expert_flops_count_rows_routed():
    """A row through one expert: 3 matrices x 2048 x 1536 x 2, x 3 with the
    backward = 56,623,104.  4,096 rows a layer on four layers at the bf16
    peak are 4.71 ms: what moe.expert_mxu_pct divides by the class's time."""
    assert FAMILY.expert_flops(CONFIG, 1) == 56_623_104
    need_s = FAMILY.expert_flops(CONFIG, 4 * 4096) / 197e12
    assert round(need_s * 1e3, 2) == 4.71


def test_expert_mxu_pct_reader_by_hand():
    reader = loader.load_module("metrics", "moe.expert_mxu_pct")
    import numpy as np
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(class_ms_per_step=lambda c: 47.1),
        family=types.SimpleNamespace(
            routing=lambda run: {"rows": np.full((4, 8), 512)},
            expert_flops=FAMILY.expert_flops),
        config=CONFIG, peaks={"bf16_flops": 197e12})
    assert round(reader.read(run), 1) == 10.0
    run.trace = None
    assert reader.read(run) is None


def test_batch_is_next_token_over_the_slice():
    import jax
    job = dict(JOB, seq_len=16)
    toks, labels = FAMILY.make_batch(jax.random.PRNGKey(2147483900),
                                     CONFIG, job)
    assert toks.shape == labels.shape == (2, 16)
    assert int(toks.min()) >= 0 and int(toks.max()) < 19360
    assert (labels[:, :-1] == toks[:, 1:]).all()
    assert (labels[:, -1] == -100).all()


# -- the classes -------------------------------------------------------------

BF = "{1,0:T(8,128)(2,1)}"
EVENTS = [
    # the grouped products: XLA's own Mosaic kernels, no name of the table
    ('%ragged-dot-none.2 = bf16[32768,1536]' + BF + ' custom-call(s32[1]'
     '{0:T(128)} %get-tuple-element.5265, bf16[32768,2048]' + BF
     + ' %fusion.9, bf16[8,2048,1536]{2,1,0:T(8,128)(2,1)} %copy-done.72), '
     'custom_call_target="tpu_custom_call"', "moe"),
    ('%ragged-dot-metadata.1 = (s32[9]{0:T(128)}, s32[71]{0:T(128)}, s32[71]'
     '{0:T(128)}, s32[1]{0:T(128)}) custom-call(s32[8]{0:T(128)} %gte.5264), '
     'custom_call_target="tpu_custom_call"', "moe"),
    # a layer's slice of the stacked experts, and a weight gradient
    ('%dynamic-slice_bitcast_fusion.59 = bf16[8,1536,2048]{2,1,0:T(8,128)'
     '(2,1)} fusion(bf16[4,8,1536,2048]{3,2,1,0:T(8,128)(2,1)} %gte.1, s32[] '
     '%gte.2), kind=kLoop', "moe"),
    # the whole stack alone is the scan's bookkeeping, not the experts
    ('%copy.12 = bf16[4,8,2048,1536]{3,2,1,0:T(8,128)(2,1)} copy(bf16[4,8,'
     '2048,1536]{3,2,1,0:T(8,128)(2,1)} %gte.3)', "model"),
    # latent attention's projections
    ('%fusion.975 = (f32[2,4096]{1,0}, bf16[2,4096,768]' + BF + ') fusion('
     'bf16[2048,768]' + BF + ' %gte.4, bf16[2,4096,2048]' + BF + ' %gte.5), '
     'kind=kOutput', "mla"),
    ('%fusion.1034 = bf16[2,4096,8960]' + BF + ' fusion(bf16[512,8960]' + BF
     + ' %gte.6, bf16[2,4096,576]' + BF + ' %fusion.955), kind=kOutput',
     "mla"),
    ('%fusion.991 = bf16[2,4096,2048]' + BF + ' fusion(bf16[2,4096,5120]'
     + BF + ' %fusion.7, bf16[5120,2048]' + BF + ' %gte.8), kind=kOutput',
     "mla"),
    # the layer's slice fused into the product, the gradient into the stack
    ('%bitcast_dynamic-update-slice_fusion.41 = bf16[4,5120,2048]{2,1,0:T(8,'
     '128)(2,1)} fusion(bf16[4,5120,2048]{2,1,0:T(8,128)(2,1)} %gte.5907, '
     's32[] %subtract.20, bf16[2,4096,5120]{1,2,0:T(8,128)(2,1)} %bitcast.7)'
     ', kind=kLoop', "mla"),
    # a block of scores of the 20 heads, and the scan's output accumulator
    ('%broadcast_multiply_fusion.5 = (f32[2,20,4096,256]{3,2,1,0:T(8,128)}, '
     'f32[2,20,4096]{2,1,0:T(8,128)S(1)}) fusion(f32[2,20,4096,256]{3,2,1,0:'
     'T(8,128)} %remat2.900, f32[2,20,4096]{2,1,0} %copy-done.65), '
     'kind=kLoop', "attention"),
    ('%dynamic-slice_bitcast_fusion.62 = f32[2,20,4096,256]{3,2,1,0:T(8,128)}'
     ' fusion(f32[8,2,20,4096,256]{4,3,2,1,0:T(8,128)} %gte.5709, s32[] '
     '%subtract.22), kind=kLoop', "attention"),
    # dK written block by block into the whole, in the backward's scan
    ('%dynamic-update-slice.424 = bf16[2,20,4096,256]{2,3,1,0:T(8,128)(2,1)} '
     'dynamic-update-slice(bf16[2,20,4096,256]{2,3,1,0} %gte.23578, bf16[2,'
     '20,512,256]{2,3,1,0} %copy.2496, s32[] %i)', "attention"),
    # the dispatch around the grouped products, on every assignment row
    ('%fusion.2443 = bf16[32768,2048]' + BF + ' fusion(bf16[8192,2048]' + BF
     + ' %bitcast.3545, s32[32768]{0:T(1024)S(1)} %copy-done.180), '
     'kind=kCustom', "dispatch"),
    ('%multiply_select_fusion.3 = (bf16[32768,1536]' + BF + ', bf16[32768,'
     '1536]' + BF + ') fusion(pred[32768]{0} %copy-done.182, bf16[32768,1536]'
     + BF + ' %ragged-dot-none.1), kind=kLoop', "dispatch"),
    ('%reshape.3537 = f32[8192,4,2048]{2,1,0:T(4,128)} reshape(bf16[32768,'
     '2048]' + BF + ' %fusion.2455)', "dispatch"),
    ('%fusion.412 = f32[2,20,4096,512]{3,2,1,0:T(8,128)} fusion(f32[2,20,'
     '4096,256]{3,2,1,0:T(8,128)} %gte.9, f32[2,20,512,256]{3,2,1,0:T(8,128)}'
     ' %fusion.411), kind=kOutput', "attention"),
    # the head over the slice held
    ('%fusion.2 = bf16[8192,19360]' + BF + ' fusion(bf16[8192,2048]' + BF
     + ' %fusion.1, bf16[2048,19360]' + BF + ' %gte.10), kind=kOutput',
     "head"),
    # the embedding has the vocabulary first
    ('%gather.3 = bf16[8192,2048]' + BF + ' gather(bf16[19360,2048]' + BF
     + ' %gte.11, s32[8192]{0} %x)', "model"),
    # the flatten and the unflatten touch every leaf and the flat vector
    ('%concatenate.0 = f32[591296512]{0:T(1024)} concatenate(f32[2048,768]'
     '{1,0} %convert.176, f32[2048,19360]{1,0} %convert.9, f32[8,2048,1536]'
     '{2,1,0} %c)', "model"),
    ('%slice_bitcast_fusion.4 = bf16[5120,2048]' + BF + ' fusion(bf16['
     '591296512]{0:T(1024)(128)(2,1)} %fusion.1500), kind=kLoop', "model"),
    # a loop is one event around its body's, carrying every array
    ('%while.5 = (s32[], bf16[2,4096,2048]' + BF + ', bf16[4,2048,768]'
     '{2,1,0}, bf16[8,2048,1536]{2,1,0}, f32[2,20,4096,512]{3,2,1,0}) while('
     '(s32[], ...) %tuple.3), condition=%cond, body=%body', "model"),
    # the other configurations' operations keep their classes
    ('%fusion.742 = bf16[16384,30522]' + BF + ' fusion(bf16[16384,768]' + BF
     + ' %fusion.741, bf16[30522,768]' + BF + ' %gte.12), kind=kOutput',
     "head"),
    ('%fusion.817 = f32[32,12,512,512]{3,2,1,0:T(8,128)} fusion(bf16[32,12,'
     '512,64]{3,2,1,0} %a, bf16[32,12,512,64]{3,2,1,0} %b), kind=kOutput',
     "attention"),
    ('%fusion.12 = bf16[131072,2048]' + BF + ' fusion(bf16[131072,2048]' + BF
     + ' %p, bf16[2048,2048]' + BF + ' %w), kind=kOutput', "model"),
    ('%fusion.90 = bf16[16384,768]' + BF + ' fusion(bf16[16384,3072]' + BF
     + ' %h, bf16[3072,768]' + BF + ' %w2), kind=kOutput', "model"),
]


@pytest.mark.parametrize("event,cls", EVENTS,
                         ids=[e.split(" = ")[0] for e, _ in EVENTS])
def test_the_rules_give_this_model_its_classes_and_leave_the_others(event,
                                                                    cls):
    assert tr.classify(event, tr.load_rules()) == cls


def test_the_rule_file_sorts_between_the_head_and_the_fallback():
    files = sorted(f for f in os.listdir(os.path.join(loader.HERE,
                                                      "op_classes")))
    assert files.index("07-head.json") < files.index("08-glm-moe.json") \
        < files.index("10-kernels.json")


@pytest.mark.parametrize("recorded", ["mlp-dp4-ring.trace.json",
                                      "mlp-dp4-ring.named.trace.json"])
def test_recorded_traces_read_the_same_without_the_new_rules(recorded):
    reduced = loader.read_json(os.path.join(loader.HERE, "tests/data",
                                            recorded))
    old = [r for r in tr.load_rules()
           if r["class"] not in ("moe", "mla", "dispatch")
           and "19360" not in r["regex"].pattern
           and ",20," not in r["regex"].pattern]
    assert len(old) == len(tr.load_rules()) - 6      # this file's six
    now, before = tr.Trace(reduced), tr.Trace(reduced, rules=old)
    for cls in ("ring", "codec", "attention", "head", "model", "moe", "mla",
                "dispatch", "pallas_unknown"):
        assert now.class_ms_per_step(cls) == before.class_ms_per_step(cls)
