"""The `lfm2_moe` family (PR 35): its operation counts against numbers worked
by hand from LFM2-24B-A2B's published config.json, the cut configuration
against what the source publishes, the loader's acceptance of it, the
per-layer readers on hand-made runs, and the classes of
op_classes/075-lfm2-moe.json on instruction texts written in the v5e
trace's own form (read off this cell's first trace) — this model's
operations get their class, and every other cell's keep theirs although
this file is asked before 08-glm-moe.json."""

import json
import os
import types

import pytest

from benchmark import loader
from benchmark import trace_reduce as tr

CELL = "lfm2-24b-ep8share-s8192"
CONFIG = loader.read_json(os.path.join(
    loader.ROOT, "benchmark/configs/lfm2-24b-l5-e8of64.json"))
JOB = loader.read_json(os.path.join(loader.HERE, "jobs/dp1-b4-s8192.json"))
FAMILY = loader.load_module("families", "lfm2_moe")
RULE_FILE = "075-lfm2-moe.json"

# LiquidAI/LFM2-24B-A2B config.json, every key that shapes the model (the
# catalog row of the model-configs guide)
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776,
    layer_types=["conv", "conv"] + ["full_attention", "conv", "conv",
                                    "conv"] * 9 + ["full_attention", "conv"],
    max_position_embeddings=128000, model_type="lfm2_moe",
    moe_intermediate_size=1536, norm_eps=1e-05, norm_topk_prob=True,
    num_attention_heads=32, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)


def test_the_loader_accepts_the_cut_configuration():
    spec = loader.load_spec()
    cell = loader.load_cell(spec, CELL)
    assert cell["workload"]["chips"] == 1 and cell["job"]["dp"] == 1
    assert cell["config"]["family"] == "lfm2_moe"
    entry = next(c for c in spec["configs"]
                 if c["name"] == "lfm2-24b-l5-e8of64")
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types", "num_experts", "vocab_size"]
    assert len(entry["source"]) <= 200 and entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert {"conv.mixer_ms_per_step", "conv.mixer_mxu_pct",
            "gqa.projection_ms_per_step", "attention.kernel_ms_per_step",
            "head.ms_per_step", "moe.expert_ms_per_step",
            "moe.expert_mxu_pct", "moe.dispatch_ms_per_step",
            "moe.rows_max_over_mean", "moe.held_share_pct",
            "codec.kernel_ms_per_step", "codec.hbm_roofline_pct",
            "model.xla_ms_per_step", "device.idle_pct",
            "routing.unnamed_kernel_ms_per_step"} \
        <= set(cell["metrics"]["per_layer"])
    assert "mla.projection_ms_per_step" not in cell["metrics"]["per_layer"]
    assert set(cell["metrics"]["end_to_end"]) == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    assert len(spec["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_is_the_published_one_or_listed_as_reduced(key):
    if key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == PUBLISHED[key]
        assert CONFIG[key] != PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_is_the_guide_s_floors_and_no_width():
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (5, 1, 8, 8192)
    # layer 0, then layers 2-5 of the published stack: one whole period
    assert CONFIG["layer_types"] == [PUBLISHED["layer_types"][0]] \
        + PUBLISHED["layer_types"][2:6]
    assert CONFIG["layer_types"][1:].count("conv") == 3
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["router_width"] == PUBLISHED["num_experts"]
    assert FAMILY.held_experts(CONFIG) == tuple(range(8))
    assert FAMILY.held_experts(dict(CONFIG, ep_rank=7)) \
        == tuple(range(56, 64))
    with pytest.raises(ValueError, match="router"):
        FAMILY.held_experts(dict(CONFIG, ep_size=4))
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    assert FAMILY.layer_runs(CONFIG) == (
        ("conv", "dense", 1), ("full_attention", "moe", 1),
        ("conv", "moe", 3))
    assert FAMILY.layer_runs(dict(
        CONFIG, **{k: PUBLISHED[k] for k in (
            "layer_types", "num_dense_layers", "num_hidden_layers")}))[:3] \
        == (("conv", "dense", 2), ("full_attention", "moe", 1),
            ("conv", "moe", 3))
    assert set(CONFIG["assumed"]) >= {
        "seq_len", "tie_word_embeddings", "head_dim", "expert_bias",
        "aux_loss", "rope", "init", "compute_dtype", "tokens", "optimizer",
        "attention_route"}


@pytest.mark.parametrize("change,match", [
    (dict(tie_word_embeddings=False), "ties its head"),
    (dict(conv_bias=True), "no convolution bias"),
    (dict(num_hidden_layers=6), "layer_types")])
def test_a_configuration_the_program_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.model_config(dict(CONFIG, **change))


def test_matmul_weights_by_hand():
    """A convolution mixer W_in 2048 x 6144 + W_out 2048 x 2048 =
    16,777,216; the attention mixer q and o 2048 x 2048, k and v 2048 x 512
    = 10,485,760; the dense SwiGLU 3 x 2048 x 11776; an expert layer's
    router 2048 x 64 and half a routed expert (4 x 8 / 64); the tied head
    2048 x 8192."""
    assert FAMILY.conv_weights(CONFIG) == 16_777_216
    assert FAMILY.conv_weights(CONFIG, with_out=False) == 12_582_912
    assert FAMILY.attention_weights(CONFIG) == 10_485_760
    assert FAMILY.expert_weights(CONFIG) == 9_437_184
    assert FAMILY.mixer_layers(CONFIG) == {"conv": 4, "full_attention": 1}
    want = (4 * 16_777_216 + 10_485_760 + 72_351_744
            + 4 * (131_072 + 4_718_592) + 16_777_216)
    assert want == 186_122_240
    assert FAMILY.matmul_weights(CONFIG) == want


def test_flops_per_token_by_hand():
    """2 a weight, + one attention layer x 2 x 8192 x 2048 of causal
    attention (half the square), x 3 for the backward: 1.22 GFLOP a token,
    39.9 TFLOP a step of 32,768 tokens."""
    per_token = 3 * (2 * 186_122_240 + 2 * 8192 * 2048)
    assert per_token == 1_217_396_736
    assert FAMILY.flops_per_item(CONFIG, JOB) == per_token
    assert FAMILY.items_per_step(CONFIG, JOB) == 32768
    assert round(per_token * 32768 / 1e12, 1) == 39.9
    # all of the width, whatever the share held: twice the experts, more work
    more = dict(CONFIG, num_experts=16, ep_size=4)
    assert FAMILY.matmul_weights(more) - FAMILY.matmul_weights(CONFIG) \
        == 4 * 4_718_592


def test_expert_and_convolution_flops_by_hand():
    """A row through one expert: 3 matrices x 2048 x 1536 x 2, x 3 with the
    backward = 56,623,104; 2,048 rows an expert, 8 experts, 4 layers at the
    bf16 peak are 18.84 ms.  W_in's products of the four convolution
    layers: 3 x 2 x 12,582,912 x 32,768 x 4 = 9.9 TFLOP, 50.23 ms at the
    peak; with W_out 66.98."""
    assert FAMILY.expert_flops(CONFIG, 1) == 56_623_104
    need_s = FAMILY.expert_flops(CONFIG, 4 * 8 * 2048) / 197e12
    assert round(need_s * 1e3, 2) == 18.84
    w_in = FAMILY.conv_flops(CONFIG, JOB, with_out=False)
    assert w_in == 3 * 2 * 12_582_912 * 32768 * 4 == 9_895_604_649_984
    assert round(w_in / 197e12 * 1e3, 2) == 50.23
    assert round(FAMILY.conv_flops(CONFIG, JOB, with_out=True)
                 / 197e12 * 1e3, 2) == 66.98


def _run(ms_by_class, **more):
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(
            class_ms_per_step=lambda c: ms_by_class.get(c)),
        family=FAMILY, config=CONFIG, job=JOB, peaks={"bf16_flops": 197e12},
        **more)


def test_conv_readers_by_hand():
    """50.23 ms of W_in's products at the peak in a class that took 125.58
    ms: 40.0%."""
    ms = loader.load_module("metrics", "conv.mixer_ms_per_step")
    pct = loader.load_module("metrics", "conv.mixer_mxu_pct")
    run = _run({"conv": 125.58})
    assert ms.read(run) == 125.58
    assert round(pct.read(run), 1) == 40.0
    # a trace without the class, or no trace: nothing, and no raise
    assert pct.read(_run({})) is None and ms.read(_run({})) is None
    run.trace = None
    assert pct.read(run) is None and ms.read(run) is None


def test_gqa_reader_reads_its_class():
    reader = loader.load_module("metrics", "gqa.projection_ms_per_step")
    assert reader.read(_run({"gqa": 10.08, "conv": 1.0})) == 10.08
    assert reader.read(_run({})) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None


def test_batch_is_next_token_over_the_slice():
    import jax
    job = dict(JOB, seq_len=16)
    toks, labels = FAMILY.make_batch(jax.random.PRNGKey(2147483900),
                                     CONFIG, job)
    assert toks.shape == labels.shape == (4, 16)
    assert int(toks.min()) >= 0 and int(toks.max()) < 8192
    assert (labels[:, :-1] == toks[:, 1:]).all()
    assert (labels[:, -1] == -100).all()


# -- the classes -------------------------------------------------------------

BF = "{1,0:T(8,128)(2,1)}"
B3 = "{2,1,0:T(8,128)(2,1)}"
EVENTS = [
    # the tied head: logits and the [2048,8192] side; lse is not the head
    ('%fusion.2 = bf16[32768,8192]' + BF + ' fusion(bf16[32768,2048]' + BF
     + ' %fusion.1, bf16[2048,8192]{0,1:T(8,128)(2,1)} %bitcast.10), '
     'kind=kOutput', "head"),
    ('%fusion.9 = f32[8192,2048]{1,0:T(8,128)} fusion(bf16[32768,8192]' + BF
     + ' %fusion.8, bf16[32768,2048]' + BF + ' %fusion.1), kind=kOutput',
     "head"),
    ('%fusion.688 = f32[4,32,8192]{2,1,0:T(8,128)S(1)} fusion(f32[4,32,8192,'
     '64]{3,2,1,0:T(8,128)} %a, f32[4,32,8192]{2,1,0} %b), kind=kLoop',
     "attention"),
    # the grouped products and the held experts' stack
    ('%ragged-dot-none.12 = bf16[131072,1536]' + BF + ' custom-call(s32[1]'
     '{0:T(128)} %gte.1, bf16[131072,2048]' + BF + ' %fusion.652, bf16[8,2048,'
     '1536]' + B3 + ' %copy-done.72), custom_call_target="tpu_custom_call"',
     "moe"),
    ('%ragged-dot-metadata.2 = (s32[9]{0:T(128)}, s32[263]{0:T(512)}, s32[263]'
     '{0:T(512)}, s32[1]{0:T(128)}) custom-call(s32[8]{0:T(128)S(1)} %gte.2), '
     'custom_call_target="tpu_custom_call"', "moe"),
    ('%dynamic-slice_bitcast_fusion.51 = bf16[8,2048,1536]' + B3 + ' fusion('
     'bf16[3,8,2048,1536]{3,2,1,0:T(8,128)(2,1)} %gte.4857, s32[] %gte.4833),'
     ' kind=kLoop', "moe"),
    # the dispatch on all 131,072 assignment rows (read off the trace)
    ('%fusion.652 = bf16[131072,2048]' + BF + ' fusion(bf16[32768,2048]' + BF
     + ' %bitcast.874, s32[131072]{0:T(1024)S(1)} %gte.4320), kind=kCustom',
     "dispatch"),
    ('%fusion.658 = bf16[131072,2048]' + BF + ' fusion(bf16[131072,2048]' + BF
     + ' %get-tuple-element.4328, s32[131072]{0:T(1024)S(1)} %copy-done.42), '
     'kind=kCustom, calls=%fused_computation.8.clone.clone', "dispatch"),
    ('%multiply_select_fusion.5 = (bf16[131072,1536]' + BF + ', bf16[131072,'
     '1536]' + BF + ') fusion(pred[131072]{0:T(1024)(128)(4,1)S(1)} '
     '%copy-done.56, bf16[131072,1536]' + BF + ' %ragged-dot-none.12), '
     'kind=kLoop', "dispatch"),
    ('%broadcast_select_fusion.28 = bf16[131072,2048]' + BF + ' fusion(bf16['
     '131072,2048]' + BF + ' %ragged-dot-none.23, pred[131072]{0:T(1024)(128)'
     '(4,1)S(1)} %copy-done.60), kind=kLoop', "dispatch"),
    ('%reshape.2606 = f32[32768,4,2048]{2,1,0:T(4,128)} reshape(bf16[131072,'
     '2048]' + BF + ' %fusion.680)', "dispatch"),
    ('%multiply_convert_fusion.16 = bf16[131072,2048]' + BF + ' fusion(f32['
     '131072,2048]{1,0:T(8,128)} %reshape.2585, f32[131072]{0:T(1024)S(1)} '
     '%copy-done.51), kind=kLoop', "dispatch"),
    ('%multiply_reduce_fusion.41 = bf16[32768,2048]' + BF + ' fusion(f32['
     '32768,4,2048]{2,1,0:T(4,128)} %reshape.2606, f32[32768,4]{1,0:T(8,128)'
     'S(1)} %copy-done.18), kind=kLoop', "dispatch"),
    # the router reads the token-major residual: not 08's dispatch
    ('%fusion.661 = bf16[32768,2048]' + BF + ' fusion(bf16[32768,2048]' + BF
     + ' %reduce.551, f32[2048,64]{0,1:T(8,128)S(1)} %copy-done.39, f32[32768,'
     '64]{0,1:T(8,128)S(1)} %custom-call.159), kind=kOutput', "model"),
    # the convolution mixer: W_in alone, stacked and its gradient; the gates
    ('%fusion.643 = bf16[4,8192,6144]' + B3 + ' fusion(bf16[4,8192,2048]' + B3
     + ' %dsbf.40, f32[4,8192]{1,0:T(4,128)S(1)} %fusion.642, bf16[2048]'
     '{0:T(1024)(128)(2,1)S(1)} %copy-done.147, bf16[2048,6144]' + BF
     + ' %custom-call.167), kind=kOutput', "conv"),
    ('%bitcast_dynamic-update-slice_fusion.28 = bf16[3,2048,6144]' + B3
     + ' fusion(bf16[3,2048,6144]' + B3 + ' %gte.4901, s32[] %subtract.6, '
     'bf16[4,8192,2048]' + B3 + ' %gte.4336), kind=kOutput', "conv"),
    ('%convert_multiply_fusion.95 = f32[4,8192,2048]{2,1,0:T(8,128)} fusion('
     'bf16[4,8192,6144]' + B3 + ' %fusion.643), kind=kLoop', "conv"),
    # the 3-tap filter works on float32 [4,8192,2048] arrays
    ('%multiply_add_fusion.16 = f32[4,8192,2048]{2,1,0:T(8,128)} fusion(f32['
     '4,8192,2048]{2,1,0:T(8,128)} %convert_multiply_fusion.95, f32[2048]'
     '{0:T(1024)S(1)} %bitcast.1065, f32[2048]{0:T(1024)S(1)} %bitcast.1067, '
     'f32[2048]{0:T(1024)S(1)} %bitcast.1063), kind=kLoop', "conv"),
    # W_out's product with the gate fused in reads the [.,6144] array
    ('%fusion.644 = (f32[4,8192]{1,0:T(4,128)S(1)}, bf16[4,8192,2048]' + B3
     + ') fusion(bf16[4,8192,2048]' + B3 + ' %dsbf.40, bf16[2048,2048]' + BF
     + ' %copy-done.26, f32[4,8192,2048]{2,1,0:T(8,128)} %maf.16, bf16[4,8192,'
     '6144]' + B3 + ' %fusion.643), kind=kOutput', "conv"),
    # ... alone it has the shapes of any 2048 x 2048 product: the model's
    ('%fusion.430 = bf16[4,8192,2048]{1,2,0:T(8,128)(2,1)} fusion(bf16[4,8192,'
     '2048]' + B3 + ' %gte.3169, bf16[2048,2048]' + BF + ' %bitcast.1109), '
     'kind=kOutput', "model"),
    # grouped-query attention: the fused projection, then per-head arrays
    ('%fusion.309 = bf16[4,8192,3072]{1,2,0:T(8,128)(2,1)} fusion(bf16[4,8192,'
     '2048]' + B3 + ' %remat2.84, bf16[1,2048,3072]' + B3 + ' %copy-done.225)'
     ', kind=kOutput', "gqa"),
    ('%slice_convert_fusion.6 = f32[4,8192,2048]{1,2,0:T(8,128)} fusion(bf16['
     '4,8192,3072]{1,2,0:T(8,128)(2,1)} %fusion.309), kind=kLoop', "gqa"),
    ('%fusion.500 = bf16[4,32,8192,64]{3,2,1,0:T(8,128)(2,1)} fusion(f32[4,'
     '8192,2048]{1,2,0:T(8,128)} %slice_convert_fusion.6, f32[8192,32]{1,0} '
     '%cos), kind=kLoop', "attention"),
    ('%fusion.510 = bf16[4,32,8192,64]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[4,8,'
     '8192,64]{3,2,1,0:T(8,128)(2,1)} %k), kind=kLoop', "attention"),
    ('%fusion.412 = f32[1,32,512,512]{3,2,1,0:T(8,128)S(1)} fusion(f32[1,32,'
     '512,64]{3,2,1,0:T(8,128)} %q, f32[1,32,512,64]{3,2,1,0:T(8,128)} %k), '
     'kind=kOutput', "attention"),
    ('%dynamic-slice_bitcast_fusion.62 = bf16[1,32,8192,64]{3,2,1,0:T(8,128)'
     '(2,1)} fusion(bf16[4,1,32,8192,64]{4,3,2,1,0:T(8,128)(2,1)} %gte.5709, '
     's32[] %i), kind=kLoop', "attention"),
    # the dense SwiGLU, the embedding's gradient side, the flat master
    ('%fusion.395 = bf16[4,8192,11776]' + B3 + ' fusion(bf16[4,8192,2048]'
     + B3 + ' %remat2.120, bf16[11776,2048]' + BF + ' %bitcast.1103), '
     'kind=kOutput', "model"),
    ('%concatenate.15 = f32[469285248]{0:T(1024)} concatenate(bf16[2048]'
     '{0:T(1024)(128)(2,1)} %mrf.22, f32[6144]{0:T(1024)} %reshape.1052, f32['
     '2048,6144]{1,0} %c, f32[8,2048,1536]{2,1,0} %d)', "model"),
    ('%while.271 = (s32[], bf16[4,8192,2048]' + B3 + ', bf16[3,2048,6144]'
     + B3 + ', bf16[131072,2048]' + BF + ', pred[131072]{0}) while((s32[], '
     '...) %tuple.3), condition=%cond, body=%body', "model"),
    # the other cells' operations keep their classes, this file asked first:
    # GLM's assignment rows are [32768,2048], its tokens [8192,2048]
    ('%fusion.2443 = bf16[32768,2048]' + BF + ' fusion(bf16[8192,2048]' + BF
     + ' %bitcast.3545, s32[32768]{0:T(1024)S(1)} %copy-done.180), '
     'kind=kCustom', "dispatch"),
    ('%reshape.3537 = f32[8192,4,2048]{2,1,0:T(4,128)} reshape(bf16[32768,'
     '2048]' + BF + ' %fusion.2455)', "dispatch"),
    ('%fusion.975 = (f32[2,4096]{1,0}, bf16[2,4096,768]' + BF + ') fusion('
     'bf16[2048,768]' + BF + ' %gte.4, bf16[2,4096,2048]' + BF + ' %gte.5), '
     'kind=kOutput', "mla"),
    ('%fusion.412 = f32[2,20,4096,512]{3,2,1,0:T(8,128)} fusion(f32[2,20,'
     '4096,256]{3,2,1,0:T(8,128)} %gte.9, f32[2,20,512,256]{3,2,1,0:T(8,128)}'
     ' %fusion.411), kind=kOutput', "attention"),
    ('%fusion.2 = bf16[8192,19360]' + BF + ' fusion(bf16[8192,2048]' + BF
     + ' %fusion.1, bf16[2048,19360]' + BF + ' %gte.10), kind=kOutput',
     "head"),
    # the MLP cells hold 131,072 samples of width 2,048: not assignment rows
    ('%convolution_add_fusion.9 = bf16[131072,2048]' + BF + ' fusion(bf16['
     '131072,2048]' + BF + ' %batch_0_.1, bf16[2048,2048]' + BF + ' %w, bf16['
     '2048]{0:T(1024)(128)(2,1)} %b), kind=kOutput', "model"),
    ('%fusion.2 = f32[131072,2048]{0,1:T(8,128)} fusion(bf16[131072,2048]{0,1:'
     'T(8,128)(2,1)} %gte.22, f32[131072]{0:T(1024)S(1)} %copy-done.16, bf16['
     '131072]{0:T(1024)(128)(2,1)S(1)} %copy-done.15), kind=kLoop', "model"),
    ('%fusion = f32[131072]{0:T(1024)S(1)} fusion(f32[131072,2048]{0,1:T(8,'
     '128)} %fusion.2, s32[131072]{0:T(1024)S(1)} %bitcast.77), kind=kCustom',
     "model"),
    ('%fusion.33 = bf16[2048]{0:T(1024)(128)(2,1)} fusion(bf16[131072]{0:'
     'T(1024)(128)(2,1)S(1)} %gte.21, bf16[131072,2048]{0,1:T(8,128)(2,1)} '
     '%gte.22, f32[131072]{0:T(1024)S(1)} %gte.20, s32[131072]{0:T(1024)S(1)}'
     ' %copy-done.18), kind=kLoop', "model"),
    # the dp=4 gather's tail segment is [4,6144]: not a convolution mixer
    ('%reshape.232 = bf16[4,6144]{1,0:T(4,128)(2,1)S(1)} reshape(f32[192,128]'
     '{1,0:T(8,128)S(1)} %_ag_stream_call.3)', "model"),
    # BERT: 12 heads, a 3,072-wide feed-forward on 16,384 tokens
    ('%fusion.90 = bf16[16384,768]' + BF + ' fusion(bf16[16384,3072]' + BF
     + ' %h, bf16[3072,768]' + BF + ' %w2), kind=kOutput', "model"),
    ('%fusion.817 = f32[4,12,512,512]{3,2,1,0:T(8,128)} fusion(bf16[4,12,'
     '512,64]{3,2,1,0} %a, bf16[4,12,512,64]{3,2,1,0} %b), kind=kOutput',
     "attention"),
    ('%fusion.818 = bf16[8,4,12,512,64]{4,3,2,1,0} fusion(bf16[32,12,512,64]'
     '{3,2,1,0} %a), kind=kLoop', "model"),
    ('%fusion.742 = bf16[1024,30522]' + BF + ' fusion(bf16[1024,768]' + BF
     + ' %fusion.741, bf16[30522,768]' + BF + ' %gte.12), kind=kOutput',
     "head"),
]


@pytest.mark.parametrize("event,cls", EVENTS,
                         ids=["%d-%s" % (i, e.split(" = ")[0])
                              for i, (e, _) in enumerate(EVENTS)])
def test_the_rules_give_this_model_its_classes_and_leave_the_others(event,
                                                                    cls):
    assert tr.classify(event, tr.load_rules()) == cls


def test_the_rule_file_sorts_between_the_head_and_glm_s():
    files = sorted(os.listdir(os.path.join(loader.HERE, "op_classes")))
    assert files.index("07-head.json") < files.index(RULE_FILE) \
        == files.index("08-glm-moe.json") - 1


def _rules_without_this_file():
    rules = []
    for f in sorted(os.listdir(os.path.join(loader.HERE, "op_classes"))):
        if f.endswith(".json") and f != RULE_FILE:
            with open(os.path.join(loader.HERE, "op_classes", f)) as fh:
                rules += [dict(r, regex=tr.re.compile(r["regex"]))
                          for r in json.load(fh)["rules"]]
    return rules


@pytest.mark.parametrize("recorded", ["mlp-dp4-ring.trace.json",
                                      "mlp-dp4-ring.named.trace.json"])
def test_recorded_traces_read_the_same_without_the_new_rules(recorded):
    """Every operation of a trace recorded in another cell has the class it
    had before this file was asked first."""
    reduced = loader.read_json(os.path.join(loader.HERE, "tests/data",
                                            recorded))
    before = _rules_without_this_file()
    assert len(before) == len(tr.load_rules()) - 10      # this file's ten
    for name in reduced["names"]:
        assert tr.classify(name, tr.load_rules()) \
            == tr.classify(name, before), name


def test_this_cell_s_trace_reads_as_it_did_on_the_chip():
    """The names of this cell's first traced run (PR 35, seed 2147483001)
    with the time each took a step: the classes' sums are the result
    line's, and without this file 08's `dispatch` would take the
    token-major residual."""
    path = os.path.join(loader.HERE, "tests/data",
                        "lfm2-24b-ep8share-s8192.ops.json")
    ops = loader.read_json(path)["ops"]          # [[name, ms a step]...]
    rules, before = tr.load_rules(), _rules_without_this_file()
    by, by_before = {}, {}
    for name, ms in ops:
        by[tr.classify(name, rules)] = by.get(
            tr.classify(name, rules), 0.0) + ms
        by_before[tr.classify(name, before)] = by_before.get(
            tr.classify(name, before), 0.0) + ms
    assert "pallas_unknown" not in by
    assert set(by) == {"dispatch", "model", "conv", "attention", "moe",
                       "head", "gqa", "codec"}
    assert 240 < by["dispatch"] < 260 and 120 < by["conv"] < 145
    assert 60 < by["attention"] < 80 and 45 < by["moe"] < 65
    assert 20 < by["head"] < 35 and 5 < by["gqa"] < 15
    # without this file: no conv, no gqa, and a head that is not read
    assert "conv" not in by_before and "gqa" not in by_before \
        and "head" not in by_before
