"""The `nemotron_h` family (PR 39): its operation counts against numbers
worked by hand from Nemotron-Labs-TwoTower-30B-A3B's published config.json,
the cut configuration against what the source publishes, the loader's
acceptance of it, the per-layer readers on hand-made runs, and the classes
of op_classes/076-nemotron-h.json on instruction texts written in the v5e
trace's own form (read off this cell's first trace) — this model's
operations get their class, no conditional is read whole under any of its
names, and every other cell's operations keep theirs although this file is
asked before 08-glm-moe.json and 10-kernels.json."""

import json
import os
import types

import pytest

from benchmark import loader
from benchmark import trace_reduce as tr

CELL = "nemotron-twotower-ep16share-s8192"
CONFIG_NAME = "nemotron-twotower-30b-l9-e8of128"
CONFIG = loader.read_json(os.path.join(
    loader.ROOT, f"benchmark/configs/{CONFIG_NAME}.json"))
JOB = loader.read_json(os.path.join(loader.HERE, "jobs/dp1-b1-s8192.json"))
FAMILY = loader.load_module("families", "nemotron_h")
RULE_FILE = "076-nemotron-h.json"
RULES_DIR = os.path.join(loader.HERE, "op_classes")

# nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 config.json, every key of
# the catalog row of the model-configs guide
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688,
    hybrid_override_pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
    intermediate_size=1856, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_routed_experts=128, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_hidden_layers=52, num_key_value_heads=2, num_logits_to_keep=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=2.5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_limit=[0, None], time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)


def test_the_loader_accepts_the_cut_configuration():
    spec = loader.load_spec()
    cell = loader.load_cell(spec, CELL)
    assert cell["workload"]["chips"] == 1 and cell["job"]["dp"] == 1
    assert cell["job"]["batch_per_chip"] == 1
    assert cell["config"]["family"] == "nemotron_h"
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG_NAME)
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size"]
    assert len(entry["source"]) <= 200 and entry["source"].startswith(
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-"
        "BF16/blob/main/config.json")
    assert {"ssm.scan_ms_per_step", "ssm.projection_ms_per_step",
            "ssm.scan_mxu_pct", "model.xla_ms_per_step", "device.idle_pct",
            "device.peak_hbm_GiB", "routing.unnamed_kernel_ms_per_step",
            "moe.rows_max_over_mean", "moe.held_share_pct",
            "moe.compact_fit_pct", "gqa.projection_ms_per_step"} \
        <= set(cell["metrics"]["per_layer"])
    for other in ("mla.projection_ms_per_step", "conv.mixer_ms_per_step"):
        assert other not in cell["metrics"]["per_layer"]
    assert set(cell["metrics"]["end_to_end"]) == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert CELL in [w["name"] for w in spec["workloads"]]
    # the three readers this PR adds read this cell and no other
    for m in spec["per_layer"]:
        if m["name"].startswith("ssm."):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "tokens_per_s_per_chip"


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_key_is_the_published_one_or_listed_as_reduced(key):
    if key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == PUBLISHED[key]
        assert CONFIG[key] != PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_is_the_guide_s_floors_and_no_width():
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (9, 8, 16384)
    # blocks 0-8 of the published pattern: more than a whole period
    # (MEMEM*E), 4 mixers, 4 expert blocks, 1 attention block
    pattern = CONFIG["hybrid_override_pattern"]
    assert pattern == PUBLISHED["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert FAMILY.blocks_of(CONFIG) == {"M": 4, "E": 4, "*": 1}
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["router_width"] == PUBLISHED["n_routed_experts"]
    assert FAMILY.held_experts(CONFIG) == tuple(range(8))
    assert FAMILY.held_experts(dict(CONFIG, ep_rank=15)) \
        == tuple(range(120, 128))
    with pytest.raises(ValueError, match="router"):
        FAMILY.held_experts(dict(CONFIG, ep_size=8))
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    assert FAMILY.block_runs(CONFIG) == tuple((k, 1) for k in "MEMEM*EME")
    assert set(CONFIG["assumed"]) >= {
        "towers", "seq_len", "expert_bias", "rope", "qkv", "mamba_init",
        "init", "compute_dtype", "scan", "tokens", "optimizer", "aux_loss",
        "attention_route", "d_inner"}
    assert "2408.15664" in CONFIG["assumed"]["expert_bias"]
    assert "denois" in CONFIG["assumed"]["towers"]
    assert "sixteen chips" in CONFIG["deployment"]
    loader.check_config_file(
        next(c for c in loader.load_spec()["configs"]
             if c["name"] == CONFIG_NAME), CONFIG)


@pytest.mark.parametrize("change,match", [
    (dict(tie_word_embeddings=True), "untied head"),
    (dict(use_conv_bias=False), "convolution bias"),
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(n_group=2), "group-limited"),
    (dict(num_hidden_layers=52), "a pattern of 9 blocks")])
def test_a_configuration_the_program_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.model_config(dict(CONFIG, **change))


def test_matmul_weights_by_hand():
    """A mixer: W_in 2688 x 10304 (4096 + 6144 + 64) and W_out 4096 x 2688
    = 38,707,200; attention: q and o 2688 x 4096, k and v 2688 x 256 =
    23,396,352; an expert 2 x 2688 x 1856; an expert block: the router 2688 x
    128, the shared expert 2 x 2688 x 3712 and 6 x 8 / 128 = 0.375 of a
    routed expert; the head 2688 x 16384."""
    assert FAMILY.d_inner(CONFIG) == 4096 and FAMILY.conv_dim(CONFIG) == 6144
    assert FAMILY.mixer_weights(CONFIG) == 2688 * 10304 + 4096 * 2688 \
        == 38_707_200
    assert FAMILY.attention_weights(CONFIG) == 23_396_352
    assert FAMILY.expert_weights(CONFIG) == 9_977_856
    assert FAMILY.shared_weights(CONFIG) == 19_955_712
    want = (4 * 38_707_200 + 23_396_352
            + 4 * (344_064 + 19_955_712 + 3_741_696) + 44_040_192)
    assert want == 318_431_232
    assert FAMILY.matmul_weights(CONFIG) == want


def test_flops_per_token_by_hand():
    """2 a weight, + one attention block x 2 x 8192 x 32 heads x 128 of
    causal attention (half the square), + four mixers' scans in their matrix
    form, x 3 for the backward: 2.15 GFLOP a token, 17.6 TFLOP a step of
    8,192 tokens."""
    assert FAMILY.ssm_flops_per_token(CONFIG) == (
        2 * 128 * 8 * 128 + 2 * 128 * 4096 + 2 * 2 * 4096 * 128) == 3_407_872
    per_token = 3 * (2 * 318_431_232 + 2 * 8192 * 4096 + 4 * 3_407_872)
    assert per_token == 2_152_808_448
    assert FAMILY.flops_per_item(CONFIG, JOB) == per_token
    assert FAMILY.items_per_step(CONFIG, JOB) == 8192
    assert round(per_token * 8192 / 1e12, 2) == 17.64
    # all of the width, whatever the share held: twice the experts, more work
    more = dict(CONFIG, n_routed_experts=16, ep_size=8)
    assert FAMILY.matmul_weights(more) - FAMILY.matmul_weights(CONFIG) \
        == 4 * 3_741_696


def test_expert_and_scan_flops_by_hand():
    """A row through one expert: TWO matrices x 2688 x 1856 x 2, x 3 with
    the backward = 59,867,136; 384 rows an expert, 8 experts, 4 blocks at the
    bf16 peak are 3.73 ms.  The four scans: 3 x 3,407,872 x 8,192 x 4 = 0.335
    TFLOP, 1.70 ms at the peak."""
    assert FAMILY.expert_flops(CONFIG, 1) == 3 * 2 * 2 * 2688 * 1856 \
        == 59_867_136
    need_s = FAMILY.expert_flops(CONFIG, 4 * 8 * 384) / 197e12
    assert round(need_s * 1e3, 2) == 3.73
    scans = FAMILY.ssm_flops(CONFIG, JOB)
    assert scans == 3 * 3_407_872 * 8192 * 4 == 335_007_449_088
    assert round(scans / 197e12 * 1e3, 2) == 1.70


def _run(ms_by_class, **more):
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(
            class_ms_per_step=lambda c: ms_by_class.get(c)),
        family=FAMILY, config=CONFIG, job=JOB, peaks={"bf16_flops": 197e12},
        **more)


def test_ssm_readers_by_hand():
    """1.70 ms of scan products at the peak in a class that took 42.5 ms:
    4.0%."""
    scan = loader.load_module("metrics", "ssm.scan_ms_per_step")
    proj = loader.load_module("metrics", "ssm.projection_ms_per_step")
    pct = loader.load_module("metrics", "ssm.scan_mxu_pct")
    run = _run({"ssm_scan": 42.5, "ssm": 51.0})
    assert scan.read(run) == 42.5 and proj.read(run) == 51.0
    assert round(pct.read(run), 1) == 4.0
    # the products alone at the peak would read 100, never more
    assert round(pct.read(_run({"ssm_scan": 335_007_449_088 / 197e12 * 1e3})),
                 6) == 100.0
    # a trace without the class, or no trace: nothing, and no raise
    for reader in (scan, proj, pct):
        assert reader.read(_run({})) is None
        assert reader.read(types.SimpleNamespace(trace=None)) is None


def test_the_scan_s_share_is_silent_for_a_family_without_a_scan():
    """On the parent's program, or in another family's cell, the reader
    finds nothing to read and says nothing."""
    pct = loader.load_module("metrics", "ssm.scan_mxu_pct")
    other = _run({"ssm_scan": 10.0})
    other.family = loader.load_module("families", "lfm2_moe")
    assert pct.read(other) is None


def test_batch_is_next_token_over_the_slice():
    import jax
    job = dict(JOB, seq_len=16, batch_per_chip=3)
    toks, labels = FAMILY.make_batch(jax.random.PRNGKey(2147483900),
                                     CONFIG, job)
    assert toks.shape == labels.shape == (3, 16)
    assert int(toks.min()) >= 0 and int(toks.max()) < 16384
    assert (labels[:, :-1] == toks[:, 1:]).all()
    assert (labels[:, -1] == -100).all()


# -- the classes -------------------------------------------------------------

def _rules_of(files):
    rules = []
    for f in files:
        with open(os.path.join(RULES_DIR, f)) as fh:
            rules += [dict(r, regex=tr.re.compile(r["regex"]))
                      for r in json.load(fh)["rules"]]
    return rules


RULE_FILES = sorted(f for f in os.listdir(RULES_DIR) if f.endswith(".json"))
OTHERS = [f for f in RULE_FILES if f != RULE_FILE]


def test_the_rule_file_sorts_between_lfm2_s_and_glm_s():
    assert RULE_FILES.index("075-lfm2-moe.json") + 1 \
        == RULE_FILES.index(RULE_FILE) \
        == RULE_FILES.index("08-glm-moe.json") - 1
    assert tr.load_rules()[0]["class"] == _rules_of(RULE_FILES)[0]["class"]
    assert len(tr.load_rules()) == len(_rules_of(RULE_FILES))


def _names(recorded):
    data = loader.read_json(os.path.join(loader.HERE, "tests/data", recorded))
    if "names" in data:
        return data["names"]
    return [n for n, _ in data["ops"] + data.get("enclosing", [])]


# every recorded trace of another cell: the two the harness's own tests
# read, the LFM2 cell's (PR 35 and, with the compact program, PR 39) and,
# recorded by PR 39 for this test, a BERT cell's and the GLM cell's
OTHER_CELLS = ["mlp-dp4-ring.trace.json", "mlp-dp4-ring.named.trace.json",
               "lfm2-24b-ep8share-s8192.ops.json",
               "lfm2-24b-ep8share-s8192.pr39.ops.json",
               "bert-base-seq512.ops.json",
               "glm47-flash-ep8share-s4096.ops.json"]


@pytest.mark.parametrize("recorded", OTHER_CELLS)
def test_recorded_traces_read_the_same_without_this_rule_file(recorded):
    """Every operation of a trace recorded in another cell has, with this
    file among the rules, the class it has without it.  The rule files are
    told apart by NAME, not by a count of rules (PERF.md 7.5), so the next
    rule file does not break this test."""
    names = _names(recorded)
    before = _rules_of(OTHERS)
    assert RULE_FILE not in OTHERS and len(OTHERS) == len(RULE_FILES) - 1
    assert len(tr.load_rules()) - len(before) == len(_rules_of([RULE_FILE]))
    assert len(names) > 100
    for name in names:
        assert tr.classify(name, tr.load_rules()) \
            == tr.classify(name, before), name


@pytest.mark.parametrize("recorded", OTHER_CELLS)
def test_no_rule_of_this_file_meets_another_cell_s_operation(recorded):
    """Stronger than the class staying: asked ALONE, this file's rules
    meet no operation of another cell at all (but the compiler's
    ragged-dot kernels, which 075 and 08 name letter for letter too)."""
    mine = _rules_of([RULE_FILE])
    for name in _names(recorded):
        if name.startswith("%ragged-dot"):
            continue
        assert tr.classify(name, mine) == tr.DEFAULT_CLASS, name


# What PR 39's first version of this file read as `attention` or `ssm_scan`
# in ANY cell (its review): bare [heads,rows] and [..,heads,n,width] forms.
# Written as the traces write an operation.
T = "{1,0:T(8,128)}"
NOT_THIS_MODEL_S = [
    f"%fusion.7 = f32[32,512]{T} fusion(f32[32,512,768]{T} %p.1), kind=kLoop",
    f"%copy.3 = s32[32,512]{T} copy(s32[32,512]{T} %ids)",
    f"%fusion.9 = f32[32,512,1]{T} fusion(f32[32,512]{T} %p.2), kind=kLoop",
    f"%fusion.11 = bf16[2,4096,128]{T} fusion(bf16[2,4096,2048]{T} %p.3)",
    f"%fusion.12 = bf16[8,512,128]{T} fusion(bf16[8,512,64]{T} %p.4)",
    f"%fusion.13 = f32[1,32,512]{T} fusion(f32[1,32,512,64]{T} %p.5)",
    f"%fusion.14 = bf16[4,2,8192,128]{T} fusion(bf16[4,8192,256]{T} %p.6)",
    f"%fusion.15 = f32[16,64,128,128]{T} fusion(f32[16,64,128]{T} %p.7)",
    f"%fusion.16 = bf16[128,64,64,128]{T} fusion(bf16[128,64,64]{T} %p.8)",
    f"%fusion.17 = bf16[8192,6144]{T} fusion(bf16[8192,2048]{T} %p.9)",
    f"%fusion.18 = f32[2,4096,4096]{T} fusion(f32[2,4096,2048]{T} %p.10)",
]


@pytest.mark.parametrize("name", NOT_THIS_MODEL_S,
                         ids=[n.split(" = ")[1].split("{")[0]
                              for n in NOT_THIS_MODEL_S])
def test_a_shape_any_model_could_hold_is_not_claimed(name):
    assert tr.classify(name, _rules_of([RULE_FILE])) == tr.DEFAULT_CLASS


@pytest.mark.parametrize("rule", range(8))
def test_every_alternative_is_a_shape_written_out_whole(rule):
    """No alternative of a shape rule leaves a dimension open but the unit
    or stacking axis in front that it writes out ((?:1,)?, (?:16,)?): no
    \\d+ and no (?:\\d+,)* behind the skip's own [\\d{8,}]."""
    with open(os.path.join(RULES_DIR, RULE_FILE)) as fh:
        rules = json.load(fh)["rules"]
    assert len(rules) == 8
    regex = rules[rule]["regex"]
    if regex.startswith("^%(?:ragged-dot"):
        return
    head, _, shapes = regex.partition("tpu_custom_call)[^\\n]*")
    assert head.startswith("^(?!%(?:while|cond))") and shapes
    assert "\\d" not in shapes and "*" not in shapes and "+" not in shapes


# -- this cell's own trace ---------------------------------------------------

RECORDED = loader.read_json(os.path.join(
    loader.HERE, "tests/data", "nemotron-twotower-ep16share-s8192.ops.json"))

# (how an operation of this cell's first traced run begins, its class): read
# off the trace by hand (PR 39, seed 2147483001), each found in the
# recorded file by its beginning
PICKS = [
    # the flat master: AdamW, the flatten, the codec's converts
    ("%multiply_subtract_fusion = (f32[666963968]", "model"),
    ("%concatenate.59 = f32[666963456]", "model"),
    ("%bitcast_convert_fusion.8 = bf16[666963968]", "model"),
    ("%codec_bfp_encode.1 = ", "codec"),
    ("%codec_bfp_decode.1 = ", "codec"),
    # a whole leaf of the flat vector: the experts' stack as one dimension
    ("%fusion.1384 = (bf16[39911424]", "model"),
    # the embedding's gather and its gradient: vocabulary first
    ("%fusion.90 = bf16[16384,2688]", "model"),
    # attention's fused q|k|v projection; the shared expert is the model's
    ("%convolution_bitcast_fusion.8 = bf16[1,8192,4608]", "gqa"),
    ("%fusion.969 = f32[2688,4608]", "gqa"),
    ("%fusion.1307 = bf16[8192,3712]", "model"),
    # W_out's backward products have W_o's shapes: the model's (PERF.md 7)
    ("%fusion.990 = f32[4096,2688]", "model"),
    ("%fusion.972 = f32[4096,2688]", "model"),
    ("%fusion.917 = bf16[8192,4096]", "model"),
    # the untied head
    ("%fusion.1397 = (bf16[8192]", "head"),
    ("%fusion.401 = f32[8192,16384]", "head"),
    ("%convert_bitcast_fusion.4 = f32[336,128,8,128]", "head"),
    # attention proper: score blocks (075's pattern), heads of 128 (this file)
    ("%fusion.1680 = f32[32,512,512]", "attention"),
    ("%fusion.1682 = f32[1,32,8192,128]", "attention"),
    ("%fusion.1669 = f32[1,32,512,128]", "attention"),
    ("%broadcast.3784 = bf16[2,16,8192,128]", "attention"),
    # the grouped products: the compiler's kernels and the experts' stacks
    ("%ragged-dot-none.22 = bf16[8,2688,1856]", "moe"),
    ("%ragged-dot-none.27 = bf16[6144,1856]", "moe"),
    ("%ragged-dot-none.24 = bf16[6144,2688]", "moe"),
    ("%copy.1476 = bf16[8,1856,2688]", "moe"),
    # the compact program around them, on C = 6,144 rows
    ("%fusion.386 = (bf16[6144,2688]", "dispatch"),
    ("%fusion.321 = (bf16[6144,1856]", "dispatch"),
    ("%fusion.63 = bf16[6144,2688]", "dispatch"),
    ("%fusion.33 = bf16[8192,2688]", "dispatch"),
    # the mixer around its scan: W_in, the convolution, the gate, W_out
    ("%convolution_bitcast_fusion.4 = bf16[1,8192,10304]", "ssm"),
    ("%fusion.800 = f32[2688,10304]", "ssm"),
    ("%fusion.707 = (f32[8192]", "ssm"),
    ("%broadcast_multiply_fusion.8 = (f32[1,8192,6144]", "ssm"),
    ("%fusion.443 = bf16[1,8192,6144]", "ssm"),
    ("%fusion.652 = (f32[8192]", "ssm"),
    ("%fusion.523 = (f32[4096]", "ssm"),
    # the chunked scan: decay arrays, C B^T, states, the carry, transposes
    ("%fusion.641 = (f32[64,64,128]", "ssm_scan"),
    ("%multiply_reduce_fusion.30 = (f32[64,128,64]", "ssm_scan"),
    ("%broadcast.1901 = f32[64,8,8,128,128]", "ssm_scan"),
    ("%fusion.1658 = (f32[64]", "ssm_scan"),
    ("%fusion.1411 = f32[64,8,512,128]", "ssm_scan"),
    ("%multiply_reduce_fusion.8 = bf16[64,8,128,128]", "ssm_scan"),
    ("%fusion.588 = bf16[1,64,64,64,128]", "ssm_scan"),
    ("%reshape.2146 = f32[1,8192,4096]", "ssm_scan"),
]


def _recorded(begins):
    found = [n for n, _ in RECORDED["ops"] if n.startswith(begins)]
    assert found, begins
    return found[0]


@pytest.mark.parametrize("begins,cls", PICKS,
                         ids=[b.split(" = ")[0] for b, _ in PICKS])
def test_the_rules_give_this_cell_s_operations_their_classes(begins, cls):
    assert tr.classify(_recorded(begins), tr.load_rules()) == cls


ENCLOSING = [n for n, _ in RECORDED["enclosing"]]


def test_the_recorded_run_holds_every_kind_of_enclosing_event():
    """Four expert blocks: a forward and a backward conditional each, the
    forward ones under the name jax's `cond` keeps, `%cond.N`; and the
    attention route's loops."""
    kinds = [n.split(" = ")[0].split(".")[0] for n in ENCLOSING]
    assert kinds.count("%cond") == 4 and kinds.count("%conditional") == 4
    assert kinds.count("%while") >= 3
    assert all("[8,2688,1856]" in n for n in ENCLOSING
               if n.startswith("%cond"))


@pytest.mark.parametrize("name", ENCLOSING,
                         ids=[n.split(" = ")[0] for n in ENCLOSING])
def test_no_conditional_and_no_loop_is_read_whole(name):
    """Every one of them carries the held experts' stacks among its
    operands; none is `moe`, under either name of a conditional."""
    assert tr.classify(name, tr.load_rules()) == "model"
    assert tr.classify(name, _rules_of([RULE_FILE])) == "model"


def test_a_cond_is_what_the_older_skip_reads_whole():
    """Why this file's skip is %(?:while|cond): 08-glm-moe.json's rules, which
    skip %while and %conditional, read this cell's `%cond.N` as `moe`, whole
    (PERF.md 7.7); its `%conditional.N` they leave alone."""
    glm = _rules_of(["08-glm-moe.json"])
    mine = dict(r for r in RECORDED["enclosing"])
    conds = [n for n in mine if n.startswith("%cond.")]
    assert conds
    for name in conds:
        widened = name.replace("[8,2688,1856]", "[8,2048,1536]")
        assert tr.classify(widened, glm) == "moe"
    for name in (n for n in mine if n.startswith("%conditional.")):
        assert tr.classify(name.replace("[8,2688,1856]", "[8,2048,1536]"),
                           glm) == "model"


def test_this_cell_s_trace_reads_as_it_did_on_the_chip():
    """The names of this cell's first traced run with the time each took a
    step: the classes' sums are the result line's, they add up to the
    step (430 ms; no loop and no conditional is among them), and without
    this file the scan reads as attention (10-kernels.json's [a,b,n,n])
    and nothing reads as `ssm`."""
    rules, before = tr.load_rules(), _rules_of(OTHERS)
    by, by_before = {}, {}
    for name, ms in RECORDED["ops"]:
        by[tr.classify(name, rules)] = by.get(
            tr.classify(name, rules), 0.0) + ms
        by_before[tr.classify(name, before)] = by_before.get(
            tr.classify(name, before), 0.0) + ms
    assert "pallas_unknown" not in by
    assert set(by) == {"model", "ssm", "ssm_scan", "moe", "dispatch",
                       "attention", "gqa", "head", "codec"}
    assert 415 < sum(by.values()) < 432
    assert 50 < by["ssm_scan"] < 65 and 90 < by["ssm"] < 110
    assert 60 < by["moe"] < 75 and 2 < by["dispatch"] < 8
    assert 22 < by["attention"] < 33 and 15 < by["head"] < 23
    assert 9 < by["codec"] < 13 and 120 < by["model"] < 150
    assert 3 < by["gqa"] < 8
    assert "ssm" not in by_before and "ssm_scan" not in by_before
    assert by_before["attention"] > by["attention"] + 10
