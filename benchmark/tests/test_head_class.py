"""The class `head` of op_classes/07-head.json and its reader (PR 26), on
instruction texts written by hand in the v5e trace's own form (an event of
the 'XLA Ops' line is named by its whole HLO instruction): what touches an
array whose last dimension is the vocabulary is the masked-LM head, at any
row count; the embedding, attention, the codec and a loop over something
else are not.  Then the two tiny BERT cells of rehearse.json, end to end on
the CPU, which run the program's blockwise loss at dp=1 and under
shard_map at dp=4."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import loader
from benchmark import trace_reduce as tr

ROOT = loader.ROOT

# the parent's: logits of every position, [16384, 30522]
LOGITS_ALL = ('%fusion.742 = bf16[16384,30522]{1,0:T(8,128)(2,1)} fusion('
              'bf16[16384,768]{1,0:T(8,128)(2,1)} %fusion.741, bf16[30522,768]'
              '{1,0:T(8,128)(2,1)} %get-tuple-element.12), kind=kOutput')
SOFTMAX_ALL = ('%multiply_subtract_fusion = f32[16384,30522]{1,0:T(8,128)} '
               'fusion(bf16[16384,30522]{1,0:T(8,128)(2,1)} %fusion.742, '
               'f32[16384]{0:T(1024)} %reduce.3), kind=kLoop')
# the change's: one block of the masked rows
LOGITS_BLOCK = ('%fusion.31 = bf16[1024,30522]{1,0:T(8,128)(2,1)} fusion('
                'bf16[1024,768]{1,0:T(8,128)(2,1)} %fusion.30, bf16[30522,768]'
                '{1,0:T(8,128)(2,1)} %get-tuple-element.7), kind=kOutput')
# the decoder's weight gradient: the vocabulary-wide side is an operand
DECODER_GRAD = ('%fusion.44 = bf16[30522,768]{1,0:T(8,128)(2,1)} fusion('
                'bf16[1024,30522]{1,0:T(8,128)(2,1)} %fusion.40, bf16[1024,'
                '768]{1,0:T(8,128)(2,1)} %fusion.30), kind=kOutput')
DECODER_T = ('%transpose.5 = bf16[768,30522]{0,1:T(8,128)(2,1)} transpose('
             'bf16[30522,768]{1,0:T(8,128)(2,1)} %param.3), dimensions={1,0}')
STACKED = ('%fusion.50 = f32[16,1024,30522]{2,1,0:T(8,128)} fusion(f32[1024,'
           '30522]{1,0:T(8,128)} %fusion.49), kind=kLoop')
HEAD_LOOP = ('%while.7 = (s32[], bf16[768,30522]{0,1:T(8,128)(2,1)}, '
             'bf16[16,1024,768]{2,1,0:T(8,128)(2,1)}, f32[16]{0:T(128)}) '
             'while((s32[], bf16[768,30522]{0,1:T(8,128)(2,1)}, bf16[16,1024,'
             '768]{2,1,0:T(8,128)(2,1)}, f32[16]{0:T(128)}) %tuple.9), '
             'condition=%cond.1, body=%body.1')

EMBED_GATHER = ('%fusion.3 = bf16[16384,768]{1,0:T(8,128)(2,1)} fusion('
                'bf16[30522,768]{1,0:T(8,128)(2,1)} %param.3, s32[16384]'
                '{0:T(1024)} %bitcast.2), kind=kLoop')
EMBED_GRAD = ('%scatter.1 = f32[30522,768]{1,0:T(8,128)} scatter(f32[30522,'
              '768]{1,0:T(8,128)} %broadcast.9, s32[16384,1]{1,0} %bitcast.4, '
              'f32[16384,768]{1,0:T(8,128)} %convert.8), to_apply=%add.1')
BIAS_ONLY = ('%convert.12 = f32[30522]{0:T(1024)} convert(bf16[30522]'
             '{0:T(1024)(2,1)} %param.9)')
SCORES = ('%fusion.786 = bf16[32,12,512,64]{2,3,1,0:T(8,128)(2,1)} fusion('
          'bf16[32,12,512,64]{2,3,1,0} %bitcast.1418, f32[32,12,512,512]'
          '{2,3,1,0:T(8,128)} %get-tuple-element.322), kind=kOutput')
ENCODE = ('%codec_bfp_encode.1 = (s8[851584,128]{1,0:T(8,128)(4,1)}, '
          's8[53224,128]{1,0:T(8,128)(4,1)}) custom-call(f32[851584,128]'
          '{1,0:T(8,128)} %bitcast), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={\n"ainic_kernel":'
          '"codec.bfp_encode"\n}}')
OTHER_LOOP = ('%while.2 = (s32[], f32[12,16384,768]{2,1,0:T(8,128)}, '
              'bf16[30522,768]{1,0:T(8,128)(2,1)}) while((s32[], f32[12,16384,'
              '768]{2,1,0:T(8,128)}, bf16[30522,768]{1,0:T(8,128)(2,1)}) '
              '%tuple.3), condition=%cond.0, body=%body.0')
FFN = ('%fusion.120 = bf16[16384,3072]{1,0:T(8,128)(2,1)} fusion(bf16[16384,'
       '768]{1,0:T(8,128)(2,1)} %fusion.119, bf16[768,3072]{1,0:T(8,128)(2,1)}'
       ' %get-tuple-element.40), kind=kOutput')


@pytest.mark.parametrize("name,want", [
    (LOGITS_ALL, "head"), (SOFTMAX_ALL, "head"), (LOGITS_BLOCK, "head"),
    (DECODER_GRAD, "head"), (DECODER_T, "head"), (STACKED, "head"),
    (HEAD_LOOP, "head"),
    (EMBED_GATHER, "model"), (EMBED_GRAD, "model"), (BIAS_ONLY, "model"),
    (SCORES, "attention"), (ENCODE, "codec"), (OTHER_LOOP, "model"),
    (FFN, "model")],
    ids=["logits-16384", "softmax-16384", "logits-1024", "decoder-grad",
         "decoder-transpose", "stacked-blocks", "head-loop",
         "embedding-gather", "embedding-grad", "bias-alone", "attention",
         "named-codec", "other-loop", "ffn"])
def test_head_is_what_touches_a_vocabulary_wide_array(name, want):
    assert tr.classify(name, tr.load_rules()) == want


def _run(ops, steps=4, step_ns=10_000):
    """A reduced trace of `steps` equal steps on one device; `ops` are
    (name, offset in the step, duration), in ns."""
    names = ["jit__step(1)"] + [n for n, _, _ in ops]
    dev = {"modules": [[0, i * step_ns, step_ns] for i in range(steps)],
           "ops": [[1 + k, i * step_ns + at, dur] for i in range(steps)
                   for k, (_, at, dur) in enumerate(ops)]}
    reduced = {"devices": {"/device:TPU:0": dev}, "names": names, "host": []}
    return types.SimpleNamespace(trace=tr.Trace(reduced))


def read(name, run):
    return loader.load_module("metrics", name).read(run)


def test_reader_sums_the_head_and_leaves_it_inside_the_model_step():
    run = _run([(EMBED_GATHER, 0, 1000), (LOGITS_BLOCK, 1000, 2000),
                (DECODER_GRAD, 3000, 500), (SCORES, 4000, 700),
                (ENCODE, 5000, 300)])
    assert read("head.ms_per_step", run) == pytest.approx(2500e-6)
    assert read("attention.kernel_ms_per_step", run) == pytest.approx(700e-6)
    assert read("model.xla_ms_per_step", run) == pytest.approx(4200e-6)


def test_a_loop_that_is_the_heads_counts_once_with_what_it_encloses():
    run = _run([(HEAD_LOOP, 1000, 3000), (LOGITS_BLOCK, 1200, 1000),
                (DECODER_GRAD, 2500, 1000)])
    assert read("head.ms_per_step", run) == pytest.approx(3000e-6)


def test_reader_returns_nothing_where_no_head_ran_or_no_trace_was_read():
    assert read("head.ms_per_step", _run([(FFN, 0, 1000)])) is None
    assert read("head.ms_per_step", types.SimpleNamespace(trace=None)) is None
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "mlp-dp4-ring.named.trace.json")) as f:
        mlp = types.SimpleNamespace(trace=tr.Trace(json.load(f)))
    assert read("head.ms_per_step", mlp) is None


def test_only_the_bert_cells_report_the_head():
    spec = loader.load_spec()
    for w in spec["workloads"]:
        mine = loader.metrics_of(spec, w["name"])["per_layer"]
        assert ("head.ms_per_step" in mine) == (w["config"] == "bert-base")


@pytest.mark.parametrize("cell", ["tiny-bert-dp1", "tiny-bert-dp4"])
def test_tiny_bert_cells_still_rehearse(cell, tmp_path):
    """The blockwise loss under the trainer, at dp=1 and under shard_map at
    dp=4 with counts that differ per shard: first step inside the family's
    tolerances of the float32 reference, no compile in the window."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    # no device trace on the CPU: the reader finds nothing and says nothing
    assert "head.ms_per_step" not in result["metrics"]
    assert result["metrics"]["trainer.step_compiles"]["value"] == 2
