"""The reduction from a trace to numbers: the interval arithmetic on made-up
intervals, the rules on names the v5e's trace really gave, and the whole
reduction on a trace recorded on four chips (mlp-dp4-ring, PR 23, six runs
of the step cut out of the traced window by hand)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "mlp-dp4-ring.trace.json")

# names as the chip's trace gave them (PR 23), shortened where "..." stands
ENCODE = ('%_step.2 = (s8[327840,128]{1,0:T(8,128)(4,1)S(1)}, s8[20490,128]'
          '{1,0:T(8,128)(4,1)S(1)}) custom-call(f32[327840,128]{1,0:T(8,128)}'
          ' %bitcast), custom_call_target="tpu_custom_call", operand_layout_'
          'constraints={f32[327840,128]{1,0}}')
DECODE = ('%_step.3 = f32[327840,128]{1,0:T(8,128)} custom-call(s8[327840,128]'
          '{1,0:T(8,128)(4,1)S(1)} %pallas_call.5, s8[20490,128]{1,0:T(8,128)'
          '(4,1)S(1)} %pallas_call.6), custom_call_target="tpu_custom_call"')
RS = ('%_rs_stream_call.1 = (f32[327872,128]{1,0:T(8,128)}, f32[81968,128]'
      '{1,0:T(8,128)S(1)}) custom-call(s32[3]{0:T(128)S(1)} %copy-done.39, ...'
      '), custom_call_target="tpu_custom_call", custom_call_has_side_effect='
      'true')
AG = ('%_ag_stream_call.4 = f32[48640,128]{1,0:T(8,128)S(1)} custom-call('
      's32[3]{0:T(128)S(1)} %copy-done.39, ...), custom_call_target='
      '"tpu_custom_call"')
SCORES = ('%fusion.786 = bf16[32,12,512,64]{2,3,1,0:T(8,128)(2,1)} fusion('
          'bf16[32,12,512,64]{2,3,1,0} %bitcast.1418, f32[32,12,512,512]'
          '{2,3,1,0:T(8,128)} %get-tuple-element.322), kind=kOutput')
MATMUL = ('%fusion.79 = bf16[256,16,8,128]{3,2,1,0:T(8,128)(2,1)} fusion('
          'bf16[131072,2048]{0,1:T(8,128)(2,1)} %x), kind=kOutput')
OTHER_KERNEL = ('%_step.9 = bf16[32,12,512,64]{3,2,1,0} custom-call(bf16[32,'
                '12,512,64]{3,2,1,0} %q), custom_call_target="tpu_custom_call"')


def test_merge_and_total():
    merged = tr.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 7), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert tr.total_len(merged) == 7


def test_gaps_are_what_the_union_leaves_of_the_window():
    assert tr.gaps([(0, 3), (5, 9)], (1, 12)) == [(3, 5), (9, 12)]
    assert tr.gaps([], (1, 4)) == [(1, 4)]
    assert tr.gaps([(0, 10)], (2, 8)) == []


@pytest.mark.parametrize("name,want", [
    (ENCODE, "codec"), (DECODE, "codec"), (RS, "ring"), (AG, "ring"),
    (SCORES, "attention"), (MATMUL, "model"),
    (OTHER_KERNEL, "pallas_unknown")])
def test_rules_on_names_from_the_chip(name, want):
    assert tr.classify(name, tr.load_rules()) == want


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return tr.Trace(json.load(f))


def test_recorded_trace_has_four_devices_and_four_whole_steps(trace):
    assert [d["plane"] for d in trace.devices] == [
        f"/device:TPU:{i}" for i in range(4)]
    assert trace.steps() == 4
    assert all(d["module"].startswith("jit__step(") for d in trace.devices)


def test_recorded_trace_ring_time_is_one_rs_update_and_eight_gathers(trace):
    for d in trace.devices:
        ring = [n for n, c, _, _ in d["ops"] if c == "ring"]
        assert len(ring) == 9 * d["steps"]
        assert sum(n.startswith("%_rs_stream_call") for n in ring) \
            == d["steps"]
    # 19.70 ms of reduce-scatter+update and 1.67 ms of gathers a step
    assert 21.0 < trace.class_ms_per_step("ring") < 21.8
    assert trace.class_ms_per_step("codec") is None
    assert trace.class_ms_per_step("attention") is None


def test_recorded_trace_busy_idle_and_step_time(trace):
    window, busy = trace.window_s(), trace.busy_s()
    assert 0.99 * window < busy <= window          # under 1% idle
    assert 198.0 < window * 1e3 / trace.steps() < 200.0     # 199.0 ms a step
    model = 1e3 * busy / trace.steps() - trace.class_ms_per_step("ring")
    assert 176.0 < model < 178.5                   # the matmuls


def test_recorded_trace_breakdown(trace):
    ops = trace.device_ops()
    assert len(ops) <= 10 and ops[0][0] == "ring:_rs_stream_call.1"
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    idle = trace.idle_gaps()
    assert idle and all(k.startswith("host:") for k, _ in idle)
    assert idle[0][0] == "host:bench.sync"
