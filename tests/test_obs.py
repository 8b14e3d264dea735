"""Telemetry plane (fpga_ai_nic_tpu.obs): event stream, in-graph metric
taps, Perfetto timeline export, and the artifact regression gate.

The load-bearing contracts:
- the stream is bounded with EXPLICIT drop accounting and survives a
  JSONL round-trip under its schema version;
- ``TrainConfig.obs_metrics=False`` compiles the training step to a
  program with NO trace of the metrics plumbing (the abstract-eval test:
  the tap is a literal identity at trace time);
- the merged timeline carries host spans, queue tickets and device
  intervals on one timebase in Chrome-trace JSON;
- the gate passes on itself and fails (nonzero) on a synthetically
  regressed summary.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fpga_ai_nic_tpu.models import mlp
from fpga_ai_nic_tpu.obs import (EventStream, MetricsSink, read_jsonl,
                                 timeline, use_sink)
from fpga_ai_nic_tpu.obs import events as events_lib
from fpga_ai_nic_tpu.obs import metrics as metrics_lib
from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
from fpga_ai_nic_tpu.parallel.fsdp import FSDPTrainer
from fpga_ai_nic_tpu.runtime.queue import CollectiveQueue
from fpga_ai_nic_tpu.utils.config import (CollectiveConfig, MeshConfig,
                                          MLPConfig, TrainConfig)
from fpga_ai_nic_tpu.utils.observability import Profiler

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


MCFG = MLPConfig(layer_sizes=(32, 64, 10), dtype="float32")


def _loss(params, batch):
    return mlp.loss_fn(params, batch, MCFG)


def _batch(n=64):
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((n, 32)).astype(np.float32))
    y = jnp.asarray(r.integers(0, 10, n).astype(np.int32))
    return x, y


def _trainer(cls=DPTrainer, axis="dp", **kw):
    mesh_kw = {axis: 8}
    cfg = TrainConfig(global_batch=64, mesh=MeshConfig(**mesh_kw), **kw)
    tr = cls(_loss, make_mesh(cfg.mesh), cfg,)
    state = tr.init_state(mlp.init(jax.random.PRNGKey(0), MCFG))
    return tr, state, tr.shard_batch(_batch())


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

def test_event_stream_records_all_kinds():
    ev = EventStream()
    with ev.span("phase", stage=1):
        pass
    ev.instant("fault", kind="hang")
    ev.counter("loss", 2.5)
    snap = ev.snapshot()
    assert [e["kind"] for e in snap] == ["span", "instant", "counter"]
    assert snap[0]["dur_ns"] >= 0 and snap[0]["attrs"] == {"stage": 1}
    assert snap[2]["value"] == 2.5
    s = ev.summary()
    assert s["schema_version"] == events_lib.SCHEMA_VERSION
    assert s["spans"]["phase"]["count"] == 1
    assert s["counters"]["loss"] == 2.5
    assert s["events_dropped"] == 0


def test_event_stream_bounded_with_drop_accounting():
    ev = EventStream(capacity=8)
    for i in range(20):
        ev.counter("c", float(i))
    s = ev.summary()
    assert s["recorded"] == 8
    assert s["emitted"] == 20
    assert s["events_dropped"] == 12
    # ring semantics: newest survive
    assert [e["value"] for e in ev.snapshot()] == list(range(12, 20))


def test_event_stream_jsonl_round_trip(tmp_path):
    ev = EventStream()
    with ev.span("step", i=0):
        ev.instant("inner")
    path = ev.dump_jsonl(str(tmp_path / "events.jsonl"))
    header, events = read_jsonl(path)
    assert header["schema_version"] == events_lib.SCHEMA_VERSION
    assert header["events_dropped"] == 0
    assert [e["name"] for e in events] == ["inner", "step"]
    # timestamps are absolute unix ns on one axis
    assert abs(events[0]["t_unix_ns"] - header["t0_unix_ns"]) < 60 * 1e9


def test_read_jsonl_rejects_unknown_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"schema_version": 999}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        read_jsonl(str(p))


def test_span_records_on_exception():
    ev = EventStream()
    with pytest.raises(RuntimeError):
        with ev.span("dying"):
            raise RuntimeError("x")
    assert ev.summary()["spans"]["dying"]["count"] == 1


# ---------------------------------------------------------------------------
# metrics: the tap and the compiled-out contract
# ---------------------------------------------------------------------------

def test_tap_disabled_is_trace_level_identity():
    """The abstract-eval guarantee: a disabled tap contributes NOTHING —
    the jaxpr is bit-identical to the identity function's."""
    def with_tap(x):
        return metrics_lib.tap(x, lambda: {"m": x * 2.0}, enabled=False)

    jaxpr_tap = jax.make_jaxpr(with_tap)(1.0)
    jaxpr_id = jax.make_jaxpr(lambda x: x)(1.0)
    assert str(jaxpr_tap) == str(jaxpr_id)


def test_tap_delivers_to_ambient_sink():
    ev = EventStream()
    sink = MetricsSink(events=ev)

    @jax.jit
    def f(x):
        return metrics_lib.tap(x.sum(), {"norm": jnp.sqrt((x * x).sum())})

    with use_sink(sink):
        out = f(jnp.arange(4.0))
        jax.block_until_ready(out)
    assert float(out) == 6.0                       # value passes through
    assert sink.latest["norm"] == pytest.approx(np.sqrt(14.0))
    assert ev.summary()["counters"]["metric.norm"] == \
        pytest.approx(np.sqrt(14.0))
    # no active sink -> the callback is a silent no-op, never an error
    jax.block_until_ready(f(jnp.arange(4.0)))


def test_sink_ewma_and_step_time():
    sink = MetricsSink(ewma_alpha=0.5)
    sink.update({"loss": 4.0})
    sink.update({"loss": 2.0})
    d = sink.as_dict()
    assert d["loss_ewma"] == pytest.approx(3.0)
    assert d["n_updates"] == 2
    assert d["step_time_ewma_s"] > 0


def test_trainer_metrics_disabled_compiles_no_callback():
    tr, state, batch = _trainer(
        collective=CollectiveConfig(impl="ring"), obs_metrics=False)
    txt = tr.step_fn.lower(state, batch).as_text()
    assert "callback" not in txt.lower()


def test_trainer_metrics_enabled_taps_and_preserves_loss():
    tr0, state0, batch = _trainer(
        collective=CollectiveConfig(impl="ring"), obs_metrics=False)
    tr1, state1, _ = _trainer(
        collective=CollectiveConfig(impl="ring"), obs_metrics=True)
    assert "callback" in tr1.step_fn.lower(state1, batch).as_text().lower()
    sink = MetricsSink(static=tr1.obs_static_metrics())
    with use_sink(sink):
        state1, loss1 = tr1.step(state1, batch)
        jax.block_until_ready(loss1)
    state0, loss0 = tr0.step(state0, batch)
    # telemetry must be an observer: identical numerics on and off
    assert float(loss1) == float(loss0)
    assert set(sink.latest) == {"grad_norm", "loss"}
    assert sink.latest["loss"] == pytest.approx(float(loss0))
    assert sink.latest["grad_norm"] > 0
    assert sink.static["n_devices"] == 8


def test_trainer_codec_metrics_declared_vs_observed():
    """BFP declares error_bound = 2^-7 of the unit max; the observed
    per-unit relative error on a real gradient must respect it.  The EF
    codec (topk) additionally reports residual mass."""
    tr, state, batch = _trainer(
        collective=CollectiveConfig(impl="ring", codec="bfp"),
        obs_metrics=True)
    sink = MetricsSink(static=tr.obs_static_metrics())
    with use_sink(sink):
        state, loss = tr.step(state, batch)
        jax.block_until_ready(loss)
    bound = sink.static["declared_error_bound"]
    assert 0 < sink.latest["codec_obs_rel_err"] <= bound * (1 + 1e-6)

    tr2, state2, batch2 = _trainer(
        collective=CollectiveConfig(impl="ring", codec="topk"),
        obs_metrics=True)
    sink2 = MetricsSink(static=tr2.obs_static_metrics())
    with use_sink(sink2):
        state2, loss2 = tr2.step(state2, batch2)
        jax.block_until_ready(loss2)
    assert sink2.latest["ef_resid_norm"] > 0      # top-k drops mass
    assert sink2.static["codec"] == "topk"


def test_fsdp_metrics_tap():
    tr, state, batch = _trainer(
        FSDPTrainer, axis="fsdp",
        collective=CollectiveConfig(impl="ring", codec="topk"),
        obs_metrics=True)
    sink = MetricsSink()
    with use_sink(sink):
        state, loss = tr.step(state, batch)
        jax.block_until_ready(loss)
    assert {"grad_norm", "loss", "ef_resid_norm",
            "codec_obs_rel_err"} <= set(sink.latest)
    tr0, state0, _ = _trainer(FSDPTrainer, axis="fsdp",
                              collective=CollectiveConfig(impl="ring",
                                                          codec="topk"),
                              obs_metrics=False)
    assert "callback" not in tr0.step_fn.lower(state0, batch).as_text().lower()


# ---------------------------------------------------------------------------
# queue tickets + timeline
# ---------------------------------------------------------------------------

def _queue_run():
    prof = Profiler()
    q = CollectiveQueue(jax.jit(lambda a: a * 2.0),
                        CollectiveConfig(impl="ring"), prof)
    with prof.bucket("grads"):
        t1 = q.issue(jnp.ones(64), raw_bytes=256, wire_bytes=64)
        t2 = q.issue(jnp.ones(64), raw_bytes=256, wire_bytes=64)
    q.wait(t1)
    q.wait(t2)
    return prof


def test_queue_emits_ticket_spans():
    prof = _queue_run()
    spans = [e for e in prof.events.snapshot()
             if e["kind"] == "span" and e["name"] == "collective"]
    assert len(spans) == 2
    a = spans[0]["attrs"]
    assert a["lane"] == "queue" and a["uid"] == 1
    assert a["wire_bytes"] == 64 and a["raw_bytes"] == 256
    assert a["stall_s"] >= 0 and a["overlap_s"] >= 0


def test_timeline_merges_three_sources_on_one_axis(tmp_path):
    prof = _queue_run()
    path = prof.dump_events(str(tmp_path / "events.jsonl"))
    header, host_events = read_jsonl(path)
    # synthetic device plane on an alien epoch: the anchor must rebase it
    dev = [{"plane": "/device:TPU:0", "line": "XLA Ops",
            "name": "fusion.1", "start_ns": 1000, "end_ns": 5000,
            "cls": "sync"},
           {"plane": "/device:TPU:0", "line": "Async XLA Ops",
            "name": "all-reduce-start.2", "start_ns": 2000,
            "end_ns": 9000, "cls": "async"}]
    trace = timeline.chrome_trace(host_events, dev, header=header)
    # loadable chrome-trace JSON (what Perfetto ingests)
    parsed = json.loads(json.dumps(trace))
    assert parsed["displayTimeUnit"] == "ms"
    evs = parsed["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "C", "M", "i"}
    pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert pids == {1, 2, 3}          # host spans, queue tickets, device
    od = parsed["otherData"]
    assert od["n_host_events"] == len(host_events)
    assert od["n_device_intervals"] == 2
    assert od["device_offset_ns"] != 0        # alien epoch was rebased
    # one axis: every complete event's ts is within the rebased range
    xs = [e for e in evs if e["ph"] == "X"]
    assert min(e["ts"] for e in xs) >= 0
    dev_ev = [e for e in xs if e["pid"] == 3]
    assert {e["name"] for e in dev_ev} == {"fusion.1",
                                           "all-reduce-start.2"}
    assert dev_ev[0]["ts"] <= max(e["ts"] + e["dur"] for e in xs)


def test_timeline_cli_writes_perfetto_json(tmp_path):
    prof = _queue_run()
    events_path = prof.dump_events(str(tmp_path / "events.jsonl"))
    out = str(tmp_path / "timeline.json")
    rc = timeline.main([events_path, "-o", out])
    assert rc == 0
    parsed = json.load(open(out))
    assert parsed["traceEvents"]


# ---------------------------------------------------------------------------
# the obs gate
# ---------------------------------------------------------------------------

def _gate_mod():
    import importlib
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    return importlib.import_module("obs_gate")


def test_obs_gate_self_passes_and_regression_fails():
    og = _gate_mod()
    banked = og.build_banked_summary()
    assert banked["metrics"], "repo has banked artifacts; summary empty"
    self_verdict = og.gate(banked, banked)
    assert self_verdict["ok"] and not self_verdict["regressions"]
    assert self_verdict["compared"] == len(banked["metrics"])
    # synthetic regression: halve one higher-is-better metric
    name = next(k for k, v in banked["metrics"].items()
                if v["higher_is_better"])
    bad = json.loads(json.dumps(banked))
    bad["metrics"][name]["value"] *= 0.5
    verdict = og.gate(bad, banked)
    assert not verdict["ok"]
    assert any(r["metric"] == name for r in verdict["regressions"])


def test_obs_gate_flat_candidate_and_missing_accounting():
    og = _gate_mod()
    banked = og.build_banked_summary()
    name, spec = next(iter(banked["metrics"].items()))
    # flat {name: value} mapping, a subset: only that metric is compared
    verdict = og.gate({name: spec["value"] * 1.0}, banked)
    assert verdict["ok"] and verdict["compared"] == 1
    assert verdict["missing_from_candidate"] == len(banked["metrics"]) - 1
    # an improvement beyond tol is reported, never a failure
    verdict = og.gate({name: spec["value"] * 10.0}, banked)
    assert verdict["ok"] and verdict["improvements"]


def test_obs_gate_cli_exit_codes(tmp_path):
    og = _gate_mod()
    assert og.main([]) == 0                        # gate-on-self
    summary = tmp_path / "s.json"
    assert og.main(["--write-summary", str(summary)]) == 0
    bad = json.load(open(summary))
    for m in bad["metrics"].values():
        if m["higher_is_better"]:
            m["value"] *= 0.1
    badp = tmp_path / "bad.json"
    json.dump(bad, open(badp, "w"))
    assert og.main(["--summary", str(badp)]) == 1


# ---------------------------------------------------------------------------
# the timeline's XPlane helpers (the program's only trace reader)
# ---------------------------------------------------------------------------

def test_find_xplane_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        timeline._find_xplane(str(tmp_path))


def test_cpu_thunk_classification_is_word_scoped():
    # bare primitive instruction names (with XLA's .uid) classify
    assert timeline._is_cpu_collective("psum.7")
    assert timeline._is_cpu_collective("ppermute")
    assert timeline._is_cpu_collective("all_gather.12")
    # hyphenated HLO names still classify on the CPU path too
    assert timeline._is_cpu_collective("all-reduce-start.1")
    # but a name that merely CONTAINS a primitive does not
    assert not timeline._is_cpu_collective("psum_invariant_fusion.3")
    assert not timeline._is_cpu_collective("my_psum")
    assert not timeline._is_cpu_collective("broadcast_add_fusion")


def test_cpu_thunk_trace_attributes_collectives(tmp_path):
    """A REAL collective, traced and read back as intervals: the 8-device
    mesh's psum thunks must come out `async`, the tanh compute on the
    shards' executor threads `sync`, one line per shard at least."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    if not hasattr(jax.profiler, "ProfileOptions"):
        pytest.skip("this jaxlib has no jax.profiler.ProfileOptions "
                    "(host_tracer_level is not settable)")
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    f = jax.jit(jax.shard_map(
        lambda v: lax.psum(jnp.tanh(lax.pcast(v, "dp", to="varying")), "dp"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    x = jnp.ones((8, 1 << 18), jnp.float32)
    f(x).block_until_ready()                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 3                 # per-op thunk events
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()

    ivs = timeline._device_intervals(str(tmp_path))
    by_cls = {c: [iv for iv in ivs if iv["cls"] == c]
              for c in ("async", "sync")}
    assert len(by_cls["async"]) + len(by_cls["sync"]) == len(ivs)
    assert by_cls["async"] and by_cls["sync"], {
        c: len(v) for c, v in by_cls.items()}
    # three calls on eight shards: every psum thunk, and nothing else
    assert {iv["name"].split(".")[0] for iv in by_cls["async"]} == {"psum"}
    assert len(by_cls["async"]) == 3 * 8
    assert any("tanh" in iv["name"] for iv in by_cls["sync"])
    assert all(iv["end_ns"] > iv["start_ns"] for iv in ivs)
    assert len({iv["line"] for iv in ivs}) >= 8   # one line per shard thread


# ---------------------------------------------------------------------------
# the demo (the acceptance artifact), host+queue sources
# ---------------------------------------------------------------------------

def test_obs_demo_emits_loadable_timeline(tmp_path):
    from examples import obs_demo
    out = str(tmp_path / "demo")
    summary = obs_demo.run(steps=3, out_dir=out, trace=False)
    tl = json.load(open(os.path.join(out, "timeline.json")))
    pids = {e["pid"] for e in tl["traceEvents"] if e["ph"] == "X"}
    assert {1, 2} <= pids                  # host spans + queue tickets
    assert summary["metrics"]["latest"]["loss"] == \
        pytest.approx(summary["final_loss"])
    assert summary["profiler"]["collectives"]["completed"] == 3
    header, events = read_jsonl(os.path.join(out, "events.jsonl"))
    assert header["events_dropped"] == 0
    assert any(e["name"] == "collective" for e in events)


@pytest.mark.slow
def test_obs_demo_with_device_intervals(tmp_path):
    """End-to-end acceptance: the demo's Perfetto JSON carries host spans,
    queue tickets AND device-plane intervals on one timebase (needs a
    working profiler trace capture on this backend)."""
    from examples import obs_demo
    out = str(tmp_path / "demo")
    try:
        obs_demo.run(steps=4, out_dir=out, trace=True)
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"profiler trace capture unavailable here: {e!r}")
    tl = json.load(open(os.path.join(out, "timeline.json")))
    if tl["otherData"]["n_device_intervals"] == 0:
        pytest.skip("no device intervals in this backend's trace")
    pids = {e["pid"] for e in tl["traceEvents"] if e["ph"] == "X"}
    assert pids == {1, 2, 3}
