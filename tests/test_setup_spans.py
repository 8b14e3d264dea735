"""Set-up in the program's own event stream: jax's compile events as child
spans of the innermost open ``Profiler.bucket`` (obs.events), the trainer's
``trainer.init_state`` / ``trainer.step`` spans, and the benchmark's six
``setup.*`` readers of them (benchmark/setup_spans.py)."""

import glob
import os
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import loader
from fpga_ai_nic_tpu.models import mlp
from fpga_ai_nic_tpu.obs import events as events_lib
from fpga_ai_nic_tpu.obs.events import SPAN, EventStream
from fpga_ai_nic_tpu.obs.names import SPANS
from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
from fpga_ai_nic_tpu.utils.config import (MeshConfig, MLPConfig,
                                          OptimizerConfig, TrainConfig)
from fpga_ai_nic_tpu.utils.observability import Profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MCFG = MLPConfig(layer_sizes=(32, 64, 10), dtype="float32")
COMPILE_CHILDREN = ("jax.trace", "jax.lower", "jax.compile")
READERS = ("setup.init_state_s", "setup.step_trace_s", "setup.step_lower_s",
           "setup.step_compile_s", "setup.step_cache_read_s",
           "setup.cache_misses")
# jax times its phases on time.time(), the stream on perf_counter: the two
# may slew apart by a little while a compile runs
SLEW_NS = 1_000_000


def _trainer():
    cfg = TrainConfig(global_batch=8, mesh=MeshConfig(dp=1),
                      optimizer=OptimizerConfig())
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, MCFG), make_mesh(cfg.mesh),
                   cfg)
    batch = tr.shard_batch((jnp.ones((8, 32)), jnp.zeros((8,), jnp.int32)))
    return tr, batch


def _train(tr, batch, steps=3):
    state = tr.init_state(mlp.init(jax.random.PRNGKey(0), MCFG))
    for _ in range(steps):
        state, loss = tr.step(state, batch)
    jax.block_until_ready(loss)
    return state


def _spans(evs, name):
    return [e for e in evs if e["kind"] == SPAN and e["name"] == name]


def _children(evs, parent):
    """The events the forwarder put under ``parent`` (a span event)."""
    t0, t1 = parent["t_unix_ns"], parent["t_unix_ns"] + parent["dur_ns"]
    return [e for e in evs
            if (e.get("attrs") or {}).get("parent") == parent["name"]
            and t0 - SLEW_NS <= e["t_unix_ns"]
            and e["t_unix_ns"] + e.get("dur_ns", 0) <= t1]


@pytest.fixture(scope="module")
def trained():
    tr, batch = _trainer()
    _train(tr, batch)
    return tr, tr.profiler.events.snapshot()


# -- the forwarder ------------------------------------------------------------

def test_compile_events_become_children_of_the_open_bucket():
    prof = Profiler()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    with prof.bucket("x"):
        f(jnp.ones(5)).block_until_ready()
    evs = prof.events.snapshot()
    (x,) = _spans(evs, "x")
    kids = _children(evs, x)
    names = {e["name"] for e in kids if e["kind"] == SPAN}
    assert set(COMPILE_CHILDREN) <= names
    assert len(kids) == len(evs) - 1        # everything else is x's child
    for e in kids:
        assert e["attrs"]["parent"] == "x"
        if e["kind"] == SPAN:
            assert e["attrs"]["fun_name"]
            assert e["t_unix_ns"] + e["dur_ns"] \
                <= x["t_unix_ns"] + x["dur_ns"]
    (c,) = [e for e in kids if e["name"] == "jax.compile"
            and "<lambda>" in e["attrs"]["fun_name"]]
    assert c["attrs"]["cache"] in ("hit", "miss", "none")


def test_compile_outside_any_bucket_records_nothing():
    prof = Profiler()
    with prof.bucket("closed"):
        pass
    assert events_lib._OPEN_BUCKET.get() is None
    n = len(prof.events.snapshot())
    jax.jit(lambda x: jnp.cos(x) - 2.0)(jnp.ones(7)).block_until_ready()
    assert len(prof.events.snapshot()) == n


def test_bucket_annotates_the_profiler_trace(tmp_path):
    prof = Profiler()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof.bucket("annotated.bucket"):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert "annotated.bucket" in names


@pytest.fixture
def fresh_compile_cache(tmp_path):
    """The persistent compile cache in an empty directory, every program
    written, and the process's settings as they were afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
        cc.reset_cache()


def test_first_trainer_compiles_and_a_fresh_one_reads_the_cache(
        fresh_compile_cache):
    outcomes = []
    for _ in range(2):
        tr, batch = _trainer()
        state = tr.init_state(mlp.init(jax.random.PRNGKey(0), MCFG))
        jax.block_until_ready(tr.step(state, batch))
        evs = tr.profiler.events.snapshot()
        (step,) = _spans(evs, "trainer.step")
        outcomes.append([e["attrs"]["cache"] for e in _children(evs, step)
                         if e["name"] == "jax.compile"])
        assert all(e["kind"] == SPAN for e in _children(evs, step))
    assert outcomes == [["miss"], ["hit"]]


# -- the trainer's spans ------------------------------------------------------

def test_init_state_is_one_span(trained):
    tr, evs = trained
    (init,) = _spans(evs, "trainer.init_state")
    assert any(e["name"] == "jax.compile" for e in _children(evs, init))


def test_the_two_compiling_steps_are_traced_with_children(trained):
    tr, evs = trained
    steps = _spans(evs, "trainer.step")
    assert len(steps) == 3 and tr.step_traces == 2
    assert [s["attrs"]["traced"] for s in steps] == [True, True, False]
    for s in steps[:2]:
        names = {e["name"] for e in _children(evs, s)}
        assert set(COMPILE_CHILDREN) <= names
    assert _children(evs, steps[2]) == []


def test_the_step_holds_no_callback():
    tr, batch = _trainer()
    state = tr.init_state(mlp.init(jax.random.PRNGKey(0), MCFG))

    def prims(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)    # a ClosedJaxpr's
                    if hasattr(sub, "eqns"):
                        yield from prims(sub)

    names = set(prims(jax.make_jaxpr(tr.step_fn)(state, batch).jaxpr))
    assert "dot_general" in names        # the walk reached the model
    assert not any("callback" in n for n in names)


def test_emitted_names_are_in_the_names_table(trained):
    _, evs = trained
    assert all(e["kind"] == SPAN for e in evs)
    assert {e["name"] for e in evs} <= set(SPANS)
    assert set(events_lib._COMPILE_SPANS.values()) <= set(SPANS)


def test_perf_md_names_each_span_beside_its_metric():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    layers = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    (row,) = [ln for ln in layers.splitlines() if ln.startswith("| trainer")]
    for name in list(SPANS) + list(READERS):
        assert f"`{name}`" in row, name


# -- the readers, on a recorded stream ----------------------------------------

MS = 1_000_000


def _recorded(capacity=1 << 16):
    """Set-up as the forwarder records it, in ms from the stream's anchor;
    the window starts at 60 ms, the check's calls come after it."""
    ev = EventStream(capacity)
    t0 = ev.t0_perf_ns

    def span(name, a, b, **attrs):
        ev.emit(SPAN, name, t_ns=t0 + a * MS, dur_ns=(b - a) * MS,
                attrs=attrs or None)

    span("jax.trace", 1, 2, fun_name="_init", parent="trainer.init_state")
    span("jax.compile", 2, 4, fun_name="jit(_init)",
         parent="trainer.init_state", cache="miss")
    span("trainer.init_state", 0, 5)
    span("jax.compile", 6, 7, fun_name="jit(f)", parent="elsewhere",
         cache="miss")
    # step 1: compiles; a nested trace, and a trace inside the lowering
    span("jax.trace", 12, 13, fun_name="inner", parent="trainer.step")
    span("jax.trace", 11, 15, fun_name="_step", parent="trainer.step")
    span("jax.trace", 16, 17, fun_name="lowered", parent="trainer.step")
    span("jax.lower", 15, 18, fun_name="jit(_step)", parent="trainer.step")
    span("jax.compile", 18, 26, fun_name="jit(_step)", parent="trainer.step",
         cache="miss")
    span("trainer.step", 10, 30, traced=True)
    # step 2: a new signature, read from the cache
    span("jax.trace", 41, 43, fun_name="_step", parent="trainer.step")
    span("jax.lower", 43, 45, fun_name="jit(_step)", parent="trainer.step")
    span("jax.compile", 45, 48, fun_name="jit(_step)", parent="trainer.step",
         cache="hit")
    span("trainer.step", 40, 50, traced=True)
    span("trainer.step", 55, 56, traced=False)
    # after the window's start: the check's own calls
    span("trainer.init_state", 70, 75)
    span("jax.compile", 81, 99, fun_name="jit(_step)", parent="trainer.step",
         cache="miss")
    span("trainer.step", 80, 100, traced=True)
    return ev, (t0 + 60 * MS) / 1e9


def _run(stream, window_start, peaks=None):
    return types.SimpleNamespace(
        peaks={"bf16_flops": 1.97e14} if peaks is None else peaks,
        trainer=types.SimpleNamespace(profiler=Profiler(events=stream)),
        window=(window_start, window_start + 10.0))


EXPECTED = {"setup.init_state_s": 0.005, "setup.step_trace_s": 0.006,
            "setup.step_lower_s": 0.005, "setup.step_compile_s": 0.008,
            "setup.step_cache_read_s": 0.003, "setup.cache_misses": 2}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_recorded_set_up(name):
    ev, start = _recorded()
    got = loader.load_module("metrics", name).read(_run(ev, start))
    assert got == pytest.approx(EXPECTED[name], abs=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_in_a_rehearsal(name):
    ev, start = _recorded()
    run = _run(ev, start)
    run.peaks = None
    assert loader.load_module("metrics", name).read(run) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_where_set_up_was_dropped(name):
    ev, start = _recorded(capacity=8)
    assert ev.events_dropped > 0
    assert loader.load_module("metrics", name).read(_run(ev, start)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_on_a_trainer_without_spans(name):
    run = types.SimpleNamespace(peaks={}, trainer=types.SimpleNamespace(),
                                window=(0.0, 1.0))
    assert loader.load_module("metrics", name).read(run) is None


def test_reader_leaves_out_what_ends_after_the_window_start():
    ev, start = _recorded()
    early = _run(ev, start - 25 * MS / 1e9)        # at 35 ms: step 1 only
    read = {n: loader.load_module("metrics", n).read(early) for n in READERS}
    assert read["setup.step_compile_s"] == pytest.approx(0.008, abs=1e-9)
    assert read["setup.step_cache_read_s"] == 0.0
    assert read["setup.step_trace_s"] == pytest.approx(0.004, abs=1e-9)
    assert read["setup.init_state_s"] == pytest.approx(0.005, abs=1e-9)
    late = _run(ev, start + 45 * MS / 1e9)         # at 105 ms: the check too
    assert loader.load_module("metrics", "setup.cache_misses").read(late) == 3


def test_step_phases_never_sum_past_the_step_spans():
    from benchmark import setup_spans
    ev, start = _recorded()
    run = _run(ev, start)
    phases = setup_spans.step_phases(run)
    steps = _spans(setup_spans.events(run), "trainer.step")
    assert sum(phases.values()) <= sum(s["dur_ns"] for s in steps) / 1e9


def test_readers_on_a_real_trainer(trained):
    tr, _ = trained
    later = time.perf_counter() + 3600.0
    run = types.SimpleNamespace(peaks={}, trainer=tr, window=(later, later))
    read = {n: loader.load_module("metrics", n).read(run) for n in READERS}
    assert all(v is not None for v in read.values()), read
    assert read["setup.step_trace_s"] > 0 and read["setup.step_lower_s"] > 0
    steps = [e for e in tr.profiler.events.snapshot()
             if e["name"] == "trainer.step"]
    phases = sum(read[n] for n in READERS[1:5])
    assert phases <= sum(s["dur_ns"] for s in steps) / 1e9
    assert isinstance(read["setup.cache_misses"], int)
