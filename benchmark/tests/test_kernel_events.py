"""The readers that tell the program's kernels by the names it gave them
(PR 25), on a trace recorded on four chips after the kernels were named
(mlp-dp4-ring, six runs of the step cut out of the traced window like the
one beside it), and on the trace recorded before (PR 23), where they must
find nothing and say so."""

import json
import os
import types

import pytest

from benchmark import kernel_events, loader
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _run(file: str, dp: int):
    """What run.py hands a reader, as far as these four read it."""
    with open(os.path.join(DATA, file)) as f:
        trace = tr.Trace(json.load(f))
    return types.SimpleNamespace(trace=trace,
                                 trainer=types.SimpleNamespace(n=dp))


@pytest.fixture(scope="module")
def named():
    return _run("mlp-dp4-ring.named.trace.json", 4)


@pytest.fixture(scope="module")
def unnamed():
    return _run("mlp-dp4-ring.trace.json", 4)


def read(name: str, run):
    return loader.load_module("metrics", name).read(run)


def test_every_kernel_of_the_named_trace_is_classed_by_its_name(named):
    """No event falls through to 10-kernels.json: the rules of
    05-named-kernels.json alone give the same classes."""
    by_name = [r for r in tr.load_rules() if "ainic_kernel" in
               r["regex"].pattern]
    assert len(by_name) == 3
    for d in named.trace.devices:
        kernels = [(n, c) for n, c, _, _ in d["ops"]
                   if "tpu_custom_call" in n]
        assert len(kernels) == 9 * d["steps"]
        for n, c in kernels:
            assert c == tr.classify(n, by_name) == "ring"
            assert kernel_events.kernel_name(n) in (
                "ring.rs_update_stream", "ring.ag_stream")


def test_ring_split_adds_up_to_the_ring_class(named):
    rs = read("ring.rs_update_ms_per_step", named)
    ag = read("ring.gather_ms_per_step", named)
    ring = read("ring.kernel_ms_per_step", named)
    assert 19.5 < rs < 19.9             # 19.70 read by hand in PR 23
    assert 1.6 < ag < 1.75              # 1.67
    assert abs(rs + ag - ring) < 0.05   # the kernels do not overlap
    assert read("ring.gather_launches_per_step", named) == 8


def test_no_kernel_without_a_name_reads_zero_not_none(named, unnamed):
    assert read("routing.unnamed_kernel_ms_per_step", named) == 0.0
    assert read("routing.unnamed_kernel_ms_per_step", unnamed) == 0.0
    assert read("routing.unnamed_kernel_ms_per_step",
                types.SimpleNamespace(trace=None)) is None


def test_a_kernel_nobody_named_shows_up_as_a_number():
    """Strip the metadata from the gathers, as a kernel landed without
    **kernel(...) and outside a wrapper the fallback knows would look."""
    with open(os.path.join(DATA, "mlp-dp4-ring.named.trace.json")) as f:
        reduced = json.load(f)
    reduced["names"] = [
        n.replace("%ring_ag_stream", "%new_kernel").replace(
            '\n"ainic_kernel":"ring.ag_stream"\n', "")
        for n in reduced["names"]]
    run = types.SimpleNamespace(trace=tr.Trace(reduced),
                                trainer=types.SimpleNamespace(n=4))
    assert 1.6 < read("routing.unnamed_kernel_ms_per_step", run) < 1.75
    assert read("ring.gather_ms_per_step", run) is None
    assert abs(read("ring.kernel_ms_per_step", run)
               - read("ring.rs_update_ms_per_step", run)) < 1e-9


@pytest.mark.parametrize("metric", [
    "ring.rs_update_ms_per_step", "ring.gather_ms_per_step",
    "ring.gather_launches_per_step"])
def test_ring_readers_return_nothing_without_names_or_without_a_ring(
        metric, named, unnamed):
    """On the parent of PR 25 the benchmark's files are laid over a program
    that names nothing: the reader returns None and does not raise."""
    assert read(metric, unnamed) is None
    assert read(metric, types.SimpleNamespace(
        trace=named.trace, trainer=types.SimpleNamespace(n=1))) is None
    assert read(metric, types.SimpleNamespace(
        trace=None, trainer=types.SimpleNamespace(n=4))) is None
