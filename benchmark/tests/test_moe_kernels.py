"""The expert layer's named kernels: op_classes/06-moe-kernels.json
and the reader `moe.kernel_launches_per_step`.  Every operation of the
traces recorded before the kernels had names reads the same with the rule
file among the rules, and the rule file alone meets none of them; a trace
whose kernels carry `moe.*` names reads them as `moe` and counts them."""

import json
import os
import types

import pytest

from benchmark import loader
from benchmark import trace_reduce as tr

DATA = os.path.join(loader.HERE, "tests", "data")
RULES_DIR = os.path.join(loader.HERE, "op_classes")
RULE_FILE = "06-moe-kernels.json"
RECORDED = sorted(f for f in os.listdir(DATA) if f.endswith(".json"))


def _rules(files):
    rules = []
    for f in files:
        with open(os.path.join(RULES_DIR, f)) as fh:
            rules += [dict(r, regex=tr.re.compile(r["regex"]))
                      for r in json.load(fh)["rules"]]
    return rules


def _names(recorded):
    data = loader.read_json(os.path.join(DATA, recorded))
    if "names" in data:
        return data["names"]
    return [n for n, _ in data["ops"] + data.get("enclosing", [])]


def read(name, run):
    return loader.load_module("metrics", name).read(run)


def test_the_rule_file_sorts_after_the_named_kernels_and_before_the_shapes():
    files = sorted(f for f in os.listdir(RULES_DIR) if f.endswith(".json"))
    assert files.index(RULE_FILE) == files.index("05-named-kernels.json") + 1
    assert files.index(RULE_FILE) < files.index("07-head.json")


@pytest.mark.parametrize("recorded", RECORDED)
def test_recorded_traces_read_the_same_with_this_rule_file(recorded):
    files = sorted(f for f in os.listdir(RULES_DIR) if f.endswith(".json"))
    others = _rules([f for f in files if f != RULE_FILE])
    mine, every = _rules([RULE_FILE]), tr.load_rules()
    names = _names(recorded)
    assert len(names) > 100
    for name in names:
        assert tr.classify(name, every) == tr.classify(name, others), name
        assert tr.classify(name, mine) == tr.DEFAULT_CLASS, name


def _moe_run():
    """The named mlp-dp4-ring trace with its gathers renamed `moe.gmm`, as
    a step whose grouped products run as named kernels would read."""
    with open(os.path.join(DATA, "mlp-dp4-ring.named.trace.json")) as f:
        reduced = json.load(f)
    reduced["names"] = [n.replace('"ainic_kernel":"ring.ag_stream"',
                                  '"ainic_kernel":"moe.gmm"')
                        for n in reduced["names"]]
    return types.SimpleNamespace(trace=tr.Trace(reduced))


def test_named_moe_kernels_read_as_moe_and_are_counted():
    run = _moe_run()
    assert read("moe.kernel_launches_per_step", run) == 8
    assert 1.6 < run.trace.class_ms_per_step("moe") < 1.75
    assert read("routing.unnamed_kernel_ms_per_step", run) == 0.0


@pytest.mark.parametrize("recorded", ["mlp-dp4-ring.named.trace.json",
                                      "mlp-dp4-ring.trace.json"])
def test_the_reader_finds_nothing_where_no_kernel_is_named_moe(recorded):
    """A program that multiplies with `lax.ragged_dot`: its trace
    holds no `moe.*` name, and the reader returns None, not 0."""
    with open(os.path.join(DATA, recorded)) as f:
        run = types.SimpleNamespace(trace=tr.Trace(json.load(f)))
    assert read("moe.kernel_launches_per_step", run) is None
    assert read("moe.kernel_launches_per_step",
                types.SimpleNamespace(trace=None)) is None


def test_the_metric_is_reported_on_the_expert_cells():
    spec = loader.load_spec()
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "moe.kernel_launches_per_step"]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["workloads"] == [
        "glm47-flash-ep8share-s4096", "lfm2-24b-ep8share-s8192",
        "nemotron-twotower-ep16share-s8192"]
    for cell in entry["workloads"]:
        assert "moe.kernel_launches_per_step" in loader.load_cell(
            spec, cell)["metrics"]["per_layer"]
