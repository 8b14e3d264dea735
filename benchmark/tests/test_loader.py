"""The loader refuses what the driver would refuse, and BENCHMARK.json with
the files it names is whole."""

import copy
import os

import pytest

from benchmark import loader

SPEC = loader.read_json(os.path.join(loader.ROOT, "BENCHMARK.json"))


def broken(edit):
    spec = copy.deepcopy(SPEC)
    edit(spec)
    return spec


def test_benchmark_json_is_inside_the_contract():
    loader.validate(copy.deepcopy(SPEC))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) == 1


@pytest.mark.parametrize("name", [
    "tokens per second", "a/b", "a,b", "-starts-with-dash", "", "x" * 65,
    "μs"])
def test_names_outside_the_alphabet_are_refused(name):
    with pytest.raises(loader.SpecError):
        loader.check_name(name, "metric")


@pytest.mark.parametrize("unit", [
    "tokens per second", "μs", "", "x" * 17, "a,b"])
def test_units_outside_the_alphabet_are_refused(unit):
    with pytest.raises(loader.SpecError):
        loader.check_unit(unit, "metric x")


@pytest.mark.parametrize("unit", ["tokens/s/chip", "%", "GB/s", "ms", "GiB"])
def test_units_in_use_pass(unit):
    assert loader.check_unit(unit, "metric x") == unit


def _why_on_metric(s): s["per_layer"][0]["why"] = "no such key"
def _bound_too_wide(s): s["end_to_end"][0]["bound"] = 0.2
def _no_setup(s): s["end_to_end"] = [m for m in s["end_to_end"]
                                     if m["name"] != "setup_s"]
def _pair_twice(s): s["workloads"].append(
    dict(s["workloads"][1], name="again"))
def _second_four_chip_cell(s): s["workloads"][1]["chips"] = 4
def _program_span_end_to_end(s): s["end_to_end"][0]["source"] = "program_span"
def _moves_nothing(s): s["per_layer"][0]["moves"] = "no_such_metric"
def _file_outside_paths(s): s["configs"][0]["file"] = "docs/mlp.json"
def _command_leaves_repo(s): s["command"][1] = "../run.py"
def _run_seconds(s): s["run_seconds"] = 52
def _extra_top_key(s): s["notes"] = "x"
def _config_unused(s): s["configs"].append(
    dict(s["configs"][0], name="spare", file="benchmark/configs/spare.json"))
def _long_why(s): s["workloads"][0]["why"] = "x" * 201
def _same_metric_twice(s): s["per_layer"].append(dict(s["per_layer"][0]))
def _cell_without_layer_metric(s):
    for m in s["per_layer"]:
        m["workloads"] = ["mlp-dp1"]


@pytest.mark.parametrize("edit", [
    _why_on_metric, _bound_too_wide, _no_setup, _pair_twice,
    _second_four_chip_cell, _program_span_end_to_end, _moves_nothing,
    _file_outside_paths, _command_leaves_repo, _run_seconds, _extra_top_key,
    _config_unused, _long_why, _same_metric_twice,
    _cell_without_layer_metric], ids=lambda f: f.__name__.lstrip("_"))
def test_what_the_driver_refuses_is_refused(edit):
    with pytest.raises(loader.SpecError):
        loader.validate(broken(edit))


@pytest.mark.parametrize("rehearse", [False, True])
def test_every_cell_finds_its_files(rehearse):
    spec = loader.load_spec(rehearse)
    for w in spec["workloads"]:
        cell = loader.load_cell(spec, w["name"])
        assert cell["job"]["chips"] == w["chips"]
        assert cell["family"].THROUGHPUT in cell["metrics"]["end_to_end"]
        assert "setup_s" in cell["metrics"]["end_to_end"]
        for name in cell["metrics"]["per_layer"]:
            assert callable(loader.load_module("metrics", name).read)


def test_config_files_keep_their_sources_and_list_what_they_assume():
    for c in SPEC["configs"]:
        held = loader.read_json(os.path.join(loader.ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"] == []
        assert held["assumed"]


def test_a_per_layer_metric_is_reported_only_where_what_it_moves_is():
    mine = loader.metrics_of(SPEC, "bert-base-seq128")
    assert "ring.kernel_ms_per_step" not in mine["per_layer"]
    assert "attention.kernel_ms_per_step" in mine["per_layer"]
    assert "samples_per_s_per_chip" not in mine["end_to_end"]


def test_files_under_paths_are_named_from_the_permitted_characters():
    for root, dirs, files in os.walk(loader.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), loader.ROOT)
            assert loader.PATH_RE.fullmatch(rel), rel
