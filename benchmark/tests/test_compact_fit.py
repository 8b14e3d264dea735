"""`moe.compact_fit_pct` (PR 37): through each expert family's own `routing`
on a tiny model of its program — 100 where every layer's rows fit the compact
program's capacity, 0 where the capacity is forced below them and the
conditional takes the full program — and on hand-made runs: the share of the
layers that fit, and nothing — no raise — on a program whose `routing_stats`
counts no `fit`, which is what the reader meets when it is laid over the
parent's checkout."""

import types

import numpy as np
import pytest

from benchmark import loader

READER = loader.load_module("metrics", "moe.compact_fit_pct")
CELLS = ["glm47-flash-ep8share-s4096", "lfm2-24b-ep8share-s8192"]

# the program's tests' tiny widths: 2 experts held of a router's 8, top-4
TINY = {
    "glm47-flash-ep8share-s4096": dict(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=2, n_routed_experts=2, router_width=8, ep_size=4,
        ep_rank=1, num_hidden_layers=3, q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        vocab_size=128, compute_dtype="float32", attn_block=256),
    "lfm2-24b-ep8share-s8192": dict(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=24,
        num_attention_heads=4, num_key_value_heads=2, num_experts=2,
        router_width=8, ep_size=4, ep_rank=1, vocab_size=128,
        compute_dtype="float32", attn_block=256),
}
# 2 x 1,024 tokens x top-4 = 8,192 assignments, ~2,048 of them on the two
# experts held: C = 4,096 as the program has it, 512 with no slack at all
JOB = dict(batch_per_chip=2, seq_len=1024, reference_block=2)


def _tiny_run(name):
    import jax
    cell = loader.load_cell(loader.load_spec(), name)
    config = dict(cell["config"], **TINY[name])
    job = dict(cell["job"], **JOB)
    return types.SimpleNamespace(
        trace=None, family=cell["family"], config=config, job=job,
        trainer=types.SimpleNamespace(cfg=types.SimpleNamespace(seed=3)),
        batch=cell["family"].make_batch(jax.random.PRNGKey(4), config, job))


@pytest.mark.parametrize("name", CELLS)
def test_every_layer_fits_under_the_seed_s_router(name):
    run = _tiny_run(name)
    assert READER.read(run) == 100.0
    stats = run.family.routing(run)
    assert (stats["capacity"] == 4096).all()
    assert (stats["rows"].sum(axis=1) < 4096).all()
    assert (stats["dropped"] == 0).all()


@pytest.mark.parametrize("name", CELLS)
def test_a_forced_overflow_reads_zero_and_drops_nothing(monkeypatch, name):
    from fpga_ai_nic_tpu.ops import moe
    monkeypatch.setattr(moe, "SLACK", 1e-9)        # C = one block of 512
    run = _tiny_run(name)
    assert READER.read(run) == 0.0
    stats = run.family.routing(run)
    assert (stats["capacity"] == 512).all()
    assert (stats["rows"].sum(axis=1) > 512).all()
    assert (stats["dropped"] == 0).all()


def _run(routing):
    return types.SimpleNamespace(
        trace=None, family=types.SimpleNamespace(routing=lambda run: routing))


@pytest.mark.parametrize("fit,pct", [
    ([1.0, 1.0, 1.0, 1.0], 100.0), ([1.0, 0.0, 1.0, 1.0], 75.0),
    ([0.0, 0.0, 0.0, 0.0], 0.0)])
def test_share_of_the_layers_that_fit(fit, pct):
    routing = {"held_share": np.full(4, 0.125),
               "fit": np.array(fit, np.float32)}
    assert READER.read(_run(routing)) == pct


def test_nothing_where_the_program_counts_no_fit():
    assert READER.read(_run({"held_share": np.full(4, 0.125)})) is None


def test_entry_names_the_expert_cells_and_their_rate():
    spec = loader.load_spec()
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "moe.compact_fit_pct")
    assert spec["per_layer"][-1] is entry          # appended, nothing moved
    assert entry["workloads"] == CELLS
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert entry["moves"] == "tokens_per_s_per_chip"
    held = next(m for m in spec["per_layer"]
                if m["name"] == "moe.held_share_pct")
    assert entry["layer"] == held["layer"]
    for name in CELLS:
        cell = loader.load_cell(spec, name)
        assert "moe.compact_fit_pct" in cell["metrics"]["per_layer"]
