"""ops.ring_cost: the per-stage pipeline cost model and the rebuilt
break-even table — pure math, so it is pinned exactly here (the TPU
artifacts consume it through bench_collective)."""

import pytest

from fpga_ai_nic_tpu.ops import ring_cost


def test_model_pipeline_vpu_binds():
    """Serial VPU: encode + decode - one skeleton; rdma hidden under it."""
    m = ring_cost.model_pipeline(
        {"skeleton": 1.0, "encode": 3.0, "decode": 4.0, "rdma": 2.0},
        full_s=6.5)
    assert m["valid"]
    assert m["terms_s"]["vpu"] == pytest.approx(6.0)   # 3 + 4 - 1
    assert m["binding_stage"] == "vpu"
    assert m["pipeline_efficiency"] == pytest.approx(6.0 / 6.5)
    assert m["model_rel_err"] == pytest.approx(0.5 / 6.0)


def test_model_pipeline_wire_binds():
    m = ring_cost.model_pipeline(
        {"skeleton": 0.5, "encode": 1.0, "decode": 1.0, "rdma": 9.0,
         "hbm": 4.0}, full_s=10.0)
    assert m["binding_stage"] == "rdma"
    assert m["modeled_s"] == pytest.approx(9.0)
    assert m["terms_s"]["hbm"] == pytest.approx(4.0)


def test_model_pipeline_skeleton_floor():
    """A stage can never predict a schedule faster than the bare loop —
    stage slopes below the skeleton clamp up to it."""
    m = ring_cost.model_pipeline(
        {"skeleton": 2.0, "encode": 2.1, "decode": 2.05, "rdma": 0.1})
    assert m["terms_s"]["rdma"] == pytest.approx(2.0)
    assert m["terms_s"]["vpu"] == pytest.approx(2.15)


def test_model_pipeline_invalid_inputs():
    """Non-positive slopes are unmeasured, never rates; a VPU-less set is
    flagged invalid and emits NO confident model numbers."""
    m = ring_cost.model_pipeline({"encode": -0.1, "decode": 0.0,
                                  "rdma": 3.0}, full_s=5.0)
    assert not m["valid"]
    assert "vpu" not in m["terms_s"]
    assert m["binding_stage"] == "rdma"    # still reports what it has
    assert "modeled_s" not in m and "pipeline_efficiency" not in m


def test_model_pipeline_partial_vpu_is_invalid():
    """One codec stage's slope drowned: the half-formed VPU term is kept
    as a labeled floor, but valid flips False and no modeled time or
    efficiency is fabricated from half the serial chain."""
    m = ring_cost.model_pipeline(
        {"skeleton": 1.0, "encode": 3.0, "decode": -1.0, "rdma": 2.0},
        full_s=6.0)
    assert not m["valid"] and m["vpu_partial"]
    assert m["terms_s"]["vpu"] == pytest.approx(3.0)
    assert "modeled_s" not in m and "pipeline_efficiency" not in m


def test_codec_rates_skeleton_corrected():
    """break_even ADDS the stage costs, so the per-stage rates it is fed
    must have the shared schedule skeleton subtracted — raw ablated
    rates would count it twice and understate the combined codec."""
    stages = {"skeleton": {"t_ms": 2.0}, "encode": {"t_ms": 6.0},
              "decode": {"t_ms": 10.0}}
    payload = 8 * 10**9 // 1000           # 8 GB/s at 1 ms per ms-unit
    enc, dec = ring_cost.codec_rates(stages, payload)
    assert enc == pytest.approx(payload / 4e-3 / 1e9)   # 6-2 ms
    assert dec == pytest.approx(payload / 8e-3 / 1e9)   # 10-2 ms
    # skeleton-bound stage: no honest asymptotic rate exists
    assert ring_cost.codec_rates(
        {"skeleton": {"t_ms": 5.0}, "encode": {"t_ms": 5.0},
         "decode": {"t_ms": 6.0}}, payload) == (0.0, 0.0)
    assert ring_cost.codec_rates({"encode": {"t_ms": 1.0}}, payload) == \
        (0.0, 0.0)


def test_decompose_stage_crash_keeps_full_rate():
    """A crashing stage variant (a fresh compile path, on budgeted chip
    time) costs that stage only: the full-pipeline rate is banked, the
    error recorded, and no confident model claim is made."""
    def measure(ab):
        if ab == "hbm":
            raise RuntimeError("mosaic compile boom")
        return {None: 10e-3}.get(ab, 2e-3)
    out = ring_cost.decompose(measure, streaming=True,
                              payload_bytes=1 << 20)
    assert out["pipeline_gbps"] > 0 and out["t_ms"] == pytest.approx(10.0)
    assert not out["valid"]
    assert "mosaic" in out["stage_errors"]["hbm"]
    assert "modeled_t_ms" not in out and "pipeline_efficiency" not in out


def test_break_even_serial_vpu_model():
    """The codec bound is the SUM 1/enc + 1/dec (shared VPU): equal
    stage rates of 30 GB/s combine to 15 GB/s, which wins at a 5 GB/s
    link (needs 10) and loses at 12.5 (needs 25) — under the old max()
    model both links would have (wrongly) looked winnable."""
    be = ring_cost.break_even(30.0, 30.0, 3.5, 3.76)
    assert be["combined_codec_gbps"] == pytest.approx(15.0)
    assert be["per_link_rate"]["link_5GBps"]["bfp_wins"]
    assert not be["per_link_rate"]["link_12.5GBps"]["bfp_wins"]
    assert be["per_link_rate"]["link_12.5GBps"][
        "required_codec_gbps_to_win"] == 25.0
    # wire-bound regime: speedup caps at r_fused/2
    fast = ring_cost.break_even(1e6, 1e6, 3.5, 3.76)
    for row in fast["per_link_rate"].values():
        assert row["bfp_speedup_vs_bf16_psum"] == pytest.approx(
            3.5 / 2, abs=0.01)


def test_break_even_zero_rates():
    be = ring_cost.break_even(0.0, 0.0, 3.5, 3.76)
    assert be["combined_codec_gbps"] == 0.0
    assert not any(r["bfp_wins"] for r in be["per_link_rate"].values())


def test_decompose_end_to_end():
    """decompose() against a fake measurement: stage rows, model fields,
    and the artifact-ready rounding all land."""
    times = {None: 10e-3, "skeleton": 1e-3, "encode": 3e-3,
             "decode": 4e-3, "rdma": 6e-3, "hbm": 5e-3}
    out = ring_cost.decompose(lambda ab: times[ab], streaming=True,
                              payload_bytes=12 * (1 << 20))
    assert out["valid"]
    assert set(out["stages"]) == set(ring_cost.STAGES_STREAMING)
    assert out["binding_stage"] == "vpu"              # 3+4-1 = 6.0 == rdma
    assert out["modeled_t_ms"] == pytest.approx(6.0)
    assert out["pipeline_efficiency"] == pytest.approx(0.6)
    assert out["t_ms"] == pytest.approx(10.0)
    assert out["pipeline_gbps"] == pytest.approx(
        12 * (1 << 20) / 10e-3 / 1e9, rel=1e-2)


def test_decompose_failed_full_measurement():
    out = ring_cost.decompose(
        lambda ab: -1.0 if ab is None else 1e-3, streaming=False,
        payload_bytes=1 << 20)
    assert not out["valid"]
    assert "error" in out and "pipeline_gbps" not in out
