"""The multi-chip conversion kit must stay runnable: a broken kit turns
the first real >=2-chip window into a debugging session instead of
evidence (round-5 verdict item 7).  The dryrun canary runs the full
parity checks (XLA psum + fused-vs-XLA BFP ring bit-exactness) on the
virtual mesh in a subprocess, exactly as `make multichip-dryrun` would."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_canary_passes(tmp_path):
    env = dict(os.environ)
    # state/artifacts isolated so the test never touches banked evidence
    env["MULTICHIP_DRYRUN"] = "1"
    p = subprocess.run(
        [sys.executable, "-u",
         os.path.join(REPO, "tools", "multichip_bench.py"),
         "--child", "canary"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    res = json.loads(line)
    assert res["ok"], res
    assert res["checks"]["psum_parity"]["ok"]
    assert res["checks"]["fused_bfp_ring_parity"]["bit_exact"]


def test_stage_selection_skips_unlisted(monkeypatch, tmp_path):
    """--stages= must restrict the ladder (the CI hook runs canary only)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mcb", os.path.join(REPO, "tools", "multichip_bench.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    calls = []
    monkeypatch.setattr(m, "run_attempt",
                        lambda name, *a, **k: calls.append(name) or
                        {"ok": True})
    monkeypatch.setattr(m, "save_artifact", lambda *a, **k: None)
    monkeypatch.setattr(m, "STATE_PATH", str(tmp_path / "state.json"))
    monkeypatch.setattr(sys, "argv",
                        ["multichip_bench.py", "--dryrun", "--force",
                         "--stages=canary"])
    assert m.main() == 0
    assert calls == ["canary"]


def test_stage_selection_rejects_unknown(monkeypatch, tmp_path):
    """A typo'd --stages must error, not 'complete' a zero-stage ladder."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mcb2", os.path.join(REPO, "tools", "multichip_bench.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    monkeypatch.setattr(m, "STATE_PATH", str(tmp_path / "state.json"))
    monkeypatch.setattr(sys, "argv",
                        ["multichip_bench.py", "--dryrun",
                         "--stages=busbwz"])
    assert m.main() == 2
    monkeypatch.setattr(sys, "argv",
                        ["multichip_bench.py", "--dryrun", "--stages="])
    assert m.main() == 2


def test_filtered_force_preserves_other_stages(monkeypatch, tmp_path):
    """--force --stages=busbw must clear only busbw: wiping the banked
    canary would make the filtered re-run refuse to escalate."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mcb3", os.path.join(REPO, "tools", "multichip_bench.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    calls = []
    monkeypatch.setattr(m, "run_attempt",
                        lambda name, *a, **k: calls.append(name) or
                        {"ok": True})
    monkeypatch.setattr(m, "save_artifact", lambda *a, **k: None)
    monkeypatch.setattr(m, "STATE_PATH", str(tmp_path / "state.json"))
    m._save_state({"dryrun": {"canary": {"ok": True},
                              "busbw": {"ok": True}}})
    monkeypatch.setattr(sys, "argv",
                        ["multichip_bench.py", "--dryrun", "--force",
                         "--stages=busbw"])
    assert m.main() == 0
    assert calls == ["busbw"]                     # canary stayed banked
    assert m._load_state()["dryrun"]["canary"]["ok"]


@pytest.mark.slow
def test_zoo_configs_validate_on_cpu():
    """Every zoo config must trace cleanly off-hardware (zoo --validate):
    a config bug discovered on the chip costs chip time — this caught a
    real one in round 5 (resnet50(dtype=...) didn't exist)."""
    from bench_common import cpu_env
    p = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "tools", "zoo_tpu.py"),
         "--validate"],
        env=cpu_env(1), cwd=REPO, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = [l for l in p.stdout.splitlines() if '"validated"' in l][-1]
    res = json.loads(line)
    assert res["failed"] == [], res
