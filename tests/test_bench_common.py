"""The benchmark supervisor (bench_common.run_attempt) — a hang here was
round 1's only failure mode, so the kill paths get direct tests: result
parsing, silence kill with forensic tail, budget kill, nonzero-exit
annotation, and the result-before-unclean-exit salvage — and the two rules
every chip run leans on: where the compile cache lives, and which peak a
device is held against."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_common import cpu_env, is_tpu_platform, run_attempt


def _cmd(body: str):
    return [sys.executable, "-u", "-c", body]


def test_returns_last_json_line():
    r = run_attempt("ok", _cmd(
        "print('[bench] phase=x')\n"
        "print('{\"value\": 1}')\n"
        "print('{\"value\": 2}')"), budget_s=30, silence_s=30)
    assert r == {"value": 2}


def test_silence_kill_carries_forensic_tail():
    with pytest.raises(RuntimeError) as e:
        run_attempt("hang", _cmd(
            "import time\n"
            "print('[bench] phase=import')\n"
            "print('[bench] phase=devices')\n"
            "time.sleep(60)"), budget_s=60, silence_s=2)
    msg = str(e.value)
    assert "silent for" in msg
    assert "phase=devices" in msg          # the hang is localizable


def test_budget_kill():
    with pytest.raises(RuntimeError) as e:
        run_attempt("slow", _cmd(
            "import time\n"
            "for i in range(100):\n"
            "    print(f'[bench] tick {i}', flush=True)\n"
            "    time.sleep(1)"), budget_s=3, silence_s=60)
    assert "total budget" in str(e.value)


def test_result_survives_unclean_exit():
    r = run_attempt("dirty", _cmd(
        "import sys\n"
        "print('{\"value\": 7}')\n"
        "sys.exit(3)"), budget_s=30, silence_s=30)
    assert r["value"] == 7
    assert "rc=3" in r["unclean_exit"]


def test_no_json_failure_raises_with_tail():
    with pytest.raises(RuntimeError) as e:
        run_attempt("nojson", _cmd("print('only noise'); raise SystemExit(1)"),
                    budget_s=30, silence_s=30)
    assert "only noise" in str(e.value)


def test_cpu_env_forces_platform_and_device_count():
    env = cpu_env(8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # replaces (not appends to) an inherited count; restore the
    # conftest-set value afterwards
    saved = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    try:
        env2 = cpu_env(8)
        assert "device_count=2" not in env2["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=8" in env2["XLA_FLAGS"]
    finally:
        if saved is None:
            del os.environ["XLA_FLAGS"]
        else:
            os.environ["XLA_FLAGS"] = saved


def test_is_tpu_platform():
    assert is_tpu_platform("tpu")
    assert not is_tpu_platform("cpu") and not is_tpu_platform("gpu")


def test_save_artifact_provenance(tmp_path, monkeypatch):
    """Every artifact must carry the provenance that makes a perf claim
    checkable: timestamp, git sha, argv — the round-2 lesson codified."""
    import json

    import bench_common
    monkeypatch.setattr(os.path, "dirname", os.path.dirname)
    # redirect the artifacts dir by pointing the module's file anchor
    monkeypatch.setattr(bench_common, "__file__",
                        str(tmp_path / "bench_common.py"))
    path = bench_common.save_artifact("unittest", {"value": 42})
    assert os.path.dirname(path) == str(tmp_path / "artifacts")
    with open(path) as f:
        d = json.load(f)
    assert d["value"] == 42
    prov = d["_provenance"]
    assert len(prov["git_sha"]) >= 7 or prov["git_sha"] == "unknown"
    assert "timestamp_utc" in prov and "argv" in prov


@pytest.fixture
def cache_dir_config():
    """jax_compilation_cache_dir as the test found it, restored after."""
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_dir_is_left_to_the_environment(monkeypatch,
                                                      cache_dir_config):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, no code path
    sets a directory."""
    import bench_common
    cache_dir_config.update("jax_compilation_cache_dir", "/placed/outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    bench_common.enable_compile_cache()
    assert cache_dir_config.jax_compilation_cache_dir == "/placed/outside"


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch,
                                                    cache_dir_config):
    """Unset: `<checkout>/.jax_cache` — a fixed path (it is part of the
    cache key), with no temp name, pid or timestamp in it."""
    import bench_common
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    bench_common.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache_dir_config.jax_compilation_cache_dir == os.path.join(
        root, ".jax_cache")


def test_compile_cache_counts_are_live(monkeypatch, cache_dir_config):
    """The returned counts follow jax's own cache events — what lets a
    second chip_smoke.py run report its hits."""
    import jax

    import bench_common
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    counts = bench_common.enable_compile_cache()
    assert counts == {"hits": 0, "misses": 0}
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert counts == {"hits": 2, "misses": 1}


def test_peaks_are_keyed_by_device_kind():
    """The v5e's published peaks under the name jax reports for it."""
    import bench_common
    peak, label = bench_common.bf16_peak("TPU v5 lite")
    assert peak == 197e12 and "TPU v5 lite" in label and "197" in label
    peak, label = bench_common.hbm_peak("TPU v5 lite")
    assert peak == 819e9 and "819" in label


@pytest.mark.parametrize("peak_fn", ["bf16_peak", "hbm_peak"])
@pytest.mark.parametrize("kind", ["TPU v99", "cpu", ""])
def test_unknown_device_kind_has_no_peak(peak_fn, kind):
    """No rate is assumed for a device that is not in the table: a
    utilization against the wrong peak is worse than none."""
    import bench_common
    with pytest.raises(KeyError, match="no published peak"):
        getattr(bench_common, peak_fn)(kind)
