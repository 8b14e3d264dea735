"""chip_smoke.py off the chip: its phases at a tiny width on the CPU mesh
(Pallas kernels interpreted), so a wrong path, argument or check is found
here and not at the cost of a chip call — and its refusal to run, as a
program, where jax finds no TPU."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(width=128, layers=2, batch_per_chip=64, steps=3, on_chip=False)


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_train_phase_matches_plain_sgd():
    chip_smoke.phase_train(**TINY)


def test_train_phase_fails_on_a_wrong_update(monkeypatch):
    """The first-step check has teeth: a reference that took a step twice
    as long is told apart from the trainer's."""
    real = chip_smoke.reference_sgd_step

    def doubled(params, batch, mcfg):
        loss, w, w_new = real(params, batch, mcfg)
        return loss, w, w + 2.0 * (w_new - w)

    monkeypatch.setattr(chip_smoke, "reference_sgd_step", doubled)
    with pytest.raises(AssertionError, match="master update"):
        chip_smoke.phase_train(**TINY)


def test_on_chip_mode_refuses_the_fallback_ring():
    """With on_chip=True a step that reroutes to the separate-op ring —
    what fused_kernel=True does off the TPU — cannot pass: its warning is
    an error, and where it was already spent the HLO holds no kernel."""
    with pytest.raises((UserWarning, AssertionError),
                       match="fused_kernel=True|kernels did not run"):
        chip_smoke.phase_train(**dict(TINY, on_chip=True))


def test_loopback_phase_matches_the_goldens():
    chip_smoke.phase_loopback(total_bytes=4 * 3 * 2048 * 4, slice_elems=2048,
                              on_chip=False)


def test_codec_phase_on_a_ragged_grid():
    chip_smoke.phase_codec(70 * 2048, on_chip=False)


def test_four_chip_phase_on_four_virtual_devices():
    """--chips 4's path needs exactly four devices; the suite's mesh has
    eight, so this one runs in a process of its own."""
    r = _run(["-c", "import chip_smoke; chip_smoke.phase_dp("
              "4, width=128, layers=2, batch_per_chip=64, steps=3, "
              "on_chip=False)"],
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "mesh device ids [0, 1, 2, 3]" in r.stdout
    assert "params bit-identical on all 4 devices" in r.stdout
    assert "ring vs xla" in r.stdout


def test_four_chip_phase_refuses_another_device_count():
    with pytest.raises(AssertionError, match="needs exactly 4"):
        chip_smoke.phase_dp(4, **TINY)       # the suite's mesh has eight


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_program_refuses_to_run_without_a_chip(args):
    r = _run(["chip_smoke.py", *args])
    assert r.returncode != 0
    assert r.stdout == ""                    # no result line, no phase ran
    assert "no TPU" in r.stderr
