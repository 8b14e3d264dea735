#!/usr/bin/env python
"""graftlint CLI — the repo's static-analysis entry point (`make lint`).

Planes (docs/LINT.md):
  --ast     AST rules R1–R5 over the package/tools/bench tree (no jax
            import; sub-second)
  --jaxpr   jaxpr invariant sweep J1–J13: codec x trainer x obs grid traced
            abstractly on the 8-device virtual CPU mesh (no TPU)
  --ext     ruff + mypy on the strict core, when installed (skipped with a
            notice otherwise — the container may not carry them)
  --mc      graftmc (docs/MODELCHECK.md): the exhaustive protocol model
            checker over the flat/streaming/hier/reshard op streams
            (n<=6, S<=6, D<=4 per route + n=8 fuzz; violations export
            Perfetto counterexamples to artifacts/) plus the H1
            happens-before/lockset pass.  Pure Python — no jax.  This is
            `make modelcheck`, NOT part of the default plane set (CI runs
            it as its own step between lint and obs-gate).

Default is ast+ext+jaxpr.  Exit status: nonzero iff any unsuppressed
finding (or external linter failure) is present.

CPU-only by construction: the jaxpr plane must never wait on a TPU
window, so the environment is pinned before jax ever loads.
"""

import argparse
import os
import re
import subprocess
import sys

# Pin the virtual CPU mesh BEFORE any jax import (same contract as
# tests/conftest.py; the sweep needs exactly 8 host devices).  This runs
# at module import, ahead of the fpga_ai_nic_tpu import below.
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags.strip() + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from fpga_ai_nic_tpu.lint import default_targets, lint_paths  # noqa: E402

# the strict typed core for ruff (mypy reads its own scope from
# pyproject [tool.mypy] files= — invoked bare so the two cannot drift)
STRICT_CORE = ["fpga_ai_nic_tpu/compress", "fpga_ai_nic_tpu/obs",
               "fpga_ai_nic_tpu/utils/config.py",
               "fpga_ai_nic_tpu/utils/checkpoint.py",
               "fpga_ai_nic_tpu/runtime/queue.py",
               "fpga_ai_nic_tpu/parallel/reshard.py",
               "fpga_ai_nic_tpu/tune",
               "fpga_ai_nic_tpu/verify",
               "fpga_ai_nic_tpu/serve",
               "fpga_ai_nic_tpu/runtime/requests.py"]


def run_ast(paths) -> int:
    findings = lint_paths(paths)
    live = [f for f in findings if not f.suppressed]
    for f in findings:
        print(f.format())
    n_sup = sum(f.suppressed for f in findings)
    print(f"[graftlint:ast] {len(paths)} files, {len(live)} findings"
          f" ({n_sup} suppressed)")
    return 1 if live else 0


def run_jaxpr() -> int:
    from fpga_ai_nic_tpu.lint import jaxpr_sweep
    findings = jaxpr_sweep.run_sweep(verbose=True)
    for f in findings:
        print(f.format())
    print(f"[graftlint:jaxpr] {len(findings)} findings")
    return 1 if findings else 0


def run_ext() -> int:
    """ruff (pycodestyle/pyflakes subset) + mypy on the strict core.
    Both are OPTIONAL in this container: absence is a notice, not a
    failure.  Diagnostics are ADVISORY by default and blocking under
    GRAFTLINT_EXT_STRICT=1 — the strict core's annotation claim was
    audited by AST, but mypy itself has never executed in this
    container, and a first-ever mypy run must not be able to take CI
    down inside a hard gate (round-review finding).  Flip CI to strict
    after one green run with the tools installed."""
    strict = os.environ.get("GRAFTLINT_EXT_STRICT") == "1"
    rc = 0
    # rule selection AND mypy's file scope live in pyproject
    # ([tool.ruff.lint] / [tool.mypy] files=) — no CLI duplicates that
    # would silently override or drift from the config
    for tool, args in (("ruff", ["check"] + STRICT_CORE),
                       ("mypy", [])):
        try:
            proc = subprocess.run([tool] + args, cwd=REPO)
        except FileNotFoundError:
            print(f"[graftlint:ext] {tool} not installed — skipped "
                  "(install to tighten the gate; CI images carry it)")
            continue
        if proc.returncode != 0:
            if strict:
                print(f"[graftlint:ext] {tool} FAILED")
                rc = 1
            else:
                print(f"[graftlint:ext] {tool} reported findings "
                      "(advisory; set GRAFTLINT_EXT_STRICT=1 to gate)")
        else:
            print(f"[graftlint:ext] {tool} clean")
    return rc


# State-explosion tripwire: a corpus that blows past this wall time
# fails loudly even before the banked-artifact gate sees it (the whole
# corpus runs in ~4 s today; 120 s is ~30x headroom, not a perf SLO).
MC_WALL_BUDGET_S = float(os.environ.get("GRAFTMC_WALL_BUDGET_S", "120"))


def run_mc() -> int:
    """graftmc: the exhaustive protocol corpus + the H1 lockset pass
    (`make modelcheck`).  GRAFTMC_FIXTURE names a mutated-model fixture
    module whose violation MUST surface (the J7-style anti-vacuity
    hook); any violation leaves a pretty-printed + Perfetto
    counterexample pair under artifacts/.  Every run banks its envelope
    (per-route cell counts, states, POR reduction, wall time) as
    artifacts/mc_envelope_*.json — `make modelcheck` snapshots the
    newest into MC_ENVELOPE_r*.json, and obs-gate's mc.* keys hold
    future runs to it two-sided (a silent envelope shrink is a CI
    failure, not a diff nobody reads)."""
    from fpga_ai_nic_tpu.verify import mc as graftmc
    from fpga_ai_nic_tpu.verify.lockset import run_lockset
    from fpga_ai_nic_tpu.lint.findings import Finding
    cdir = os.path.join(REPO, "artifacts")
    fixture = os.environ.get("GRAFTMC_FIXTURE")
    # GRAFTMC_SKIP_CORPUS=1 is honored ONLY alongside a fixture: the
    # per-fixture exit-code test battery re-runs --mc once per mutant
    # and must not pay the (separately green-tested) corpus each time.
    # A bare --mc can never skip the corpus — that would be a silently
    # vacuous gate.
    skip_corpus = (fixture is not None
                   and os.environ.get("GRAFTMC_SKIP_CORPUS") == "1")
    if skip_corpus:
        print("[graftmc] corpus SKIPPED (fixture-only run)")
        findings, stats = [], graftmc.CorpusStats()
    else:
        findings, stats = graftmc.run_corpus(emit=print,
                                             counterexample_dir=cdir)
    if fixture:
        findings += graftmc.run_fixture(fixture, counterexample_dir=cdir)
    if stats.wall_s > MC_WALL_BUDGET_S:
        findings.append(Finding(
            "M1", "<mc:budget>", 0,
            f"corpus wall time {stats.wall_s:.1f}s exceeds the "
            f"{MC_WALL_BUDGET_S:.0f}s explosion budget — a state-space "
            "regression, not a slow machine (raise "
            "GRAFTMC_WALL_BUDGET_S only with a banked justification)"))
    h1 = run_lockset(repo_root=REPO)
    findings += h1
    for f in findings:
        print(f.format())
    live = [f for f in findings
            if not getattr(f, "suppressed", False)]
    for cmp in stats.compare:
        print(f"[graftmc] POR reduction on flat{cmp['cell']}: "
              f"{cmp['reduction']:.1f}x ({cmp['por_states']} vs "
              f"{cmp['naive_states']} states), verdicts "
              f"{'agree' if cmp['agree'] else 'DISAGREE'}")
    record = graftmc.envelope_record(stats)
    record["wall_budget_s"] = MC_WALL_BUDGET_S
    record["ok"] = not live
    if skip_corpus:
        pass                  # no envelope to bank from a fixture-only run
    elif os.environ.get("GRAFTMC_NO_BANK") != "1":
        # GRAFTMC_NO_BANK=1: the exit-code test battery runs --mc many
        # times per pytest session and must not litter artifacts/
        from bench_common import save_artifact
        path = save_artifact("mc_envelope", record)
        print(f"[graftmc] envelope banked: {path}")
    print(f"[graftmc] {stats.cells} cells exhaustive "
          f"({stats.states} states, {stats.branch_points} branch "
          f"points), {stats.fuzz_runs} fuzz runs, "
          f"{len(h1)} lockset findings, {len(live)} findings total")
    return 1 if live else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ast", action="store_true", help="AST plane only")
    ap.add_argument("--jaxpr", action="store_true", help="jaxpr plane only")
    ap.add_argument("--ext", action="store_true",
                    help="external linters (ruff/mypy) only")
    ap.add_argument("--mc", action="store_true",
                    help="graftmc protocol model check + lockset pass "
                         "(= make modelcheck; not in the default set)")
    ap.add_argument("paths", nargs="*",
                    help="explicit files for the AST plane (default: the "
                         "package + tools + bench drivers + examples)")
    args = ap.parse_args(argv)
    planes = {p for p in ("ast", "jaxpr", "ext", "mc")
              if getattr(args, p)}
    if not planes:
        planes = {"ast", "jaxpr", "ext"}
    rc = 0
    if "ast" in planes:
        paths = args.paths or default_targets(REPO)
        rc |= run_ast(paths)
    if "ext" in planes:
        rc |= run_ext()
    if "mc" in planes:
        rc |= run_mc()
    if "jaxpr" in planes:
        rc |= run_jaxpr()
    print("[graftlint] " + ("FAIL" if rc else "OK"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
