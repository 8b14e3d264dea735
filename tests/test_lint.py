"""graftlint test battery.

Three layers:

1. Fixture corpus (`tests/lint_fixtures/`): every rule R1–R5 (plus the
   R0 suppression hygiene rule) fires on its bad fixture and stays
   silent on the good one, linted AT the package destination the
   acceptance criterion names ("copied into the package").
2. End-to-end: `tools/graftlint.py --ast` exits 0 on HEAD and nonzero
   with any single bad fixture physically copied into the package.
3. jaxpr sweep: the codec x trainer x obs grid is registry-driven
   (a future codec is auto-covered), green on HEAD, and each invariant
   checker (J1–J4) demonstrably detects a violation.
"""

import os
import shutil
import subprocess
import sys

import pytest

from fpga_ai_nic_tpu.lint import default_targets, lint_paths, lint_source
from fpga_ai_nic_tpu.lint.findings import AST_CODES, RULE_DOCS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

# where each fixture would land if copied into the package: R4 is scoped
# to ops//parallel/, R5 to tools//bench writers, the rest fire anywhere
DEST = {
    "r0": "fpga_ai_nic_tpu",
    "r1": "fpga_ai_nic_tpu/runtime",
    "r2": "fpga_ai_nic_tpu",
    "r3": "fpga_ai_nic_tpu/ops",
    "r4": "fpga_ai_nic_tpu/parallel",
    "r5": "tools",
    "r6": "fpga_ai_nic_tpu/runtime",
}
EXPECT_CODE = {"r0": "R0", "r1": "R1", "r2": "R2", "r3": "R3",
               "r4": "R4", "r5": "R5", "r6": "R6"}


def _fixture(rule, kind):
    with open(os.path.join(FIXTURES, f"{rule}_{kind}.py")) as fh:
        return fh.read()


def _live(findings):
    return [f for f in findings if not f.suppressed]


class TestFixtureCorpus:
    @pytest.mark.parametrize("rule", sorted(DEST))
    def test_bad_fixture_fires(self, rule):
        dest = os.path.join(DEST[rule], f"zz_{rule}.py")
        live = _live(lint_source(dest, _fixture(rule, "bad")))
        codes = {f.code for f in live}
        assert EXPECT_CODE[rule] in codes, (rule, live)
        # the bad fixture must be bad for exactly the documented reason
        # (plus R2 riders in the R0 fixture, whose hazards are unsuppressed)
        allowed = {EXPECT_CODE[rule]} | ({"R2"} if rule == "r0" else set())
        assert codes <= allowed, (rule, codes)

    @pytest.mark.parametrize("rule", sorted(DEST))
    def test_good_fixture_silent(self, rule):
        dest = os.path.join(DEST[rule], f"zz_{rule}.py")
        assert _live(lint_source(dest, _fixture(rule, "good"))) == [], rule

    def test_every_ast_rule_has_both_fixtures(self):
        # R0..R5 all covered; adding a rule without a corpus entry
        # fails.  H1 is the lockset pass (verify/lockset.py, suppressible
        # like any AST rule hence in AST_CODES): its engine is not
        # engine.RULES, so its fire/silent battery lives in
        # tests/test_verify.py — only the fixture pair is checked here.
        assert set(EXPECT_CODE.values()) | {"H1"} == set(AST_CODES)
        for rule in list(DEST) + ["h1"]:
            for kind in ("bad", "good"):
                assert os.path.exists(
                    os.path.join(FIXTURES, f"{rule}_{kind}.py")), (rule, kind)


class TestSuppression:
    SRC = ("import time\nimport jax\n\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    t = time.time(){}\n"
           "    return x + t\n")

    def test_reasoned_suppression_suppresses_but_reports(self):
        fs = lint_source("fpga_ai_nic_tpu/zz.py", self.SRC.format(
            "    # graftlint: disable=R2 -- deliberate trace stamp"))
        assert _live(fs) == []
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].code == "R2"
        assert "deliberate trace stamp" in sup[0].suppress_reason

    def test_suppression_without_reason_is_an_error(self):
        fs = lint_source("fpga_ai_nic_tpu/zz.py",
                         self.SRC.format("    # graftlint: disable=R2"))
        codes = {f.code for f in _live(fs)}
        assert codes == {"R0", "R2"}   # reasonless disable suppresses nothing

    def test_unknown_code_is_an_error(self):
        fs = lint_source("fpga_ai_nic_tpu/zz.py", self.SRC.format(
            "    # graftlint: disable=R7 -- misremembered code"))
        assert "R0" in {f.code for f in _live(fs)}

    def test_file_wide_disable(self):
        src = ("# graftlint: disable-file=R2 -- probe tool stamps times\n"
               + self.SRC.format(""))
        assert _live(lint_source("fpga_ai_nic_tpu/zz.py", src)) == []

    def test_wrong_code_does_not_suppress(self):
        fs = lint_source("fpga_ai_nic_tpu/zz.py", self.SRC.format(
            "    # graftlint: disable=R1 -- wrong rule entirely"))
        assert "R2" in {f.code for f in _live(fs)}


class TestReviewBlindSpots:
    """Regression cases for holes the round's code review found."""

    def test_r2_sees_through_dotted_and_aliased_imports(self):
        # `import os.path` binds `os`; `import numpy.random as npr`
        # binds the dotted module — both used to blind the hazard check
        src = ("import os.path\n"
               "import numpy.random as npr\n"
               "import jax\n\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    if os.environ.get('SCALE'):\n"
               "        x = x * 2\n"
               "    return x + npr.standard_normal(3).sum()\n")
        codes = [f.code for f in _live(lint_source("fpga_ai_nic_tpu/zz.py",
                                                   src))]
        assert codes and set(codes) == {"R2"} and len(codes) >= 2

    def test_r4_nested_def_guard_is_not_a_gate(self):
        src = ("import jax\n"
               "def hot(x):\n"
               "    def helper(y):\n"
               "        if y is None:\n"
               "            return None\n"
               "        return y\n"
               "    return jax.pure_callback(lambda v: v,\n"
               "        jax.ShapeDtypeStruct(x.shape, x.dtype), x)\n")
        fs = _live(lint_source("fpga_ai_nic_tpu/ops/zz.py", src))
        assert [f.code for f in fs] == ["R4"]

    def test_r1_collective_handle_restricted_to_collective_fields(self):
        src = ("def f(self):\n"
               "    self.profiler.collectives.recoveries += 1\n"
               "    self.profiler.recovery.recoveries += 1\n")
        fs = _live(lint_source("fpga_ai_nic_tpu/zz.py", src))
        # only the recovery-handle mutation is a finding: 'recoveries'
        # is not a CollectiveStats field
        assert len(fs) == 1 and fs[0].code == "R1" and fs[0].line == 3


class TestEmbeddedSources:
    def test_embedded_child_script_is_linted(self):
        src = ('CHILD_SRC = r"""\n'
               "import json\n"
               "rows = []\n"
               'out = {}\n'
               'out["value"] = max((r.get("gbps") for r in rows), default=0)\n'
               "print(json.dumps(out))\n"
               '"""\n'
               "def run():\n"
               "    return CHILD_SRC\n")
        live = _live(lint_source("tools/zz.py", src))
        assert [f.code for f in live] == ["R5"]
        assert "embedded CHILD_SRC" in live[0].message
        # line must point at the offending FILE line: the string opens on
        # line 1 and the max(..., default=0) is embedded content line 5,
        # i.e. file line 5 (off-by-one found by the round review)
        assert live[0].line == 5, live[0]


class TestTreeIsClean:
    def test_default_targets_lint_green(self):
        findings = _live(lint_paths(default_targets(REPO)))
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_default_targets_cover_the_stack(self):
        targets = {os.path.relpath(p, REPO) for p in default_targets(REPO)}
        for must in ("fpga_ai_nic_tpu/ops/ring.py",
                     "fpga_ai_nic_tpu/parallel/train.py",
                     "fpga_ai_nic_tpu/runtime/queue.py",
                     "tools/chaos_bench.py", "bench_collective.py"):
            assert must in targets, must


def _run_graftlint(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")]
        + list(args), cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)


class TestMakeLintExitCodes:
    def test_ast_plane_green_on_head(self):
        proc = _run_graftlint("--ast")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    @pytest.mark.parametrize("rule", sorted(DEST))
    def test_bad_fixture_copied_into_package_fails(self, rule):
        dest_dir = os.path.join(REPO, DEST[rule])
        dest = os.path.join(dest_dir, f"zz_graftlint_fixture_{rule}.py")
        shutil.copyfile(os.path.join(FIXTURES, f"{rule}_bad.py"), dest)
        try:
            proc = _run_graftlint("--ast")
            assert proc.returncode != 0, proc.stdout + proc.stderr
            assert EXPECT_CODE[rule] + ":" in proc.stdout
        finally:
            os.remove(dest)


# ---------------------------------------------------------------------------
# plane 2 — jaxpr invariant sweep
# ---------------------------------------------------------------------------

class TestJaxprSweep:
    def test_grid_covers_every_registered_codec(self):
        from fpga_ai_nic_tpu.compress import available_codecs
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _TRAINERS, sweep_grid
        grid = sweep_grid()
        codecs = {c for c, _, _ in grid}
        assert codecs == {None} | set(available_codecs())
        trainers = {t for _, t, _ in grid}
        assert trainers == set(_TRAINERS) == {
            "DPTrainer", "FSDPTrainer", "QueuedDDPTrainer"}
        for c in codecs:
            for t in trainers:
                assert {(c, t, False), (c, t, True)} <= set(grid)

    def test_sweep_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_sweep
        findings = run_sweep()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_unconstructible_codec_fails_loudly(self):
        """A registered codec the sweep cannot build must surface as J6
        findings, never a silent skip (the coverage criterion)."""
        from fpga_ai_nic_tpu.compress import base as cbase
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_sweep, sweep_grid

        class Broken:   # not even a Codec: get_codec() raises TypeError
            name = "zz_broken_lint"

            def __init__(self):
                raise TypeError("deliberately unconstructible")

        cbase._REGISTRY["zz_broken_lint"] = Broken
        try:
            assert any(c == "zz_broken_lint" for c, _, _ in sweep_grid())
            findings = run_sweep()
            j6 = [f for f in findings if f.code == "J6"
                  and "zz_broken_lint" in f.path]
            assert len(j6) == 6, findings   # 3 trainers x 2 obs, all loud
        finally:
            del cbase._REGISTRY["zz_broken_lint"]

    # -- each invariant checker detects a violation -------------------------

    def _dp_phases(self, codec="bfp", obs=False):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _trace_dp
        from fpga_ai_nic_tpu.utils.config import (CollectiveConfig,
                                                  MeshConfig, TrainConfig)
        cfg = TrainConfig(mesh=MeshConfig(dp=8),
                          collective=CollectiveConfig(impl="ring",
                                                      codec=codec),
                          global_batch=64, obs_metrics=obs)
        return _trace_dp(cfg, "dp")

    def test_j1_detects_ungated_callback(self):
        import jax
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell

        def leaky(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        jx = jax.make_jaxpr(jax.jit(leaky))(
            jax.ShapeDtypeStruct((4,), "float32"))
        fs = _check_cell("cell", "DPTrainer", None, False,
                         [("step", jx, {})], None, 8, ("dp",))
        assert [f.code for f in fs] == ["J1"]

    def test_j1_detects_vanished_tap(self):
        # obs=True with zero callbacks = the tap plumbing silently died
        import jax
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell
        jx = jax.make_jaxpr(lambda x: x + 1)(
            jax.ShapeDtypeStruct((4,), "float32"))
        fs = _check_cell("cell", "DPTrainer", None, True,
                         [("step", jx, {})], None, 8, ("dp",))
        assert [f.code for f in fs] == ["J1"]

    def test_j2_detects_f64_leak(self):
        import jax
        import jax.numpy as jnp
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell
        with jax.enable_x64(True):
            jx = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64) * 2.0)(
                jax.ShapeDtypeStruct((4,), "float32"))
        fs = _check_cell("cell", "DPTrainer", None, False,
                         [("step", jx, {})], None, 8, ("dp",))
        assert "J2" in {f.code for f in fs}

    def test_j3_detects_lost_donation(self):
        import jax
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell
        jx = jax.make_jaxpr(jax.jit(lambda s, b: s + b))(
            jax.ShapeDtypeStruct((4,), "float32"),
            jax.ShapeDtypeStruct((4,), "float32"))   # nothing donated
        fs = _check_cell("cell", "DPTrainer", None, False,
                         [("step", jx, {"n_donate": 1})], None, 8, ("dp",))
        assert [f.code for f in fs] == ["J3"]

    def test_j4_detects_wire_mismatch(self):
        phases, L, n = self._dp_phases(codec="bfp")
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell
        ok = _check_cell("cell", "DPTrainer", "bfp", False, phases, L, n,
                         ("dp",))
        assert ok == []
        bad = _check_cell("cell", "DPTrainer", "bfp", False, phases,
                          2 * L, n, ("dp",))   # declared bytes now double
        assert [f.code for f in bad] == ["J4"]

    def test_j4_cond_branches_are_not_summed(self):
        """A ppermute under lax.cond runs in exactly ONE branch; summing
        both branch jaxprs would double-count wire bytes (round-review
        finding) — conditional collectives must surface as statically
        unaccountable instead."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _collect

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))

        def hop(x):
            return jax.lax.ppermute(
                x, "dp", [(i, (i + 1) % 8) for i in range(8)])

        def step(pred, x):
            return jax.lax.cond(pred, hop, hop, x)

        jx = jax.make_jaxpr(jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P("dp")),
            out_specs=P("dp"))))(
            jax.ShapeDtypeStruct((), jnp.bool_),
            jax.ShapeDtypeStruct((64,), jnp.float32))
        c = _collect(jx.jaxpr)
        assert c["wire_unknown"] and c["wire_bytes"] == 0, c

    def test_collect_reads_the_jit_calls_donation(self):
        """J3's input: the step's donation mask, under the name the
        installed jax gives the call primitive ("pjit", or "jit")."""
        import jax
        import jax.numpy as jnp
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _collect

        jx = jax.make_jaxpr(jax.jit(lambda x: x + 1, donate_argnums=0))(
            jax.ShapeDtypeStruct((8,), jnp.float32))
        assert _collect(jx.jaxpr)["donated"] == (True,)

    def test_j5_detects_foreign_axis(self):
        phases, L, n = self._dp_phases(codec="bfp")
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _check_cell
        fs = _check_cell("cell", "DPTrainer", "bfp", False, phases, L, n,
                         mesh_axes=("tp",))    # step collects over 'dp'
        assert "J5" in {f.code for f in fs}

    def test_rule_docs_cover_all_codes(self):
        from fpga_ai_nic_tpu.lint.findings import JAXPR_CODES
        for code in AST_CODES + JAXPR_CODES:
            assert code in RULE_DOCS


class TestJ7GradScale:
    """J7: per-replica gradient invariant to n_dp on a fixed batch — the
    psum-transpose gradient-scale class (KNOWN_FAILURES #1-16) frozen as
    a sweep rule."""

    FIXTURE = os.path.join(FIXTURES, "j7_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j7
        findings = run_j7()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_fused_opt_donation_cells_green(self):
        """The fused TrainState/FSDPState (master + adamw moments) must
        keep full donation (J3) and honest wire accounting (J4)."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_fused_opt_cells
        findings = run_fused_opt_cells()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_with_ndp_ratio(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j7_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_grad_scale
        fs = check_grad_scale("j7_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J7"}
        # the finding must name the smoking gun: a ratio ~ n_dp
        assert "ratio 2" in fs[0].message and "ratio 4" in fs[1].message

    def test_exit_code_with_fixture_env(self):
        # one subprocess pays for the full sweep, so ALL value-level
        # fixture hooks ride it: J7 (grad scale), J8 (reshard wire
        # accounting), J9 (hierarchical hop accounting), J10 (serve
        # recompile-freedom), J11 (KV-handoff wire accounting), J12
        # (wire-integrity coverage), J13 (adaptive counted traces) and
        # J14 (restore-path audit) must each fire and fail the CLI
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   GRAFTLINT_J7_FIXTURE=self.FIXTURE,
                   GRAFTLINT_J8_FIXTURE=TestJ8Reshard.FIXTURE,
                   GRAFTLINT_J9_FIXTURE=TestJ9Hier.FIXTURE,
                   GRAFTLINT_J10_FIXTURE=TestJ10ServeRecompile.FIXTURE,
                   GRAFTLINT_J11_FIXTURE=TestJ11Handoff.FIXTURE,
                   GRAFTLINT_J12_FIXTURE=TestJ12Integrity.FIXTURE,
                   GRAFTLINT_J13_FIXTURE=TestJ13AdaptiveTraces.FIXTURE,
                   GRAFTLINT_J14_FIXTURE=TestJ14DurableState.FIXTURE)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
             "--jaxpr"], cwd=REPO, env=env, capture_output=True,
            text=True, timeout=600)
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "J7:" in proc.stdout
        assert "J8:" in proc.stdout
        assert "J9:" in proc.stdout
        assert "J10:" in proc.stdout
        assert "J11:" in proc.stdout
        assert "J12:" in proc.stdout
        assert "J13:" in proc.stdout
        assert "J14:" in proc.stdout


class TestJ8Reshard:
    """J8: the live-reshard transfer program (parallel.reshard) must be
    callback-free, donate its sources, and move EXACTLY the bytes the
    intersection table declares — the wire-accounting contract behind
    the reshard-vs-restore MTTR claim (docs/RESHARD.md)."""

    FIXTURE = os.path.join(FIXTURES, "j8_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j8
        findings = run_j8()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_with_byte_delta(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j8_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_reshard_program
        fs = check_reshard_program("j8_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J8"}
        # the finding must carry the moved-vs-declared numbers
        assert "declares" in fs[0].message and "move" in fs[0].message

    def test_callback_in_program_fires(self):
        """A host round-trip smuggled into the transfer program is a
        checkpoint restore wearing a costume — J8 must name it."""
        import jax
        import jax.numpy as jnp
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_reshard_program

        def build():
            def prog(x):
                return jax.pure_callback(
                    lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            jx = jax.make_jaxpr(jax.jit(prog, donate_argnums=(0,)))(
                jax.ShapeDtypeStruct((64,), jnp.float32))
            return jx, 0, 1

        fs = check_reshard_program("callback", build)
        assert any("callback" in f.message for f in fs), fs

    def test_surface_failure_lands_as_j8_finding(self, monkeypatch):
        """A surface that cannot even trace must fail LOUDLY as a J8
        finding (run_j8 wraps it), never a silent skip."""
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j8_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j8()
        assert len(fs) == 1 and fs[0].code == "J8"
        assert "boom" in fs[0].message


class TestJ9Hier:
    """J9: hierarchical collectives (ops.ring_hier) must keep the fast
    intra hop codec-free and move EXACTLY the bytes the
    HierarchicalPlan declares, per hop class — the program property the
    EQuARX-style quantize-only-the-slow-hop claim rests on."""

    FIXTURE = os.path.join(FIXTURES, "j9_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j9
        findings = run_j9()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_codec_on_fast_hop(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j9_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_hier_program
        fs = check_hier_program("j9_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J9"}
        # the finding must name BOTH violations: non-f32 payloads on the
        # fast hop and the declared-vs-moved byte mismatch
        assert any("non-f32" in f.message for f in fs)
        assert any("declares" in f.message for f in fs)

    def test_flat_collective_in_hier_program_is_other(self):
        """A full-ring permutation inside a declared-hierarchical
        program must classify as 'other' (neither hop class) — the
        smuggled-flat-collective case."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import _classify_perm
        n, ni = 8, 2
        flat = tuple((i, (i + 1) % n) for i in range(n))
        assert _classify_perm(flat, ni) == "other"
        intra = tuple((g * ni + j, g * ni + (j + 1) % ni)
                      for g in range(n // ni) for j in range(ni))
        inter = tuple((g * ni + j, ((g + 1) % (n // ni)) * ni + j)
                      for g in range(n // ni) for j in range(ni))
        assert _classify_perm(intra, ni) == "intra"
        assert _classify_perm(inter, ni) == "inter"

    def test_surface_failure_lands_as_j9_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j9_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j9()
        assert len(fs) == 1 and fs[0].code == "J9"
        assert "boom" in fs[0].message


class TestJ10ServeRecompile:
    """J10: the serving decode plane (serve.engine) must be
    recompile-free across (active-set, page-assignment) changes — a
    counted-trace check over a scripted admit/evict schedule that
    forces eviction, readmission and page recycling."""

    FIXTURE = os.path.join(FIXTURES, "j10_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j10
        findings = run_j10()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_with_trace_count(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j10_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_serve_trace
        fs = check_serve_trace("j10_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J10"}
        # the finding must carry the observed trace count and name the
        # class (shape-dependent scheduler state)
        assert "traced 3x" in fs[0].message
        assert "scheduler state" in fs[0].message

    def test_tp_bad_fixture_fires_with_trace_count(self):
        """The tp-sharded flavor: a shard_map'd tick whose page table is
        a static argument retraces per page reassignment — the counted
        discipline must reject it exactly like the unsharded case."""
        import importlib.util
        fixture = os.path.join(FIXTURES, "j10_tp_bad.py")
        spec = importlib.util.spec_from_file_location("j10_tp_bad",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_serve_trace
        fs = check_serve_trace("j10_tp_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J10"}
        assert "traced 3x" in fs[0].message
        assert "scheduler state" in fs[0].message

    def test_tp_surface_listed(self):
        """The tp-sharded engine tick is a first-class J10 surface, not
        an optional extra."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import j10_surfaces
        names = [n for n, _ in j10_surfaces()]
        assert any("tp-sharded" in n for n in names), names

    def test_vacuous_schedule_is_a_finding(self):
        """A surface whose schedule exercised nothing must fail loudly,
        not pass an empty check."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_serve_trace

        def build():
            return lambda: {"decode": 1, "_exercised": 0}

        fs = check_serve_trace("lazy", build)
        assert len(fs) == 1 and fs[0].code == "J10"
        assert "vacuous" in fs[0].message

    def test_surface_failure_lands_as_j10_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j10_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j10()
        assert len(fs) == 1 and fs[0].code == "J10"
        assert "boom" in fs[0].message


class TestJ11Handoff:
    """J11: the serving KV-handoff program (serve.handoff) must be
    callback-free, donate its pool operands, and move EXACTLY the
    migrated pages' bytes — the wire-accounting contract behind the
    fleet's zero-replay migration claim (docs/SERVING.md)."""

    FIXTURE = os.path.join(FIXTURES, "j11_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j11
        findings = run_j11()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_with_byte_delta(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j11_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_handoff_program
        fs = check_handoff_program("j11_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J11"}
        # the finding must carry the moved-vs-declared numbers
        assert any("declares" in f.message and "move" in f.message
                   for f in fs)

    def test_callback_in_program_fires(self):
        """A host round-trip smuggled into the migration is
        replay-from-prompt wearing a costume — J11 must name it."""
        import jax
        import jax.numpy as jnp
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_handoff_program

        def build():
            def prog(x):
                return jax.pure_callback(
                    lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            jx = jax.make_jaxpr(jax.jit(prog, donate_argnums=(0,)))(
                jax.ShapeDtypeStruct((64,), jnp.float32))
            return jx, 0, 1

        fs = check_handoff_program("callback", build)
        assert any("callback" in f.message for f in fs), fs

    def test_plan_wire_bytes_is_exactly_the_pages(self):
        """The declared accounting equals the pages' actual array bytes
        — and host-side movement is declared APART from the wire."""
        import jax.numpy as jnp
        from fpga_ai_nic_tpu.serve import handoff as handoff_lib
        plan = handoff_lib.make_plan(n_layers=3, kv_local=2, page_size=4,
                                     head_dim=8, n_pages=16, n_move=5)
        per_page = 2 * 4 * 8 * jnp.dtype("float32").itemsize
        assert plan.wire_bytes() == 2 * 3 * 5 * per_page
        # host bytes: the table row ids + the request's token ids
        assert plan.host_bytes(n_tokens=11) == 5 * 4 + 11 * 4

    def test_surface_failure_lands_as_j11_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j11_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j11()
        assert len(fs) == 1 and fs[0].code == "J11"
        assert "boom" in fs[0].message


class TestJ12Integrity:
    """J12: every ppermute-bearing transfer program must carry its exact
    wire checksum (ops.integrity) when integrity is requested — present
    (u32 arithmetic + boolean verdict), invisible (ppermute bytes
    IDENTICAL to the integrity-off twin: no checksum rides the wire),
    with the decode-tick ledger surface guarded by page checksums — or
    carry an explicit J12_WAIVERS entry (docs/LINT.md)."""

    FIXTURE = os.path.join(FIXTURES, "j12_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j12
        findings = run_j12()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_zero_waivers_in_shipped_tree(self):
        """The waiver table is the ONLY sanctioned skip, and the shipped
        tree must not use it: every surface is actually guarded."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import J12_WAIVERS
        assert J12_WAIVERS == {}

    def test_bad_fixture_fires_on_wire_riding_checksum(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j12_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_integrity_program
        fs = check_integrity_program("j12_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J12"}
        # both anti-patterns must be named: the checksum on the wire
        # (with the on/off byte numbers) and the missing verdict
        assert any("rides the wire" in f.message and "4100" in f.message
                   for f in fs), fs
        assert any("verdict" in f.message for f in fs), fs

    def test_unguarded_program_fires(self):
        """integrity=True lowering with no checksum arithmetic at all —
        the 'coverage theater' class — must be named."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh, PartitionSpec as P
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_integrity_program

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        perm = [(i, (i + 1) % 8) for i in range(8)]

        def trace(integrity):
            def f(x):
                out = lax.ppermute(x, "dp", perm)
                if integrity:
                    return out, jnp.bool_(True)    # vacuous verdict
                return out
            out_specs = (P("dp"), P()) if integrity else P("dp")
            return jax.make_jaxpr(jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("dp"), out_specs=out_specs,
                check_vma=False)))(
                jax.ShapeDtypeStruct((8 * 128,), jnp.float32))

        fs = check_integrity_program("unguarded", lambda: {
            "kind": "wire", "jx_on": trace(True), "jx_off": trace(False)})
        assert any("NO uint32 checksum arithmetic" in f.message
                   for f in fs), fs

    def test_waived_surface_is_skipped_not_failed(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j12_surfaces",
                            lambda: [("broken", boom)])
        monkeypatch.setattr(jaxpr_sweep, "J12_WAIVERS",
                            {"broken": "intentionally waived for test"})
        assert jaxpr_sweep.run_j12() == []

    def test_surface_failure_lands_as_j12_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j12_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j12()
        assert len(fs) == 1 and fs[0].code == "J12"
        assert "boom" in fs[0].message


class TestJ13AdaptiveTraces:
    """J13: the adaptive-training candidate set (tune.adapt) must be
    traced up front at construction, and a runtime plan switch must
    cause ZERO new traces — the J10 counted-trace discipline applied to
    training (docs/LINT.md, docs/TUNING.md)."""

    FIXTURE = os.path.join(FIXTURES, "j13_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j13
        findings = run_j13()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_bad_fixture_fires_with_trace_counts(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j13_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_adaptive_traces
        fs = check_adaptive_traces("j13_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J13"}
        # both anti-patterns must be named: the lazily-rebuilt plan's
        # retrace count and the nonzero across-switch recompiles
        assert any("traced 2x" in f.message for f in fs), fs
        assert any("ZERO new traces" in f.message for f in fs), fs

    def test_never_traced_candidate_is_a_finding(self):
        """A candidate that was never pre-traced would pay its compile
        at the switch — J13 must name it even before any switch."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_adaptive_traces

        def build():
            return lambda: {"candidates": {"plan0": 1, "plan1": 0},
                            "switches": 1,
                            "recompiles_across_switch": 0,
                            "_exercised": 1}

        fs = check_adaptive_traces("lazy", build)
        assert len(fs) == 1 and fs[0].code == "J13"
        assert "NEVER traced" in fs[0].message

    def test_vacuous_run_is_a_finding(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_adaptive_traces

        def build():
            return lambda: {"candidates": {"plan0": 1},
                            "switches": 0,
                            "recompiles_across_switch": 0,
                            "_exercised": 0}

        fs = check_adaptive_traces("lazy", build)
        assert len(fs) == 1 and fs[0].code == "J13"
        assert "vacuous" in fs[0].message

    def test_surface_failure_lands_as_j13_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j13_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j13()
        assert len(fs) == 1 and fs[0].code == "J13"
        assert "boom" in fs[0].message


class TestJ14DurableState:
    """J14: every checkpoint restore path must audit the stored bytes
    (refuse or peer-repair a flipped bit, never restore silently), the
    walk-back must land on the previous verified step, and the pair
    repair program must move exactly the shard bytes callback-free with
    the source donated (docs/LINT.md, docs/DURABILITY.md)."""

    FIXTURE = os.path.join(FIXTURES, "j14_bad.py")

    def test_green_on_head(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import run_j14
        findings = run_j14()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_zero_waivers_in_shipped_tree(self):
        """The waiver table is the ONLY sanctioned skip, and the shipped
        tree keeps it EMPTY — every restore path is audited."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import J14_WAIVERS
        assert J14_WAIVERS == {}

    def test_bad_fixture_fires_silent_restore(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location("j14_bad",
                                                      self.FIXTURE)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_restore_audit
        fs = check_restore_audit("j14_bad", mod.build)
        assert fs and {f.code for f in fs} == {"J14"}
        assert any("without refusing or repairing" in f.message
                   for f in fs), fs

    def test_wire_mismatch_is_a_finding(self):
        """A repair program shipping more than the shard (the
        ship-the-whole-leaf anti-pattern) must be named with both byte
        numbers — the J8/J11 accounting applied to the repair wire."""
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_restore_audit

        def build():
            return lambda: {"surface": "fat repair", "detected": 1,
                            "repaired": 1, "bit_exact": 1,
                            "wire_bytes": 4096, "declared_bytes": 1024,
                            "runtime_wire_bytes": 1024,
                            "callbacks": 0, "donated": 1,
                            "_exercised": 1}

        fs = check_restore_audit("fat", build)
        assert len(fs) == 1 and fs[0].code == "J14"
        assert "4096" in fs[0].message and "1024" in fs[0].message

    def test_unrepaired_mirror_is_a_finding(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_restore_audit

        def build():
            return lambda: {"surface": "dead repair tier", "detected": 1,
                            "repaired": 0, "bit_exact": 1,
                            "_exercised": 1}

        fs = check_restore_audit("dead", build)
        assert len(fs) == 1 and fs[0].code == "J14"
        assert "never fired" in fs[0].message

    def test_vacuous_run_is_a_finding(self):
        from fpga_ai_nic_tpu.lint.jaxpr_sweep import check_restore_audit
        fs = check_restore_audit(
            "noop", lambda: (lambda: {"detected": 1, "_exercised": 0}))
        assert len(fs) == 1 and fs[0].code == "J14"
        assert "vacuous" in fs[0].message

    def test_surface_failure_lands_as_j14_finding(self, monkeypatch):
        from fpga_ai_nic_tpu.lint import jaxpr_sweep

        def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(jaxpr_sweep, "j14_surfaces",
                            lambda: [("broken", boom)])
        fs = jaxpr_sweep.run_j14()
        assert len(fs) == 1 and fs[0].code == "J14"
        assert "boom" in fs[0].message
