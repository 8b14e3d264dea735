"""Plane 2 — jaxpr invariant sweep (J1–J13), CPU-only.

EQuARX (arXiv:2506.17615) and the weight-update sharding work
(arXiv:2004.13336) both rest on compiler-level invariants of the lowered
program.  We check the same class of invariants *statically* on our own
jaxprs: every registered compression codec x every trainer x obs on/off
is traced abstractly (``jax.make_jaxpr`` over ShapeDtypeStructs on the
8-device virtual CPU mesh — zero device compute beyond tracing) and the
jaxpr is asserted to satisfy:

  J1  obs_metrics=False  =>  ZERO callback primitives (the generalization
      of tests/test_obs.py's jaxpr-identity test to the whole grid); on
      the fused trainers obs=True must show the tap, so J1 cannot rot
      into vacuity.
  J2  no float64 aval anywhere (an f64 leak doubles wire bytes and trips
      TPU lowering).
  J3  the step's donated buffers are actually donated: the pjit eqn's
      ``donated_invars`` must cover every state leaf (DP/FSDP donate the
      whole state; QueuedDDP's update_fn donates master + opt state).
  J4  declared ``Codec.wire_bytes`` == bytes implied by the jaxpr's
      ppermute operands x their static trip counts (scan lengths).
  J5  every collective axis name appearing in the jaxpr exists on the
      mesh.
  J6  sweep coverage: every codec in ``compress.available_codecs()`` was
      swept (a newly registered codec is auto-covered; a cell that fails
      to trace is a loud error, never a silent skip).

No TPU is required or touched: these invariants are checked on CPU
jaxprs so that none of them costs chip time.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

from .findings import Finding

# grid constants: a model just big enough that every codec's padding
# rules engage (bfp blocks, int8 block*LANES tiles, top-k buckets)
_LAYERS = (64, 64, 32)
_BATCH = 64
_NDEV = 8


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr, mult: Optional[int] = 1):
    """Yield (eqn, static_trip_multiplier) over nested jaxprs.  ``mult``
    is how many times the eqn executes per step (scan lengths compose);
    None = statically unknown (while_loop)."""
    for eqn in jaxpr.eqns:
        yield eqn, mult
        sub_mult = mult
        if eqn.primitive.name == "scan":
            length = eqn.params.get("length")
            sub_mult = None if (mult is None or length is None) \
                else mult * int(length)
        elif eqn.primitive.name in ("while", "cond"):
            # while: trip count unknown; cond: exactly ONE branch runs,
            # so summing over branch jaxprs would double-count (round
            # review) — both are statically unaccountable for J4
            sub_mult = None
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner, sub_mult)
                elif hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub, sub_mult)


def _aval_bytes(aval) -> int:
    return int(math.prod(aval.shape)) * aval.dtype.itemsize


def _collect(jaxpr) -> Dict[str, Any]:
    """One pass: callback count, f64 leaks, ppermute wire bytes, axis
    names, top-level pjit donation mask."""
    import numpy as np

    out: Dict[str, Any] = {"callbacks": 0, "f64": [], "wire_bytes": 0,
                           "wire_unknown": False, "axes": set(),
                           "donated": None}
    for eqn in jaxpr.eqns:
        # first top-level jit call (the primitive was named "pjit";
        # jax 0.9.0 prints "jit") = the jitted step whose donation mask
        # J3 inspects (leading convert/broadcast eqns are fine)
        if eqn.primitive.name in ("pjit", "jit"):
            out["donated"] = tuple(eqn.params.get("donated_invars", ()))
            break
    for eqn, mult in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if "callback" in name:
            out["callbacks"] += 1
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) is not None \
                    and aval.dtype == np.float64:
                out["f64"].append(f"{name}: {aval.str_short()}")
        if name == "ppermute":
            if mult is None:
                out["wire_unknown"] = True
            else:
                out["wire_bytes"] += mult * sum(
                    _aval_bytes(v.aval) for v in eqn.invars)
            ax = eqn.params.get("axis_name")
            axes = ax if isinstance(ax, (tuple, list)) else (ax,)
            out["axes"].update(a for a in axes if isinstance(a, str))
        else:
            for key in ("axes", "axis_name"):
                ax = eqn.params.get(key)
                if ax is None:
                    continue
                axes = ax if isinstance(ax, (tuple, list)) else (ax,)
                out["axes"].update(a for a in axes if isinstance(a, str))
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def _require_cpu_mesh():
    import jax
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < _NDEV:
        raise RuntimeError(
            "graftlint jaxpr sweep needs the 8-device virtual CPU mesh; "
            "run via tools/graftlint.py (it pins JAX_PLATFORMS=cpu and "
            "--xla_force_host_platform_device_count=8 before jax loads), "
            f"got platform={devs[0].platform!r} n={len(devs)}")


def _mlp_pieces():
    import jax
    import jax.numpy as jnp
    from ..models import mlp
    from ..utils.config import MLPConfig

    mcfg = MLPConfig(layer_sizes=_LAYERS, dtype="float32")
    params = jax.eval_shape(lambda: mlp.init(jax.random.PRNGKey(0), mcfg))
    batch = (jax.ShapeDtypeStruct((_BATCH, _LAYERS[0]), jnp.float32),
             jax.ShapeDtypeStruct((_BATCH,), jnp.int32))

    def loss(p, b):
        return mlp.loss_fn(p, b, mcfg)

    return params, batch, loss


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _trace_dp(cfg, axis="dp"):
    import jax
    import jax.numpy as jnp
    from .. import optim
    from ..parallel import mesh as mesh_lib
    from ..parallel.train import DPTrainer, TrainState

    params, batch, loss = _mlp_pieces()
    tr = DPTrainer(loss, mesh_lib.make_mesh(cfg.mesh), cfg, axis_name=axis)
    tr._ensure_meta(params)
    L = tr._meta.padded_len
    state = TrainState(
        params=params, w_own=_sds((L,), jnp.float32),
        opt_state=jax.eval_shape(lambda: optim.init_state(cfg.optimizer, L)),
        step=_sds((), jnp.int32),
        codec_state=_sds((tr.n * L,), jnp.float32) if tr._ef else None)
    jx = jax.make_jaxpr(lambda s, b: tr.step_fn(s, b))(state, batch)
    n_state = len(jax.tree_util.tree_leaves(state))
    return [("step", jx, {"n_donate": n_state})], L, tr.n


def _trace_fsdp(cfg, axis="fsdp"):
    import jax
    import jax.numpy as jnp
    from .. import optim
    from ..parallel import mesh as mesh_lib
    from ..parallel.fsdp import FSDPTrainer, FSDPState

    params, batch, loss = _mlp_pieces()
    tr = FSDPTrainer(loss, mesh_lib.make_mesh(cfg.mesh), cfg,
                     axis_name=axis)
    tr._ensure_meta(params)
    L = tr._meta.padded_len
    state = FSDPState(
        w_own=_sds((L,), jnp.float32),
        opt_state=jax.eval_shape(lambda: optim.init_state(cfg.optimizer, L)),
        step=_sds((), jnp.int32),
        codec_state=_sds((tr.n * L,), jnp.float32) if tr._ef else None)
    jx = jax.make_jaxpr(lambda s, b: tr.step_fn(s, b))(state, batch)
    n_state = len(jax.tree_util.tree_leaves(state))
    return [("step", jx, {"n_donate": n_state})], L, tr.n


def _trace_queued(cfg, axis="dp"):
    import jax
    import jax.numpy as jnp
    from .. import optim
    from ..parallel import mesh as mesh_lib
    from ..parallel.queued import QueuedDDPTrainer

    params, batch, loss = _mlp_pieces()
    tr = QueuedDDPTrainer(loss, mesh_lib.make_mesh(cfg.mesh), cfg,
                          axis_name=axis)
    tr._ensure_meta(params)
    bucket_sds, _loss_sds = jax.eval_shape(
        lambda p, b: tr.grads_fn(p, b), params, batch)
    jx_g = jax.make_jaxpr(lambda p, b: tr.grads_fn(p, b))(params, batch)
    phases = [("grads", jx_g, {})]
    # one reduce collective per bucket; wire accounting is per bucket
    for i, (b, g_sds) in enumerate(zip(tr._plan.buckets, bucket_sds)):
        jx_r = jax.make_jaxpr(lambda g: tr.reduce_fn(g))(g_sds)
        phases.append((f"reduce[{i}]", jx_r,
                       {"wire_len": b.padded_len}))
    Lm = tr._meta.padded_len
    w_sds = _sds((Lm,), jnp.float32)
    opt_sds = jax.eval_shape(lambda: optim.init_state(cfg.optimizer, Lm))
    jx_u = jax.make_jaxpr(
        lambda m, w, o, s: tr.update_fn(m, w, o, s))(
        tuple(bucket_sds), w_sds, opt_sds, _sds((), jnp.int32))
    n_donate = 1 + len(jax.tree_util.tree_leaves(opt_sds))
    phases.append(("update", jx_u, {"n_donate": n_donate}))
    return phases, None, tr.n


_TRAINERS: Dict[str, Tuple[Callable, str]] = {
    "DPTrainer": (_trace_dp, "dp"),
    "FSDPTrainer": (_trace_fsdp, "fsdp"),
    "QueuedDDPTrainer": (_trace_queued, "dp"),
}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _check_cell(cell: str, trainer: str, codec_name: Optional[str],
                obs: bool, phases, L: Optional[int], n: int,
                mesh_axes: Tuple[str, ...]) -> List[Finding]:
    from ..compress import get_codec
    from ..ops import ring as ring_ops

    findings: List[Finding] = []
    codec = get_codec(codec_name) if codec_name else None
    total_callbacks = 0
    wire_implied = 0
    wire_declared = 0
    wire_checked = False
    for phase_name, jx, info in phases:
        c = _collect(jx.jaxpr)
        total_callbacks += c["callbacks"]
        if c["f64"]:
            findings.append(Finding(
                "J2", cell, 0,
                f"f64 leak in {phase_name}: {c['f64'][:3]}"))
        bad_axes = c["axes"] - set(mesh_axes)
        if bad_axes:
            findings.append(Finding(
                "J5", cell, 0,
                f"{phase_name}: collective axis name(s) "
                f"{sorted(bad_axes)} not on mesh {mesh_axes}"))
        n_donate = info.get("n_donate")
        if n_donate is not None:
            donated = c["donated"] or ()
            if sum(donated) < n_donate:
                findings.append(Finding(
                    "J3", cell, 0,
                    f"{phase_name}: expected >= {n_donate} donated "
                    f"invars (the state), pjit donated_invars shows "
                    f"{sum(donated)}/{len(donated)} — donation lost "
                    "(peak memory doubles)"))
        if c["wire_unknown"]:
            findings.append(Finding(
                "J4", cell, 0,
                f"{phase_name}: ppermute under a while_loop — wire "
                "bytes not statically checkable (use fori_loop/scan "
                "with a static trip count)"))
        wire_implied += c["wire_bytes"]
        wire_len = info.get("wire_len", L if phase_name == "step" else None)
        if wire_len is not None:
            wire_checked = True
            wire_declared += ring_ops.wire_bytes_per_device(
                wire_len, n, codec)
    if not obs and total_callbacks:
        findings.append(Finding(
            "J1", cell, 0,
            f"obs_metrics=False but {total_callbacks} callback "
            "primitive(s) in the step — the trace-time gate leaks a "
            "host round-trip into every hot step"))
    if obs and trainer in ("DPTrainer", "FSDPTrainer") \
            and total_callbacks == 0:
        findings.append(Finding(
            "J1", cell, 0,
            "obs_metrics=True produced zero callbacks — the metrics tap "
            "vanished, so the obs-off check is vacuous"))
    if wire_checked:
        if wire_implied != wire_declared:
            findings.append(Finding(
                "J4", cell, 0,
                f"declared Codec.wire_bytes implies {wire_declared} "
                f"bytes/device/step on the ring, but the jaxpr's "
                f"ppermute operands move {wire_implied} — the wire "
                "accounting (obs counters, bench ratios) is lying"))
    return findings


# ---------------------------------------------------------------------------
# J7 — per-replica gradient invariant to n_dp (the psum-transpose
# gradient-scale class: docs/KNOWN_FAILURES.md #1-16, all root-caused to
# collectives sitting on a loss head's gradient path, whose transpose
# convention moved between jaxlibs and silently scaled every update by
# the axis size).  Unlike J1-J6 this rule evaluates tiny CONCRETE
# gradients (a jaxpr alone cannot prove a value-level invariant): a fixed
# global batch with UNEVENLY masked labels is sharded over n_dp in
# {2, 4}; the trainer-effective update (psum/n of the per-replica grads)
# must match the single-device gradient of the same objective — and each
# other — to f32 tolerance.  An n_dp-proportional mismatch is exactly
# the 8x-learning-rate bug class.
# ---------------------------------------------------------------------------

_J7_NDPS = (2, 4)
_J7_RTOL = 2e-3


def _j7_bert_build():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models import bert

    cfg = bert.BertConfig(vocab=64, dim=32, n_layers=1, n_heads=2,
                          ffn_dim=64, max_pos=16, dtype="float32",
                          attn_impl="xla")
    params = bert.init(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(0)
    tokens = jnp.asarray(r.integers(1, 64, (8, 8)).astype(np.int32))
    labels = np.asarray(r.integers(0, 64, (8, 8)), np.int32)
    # uneven masking: shard token counts differ, so uniform-mean vs
    # token-weighted gradients genuinely disagree (the correction term
    # carries weight)
    labels[:4, :6] = -100
    labels[4:, :2] = -100

    def loss(p, batch, dp_axis):
        return bert.loss_fn(p, batch, cfg, dp_axis=dp_axis)

    return params, (tokens, jnp.asarray(labels)), loss


def _j7_llama_build():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models import llama

    cfg = llama.LlamaConfig.tiny(vocab=64, dim=32, n_layers=1, n_heads=2,
                                 n_kv_heads=1, ffn_dim=64)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(1)
    tokens = jnp.asarray(r.integers(1, 64, (8, 8)).astype(np.int32))
    labels = np.asarray(r.integers(0, 64, (8, 8)), np.int32)
    labels[:4, :6] = -100
    labels[4:, :2] = -100

    def loss(p, batch, dp_axis):
        return llama.loss_fn(p, batch, cfg, dp_axis=dp_axis)

    return params, (tokens, jnp.asarray(labels)), loss


def j7_surfaces() -> List[Tuple[str, Callable]]:
    """The dp-axis-correcting loss heads under guard.  The
    GRAFTLINT_J7_FIXTURE env var appends a surface from a module path
    exposing ``build()`` — the bad-fixture / exit-code hook
    (tests/test_lint.py)."""
    surfaces: List[Tuple[str, Callable]] = [
        ("models.bert.loss_fn", _j7_bert_build),
        ("models.llama.loss_fn", _j7_llama_build),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J7_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j7_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def check_grad_scale(name: str, build: Callable,
                     ndps: Tuple[int, ...] = _J7_NDPS,
                     rtol: float = _J7_RTOL) -> List[Finding]:
    """Evaluate one J7 surface: trainer-effective gradient at each n_dp
    vs the single-device gradient of the identical objective."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import lax

    findings: List[Finding] = []
    params, batch, loss = build()
    ref = jax.jit(jax.grad(lambda p: loss(p, batch, None)))(params)
    ref_flat = np.concatenate([np.asarray(l, np.float32).ravel()
                               for l in jax.tree_util.tree_leaves(ref)])
    scale = float(np.abs(ref_flat).max()) or 1.0
    for ndp in ndps:
        mesh = Mesh(np.array(jax.devices()[:ndp]), ("dp",))

        def shard(p, b):
            p = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, "dp", to="varying"), p)
            g = jax.grad(lambda pp: loss(pp, b, "dp"))(p)
            # the trainer-effective update: sum over replicas / n_dp
            return jax.tree_util.tree_map(
                lambda x: lax.psum(x, "dp") / ndp, g)

        got = jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False))(params, batch)
        got_flat = np.concatenate([
            np.asarray(l, np.float32).ravel()
            for l in jax.tree_util.tree_leaves(got)])
        err = float(np.abs(got_flat - ref_flat).max()) / scale
        if not np.isfinite(err) or err > rtol:
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = float(np.nanmedian(got_flat / ref_flat))
            findings.append(Finding(
                "J7", f"jaxpr[grad-scale {name}]", 0,
                f"per-replica gradient is NOT invariant to n_dp: at "
                f"n_dp={ndp} the trainer-effective update deviates from "
                f"the single-device gradient by rel {err:.3g} (median "
                f"elementwise ratio {ratio:.3g}; a ratio ~= n_dp is the "
                f"psum-transpose gradient-scale class — keep collectives "
                f"off the loss head's gradient path)"))
    return findings


def run_j7(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j7_surfaces():
        try:
            fs = check_grad_scale(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J7", f"jaxpr[grad-scale {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] grad-scale {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J8 — the live-reshard transfer program (parallel.reshard).  The MTTR
# claim of the reshard recovery tier rests on the program moving EXACTLY
# the bytes the intersection table says change owner — no padding waste,
# no hidden host round-trips, and the source buffers actually donated
# (the transfer must run in ~one state's footprint).  Checked statically
# the same way J4 checks the ring: trace the lowered program abstractly,
# sum ppermute operand bytes x static trip counts, and compare against
# the plan's declared wire_bytes; any callback primitive or lost
# donation is a finding.  Surfaces cover a shrink (dp8->dp4, divisor), a
# NON-divisor shrink (dp8->dp3 — the boundary-splitting segments), and
# an EF-residual move (topk-padded layout).
# ---------------------------------------------------------------------------

def _j8_build(n_src: int, n_tgt: int, codec_name: Optional[str],
              n_flat_leaves: int, residual: bool):
    def build():
        import jax
        from jax.sharding import Mesh
        import numpy as np
        from ..compress import get_codec
        from ..parallel import reshard as reshard_lib

        live = 5000                    # deliberately non-round
        unit = 1 if codec_name is None else get_codec(codec_name).pad_elems
        pad_src = live + (-live) % (n_src * unit)
        pad_tgt = live + (-live) % (n_tgt * unit)
        plan = reshard_lib.make_plan(
            live, n_src, pad_src, n_tgt, pad_tgt,
            n_flat_leaves=n_flat_leaves, residual=residual)
        mesh = Mesh(np.array(jax.devices()[:plan.flat.n_union]), ("dp",))
        fn = reshard_lib.lower_apply(plan, mesh, "dp", donate=True)
        jx = jax.make_jaxpr(fn)(*reshard_lib.abstract_operands(plan))
        n_ops = plan.n_flat_leaves + (1 if plan.residual else 0)
        return jx, plan.wire_bytes(), n_ops
    return build


def j8_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs; build() -> (closed jaxpr, declared wire
    bytes, donated operand count).  GRAFTLINT_J8_FIXTURE appends a
    surface from a module path exposing ``build()`` — the bad-fixture /
    exit-code hook, same contract as J7's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("reshard dp8->dp4 adamw", _j8_build(8, 4, None, 3, False)),
        ("reshard dp8->dp3 non-divisor", _j8_build(8, 3, None, 1, False)),
        ("reshard dp8->dp4 topk+EF", _j8_build(8, 4, "topk", 2, True)),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J8_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j8_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def check_reshard_program(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J8 surface against the three invariants."""
    findings: List[Finding] = []
    jx, declared, n_ops = build()
    c = _collect(jx.jaxpr)
    cell = f"jaxpr[reshard {name}]"
    if c["callbacks"]:
        findings.append(Finding(
            "J8", cell, 0,
            f"{c['callbacks']} callback primitive(s) in the transfer "
            "program — a reshard that round-trips the host is a "
            "checkpoint restore wearing a costume"))
    if c["wire_unknown"]:
        findings.append(Finding(
            "J8", cell, 0,
            "ppermute under a while_loop — transfer bytes not statically "
            "accountable (lower with a static table, not a data-"
            "dependent loop)"))
    elif c["wire_bytes"] != declared:
        findings.append(Finding(
            "J8", cell, 0,
            f"the lowered program's ppermute operands move "
            f"{c['wire_bytes']} bytes but the intersection table "
            f"declares {declared} changing owner — the reshard wire "
            "accounting (MTTR claims, obs counters) is lying"))
    donated = c["donated"] or ()
    if sum(donated) < n_ops:
        findings.append(Finding(
            "J8", cell, 0,
            f"expected all {n_ops} source operands donated, pjit "
            f"donated_invars shows {sum(donated)}/{len(donated)} — the "
            "transfer holds two full states in memory"))
    return findings


def run_j8(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j8_surfaces():
        try:
            fs = check_reshard_program(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J8", f"jaxpr[reshard {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] reshard {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J9 — hierarchical (intra x inter) collectives (ops.ring_hier).  The
# EQuARX-style claim — codec only on the SLOW hop — is a program
# property, so it is checked on the program: every ppermute in the
# lowered collective is classified by its permutation (intra = pairs
# stay inside a group of n_intra consecutive ranks; inter = pairs keep
# their intra position), and per class the operand bytes x static trip
# counts must equal the HierarchicalPlan's declaration EXACTLY, with
# every intra-hop operand a 4-byte float (a codec payload on the fast
# hop is the regression this rule freezes out).  Permutations that are
# neither class are findings: a flat collective smuggled into a
# "hierarchical" program breaks the accounting the tuner banks.
# ---------------------------------------------------------------------------

def _collect_ppermutes(jaxpr) -> List[Dict[str, Any]]:
    """Per-ppermute records: perm pairs, static trip multiplier (None =
    unaccountable), operand bytes per execution, operand dtypes."""
    out: List[Dict[str, Any]] = []
    for eqn, mult in _iter_eqns(jaxpr):
        if eqn.primitive.name != "ppermute":
            continue
        perm = tuple((int(s), int(d)) for s, d in eqn.params.get("perm", ()))
        out.append({
            "perm": perm,
            "mult": mult,
            "bytes": sum(_aval_bytes(v.aval) for v in eqn.invars),
            "dtypes": sorted({str(v.aval.dtype) for v in eqn.invars
                              if getattr(v, "aval", None) is not None}),
            "f32_only": all(
                getattr(v.aval.dtype, "kind", "") == "f"
                and v.aval.dtype.itemsize == 4
                for v in eqn.invars if getattr(v, "aval", None) is not None),
        })
    return out


def _classify_perm(perm, n_intra: int) -> str:
    if not perm:
        return "other"
    if all(s // n_intra == d // n_intra for s, d in perm):
        return "intra"
    if all(s % n_intra == d % n_intra for s, d in perm):
        return "inter"
    return "other"


def check_hier_program(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J9 surface.  build() -> (closed jaxpr, plan, which)
    where plan is an ops.ring_hier.HierarchicalPlan and which names the
    collective ("reduce_scatter" / "all_gather" / "all_reduce")."""
    findings: List[Finding] = []
    jx, plan, which = build()
    cell = f"jaxpr[hier {name}]"
    perms = _collect_ppermutes(jx.jaxpr)
    got = {"intra": 0, "inter": 0}
    for p in perms:
        klass = _classify_perm(p["perm"], plan.n_intra)
        if klass == "other":
            findings.append(Finding(
                "J9", cell, 0,
                f"ppermute whose permutation is neither intra nor inter "
                f"for n_intra={plan.n_intra} (first pairs "
                f"{p['perm'][:4]}) — a non-hierarchical collective inside "
                "a declared-hierarchical program breaks the banked "
                "accounting"))
            continue
        if p["mult"] is None:
            findings.append(Finding(
                "J9", cell, 0,
                f"{klass} ppermute under a while_loop — hop bytes not "
                "statically accountable (use fori_loop/scan with a "
                "static trip count)"))
            continue
        got[klass] += p["mult"] * p["bytes"]
        if klass == "intra" and not p["f32_only"]:
            findings.append(Finding(
                "J9", cell, 0,
                f"intra-hop ppermute carries non-f32 operands "
                f"{p['dtypes']} — the FAST hop must be codec-free (full "
                "precision is free there; that is the whole point of the "
                "hierarchical split)"))
    declared = {"intra": plan.intra_bytes(which),
                "inter": plan.inter_bytes(which)}
    for klass in ("intra", "inter"):
        if got[klass] != declared[klass]:
            findings.append(Finding(
                "J9", cell, 0,
                f"{klass}-hop ppermute operands move {got[klass]} bytes "
                f"but the HierarchicalPlan declares {declared[klass]} "
                f"for {which} — the hierarchical wire accounting (tuner "
                "scores, obs counters, bench ratios) is lying"))
    return findings


def _j9_build(codec_name: Optional[str], n_intra: int, which: str,
              L: int = 8192):
    def build():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from ..compress import get_codec
        from ..ops import ring_hier

        codec = get_codec(codec_name) if codec_name else None
        unit = _NDEV * (codec.pad_elems if codec else 1)
        Lp = L + (-L) % unit
        plan = ring_hier.plan_hier(Lp, _NDEV, n_intra, codec)
        mesh = Mesh(np.array(jax.devices()[:_NDEV]), ("dp",))

        def prog(x):
            if which == "reduce_scatter":
                return ring_hier.hier_reduce_scatter(
                    x, "dp", n_intra, compression=codec)
            if which == "all_gather":
                return ring_hier.hier_all_gather(
                    x, "dp", n_intra, compression=codec)
            return ring_hier.hier_all_reduce(
                x, "dp", n_intra, compression=codec)

        shape = (Lp // _NDEV,) if which == "all_gather" else (Lp,)
        jx = jax.make_jaxpr(jax.jit(jax.shard_map(
            prog, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False)))(
            jax.ShapeDtypeStruct((_NDEV * shape[0],), jnp.float32))
        return jx, plan, which
    return build


def j9_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs covering codec x factorization x collective.
    GRAFTLINT_J9_FIXTURE appends a surface from a module path exposing
    ``build()`` — the bad-fixture / exit-code hook, same contract as
    J7/J8's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("rs ni=2 bfp", _j9_build("bfp", 2, "reduce_scatter")),
        ("ag ni=2 bfp", _j9_build("bfp", 2, "all_gather")),
        ("rs ni=4 topk", _j9_build("topk", 4, "reduce_scatter")),
        ("ar ni=2 int8", _j9_build("int8", 2, "all_reduce")),
        ("ar ni=4 none", _j9_build(None, 4, "all_reduce")),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J9_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j9_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j9(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j9_surfaces():
        try:
            fs = check_hier_program(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J9", f"jaxpr[hier {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] hier {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J10 — the serving decode plane (serve.engine) must be recompile-free
# across (active-set, page-assignment) changes.  The continuous-batching
# contract is that admissions, evictions, slot churn and page recycling
# change operand VALUES only; a step whose jaxpr depends on scheduler
# state (e.g. batching only the active slots, so the batch dim tracks
# the active count) retraces on every transition and the serving tail
# latency grows a compile spike.  Like J7, this rule runs CONCRETELY: a
# tiny engine serves a scripted two-wave schedule sized to force
# eviction + readmission + page recycling, and each jitted program's
# counted traces (serve.engine.counted_jit) must equal exactly 1.  A
# schedule that fails to exercise eviction is itself a finding — the
# check must not rot into vacuity.
# ---------------------------------------------------------------------------

def _j10_engine_build() -> Callable:
    def run() -> Dict[str, int]:
        import jax
        import numpy as np
        from ..models import llama
        from ..serve import ServeConfig, ServeEngine

        cfg = llama.LlamaConfig.tiny(vocab=64, dim=32, n_layers=1,
                                     n_heads=2, n_kv_heads=1, ffn_dim=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        scfg = ServeConfig(max_reqs=3, page_size=4, n_pages=5,
                           max_pages_per_seq=4, prefill_chunk=4)
        eng = ServeEngine(params, cfg, scfg)
        rng = np.random.default_rng(11)
        for _ in range(5):
            eng.submit(rng.integers(0, cfg.vocab,
                                    int(rng.integers(3, 10))).astype(
                np.int32), max_new=int(rng.integers(2, 6)))
        eng.run()
        for i in range(4):
            eng.submit(rng.integers(0, cfg.vocab,
                                    int(rng.integers(3, 10))).astype(
                np.int32), max_new=3, not_before_s=0.01 * i)
        eng.run()
        counts = dict(eng.trace_counts())
        counts["_exercised"] = int(eng.batcher.evictions > 0
                                   and eng.stats.as_dict()["completed"] == 9)
        return counts
    return run


def _j10_engine_tp_build() -> Callable:
    """The same scripted schedule over the TP-SHARDED tick: one replica
    spanning a 2-way mesh via shard_map (pool kv-sharded, kernel attend
    path on).  shard_map must not add a trace axis of its own — page
    reassignment, slot churn and the mesh wrapper together still leave
    exactly one trace per program."""
    def run() -> Dict[str, int]:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from ..models import llama
        from ..serve import ServeConfig, ServeEngine

        cfg = llama.LlamaConfig.tiny(vocab=64, dim=32, n_layers=1,
                                     n_heads=2, n_kv_heads=1, ffn_dim=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        # page_integrity off: the checksum ledger is global-pool-only
        # and the tp tick rejects it at construction
        scfg = ServeConfig(max_reqs=3, page_size=4, n_pages=5,
                           max_pages_per_seq=4, prefill_chunk=4,
                           page_integrity=False)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        # reference attend keeps this surface ~3s cheaper per sweep on
        # the 1-core CI box; the pallas-impl tp tick's trace count is
        # asserted by tests/test_paged_attend.py (TestTpParity
        # test_tp_engine_tick_tokens_and_traces), so the kernel axis
        # stays covered without paying its interpret-mode compile here
        eng = ServeEngine(params, cfg, scfg, tp_mesh=mesh,
                          attend_impl="reference")
        rng = np.random.default_rng(11)
        for _ in range(5):
            eng.submit(rng.integers(0, cfg.vocab,
                                    int(rng.integers(3, 10))).astype(
                np.int32), max_new=int(rng.integers(2, 6)))
        eng.run()
        for i in range(4):
            eng.submit(rng.integers(0, cfg.vocab,
                                    int(rng.integers(3, 10))).astype(
                np.int32), max_new=3, not_before_s=0.01 * i)
        eng.run()
        counts = dict(eng.trace_counts())
        counts["_exercised"] = int(eng.batcher.evictions > 0
                                   and eng.stats.as_dict()["completed"] == 9)
        return counts
    return run


def check_serve_trace(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J10 surface.  ``build()`` returns a zero-arg runner
    executing the scripted schedule and returning {phase: traces}
    (optionally ``_exercised``: falsy = the schedule proved nothing)."""
    findings: List[Finding] = []
    cell = f"jaxpr[serve {name}]"
    counts = dict(build()())
    exercised = counts.pop("_exercised", 1)
    if not exercised:
        findings.append(Finding(
            "J10", cell, 0,
            "the scripted admit/evict schedule exercised no eviction/"
            "readmission (or lost requests) — the recompile check is "
            "vacuous; widen the schedule"))
    for phase, n in sorted(counts.items()):
        if n > 1:
            findings.append(Finding(
                "J10", cell, 0,
                f"serving '{phase}' step traced {n}x across the scripted "
                "admit/evict schedule — the decode plane's jaxpr depends "
                "on scheduler state (slot occupancy / page assignment / "
                "active-set size); those must be operand VALUES under "
                "static ServeConfig shapes so steady-state serving "
                "records 0 recompiles"))
    return findings


def j10_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs.  GRAFTLINT_J10_FIXTURE appends a surface from
    a module path exposing ``build()`` — the bad-fixture / exit-code
    hook, same contract as J7/J8/J9's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("engine admit/evict schedule", _j10_engine_build),
        ("tp-sharded engine admit/evict schedule", _j10_engine_tp_build),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J10_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j10_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j10(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j10_surfaces():
        try:
            fs = check_serve_trace(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J10", f"jaxpr[serve {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] serve {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J11 — the serving-plane KV handoff program (serve.handoff).  The
# fleet's zero-replay claim rests on the migration being a pure
# device-side transfer that moves EXACTLY the migrated pages: like J8
# for the training reshard, the lowered pair-ppermute program is traced
# abstractly and must be callback-free, donate every pool operand, and
# move ppermute operand bytes == HandoffPlan.wire_bytes() precisely
# (page ids / table rows / host tokens are declared as host_bytes,
# never smuggled into the wire accounting).  Surfaces cover a single
# page, a multi-page multi-layer move, and a GQA (kv_local > 1) pool.
# ---------------------------------------------------------------------------

def _j11_build(n_layers: int, kv_local: int, page_size: int,
               head_dim: int, n_pages: int, n_move: int):
    def build():
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from ..serve import handoff as handoff_lib

        plan = handoff_lib.make_plan(
            n_layers=n_layers, kv_local=kv_local, page_size=page_size,
            head_dim=head_dim, n_pages=n_pages, n_move=n_move)
        mesh = Mesh(np.array(jax.devices()[:2]), ("rep",))
        fn = handoff_lib.lower_apply(plan, mesh, "rep", donate=True)
        jx = jax.make_jaxpr(fn)(*handoff_lib.abstract_operands(plan))
        return jx, plan.wire_bytes(), 2 * n_layers
    return build


def check_handoff_program(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J11 surface: build() -> (closed jaxpr, declared wire
    bytes, donated pool-operand count)."""
    findings: List[Finding] = []
    jx, declared, n_pool = build()
    c = _collect(jx.jaxpr)
    cell = f"jaxpr[handoff {name}]"
    if c["callbacks"]:
        findings.append(Finding(
            "J11", cell, 0,
            f"{c['callbacks']} callback primitive(s) in the handoff "
            "program — a migration that round-trips the host is "
            "replay-from-prompt wearing a costume"))
    if c["wire_unknown"]:
        findings.append(Finding(
            "J11", cell, 0,
            "ppermute under a while_loop — handoff bytes not statically "
            "accountable (lower with static page counts, dynamic page "
            "IDS as operands)"))
    elif c["wire_bytes"] != declared:
        findings.append(Finding(
            "J11", cell, 0,
            f"the lowered program's ppermute operands move "
            f"{c['wire_bytes']} bytes but the HandoffPlan declares "
            f"{declared} — the fleet's handoff wire accounting (MTTR "
            "claims, FLEET_BENCH gate) is lying"))
    donated = c["donated"] or ()
    if sum(donated) < n_pool:
        findings.append(Finding(
            "J11", cell, 0,
            f"expected all {n_pool} pool operands donated, pjit "
            f"donated_invars shows {sum(donated)}/{len(donated)} — the "
            "transfer holds two full pools in memory"))
    return findings


def j11_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs; GRAFTLINT_J11_FIXTURE appends a surface from
    a module path exposing ``build()`` — the bad-fixture / exit-code
    hook, same contract as J7–J10's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("1 page 2 layers", _j11_build(2, 2, 4, 8, 8, 1)),
        ("5 pages 3 layers", _j11_build(3, 1, 8, 16, 12, 5)),
        ("gqa kv=4 3 pages", _j11_build(2, 4, 4, 8, 10, 3)),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J11_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j11_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j11(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j11_surfaces():
        try:
            fs = check_handoff_program(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J11", f"jaxpr[handoff {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] handoff {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J12 — wire-integrity coverage (ops.integrity).  PR 12's contract: every
# ppermute-bearing transfer program must CARRY its exact checksum check
# when integrity is requested — and carrying it must not change what
# rides the wire.  Each surface traces the shipped program twice
# (integrity on / off) and asserts, statically on the jaxprs:
#
#   guarded    the integrity=True trace contains uint32 checksum
#              arithmetic (the odd-weighted word sums) and emits a
#              boolean verdict output — an integrity flag that lowers to
#              nothing is coverage theater;
#   invisible  the ppermute operand bytes x static trip counts are
#              IDENTICAL between the two traces — no checksum ever rides
#              the wire, so the exact byte accounting frozen by
#              J4/J8/J9/J11 holds with integrity on (checksums travel as
#              psum'd scalars, never payload);
#   non-vacuous  the program has at least one ppermute to guard (except
#              the decode-tick surface, whose wire is the KV pool's
#              write-to-read window — it must emit the [n_pages] uint32
#              ledger and the checksum arithmetic instead).
#
# A surface may be waived ONLY through J12_WAIVERS (name -> reason) —
# the explicit, greppable escape hatch; the shipped tree must keep it
# EMPTY (tests/test_lint.py pins that), so any future ppermute program
# either carries its checksum or carries a visible waiver in review.
# ---------------------------------------------------------------------------

# name -> reason.  SHIPPED TREE: EMPTY — every surface is guarded.
J12_WAIVERS: Dict[str, str] = {}


def _u32_eqn_count(jaxpr) -> int:
    """# of eqns (nested) producing a uint32 output — the static
    signature of the ops.integrity word-sum arithmetic."""
    import numpy as np
    n = 0
    for eqn, _ in _iter_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) is not None \
                    and aval.dtype == np.uint32:
                n += 1
                break
    return n


def _ppermute_count(jaxpr) -> int:
    return sum(1 for eqn, _ in _iter_eqns(jaxpr)
               if eqn.primitive.name == "ppermute")


def _has_bool_output(jaxpr) -> bool:
    import numpy as np
    return any(getattr(getattr(v, "aval", None), "dtype", None) == np.bool_
               for v in jaxpr.outvars)


def check_integrity_program(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J12 surface.  ``build()`` returns a dict:
    kind='wire' with jx_on/jx_off (the integrity on/off twin traces of
    the same program), or kind='page' with jx + n_pages (the decode-tick
    ledger surface, whose guard is page checksums, not hop carries)."""
    import numpy as np
    findings: List[Finding] = []
    cell = f"jaxpr[integrity {name}]"
    spec = build()

    if spec["kind"] == "page":
        jx, n_pages = spec["jx"], spec["n_pages"]
        if _u32_eqn_count(jx.jaxpr) == 0:
            findings.append(Finding(
                "J12", cell, 0,
                "the decode-tick program carries NO exact checksum "
                "arithmetic — the per-page KV ledger (the tier that "
                "closes the finite wrong-KEY class the logit guard "
                "cannot see) has vanished from the traced program"))
        has_ledger = any(
            getattr(getattr(v, "aval", None), "dtype", None) == np.uint32
            and tuple(getattr(v.aval, "shape", ())) == (n_pages,)
            for v in jx.jaxpr.outvars)
        if not has_ledger:
            findings.append(Finding(
                "J12", cell, 0,
                f"the decode-tick program emits no [n_pages={n_pages}] "
                "uint32 ledger output — the next tick would have nothing "
                "to verify its input pool against (write-time -> "
                "read-time coverage broken)"))
        return findings

    jx_on, jx_off = spec["jx_on"], spec["jx_off"]
    n_pp = _ppermute_count(jx_on.jaxpr)
    if n_pp == 0:
        findings.append(Finding(
            "J12", cell, 0,
            "surface has no ppermute to guard — the integrity check is "
            "vacuous here; fix the surface (or waive it explicitly via "
            "J12_WAIVERS with a reason)"))
    if _u32_eqn_count(jx_on.jaxpr) == 0:
        findings.append(Finding(
            "J12", cell, 0,
            "integrity=True traced to a program with NO uint32 checksum "
            "arithmetic — the wire is unguarded; every ppermute program "
            "must carry its exact frame checksums (ops.integrity) or an "
            "explicit J12_WAIVERS entry"))
    if not _has_bool_output(jx_on.jaxpr):
        findings.append(Finding(
            "J12", cell, 0,
            "integrity=True program emits no boolean verdict output — a "
            "checksum nobody can act on guards nothing (return wire_ok "
            "so the recovery machinery can gate/invalidate the step)"))
    c_on, c_off = _collect(jx_on.jaxpr), _collect(jx_off.jaxpr)
    if c_on["wire_unknown"] or c_off["wire_unknown"]:
        findings.append(Finding(
            "J12", cell, 0,
            "ppermute under a while_loop — integrity-on/off wire bytes "
            "not statically comparable (use fori_loop/scan with static "
            "trip counts)"))
    elif c_on["wire_bytes"] != c_off["wire_bytes"]:
        findings.append(Finding(
            "J12", cell, 0,
            f"integrity=True moves {c_on['wire_bytes']} ppermute bytes "
            f"but the same program with integrity off moves "
            f"{c_off['wire_bytes']} — the checksum rides the wire.  The "
            "exact byte accounting (J4/J8/J9/J11, obs counters, banked "
            "ratios) must be IDENTICAL with integrity on: checksums "
            "travel as psum'd scalars, never as payload"))
    return findings


def _j12_ring_build(codec_name: Optional[str], which: str,
                    topology: str = "flat", n_intra: int = 2,
                    sliced: bool = False, L: int = 8192):
    def build():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from ..compress import get_codec
        from ..ops import ring as ring_ops, ring_hier

        codec = get_codec(codec_name) if codec_name else None
        unit = _NDEV * (codec.pad_elems if codec else 1)
        Lp = L + (-L) % unit
        slice_elems = (Lp // _NDEV) // 2 if sliced else None
        mesh = Mesh(np.array(jax.devices()[:_NDEV]), ("dp",))

        def trace(integrity: bool):
            def f(x):
                kw: Dict[str, Any] = dict(compression=codec,
                                          integrity=integrity)
                if topology == "hier":
                    if which == "reduce_scatter":
                        return ring_hier.hier_reduce_scatter(
                            x, "dp", n_intra, slice_elems=slice_elems,
                            **kw)
                    if which == "all_gather":
                        return ring_hier.hier_all_gather(x, "dp",
                                                         n_intra, **kw)
                    return ring_hier.hier_all_reduce(
                        x, "dp", n_intra, slice_elems=slice_elems, **kw)
                if which == "reduce_scatter":
                    return ring_ops.ring_reduce_scatter(
                        x, "dp", slice_elems=slice_elems, **kw)
                if which == "all_gather":
                    return ring_ops.ring_all_gather(x, "dp", **kw)
                return ring_ops.ring_all_reduce(
                    x, "dp", slice_elems=slice_elems, **kw)

            C = Lp // _NDEV
            per_dev = C if which == "all_gather" else Lp
            out_specs = (P("dp"), P()) if integrity else P("dp")
            return jax.make_jaxpr(jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("dp"), out_specs=out_specs,
                check_vma=False)))(
                jax.ShapeDtypeStruct((_NDEV * per_dev,), jnp.float32))

        return {"kind": "wire", "jx_on": trace(True),
                "jx_off": trace(False)}
    return build


def _j12_train_build(codec_name: Optional[str], fused: bool):
    def build():
        from ..utils.config import (CollectiveConfig, MeshConfig,
                                    OptimizerConfig, TrainConfig)

        def trace(integrity: bool):
            cfg = TrainConfig(
                mesh=MeshConfig(dp=_NDEV),
                collective=CollectiveConfig(impl="ring", codec=codec_name,
                                            fused_optimizer=fused,
                                            integrity_check=integrity),
                optimizer=OptimizerConfig(kind="adamw"),
                global_batch=_BATCH, obs_metrics=False)
            phases, _, _ = _trace_dp(cfg, "dp")
            return phases[0][1]

        return {"kind": "wire", "jx_on": trace(True),
                "jx_off": trace(False)}
    return build


def _j12_reshard_build(n_src: int, n_tgt: int, codec_name: Optional[str],
                       n_flat_leaves: int, residual: bool):
    def build():
        import jax
        from jax.sharding import Mesh
        import numpy as np
        from ..compress import get_codec
        from ..parallel import reshard as reshard_lib

        live = 5000
        unit = 1 if codec_name is None else get_codec(codec_name).pad_elems
        pad_src = live + (-live) % (n_src * unit)
        pad_tgt = live + (-live) % (n_tgt * unit)
        plan = reshard_lib.make_plan(
            live, n_src, pad_src, n_tgt, pad_tgt,
            n_flat_leaves=n_flat_leaves, residual=residual)
        mesh = Mesh(np.array(jax.devices()[:plan.flat.n_union]), ("dp",))
        ops = reshard_lib.abstract_operands(plan)

        def trace(integrity: bool):
            fn = reshard_lib.lower_apply(plan, mesh, "dp", donate=True,
                                         integrity=integrity)
            return jax.make_jaxpr(fn)(*ops)

        return {"kind": "wire", "jx_on": trace(True),
                "jx_off": trace(False)}
    return build


def _j12_handoff_build(n_layers: int, kv_local: int, page_size: int,
                       head_dim: int, n_pages: int, n_move: int):
    def build():
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from ..serve import handoff as handoff_lib

        plan = handoff_lib.make_plan(
            n_layers=n_layers, kv_local=kv_local, page_size=page_size,
            head_dim=head_dim, n_pages=n_pages, n_move=n_move)
        mesh = Mesh(np.array(jax.devices()[:2]), ("rep",))

        def trace(integrity: bool):
            fn = handoff_lib.lower_apply(plan, mesh, "rep", donate=True,
                                         integrity=integrity)
            return jax.make_jaxpr(fn)(
                *handoff_lib.abstract_operands(plan, integrity=integrity))

        return {"kind": "wire", "jx_on": trace(True),
                "jx_off": trace(False)}
    return build


def _j12_decode_build():
    def build():
        import jax
        import jax.numpy as jnp
        from ..models import llama
        from ..serve import ServeConfig, ServeEngine

        cfg = llama.LlamaConfig.tiny(vocab=64, dim=32, n_layers=1,
                                     n_heads=2, n_kv_heads=1, ffn_dim=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        scfg = ServeConfig(max_reqs=2, page_size=4, n_pages=4,
                           max_pages_per_seq=2, prefill_chunk=4,
                           page_integrity=True)
        eng = ServeEngine(params, cfg, scfg)
        toks = jnp.zeros((scfg.max_reqs, 1), jnp.int32)
        table = jnp.zeros((scfg.max_reqs, scfg.max_pages_per_seq),
                          jnp.int32)
        pos = jnp.zeros((scfg.max_reqs,), jnp.int32)
        act = jnp.zeros((scfg.max_reqs,), bool)
        jx = jax.make_jaxpr(eng._decode_impl)(
            eng.pool, eng.params, toks, table, pos, act, eng.ledger)
        return {"kind": "page", "jx": jx, "n_pages": scfg.n_pages}
    return build


def j12_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs — one per ppermute-bearing program family x
    route shape (flat/hier/sliced, trainer step incl. the fused route,
    reshard, handoff) plus the decode-tick ledger surface.
    GRAFTLINT_J12_FIXTURE appends a surface from a module path exposing
    ``build()`` — the bad-fixture / exit-code hook, same contract as
    J7–J11's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("ring rs bfp", _j12_ring_build("bfp", "reduce_scatter")),
        ("ring rs bfp sliced", _j12_ring_build("bfp", "reduce_scatter",
                                               sliced=True)),
        ("ring ag int8", _j12_ring_build("int8", "all_gather")),
        ("ring ar none", _j12_ring_build(None, "all_reduce")),
        ("hier rs ni=2 bfp", _j12_ring_build("bfp", "reduce_scatter",
                                             topology="hier", n_intra=2)),
        ("hier ar ni=4 int8", _j12_ring_build("int8", "all_reduce",
                                              topology="hier", n_intra=4)),
        ("train step adamw bfp", _j12_train_build("bfp", False)),
        ("train step fused-opt bfp", _j12_train_build("bfp", True)),
        ("reshard dp8->dp4 adamw", _j12_reshard_build(8, 4, None, 3,
                                                      False)),
        ("reshard dp8->dp3 topk+EF", _j12_reshard_build(8, 3, "topk", 2,
                                                        True)),
        ("handoff gqa 3 pages", _j12_handoff_build(2, 4, 4, 8, 10, 3)),
        ("decode tick page ledger", _j12_decode_build()),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J12_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j12_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j12(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j12_surfaces():
        waiver = J12_WAIVERS.get(name)
        if waiver:
            # an explicit waiver is the ONLY sanctioned skip — loud in
            # the sweep output, greppable in review, and pinned EMPTY
            # for the shipped tree by tests/test_lint.py
            if verbose:
                print(f"[graftlint:jaxpr] integrity {name}: WAIVED "
                      f"({waiver})")
            continue
        try:
            fs = check_integrity_program(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J12", f"jaxpr[integrity {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] integrity {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J13 — the adaptive-training candidate set (tune.adapt) must be traced
# UP FRONT and a runtime plan switch must cause ZERO new traces — the
# J10 counted-trace discipline applied to training.  The tempting-but-
# wrong implementation compiles the target plan lazily "when we need
# it": the switch then pays a compile spike exactly when the job is
# already degraded (the regime shift that triggered it), and every
# switch after that retraces again.  Like J10 this rule runs CONCRETELY:
# a tiny AdaptiveTrainer (fixture calibration — zero banked-artifact
# dependence) is built, prewarmed, stepped, forced through a plan switch
# (the deterministic inject_shift seam; the chaos `slowdown@collective`
# cell proves the measured detection path), and stepped again; every
# candidate's step must have traced EXACTLY once and the total trace
# count must not move across the switch.  A run that performs no switch
# (or has a one-plan "set") proves nothing and is itself a finding.
# ---------------------------------------------------------------------------

def _j13_adaptive_build() -> Callable:
    def run() -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from ..models import mlp
        from ..parallel import mesh as mesh_lib
        from ..tune import adapt as adapt_lib
        from ..tune.calibration import fixture_calibration
        from ..utils.config import (AdaptConfig, CollectiveConfig,
                                    MeshConfig, MLPConfig,
                                    OptimizerConfig, TrainConfig)

        mcfg = MLPConfig(layer_sizes=_LAYERS, dtype="float32")
        # THE shared fixture regime (tune.calibration.fixture_calibration
        # — also the adapt chaos cells'): a fast wire so plan 0 is the
        # uncompressed ring and the injected regime shift has a cheaper
        # wire format to move to — deterministic, no banked artifacts
        calib = fixture_calibration()
        cfg = TrainConfig(
            iters=8, global_batch=_BATCH, mesh=MeshConfig(dp=_NDEV),
            collective=CollectiveConfig(impl="ring", codec="auto"),
            optimizer=OptimizerConfig(),
            adapt=AdaptConfig(enabled=True, n_candidates=2,
                              live_calibration=False, warmup_steps=2,
                              cooldown_steps=2))
        at = adapt_lib.AdaptiveTrainer(
            lambda p, b: mlp.loss_fn(p, b, mcfg),
            mesh_lib.make_mesh(cfg.mesh), cfg, calibration=calib)
        params = mlp.init(jax.random.PRNGKey(0), mcfg)
        state = at.init_state(params)
        r = np.random.default_rng(0)
        batch = at.shard_batch((
            jnp.asarray(r.standard_normal((_BATCH, _LAYERS[0]))
                        .astype(np.float32)),
            jnp.asarray(r.integers(0, _LAYERS[-1], _BATCH)
                        .astype(np.int32))))
        for _ in range(3):
            state, _loss = at.step(state, batch)
        # the forced regime shift: the wire now behaves ~dead-slow, the
        # re-priced argmin moves to a compressed candidate
        at.controller.inject_shift(1e-4, step=3)
        for _ in range(3):
            state, _loss = at.step(state, batch)
        return {
            "candidates": at.trace_counts(),
            "switches": at.switches,
            "recompiles_across_switch": at.recompiles_across_switch,
            "_exercised": int(at.switches >= 1 and len(at.plans) >= 2),
        }
    return run


def check_adaptive_traces(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J13 surface.  ``build()`` returns a zero-arg runner
    executing a scripted adaptive run and returning ``candidates``
    ({plan label: step trace count}), ``switches``,
    ``recompiles_across_switch`` and optionally ``_exercised`` (falsy =
    the run proved nothing)."""
    findings: List[Finding] = []
    cell = f"jaxpr[adapt {name}]"
    out = dict(build()())
    if not out.pop("_exercised", 1):
        findings.append(Finding(
            "J13", cell, 0,
            "the scripted adaptive run performed no plan switch (or the "
            "candidate set has fewer than 2 plans) — the counted-trace "
            "check is vacuous; widen the scenario"))
    for label, n in sorted(out.get("candidates", {}).items()):
        if n == 0:
            findings.append(Finding(
                "J13", cell, 0,
                f"candidate plan '{label}' was NEVER traced — the "
                "candidate set must be compiled up front at "
                "construction; a lazily-compiled plan pays its compile "
                "spike at the switch, exactly when the job is already "
                "degraded by the regime shift"))
        elif n > 1:
            findings.append(Finding(
                "J13", cell, 0,
                f"candidate plan '{label}' traced {n}x across the "
                "scripted run — a plan switch must replay the "
                "pre-compiled program, never retrace it (slot the "
                "switch-shaped state into the prewarm, or the jit cache "
                "misses on sharding/weak-type drift)"))
    rec = out.get("recompiles_across_switch", 0)
    if rec:
        findings.append(Finding(
            "J13", cell, 0,
            f"{rec} new trace(s) appeared across the plan switch — the "
            "switch must cause ZERO new traces (the J10 counted-trace "
            "discipline applied to training); trace every candidate's "
            "step AND gather programs at construction"))
    return findings


def j13_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs.  GRAFTLINT_J13_FIXTURE appends a surface
    from a module path exposing ``build()`` — the bad-fixture /
    exit-code hook, same contract as J7–J12's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("candidate-set switch schedule", _j13_adaptive_build),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J13_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j13_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j13(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j13_surfaces():
        try:
            fs = check_adaptive_traces(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J13", f"jaxpr[adapt {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] adapt {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


# ---------------------------------------------------------------------------
# J14 — durable-state integrity (utils.checkpoint).  The J12 discipline
# applied to disk: every restore path must AUDIT the stored bytes
# against the manifest's exact checksums, so a single flipped stored
# bit either REFUSES (CheckpointIntegrityError) or peer-repairs
# bit-exactly — never restores silently; the walk-back
# (restore_latest_verified / latest_step(verified=True)) must land on
# the previous verified step past a torn one; and the peer-repair pair
# transfer program must be callback-free, donate its source operand and
# move EXACTLY the shard bytes (the J8/J11 accounting applied to the
# repair wire).  Like J10/J13 the rule runs CONCRETELY: each surface
# saves a checkpoint into a temp dir, damages one stored bit, and
# drives the real restore path; a surface whose damage provably landed
# nowhere proves nothing and is itself a finding.  J14_WAIVERS is the
# only sanctioned skip and the shipped tree keeps it EMPTY
# (tests/test_lint.py pins that).
# ---------------------------------------------------------------------------

# name -> reason.  SHIPPED TREE: EMPTY — every restore path is audited.
J14_WAIVERS: Dict[str, str] = {}


def _j14_refuse_build() -> Callable:
    def run() -> Dict[str, Any]:
        import os
        import tempfile
        import numpy as np
        from ..utils import checkpoint as ckpt_lib
        with tempfile.TemporaryDirectory(prefix="j14_refuse_") as d:
            c = ckpt_lib.Checkpointer(d)      # no mirror: refusal path
            golden = np.random.default_rng(0).standard_normal(256) \
                .astype(np.float32)
            c.save(1, {"w": golden})
            ckpt_lib.flip_stored_bit(
                os.path.join(c._path(1), "leaf_00000.npy"))
            out: Dict[str, Any] = {"surface": "Checkpointer.restore",
                                   "detected": 0, "silently_restored": 0,
                                   "_exercised": 1}
            try:
                tree = c.restore(1)
                # a byte flipped on disk and restore handed bytes back:
                # silent restore whether or not they happen to differ
                out["silently_restored"] = 1
                out["_exercised"] = int(
                    not np.array_equal(tree["w"], golden))
            except ckpt_lib.CheckpointIntegrityError:
                out["detected"] = 1
            return out
    return run


def _j14_repair_build() -> Callable:
    def run() -> Dict[str, Any]:
        import os
        import tempfile
        import numpy as np
        from ..utils import checkpoint as ckpt_lib
        with tempfile.TemporaryDirectory(prefix="j14_repair_") as d:
            c = ckpt_lib.Checkpointer(d, shards=4, mirror=True)
            golden = np.random.default_rng(1).standard_normal(1024) \
                .astype(np.float32)
            c.save(1, {"w": golden})
            ckpt_lib.flip_stored_bit(
                os.path.join(c._path(1), "leaf_00000.s01.npy"))
            rep = c.audit_step(1, repair=True)
            shard_bytes = golden[256:512].nbytes
            out: Dict[str, Any] = {
                "surface": "Checkpointer.restore(repair)",
                "detected": int(bool(rep.repaired or rep.failures)),
                "silently_restored": int(not rep.repaired
                                         and not rep.failures),
                "repaired": len(rep.repaired),
                "bit_exact": int(rep.restorable
                                 and np.array_equal(rep.tree["w"],
                                                    golden)),
                "runtime_wire_bytes": rep.repair_wire_bytes,
                "declared_bytes": shard_bytes,
                "_exercised": 1,
            }
        # static half: the pair transfer program itself (J8/J11-style
        # accounting on the repair wire)
        import jax
        fn, _mesh = ckpt_lib.pair_transfer_fn(shard_bytes)
        if fn is None:
            out["_exercised"] = 0       # single-device runtime
            return out
        jx = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((2, shard_bytes), np.uint8))
        co = _collect(jx.jaxpr)
        out["callbacks"] = co["callbacks"]
        out["wire_bytes"] = co["wire_bytes"]
        donated = co["donated"] or ()
        out["donated"] = int(sum(donated)) if donated else 0
        return out
    return run


def _j14_walkback_build() -> Callable:
    def run() -> Dict[str, Any]:
        import os
        import tempfile
        import numpy as np
        from ..utils import checkpoint as ckpt_lib
        with tempfile.TemporaryDirectory(prefix="j14_walk_") as d:
            c = ckpt_lib.Checkpointer(d)
            g1 = np.random.default_rng(2).standard_normal(128) \
                .astype(np.float32)
            c.save(1, {"w": g1})
            c.save(2, {"w": g1 + 1.0})
            # tear the newest step's manifest (the kill-during-save
            # shape)
            with open(os.path.join(c._path(2), ckpt_lib.MANIFEST_FILE),
                      "w") as f:
                f.write("{\"format\": 2, \"truncat")
            step, tree = c.restore_latest_verified()
            return {
                "surface": "Checkpointer.restore_latest_verified",
                "detected": int(step == 1),
                "silently_restored": int(step == 2),
                "bit_exact": int(np.array_equal(tree["w"], g1)),
                "verified_step": int(c.latest_step(verified=True) or -1),
                "_exercised": int(c.latest_step() == 2),
            }
    return run


def check_restore_audit(name: str, build: Callable) -> List[Finding]:
    """Evaluate one J14 surface.  ``build()`` returns a zero-arg runner
    that saves/damages/restores a real checkpoint and reports:
    ``detected`` (the damage refused, repaired or walked past),
    ``silently_restored`` (damaged bytes handed to the caller — THE
    violation), optional ``repaired``/``bit_exact``/``wire_bytes``/
    ``declared_bytes``/``callbacks``/``donated`` for the repair
    program, and ``_exercised`` (falsy = the damage provably landed
    nowhere, which proves nothing)."""
    findings: List[Finding] = []
    cell = f"jaxpr[ckpt {name}]"
    out = dict(build()())
    if not out.pop("_exercised", 1):
        findings.append(Finding(
            "J14", cell, 0,
            "the scripted damage landed nowhere (or the runtime cannot "
            "exercise the surface) — the audit check is vacuous; widen "
            "the scenario"))
        return findings
    if out.get("silently_restored"):
        findings.append(Finding(
            "J14", cell, 0,
            f"{out.get('surface', name)} handed back bytes from a "
            "checkpoint with a flipped stored bit without refusing or "
            "repairing — the disk-corruption blind spot (a corrupt "
            "master silently becomes the restore target); every restore "
            "path must audit against the manifest checksums"))
    elif not out.get("detected"):
        findings.append(Finding(
            "J14", cell, 0,
            f"{out.get('surface', name)} neither detected nor survived "
            "the stored-bit damage — the audit/walk-back contract is "
            "broken"))
    if "bit_exact" in out and not out["bit_exact"]:
        findings.append(Finding(
            "J14", cell, 0,
            "the repaired/walked-back state is not bit-identical to the "
            "uncorrupted golden — repair must hand back EXACTLY the "
            "bytes the manifest describes"))
    if "repaired" in out and out["repaired"] < 1:
        findings.append(Finding(
            "J14", cell, 0,
            "a corrupt primary with a clean peer mirror was not "
            "repaired — the peer-repair tier never fired"))
    if "wire_bytes" in out and out["wire_bytes"] != out["declared_bytes"]:
        findings.append(Finding(
            "J14", cell, 0,
            f"the pair repair program's ppermute operands move "
            f"{out['wire_bytes']} bytes but the shard is "
            f"{out['declared_bytes']} — the repair wire accounting "
            "(CKPT_BENCH repair_wire_bytes) is lying"))
    if "runtime_wire_bytes" in out and \
            out["runtime_wire_bytes"] != out["declared_bytes"]:
        findings.append(Finding(
            "J14", cell, 0,
            f"the executed repair recorded {out['runtime_wire_bytes']} "
            f"wire bytes for a {out['declared_bytes']}-byte shard"))
    if out.get("callbacks"):
        findings.append(Finding(
            "J14", cell, 0,
            f"{out['callbacks']} callback primitive(s) inside the pair "
            "repair program — the transfer must be pure device code"))
    if "donated" in out and out["donated"] < 1:
        findings.append(Finding(
            "J14", cell, 0,
            "the pair repair program does not donate its source operand "
            "— repair would hold two copies of the shard in memory"))
    return findings


def j14_surfaces() -> List[Tuple[str, Callable]]:
    """(name, build) pairs.  GRAFTLINT_J14_FIXTURE appends a surface
    from a module path exposing ``build()`` — the bad-fixture /
    exit-code hook, same contract as J7–J13's."""
    surfaces: List[Tuple[str, Callable]] = [
        ("refuse unmirrored bit flip", _j14_refuse_build),
        ("peer-repair mirrored shard", _j14_repair_build),
        ("walk back past torn step", _j14_walkback_build),
    ]
    import os
    fixture = os.environ.get("GRAFTLINT_J14_FIXTURE")
    if fixture:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_j14_fixture",
                                                      fixture)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surfaces.append((f"fixture:{os.path.basename(fixture)}",
                         mod.build))
    return surfaces


def run_j14(verbose: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for name, build in j14_surfaces():
        waiver = J14_WAIVERS.get(name)
        if waiver:
            if verbose:
                print(f"[graftlint:jaxpr] ckpt {name}: WAIVED ({waiver})")
            continue
        try:
            fs = check_restore_audit(name, build)
        except Exception as e:  # noqa: BLE001 — a surface must fail LOUDLY
            fs = [Finding("J14", f"jaxpr[ckpt {name}]", 0,
                          f"surface failed to evaluate: "
                          f"{type(e).__name__}: {str(e)[:300]}")]
        findings.extend(fs)
        if verbose:
            print(f"[graftlint:jaxpr] ckpt {name}: "
                  f"{'FAIL' if fs else 'ok'}")
    return findings


def sweep_grid() -> List[Tuple[Optional[str], str, bool]]:
    """(codec, trainer, obs) cells — registry-driven, so a future codec
    is auto-covered; None = uncompressed ring baseline."""
    from ..compress import available_codecs
    cells = []
    for codec in (None,) + tuple(available_codecs()):
        for trainer in _TRAINERS:
            for obs in (False, True):
                cells.append((codec, trainer, obs))
    return cells


# fused-optimizer donation cells (the acceptance gate of the fused-ring
# issue: moments + master params must stay donated on the fused
# TrainState/FSDPState) — a focused extra sweep rather than a fourth grid
# axis, so the grid's public (codec, trainer, obs) triple shape is stable
_FUSED_OPT_CELLS = ((None, "DPTrainer"), ("bfp", "DPTrainer"),
                    ("topk", "DPTrainer"), (None, "FSDPTrainer"),
                    ("bfp", "FSDPTrainer"))


def run_fused_opt_cells(verbose: bool = False) -> List[Finding]:
    from ..utils.config import (CollectiveConfig, MeshConfig,
                                OptimizerConfig, TrainConfig)
    findings: List[Finding] = []
    for codec_name, trainer in _FUSED_OPT_CELLS:
        cell = f"jaxpr[fused-opt {codec_name or 'none'} x {trainer}]"
        trace_fn, axis = _TRAINERS[trainer]
        try:
            cfg = TrainConfig(
                mesh=MeshConfig(**{axis: _NDEV}),
                collective=CollectiveConfig(impl="ring", codec=codec_name,
                                            fused_optimizer=True),
                optimizer=OptimizerConfig(kind="adamw"),
                global_batch=_BATCH, obs_metrics=False)
            phases, L, n = trace_fn(cfg, axis)
            cell_findings = _check_cell(cell, trainer, codec_name, False,
                                        phases, L, n, mesh_axes=(axis,))
        except Exception as e:  # noqa: BLE001 — a cell must fail LOUDLY
            cell_findings = [Finding(
                "J6", cell, 0, f"cell failed to trace: {type(e).__name__}: "
                f"{str(e)[:300]}")]
        findings.extend(cell_findings)
        if verbose:
            status = "FAIL" if cell_findings else "ok"
            print(f"[graftlint:jaxpr] {cell}: {status}")
    return findings


def run_sweep(verbose: bool = False) -> List[Finding]:
    _require_cpu_mesh()
    from ..compress import available_codecs
    from ..utils.config import (CollectiveConfig, MeshConfig, TrainConfig)

    findings: List[Finding] = []
    grid = sweep_grid()
    grid_codecs = {c for c, _, _ in grid}
    for codec_name, trainer, obs in grid:
        cell = (f"jaxpr[{codec_name or 'none'} x {trainer} x "
                f"obs={'on' if obs else 'off'}]")
        trace_fn, axis = _TRAINERS[trainer]
        mesh_kwargs = {axis: _NDEV}
        try:
            # config construction is inside the try: an unconstructible
            # registered codec must fail as a LOUD J6 cell, not a crash
            cfg = TrainConfig(
                mesh=MeshConfig(**mesh_kwargs),
                collective=CollectiveConfig(impl="ring", codec=codec_name),
                global_batch=_BATCH, obs_metrics=obs)
            phases, L, n = trace_fn(cfg, axis)
            cell_findings = _check_cell(
                cell, trainer, codec_name, obs, phases, L, n,
                mesh_axes=(axis,))
        except Exception as e:  # noqa: BLE001 — a cell must fail LOUDLY
            cell_findings = [Finding(
                "J6", cell, 0, f"cell failed to trace: {type(e).__name__}: "
                f"{str(e)[:300]}")]
        findings.extend(cell_findings)
        if verbose:
            status = "FAIL" if cell_findings else "ok"
            print(f"[graftlint:jaxpr] {cell}: {status}")
    # coverage: the grid snapshot was taken from the registry BEFORE any
    # cell traced; a codec registered during the sweep (e.g. by an import
    # a trainer pulls in) would otherwise be silently missed.  Same-set
    # coverage of the snapshot itself is asserted by tests/test_lint.py.
    missing = set(available_codecs()) - grid_codecs
    if missing:
        findings.append(Finding(
            "J6", "jaxpr[coverage]", 0,
            f"codec(s) registered after the grid snapshot, never swept: "
            f"{sorted(missing)} — re-run the sweep"))
    findings.extend(run_fused_opt_cells(verbose=verbose))
    findings.extend(run_j7(verbose=verbose))
    findings.extend(run_j8(verbose=verbose))
    findings.extend(run_j9(verbose=verbose))
    findings.extend(run_j10(verbose=verbose))
    findings.extend(run_j11(verbose=verbose))
    findings.extend(run_j12(verbose=verbose))
    findings.extend(run_j13(verbose=verbose))
    findings.extend(run_j14(verbose=verbose))
    return findings
