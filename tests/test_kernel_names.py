"""The names the program's kernels and phases carry (obs/names.py): the
table itself, every `pl.pallas_call` of the package held to it, the names in
each site's traced call and the scopes in the lowered step, and the
benchmark's rules reading them back out of event names as the v5e's trace
prints them.

The same names in the chip compiler's own text are checked in
tests/test_tpu_compile.py, beside the other compiles for the described
v5e:2x2 (one file, so one worker loads the TPU compiler).
"""

import ast
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import fpga_ai_nic_tpu
from benchmark import kernel_events, trace_reduce
from fpga_ai_nic_tpu import optim
from fpga_ai_nic_tpu.compress import int8
from fpga_ai_nic_tpu.obs import names
from fpga_ai_nic_tpu.ops import (bfp_pallas, flash_pallas, grouped_pallas,
                                 paged_attend_pallas, ring_pallas, ssd_pallas)
from fpga_ai_nic_tpu.utils.config import (
    BFPConfig, CollectiveConfig, MeshConfig, MLPConfig, OptimizerConfig,
    OptimizerSpec, TrainConfig)

PACKAGE = os.path.dirname(os.path.abspath(fpga_ai_nic_tpu.__file__))
ROOT = os.path.dirname(PACKAGE)
TILE = 16 * 128


# -- the table ---------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(names.KERNELS))
def test_name_survives_sanitising(name):
    """`name=` may hold no dot: the table's name goes in with `_`, stays
    an identifier, and the metadata keeps the name as the table has it."""
    layer, what = names.KERNELS[name]
    assert re.fullmatch(r"[a-z]+(?:_[a-z]+)*\.[a-z0-9_]+", name)
    assert layer == name.split(".")[0] in ("ring", "codec", "attention",
                                           "moe", "ssm_scan")
    assert what and "\n" not in what
    kw = names.kernel(name)
    assert kw["name"] == name.replace(".", "_") and kw["name"].isidentifier()
    assert kw["metadata"] == {names.METADATA_KEY: name}


def test_names_stay_unique_after_sanitising():
    plain = [n.replace(".", "_") for n in names.KERNELS]
    assert len(set(plain)) == len(plain)
    assert all(s.startswith("ainic.") for s in names.SCOPES)


def test_unknown_names_raise():
    with pytest.raises(KeyError, match="obs.names.KERNELS"):
        names.kernel("ring.new_kernel")
    with pytest.raises(KeyError, match="obs.names.SCOPES"):
        names.scope("ainic.backward")


def test_extra_rides_in_the_metadata_as_strings():
    kw = names.kernel("ring.rs_update", opt="adamw", ablate=None)
    assert kw["metadata"] == {names.METADATA_KEY: "ring.rs_update",
                              "opt": "adamw"}
    assert names.kernel("ring.rs", ablate="rdma")["metadata"]["ablate"] \
        == "rdma"


# -- no pallas_call in the package without a name ----------------------------

def _pallas_calls():
    """(file, line, the `kernel(...)` call splatted into it or None) of
    every `<x>.pallas_call(...)` in the package, from the syntax tree."""
    found = []
    for root, _, files in os.walk(PACKAGE):
        for f in files:
            # tests/test_lint.py copies its bad fixtures into the package
            # for a moment, perhaps in another worker: not the program's
            if not f.endswith(".py") or f.startswith("zz_graftlint_fixture"):
                continue
            path = os.path.join(root, f)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call":
                    splat = [k.value for k in node.keywords if k.arg is None
                             and isinstance(k.value, ast.Call)
                             and getattr(k.value.func, "id", None) == "kernel"]
                    found.append((os.path.relpath(path, ROOT), node.lineno,
                                  splat[0] if splat else None))
    return found


def _names_of(call: ast.Call):
    """The table names a `kernel(...)` call can pass: a constant, or one
    of the two arms of `a if cond else b`."""
    arg = call.args[0]
    arms = [arg.body, arg.orelse] if isinstance(arg, ast.IfExp) else [arg]
    assert all(isinstance(a, ast.Constant) for a in arms), ast.dump(arg)
    return [a.value for a in arms]


def test_every_pallas_call_in_the_package_takes_its_name_from_the_table():
    calls = _pallas_calls()
    unnamed = [f"{f}:{line}" for f, line, splat in calls if splat is None]
    assert not unnamed, f"pl.pallas_call without **kernel(...): {unnamed}"
    assert len(calls) == 17
    used = [n for _, _, splat in calls for n in _names_of(splat)]
    assert sorted(used) == sorted(names.KERNELS)      # each once, none spare


# -- each site's traced call holds its name ----------------------------------

def _pallas_metadata(fn, *args):
    """`metadata` of every pallas_call equation in fn's jaxpr, nested
    jaxprs (jit, shard_map, custom_vjp, loops) included."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(dict(eqn.params["metadata"]))
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _loopback_rs(stream, opt_kind=None, ablate=None):
    """The reduce-scatter call of one site in one-chip loopback, as
    chip_smoke.py runs it: (fn, args)."""
    call = ring_pallas._rs_stream_call if stream else ring_pallas._rs_call
    rows = 4 * 8192 // 128
    kw = dict(loopback_n=4, ablate=ablate)
    args = [_f32(rows, 128)]
    if opt_kind:
        ns = OptimizerSpec(kind=opt_kind).n_state
        hyper = optim.fused_hyperparams(
            OptimizerConfig(kind=opt_kind, learning_rate=1e-3),
            jnp.zeros((), jnp.int32))
        args += [_f32(rows // 4, 128)] * (1 + ns)

        def fn(x, w, *st):
            return call(x, None, 16, 8, "nearest", 8192, True, 7,
                        opt_kind=opt_kind, w2=w, opt_st=tuple(st),
                        hyper=hyper, **kw)
        return fn, args
    return (lambda x: call(x, None, 16, 8, "nearest", 8192, True, 7, **kw),
            args)


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: flash_pallas.flash_attention(
        *a, interpret=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))(
            q, k, v)


def _grouped_grad(x, w):
    """The grouped product forward and both cotangents: `moe.gmm` twice
    (the rows' cotangent by the transposed weights), `moe.tgmm` once."""
    sizes = jnp.array([20, 0, 12], jnp.int32)
    y, pull = jax.vjp(lambda x, w: grouped_pallas.grouped_dot(x, w, sizes),
                      x, w)
    return pull(y)


def _scan_grad(x, step, a, b, c):
    """The chunked scan forward and its gradient: `ssm_scan.fwd`, the
    chunks' states again (`ssm_scan.states`) and `ssm_scan.bwd`."""
    y, pull = jax.vjp(lambda *t: ssd_pallas.ssd(*t, 8), x, step, a, b, c)
    return pull(y)


_SCAN = [_f32(1, 16, 4, 8), _f32(1, 16, 4), _f32(4), _f32(1, 16, 2, 8),
         _f32(1, 16, 2, 8)]
_QKV = [jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.bfloat16)] * 3
_GROUPED = [_f32(48, 128), _f32(3, 128, 256)]
SITES = {
    "ring.rs": lambda: _loopback_rs(False),
    "ring.rs_update": lambda: _loopback_rs(False, "sgd"),
    "ring.rs_stream": lambda: _loopback_rs(True),
    "ring.rs_update_stream": lambda: _loopback_rs(True, "adamw"),
    "ring.ag": lambda: (lambda x: ring_pallas._ag_call(
        x, None, 16, 8, "nearest", True, 8, loopback_n=4), [_f32(64, 128)]),
    "ring.ag_stream": lambda: (lambda x: ring_pallas._ag_stream_call(
        x, None, 16, 8, "nearest", 8192, True, 8, loopback_n=4),
        [_f32(128, 128)]),
    "codec.bfp_encode": lambda: (lambda x: bfp_pallas.bfp_encode_inline(
        x, interpret=True), [_f32(64 * TILE)]),
    "codec.bfp_decode": lambda: (lambda m, s: bfp_pallas.bfp_decode_inline(
        m, s, interpret=True),
        [jax.ShapeDtypeStruct((64 * TILE,), jnp.int8),
         jax.ShapeDtypeStruct((64 * 128,), jnp.int8)]),
    "codec.int8_encode": lambda: (lambda x: int8.int8_encode_pallas(
        x, rounding="nearest", interpret=True), [_f32(64 * TILE)]),
    "codec.int8_decode": lambda: (lambda q, s: int8.int8_decode_pallas(
        q, s, interpret=True),
        [jax.ShapeDtypeStruct((64 * TILE,), jnp.int8),
         jax.ShapeDtypeStruct((64 * 128,), jnp.bfloat16)]),
    "attention.flash_fwd": lambda: (_flash_grad, _QKV),
    "attention.flash_dq": lambda: (_flash_grad, _QKV),
    "attention.flash_dkv": lambda: (_flash_grad, _QKV),
    "moe.gmm": lambda: (_grouped_grad, _GROUPED),
    "moe.tgmm": lambda: (_grouped_grad, _GROUPED),
    "ssm_scan.fwd": lambda: (_scan_grad, _SCAN),
    "ssm_scan.states": lambda: (_scan_grad, _SCAN),
    "ssm_scan.bwd": lambda: (_scan_grad, _SCAN),
    "attention.paged": lambda: (
        lambda q, pk, pv, pt, pos: paged_attend_pallas.paged_gather_attend(
            q, pk, pv, pt, pos, page_size=16, interpret=True),
        [_f32(2, 4, 1, 32), _f32(8, 2, 16, 32), _f32(8, 2, 16, 32),
         jax.ShapeDtypeStruct((2, 4), jnp.int32),
         jax.ShapeDtypeStruct((2,), jnp.int32)]),
}


def test_the_sites_cover_the_table():
    assert sorted(SITES) == sorted(names.KERNELS)


@pytest.mark.parametrize("name", sorted(SITES))
def test_site_passes_its_name_to_pallas_call(name):
    fn, args = SITES[name]()
    got = [m[names.METADATA_KEY] for m in _pallas_metadata(fn, *args)]
    assert name in got
    assert all(g in names.KERNELS for g in got)


@pytest.mark.parametrize("stream,opt", [(False, None), (True, "sgd")])
def test_ablated_kernel_says_so_in_its_metadata(stream, opt):
    """An ablated kernel is never read as the real one: same name, and the
    stage beside it."""
    fn, args = _loopback_rs(stream, opt, ablate="rdma")
    (meta,) = _pallas_metadata(fn, *args)
    assert meta["ablate"] == "rdma" and meta[names.METADATA_KEY].startswith(
        "ring.rs")
    assert meta.get("opt") == opt
    fn, args = _loopback_rs(stream, opt)
    assert "ablate" not in _pallas_metadata(fn, *args)[0]


# -- the phases of the step --------------------------------------------------

@pytest.fixture(scope="module", params=["fused", "unfused"])
def lowered_step(request):
    """DPTrainer's step for a tiny MLP on four virtual devices, lowered
    (not compiled), as text with locations: where named scopes ride."""
    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh

    fused = request.param == "fused"
    mcfg = MLPConfig(layer_sizes=(128,) * 3)
    cfg = TrainConfig(
        global_batch=8, mesh=MeshConfig(dp=4),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=fused),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, mcfg),
                   make_mesh(cfg.mesh, devices=jax.devices()[:4]), cfg)
    state = tr.init_state(mlp.init(jax.random.PRNGKey(0), mcfg))
    batch = tr.shard_batch((np.zeros((8, 128), np.float32),
                            np.zeros((8,), np.int32)))
    return tr.step_fn.lower(state, batch).as_text(debug_info=True)


# the scopes a model brings into ainic.fwd_bwd; the others are the step's own
MODEL_SCOPES = sorted(s for s in names.SCOPES
                      if s.startswith(("ainic.mla", "ainic.moe.",
                                       "ainic.attn.", "ainic.conv",
                                       "ainic.gqa", "ainic.ssm")))


@pytest.mark.parametrize("scope", sorted(set(names.SCOPES)
                                         - set(MODEL_SCOPES)))
def test_lowered_step_holds_the_scope(lowered_step, scope):
    """Metadata only: the scope stands in the instructions' locations
    (`op_name` once compiled), e.g. jit(_step)/.../ainic.fwd_bwd/..."""
    assert re.search(r"[/\"]%s/" % re.escape(scope), lowered_step)


@pytest.fixture(scope="module")
def lowered_glm_step():
    """DPTrainer's step for a tiny models/glm_moe.py, lowered as text."""
    from fpga_ai_nic_tpu.models import glm_moe
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh

    mcfg = glm_moe.GlmMoeConfig.tiny(held=(0, 1, 2))
    cfg = TrainConfig(
        global_batch=2, mesh=MeshConfig(dp=2),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=True),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=1e-3))
    tr = DPTrainer(lambda p, b: glm_moe.loss_fn(p, b, mcfg, dp_axis="dp"),
                   make_mesh(cfg.mesh, devices=jax.devices()[:2]), cfg)
    state = tr.init_state(glm_moe.init(jax.random.PRNGKey(0), mcfg))
    batch = tr.shard_batch((np.zeros((2, 16), np.int32),
                            np.zeros((2, 16), np.int32)))
    return tr.step_fn.lower(state, batch).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered_lfm2_step():
    """DPTrainer's step for a tiny models/lfm2_moe.py, lowered as text."""
    from fpga_ai_nic_tpu.models import lfm2_moe
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh

    mcfg = lfm2_moe.Lfm2MoeConfig.tiny(held=(0, 1, 2))
    cfg = TrainConfig(
        global_batch=2, mesh=MeshConfig(dp=2),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=True),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=1e-3))
    tr = DPTrainer(lambda p, b: lfm2_moe.loss_fn(p, b, mcfg, dp_axis="dp"),
                   make_mesh(cfg.mesh, devices=jax.devices()[:2]), cfg)
    state = tr.init_state(lfm2_moe.init(jax.random.PRNGKey(0), mcfg))
    batch = tr.shard_batch((np.zeros((2, 16), np.int32),
                            np.zeros((2, 16), np.int32)))
    return tr.step_fn.lower(state, batch).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered_nemotron_step():
    """DPTrainer's step for a tiny models/nemotron_h.py, lowered as text."""
    from fpga_ai_nic_tpu.models import nemotron_h
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh

    mcfg = nemotron_h.NemotronHConfig.tiny(held=(0, 1, 2))
    cfg = TrainConfig(
        global_batch=2, mesh=MeshConfig(dp=2),
        collective=CollectiveConfig(impl="ring", compression=BFPConfig(),
                                    fused_optimizer=True),
        optimizer=OptimizerConfig(kind="adamw", learning_rate=1e-3))
    tr = DPTrainer(lambda p, b: nemotron_h.loss_fn(p, b, mcfg, dp_axis="dp"),
                   make_mesh(cfg.mesh, devices=jax.devices()[:2]), cfg)
    state = tr.init_state(nemotron_h.init(jax.random.PRNGKey(0), mcfg))
    batch = tr.shard_batch((np.zeros((2, 16), np.int32),
                            np.zeros((2, 16), np.int32)))
    return tr.step_fn.lower(state, batch).as_text(debug_info=True)


# which model brings which scope into its step
GLM_SCOPES = ["ainic.attn.bwd", "ainic.attn.fwd", "ainic.mla",
              "ainic.moe.experts", "ainic.moe.route", "ainic.moe.shared"]
LFM2_SCOPES = ["ainic.attn.bwd", "ainic.attn.fwd", "ainic.conv", "ainic.gqa",
               "ainic.moe.experts", "ainic.moe.route"]
NEMOTRON_SCOPES = ["ainic.attn.bwd", "ainic.attn.fwd", "ainic.gqa",
                   "ainic.moe.experts", "ainic.moe.route", "ainic.moe.shared",
                   "ainic.ssm", "ainic.ssm.scan"]


def test_the_model_scopes_are_the_ten_the_table_lists():
    assert MODEL_SCOPES == sorted(set(GLM_SCOPES) | set(LFM2_SCOPES)
                                  | set(NEMOTRON_SCOPES))
    assert len(MODEL_SCOPES) == 10


@pytest.mark.parametrize("scope", GLM_SCOPES)
def test_lowered_glm_step_holds_the_model_scope(lowered_glm_step, scope):
    """As the step's own: the scanned layer's body keeps its own locations
    ("ainic.moe.route/dot_general"), inside ainic.fwd_bwd's."""
    assert re.search(r"[/\"]%s/" % re.escape(scope), lowered_glm_step)
    assert re.search(r"[/\"]ainic\.fwd_bwd/", lowered_glm_step)


@pytest.mark.parametrize("scope", LFM2_SCOPES)
def test_lowered_lfm2_step_holds_the_model_scope(lowered_lfm2_step, scope):
    assert re.search(r"[/\"]%s/" % re.escape(scope), lowered_lfm2_step)
    assert re.search(r"[/\"]ainic\.fwd_bwd/", lowered_lfm2_step)


@pytest.mark.parametrize("scope", sorted(set(MODEL_SCOPES)
                                         - set(LFM2_SCOPES)))
def test_lowered_lfm2_step_holds_no_scope_of_another_model(
        lowered_lfm2_step, scope):
    """No latent attention and no shared expert in this model."""
    assert not re.search(r"[/\"]%s/" % re.escape(scope), lowered_lfm2_step)


@pytest.mark.parametrize("scope", NEMOTRON_SCOPES)
def test_lowered_nemotron_step_holds_the_model_scope(lowered_nemotron_step,
                                                     scope):
    """The scan's scope lies inside the mixer's: .../ainic.ssm/ainic.ssm.scan/"""
    assert re.search(r"[/\"]%s/" % re.escape(scope), lowered_nemotron_step)
    assert re.search(r"[/\"]ainic\.fwd_bwd/", lowered_nemotron_step)
    assert re.search(r"ainic\.ssm/ainic\.ssm\.scan/", lowered_nemotron_step)


@pytest.mark.parametrize("scope", sorted(set(MODEL_SCOPES)
                                         - set(NEMOTRON_SCOPES)))
def test_lowered_nemotron_step_holds_no_scope_of_another_model(
        lowered_nemotron_step, scope):
    """No latent attention and no short convolution in this model."""
    assert not re.search(r"[/\"]%s/" % re.escape(scope),
                         lowered_nemotron_step)


@pytest.mark.parametrize("scope", ["ainic.ssm", "ainic.ssm.scan"])
def test_the_older_models_hold_no_state_space_scope(lowered_glm_step,
                                                    lowered_lfm2_step, scope):
    for text in (lowered_glm_step, lowered_lfm2_step):
        assert not re.search(r"[/\"]%s/" % re.escape(scope), text)


# -- the benchmark's rules read the names back -------------------------------

def _event(instr, shape, operands, metadata=None):
    """An `XLA Ops` event name as the v5e's trace prints a Mosaic kernel
    (PR 25): the whole instruction, kernel_metadata's content on lines of
    its own, keys sorted."""
    inside = "" if not metadata else "\n%s\n" % ",\n".join(
        f'"{k}":"{v}"' for k, v in sorted(metadata.items()))
    return (f'%{instr} = {shape} custom-call({operands}), custom_call_target='
            f'"tpu_custom_call", operand_layout_constraints={{...}}, '
            f'frontend_attributes={{kernel_metadata={{{inside}}}}}')


F32, S8 = "f32[327840,128]{1,0:T(8,128)}", "s8[327840,128]{1,0:T(8,128)(4,1)}"
SC = "s8[20490,128]{1,0:T(8,128)(4,1)S(1)}"
EVENTS = [
    # named: the class is the table's layer, whatever else the text says
    (_event("ring_rs_update_stream.1", f"({F32}, {F32})", f"{F32} %x",
            {"ainic_kernel": "ring.rs_update_stream", "opt": "sgd"}),
     "ring", "ring.rs_update_stream"),
    (_event("ring_ag_stream.7", F32, f"{F32} %slice.3",
            {"ainic_kernel": "ring.ag_stream"}), "ring", "ring.ag_stream"),
    (_event("ring_rs_stream.1", F32, f"{F32} %x",
            {"ainic_kernel": "ring.rs_stream", "ablate": "rdma"}),
     "ring", "ring.rs_stream"),
    # a codec kernel with a second output no signature rule knows
    (_event("codec_bfp_encode.1", f"({S8}, {SC}, f32[8]{{0}})", f"{F32} %b",
            {"ainic_kernel": "codec.bfp_encode"}),
     "codec", "codec.bfp_encode"),
    (_event("renamed_by_a_refactor.3", F32, f"{S8} %m, {SC} %s",
            {"ainic_kernel": "codec.int8_decode"}),
     "codec", "codec.int8_decode"),
    (_event("attention_flash_fwd.2", "bf16[384,512,64]{2,1,0}",
            "bf16[384,512,64]{2,1,0} %q", {"ainic_kernel":
                                           "attention.flash_fwd"}),
     "attention", "attention.flash_fwd"),
    (_event("ssm_scan_bwd.1", "(bf16[1,8192,4096]{2,1,0}, f32[1,8,8192,8])",
            "f32[1,64,128,4096]{3,2,1,0} %states",
            {"ainic_kernel": "ssm_scan.bwd"}), "ssm_scan", "ssm_scan.bwd"),
    # unnamed (the parent of PR 25): 10-kernels.json's fallback decides
    (_event("_rs_stream_call.1", f"({F32}, {F32})", f"{F32} %x"),
     "ring", None),
    (_event("_ag_call.2", F32, f"{F32} %x"), "ring", None),
    (_event("_step.2", f"({S8}, {SC})", f"{F32} %bitcast"), "codec", None),
    (_event("_step.3", F32, f"{S8} %pallas_call.5, {SC} %pallas_call.6"),
     "codec", None),
    # a Mosaic kernel nobody named, or named outside the table's layers
    (_event("_step.9", "bf16[32,12,512,64]{3,2,1,0}",
            "bf16[32,12,512,64]{3,2,1,0} %q"), "pallas_unknown", None),
    (_event("mystery.1", F32, f"{F32} %x", {"ainic_kernel": "other.thing"}),
     "pallas_unknown", "other.thing"),
    (_event("mystery.2", F32, f"{F32} %x", {"made_by": "somebody"}),
     "pallas_unknown", None),
    # not kernels at all
    ("%fusion.786 = bf16[32,12,512,64]{2,3,1,0} fusion(f32[32,12,512,512]"
     "{2,3,1,0:T(8,128)} %get-tuple-element.322), kind=kOutput",
     "attention", None),
    ("%fusion.79 = bf16[256,16,8,128]{3,2,1,0} fusion(bf16[131072,2048]{0,1}"
     " %x), kind=kOutput", "model", None),
]


@pytest.mark.parametrize("event,cls,kernel", EVENTS,
                         ids=[f"{i}-{e[1]}" for i, e in enumerate(EVENTS)])
def test_event_names_classify_with_both_rule_files(event, cls, kernel):
    rules = trace_reduce.load_rules()
    assert trace_reduce.classify(event, rules) == cls
    assert kernel_events.kernel_name(event) == kernel


NAMED_RULE_FILES = ["05-named-kernels.json", "06-moe-kernels.json",
                    "065-ssm-kernels.json"]


def _rule_file(name):
    with open(os.path.join(ROOT, "benchmark", "op_classes", name)) as f:
        return json.load(f)["rules"]


def test_named_rules_sort_first_and_give_no_new_class():
    d = os.path.join(ROOT, "benchmark", "op_classes")
    files = sorted(f for f in os.listdir(d) if f.endswith(".json"))
    # named kernels first, the fallback last; a class told by what an XLA
    # operation touches (07-head.json, PR 26) sorts between the two
    assert files[:3] == NAMED_RULE_FILES
    assert files[-1] == "10-kernels.json"
    named = [r for f in NAMED_RULE_FILES for r in _rule_file(f)]
    assert [r["class"] for r in named] == ["ring", "codec", "attention",
                                          "moe", "ssm_scan"]
    layers = {layer for layer, _ in names.KERNELS.values()}
    assert {r["class"] for r in named} == layers


# -- the expert layer's kernels: 06-moe-kernels.json -------------------------

MOE_EVENTS = [
    _event("moe_gmm.3", "bf16[6144,1856]{1,0:T(8,128)(2,1)}",
           "s32[32]{0} %a, s32[32]{0} %b, s32[9]{0} %c, bf16[6144,2688]{1,0} "
           "%x, bf16[8,2688,1856]{2,1,0} %w", {"ainic_kernel": "moe.gmm"}),
    _event("moe_gmm.17", "bf16[32768,2048]{1,0:T(8,128)(2,1)}",
           "bf16[32768,1536]{1,0} %dy, bf16[8,2048,1536]{2,1,0} %w",
           {"ainic_kernel": "moe.gmm", "rhs": "transposed"}),
    _event("moe_tgmm.2", "bf16[8,2048,1536]{2,1,0:T(8,128)(2,1)}",
           "bf16[8192,2048]{1,0} %x, bf16[8192,1536]{1,0} %dy",
           {"ainic_kernel": "moe.tgmm"}),
]


@pytest.mark.parametrize("event", MOE_EVENTS, ids=["gmm", "gmm-t", "tgmm"])
def test_moe_rule_reads_the_layer_out_of_the_name(event):
    """The expert cells' rule files skip every tpu_custom_call, so 06 is
    what classes these; its pattern meets a `moe.*` name and nothing
    else's, and the readers by name find the kernel."""
    mine = [dict(r, regex=re.compile(r["regex"]))
            for r in _rule_file("06-moe-kernels.json")]
    assert trace_reduce.classify(event, mine) == "moe"
    assert trace_reduce.classify(event, trace_reduce.load_rules()) == "moe"
    assert kernel_events.kernel_name(event).startswith("moe.")


@pytest.mark.parametrize("event", [e for e, *_ in EVENTS],
                         ids=[f"{i}" for i in range(len(EVENTS))])
def test_moe_rule_meets_no_other_layer_s_event(event):
    mine = [dict(r, regex=re.compile(r["regex"]))
            for r in _rule_file("06-moe-kernels.json")]
    assert trace_reduce.classify(event, mine) == trace_reduce.DEFAULT_CLASS


# -- the chunked scan's kernels: 065-ssm-kernels.json ------------------------

SSM_EVENTS = [
    _event("ssm_scan_fwd.1", "f32[1,8192,4096]{2,1,0:T(8,128)}",
           "bf16[1,8192,4096]{2,1,0} %x, f32[1,8,8192,8]{3,2,1,0} %d, "
           "f32[1,8,8192,8]{3,2,1,0} %cs, f32[1,8,8,8192]{3,2,1,0} %csr, "
           "bf16[1,8192,1024]{2,1,0} %b, bf16[1,8192,1024]{2,1,0} %c",
           {"ainic_kernel": "ssm_scan.fwd"}),
    _event("ssm_scan_states.2", "f32[1,64,128,4096]{3,2,1,0:T(8,128)}",
           "bf16[1,8192,4096]{2,1,0} %x, bf16[1,8192,1024]{2,1,0} %b",
           {"ainic_kernel": "ssm_scan.states"}),
    _event("ssm_scan_bwd.3", "(bf16[1,8192,4096]{2,1,0}, f32[1,8,8,8192])",
           "f32[1,64,128,4096]{3,2,1,0} %states, f32[1,8192,4096] %dy",
           {"ainic_kernel": "ssm_scan.bwd"}),
]


@pytest.mark.parametrize("event", SSM_EVENTS, ids=["fwd", "states", "bwd"])
def test_ssm_rule_reads_the_layer_out_of_the_name(event):
    """The Nemotron cell's rule file skips every tpu_custom_call, so 065 is
    what classes these; its pattern meets an `ssm_scan.*` name and nothing
    else's, and the readers by name find the kernel."""
    mine = [dict(r, regex=re.compile(r["regex"]))
            for r in _rule_file("065-ssm-kernels.json")]
    assert trace_reduce.classify(event, mine) == "ssm_scan"
    assert trace_reduce.classify(event, trace_reduce.load_rules()) \
        == "ssm_scan"
    assert kernel_events.kernel_name(event).startswith("ssm_scan.")


@pytest.mark.parametrize(
    "event", [e for e, cls, _ in EVENTS if cls != "ssm_scan"] + MOE_EVENTS,
    ids=[f"{i}" for i in range(len(EVENTS) - 1)]
    + ["gmm", "gmm-t", "tgmm"])
def test_ssm_rule_meets_no_other_layer_s_event(event):
    mine = [dict(r, regex=re.compile(r["regex"]))
            for r in _rule_file("065-ssm-kernels.json")]
    assert trace_reduce.classify(event, mine) == trace_reduce.DEFAULT_CLASS


# -- what the compiler made of `lax.ragged_dot` before: still read as moe ----

RAGGED = ["ragged-dot-metadata", "ragged-dot-none"]


@pytest.mark.parametrize("rule_file", ["075-lfm2-moe.json",
                                       "076-nemotron-h.json",
                                       "08-glm-moe.json"])
def test_each_expert_model_s_rule_file_holds_the_table_s_pattern(rule_file):
    """Traces recorded before the grouped products had names hold the
    compiler's own kernels; each expert model's rule file still reads them
    as `moe`, letter for letter, so those traces read as they did."""
    mine = [r["regex"] for r in _rule_file(rule_file) if r["class"] == "moe"]
    assert r"^%(?:" + "|".join(RAGGED) + r")[.\d]* = " in mine


@pytest.mark.parametrize("name", RAGGED)
def test_external_kernel_events_take_the_table_s_layer(name):
    rules = trace_reduce.load_rules()
    for instr in (name, name + ".12"):
        event = _event(instr, "bf16[32768,1536]{1,0:T(8,128)(2,1)}",
                       "s32[1]{0:T(128)} %gte.5")
        assert trace_reduce.classify(event, rules) == "moe"
        assert kernel_events.kernel_name(event) is None


def test_a_kernel_the_compiler_names_otherwise_is_unknown():
    event = _event("ragged-dot-renamed.1", "bf16[32768,1536]{1,0}",
                   "s32[1]{0:T(128)} %gte.5")
    assert trace_reduce.classify(
        event, trace_reduce.load_rules()) == "pallas_unknown"


def test_recorded_trace_of_pr23_reads_as_before_with_both_rule_files():
    """The trace recorded before the kernels had names: the fallback
    still gives the numbers benchmark/tests/test_trace_reduce.py expects,
    and the readers by name find nothing to read."""
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "mlp-dp4-ring.trace.json")) as f:
        trace = trace_reduce.Trace(json.load(f))
    assert trace.steps() == 4 and len(trace.devices) == 4
    for d in trace.devices:
        assert sum(c == "ring" for _, c, _, _ in d["ops"]) == 9 * d["steps"]
    assert 21.0 < trace.class_ms_per_step("ring") < 21.8
    assert trace.class_ms_per_step("codec") is None
    assert trace.class_ms_per_step("pallas_unknown") is None
    assert kernel_events.ms_per_step(trace, "ring.") is None
    assert kernel_events.launches_per_step(trace, "ring.ag") is None
