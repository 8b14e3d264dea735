"""The chip's compiler as a test: the main path's kernels compiled for a
described (not attached) v5e:2x2 at the sizes the canonical trainer produces.

The Pallas interpreter checks bits; it counts no semaphores and aligns no
blocks.  Both faults PR 22 repaired — the codec grid whose scale block was
not a legal TPU block, and the streaming gather whose slot window outgrew
the chip's semaphore memory — passed every interpreter test and were
refused here.  Nothing runs: these are compiles, never timings
(/opt/skills/guides/on-chip-measurement, section 2).
"""

import json
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fpga_ai_nic_tpu import optim
from fpga_ai_nic_tpu.compress import int8
from fpga_ai_nic_tpu.obs import names as obs_names
from fpga_ai_nic_tpu.ops import (bfp_pallas, flash_pallas,
                                 paged_attend_pallas, ring_pallas)
from fpga_ai_nic_tpu.utils.config import (BFPConfig, OptimizerConfig,
                                          OptimizerSpec)
from fpga_ai_nic_tpu.verify import opstream

TILE = 16 * 128
GRAD = 10 * 2048 * 2048            # the 10x2048^2 gradient, no biases
DP4_PADDED = 41_967_616            # the dp=4 trainer's padded flat length
DP1_PADDED = 41_963_520            # dp=1: 20,490 tiles, with the biases


@pytest.fixture(scope="module")
def chip():
    """The described v5e:2x2 and the shardings the cases use — made when a
    case first runs, never at collection, so every worker of a parallel
    run collects the same cases whether or not it can describe the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    # several test processes may load the TPU compiler at once; none of
    # them touches a device, so libtpu's one-process lock has nothing to
    # guard here
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: nothing to test
        pytest.skip(f"v5e:2x2 cannot be described here: {e!r}")
    mesh4 = Mesh(np.array(topo.devices), ("dp",))
    mesh1 = Mesh(np.array(topo.devices[:1]), ("lb",))
    return types.SimpleNamespace(
        devices=topo.devices, mesh4=mesh4, mesh1=mesh1,
        dp=NamedSharding(mesh4, P("dp")), one=NamedSharding(mesh1, P()))


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compiled_text(fn, *args) -> str:
    """Compile for the described chip; the compiler's own text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def kernels_in(fn, *args) -> int:
    """The number of Pallas calls in fn, compiled for the described chip."""
    return compiled_text(fn, *args).count("tpu_custom_call")


def on_mesh4(chip, fn, n_in=1, n_out=1):
    return jax.shard_map(fn, mesh=chip.mesh4, in_specs=(P("dp"),) * n_in,
                         out_specs=(P("dp"),) * n_out if n_out > 1
                         else P("dp"), check_vma=False)


def on_one_chip(chip, fn):
    return jax.shard_map(fn, mesh=chip.mesh1, in_specs=P(), out_specs=P(),
                         check_vma=False)


# -- the codec grid (`bfp_pallas._grid`) -------------------------------------

def _bfp(chip, n):
    enc = kernels_in(lambda x: bfp_pallas.bfp_encode_inline(
        x, interpret=False), sds((n,), jnp.float32, chip.one))
    dec = kernels_in(lambda m, s: bfp_pallas.bfp_decode_inline(
        m, s, interpret=False), sds((n,), jnp.int8, chip.one),
        sds((n // 16,), jnp.int8, chip.one))
    return enc, dec


def _int8(chip, n):
    enc = kernels_in(lambda x: int8.int8_encode_pallas(
        x, rounding="nearest", interpret=False),
        sds((n,), jnp.float32, chip.one))
    dec = kernels_in(lambda q, s: int8.int8_decode_pallas(
        q, s, interpret=False), sds((n,), jnp.int8, chip.one),
        sds((n // 16,), jnp.bfloat16, chip.one))
    return enc, dec


def test_bfp_codec_at_the_gradient_size(chip):
    assert _bfp(chip, GRAD) == (1, 1)


@pytest.mark.parametrize("tiles", [20_490, 20_492, 20_483])
@pytest.mark.parametrize("codec", [_bfp, _int8], ids=["bfp", "int8"])
def test_codec_grid_is_legal_at_awkward_tile_counts(chip, codec, tiles):
    """20,490 is the dp=1 trainer's own count (the MLP with its biases):
    the old largest-divisor grid made its scale block (30, 128).  20,492
    gave 47, and the prime 20,483 a grid of one-tile steps."""
    assert codec(chip, tiles * TILE) == (1, 1)
    t, steps = bfp_pallas._grid(tiles, bfp_pallas._DEF_TILES)
    assert t % 32 == 0 and (steps - 1) * t < tiles <= steps * t


# -- the ring kernels on the four-chip mesh ----------------------------------

def test_reduce_scatter_resident(chip):
    assert kernels_in(on_mesh4(
        chip, lambda x: ring_pallas.ring_reduce_scatter_fused(
            x, "dp", streaming=False, interpret=False)),
        sds((4 * 131_072,), jnp.float32, chip.dp)) == 1


def test_all_gather_resident(chip):
    assert kernels_in(on_mesh4(
        chip, lambda x: ring_pallas.ring_all_gather_fused(
            x, "dp", streaming=False, interpret=False)),
        sds((4 * 32_768,), jnp.float32, chip.dp)) == 1


def test_reduce_scatter_streaming_at_the_gradient_size(chip):
    assert kernels_in(on_mesh4(
        chip, lambda x: ring_pallas.ring_reduce_scatter_fused(
            x, "dp", streaming=True, interpret=False)),
        sds((4 * GRAD,), jnp.float32, chip.dp)) == 1


@pytest.mark.parametrize("owned", [GRAD // 4, DP4_PADDED // 4])
def test_all_gather_streaming_at_the_gradient_size(chip, owned):
    """The repaired one: S = 256 slices per 2 Mi-element segment asked for
    2 x 258 DMA semaphores and was refused (`sflag`)."""
    n = kernels_in(on_mesh4(
        chip, lambda x: ring_pallas.ring_all_gather_fused(
            x, "dp", streaming=True, interpret=False)),
        sds((4 * owned,), jnp.float32, chip.dp))
    assert n == len(ring_pallas.ag_stream_segments(owned, 8192, 16)) > 1


def _rs_update(chip, streaming, kind="sgd", n=4 * 131_072):
    """(fn, shapes) of the reduce-scatter+update on the four-chip mesh,
    n elements owned per chip."""
    keys = OptimizerSpec(kind=kind).state_keys
    hyper = optim.fused_hyperparams(
        OptimizerConfig(kind=kind, learning_rate=1e-3),
        jnp.zeros((), jnp.int32))

    def fn(x, w, *st):
        g, w2, st2 = ring_pallas.ring_reduce_scatter_update_fused(
            x, w, dict(zip(keys, st)), hyper, "dp", opt_kind=kind,
            streaming=streaming, interpret=False)
        return (g, w2) + tuple(st2[k] for k in keys)

    return (on_mesh4(chip, fn, 2 + len(keys), 2 + len(keys)),
            sds((4 * n,), jnp.float32, chip.dp),
            *[sds((n,), jnp.float32, chip.dp)] * (1 + len(keys)))


@pytest.mark.parametrize("kind", ["sgd", "adamw"])
def test_reduce_scatter_update_streaming_at_the_gradient_size(chip, kind):
    assert kernels_in(*_rs_update(chip, True, kind, n=GRAD)) == 1


# -- the one-chip loopback chip_smoke.py runs --------------------------------

def test_loopback_reduce_scatter_32mib(chip):
    assert kernels_in(on_one_chip(
        chip, lambda x: ring_pallas._rs_stream_call(
            x.reshape(-1, 128), None, 16, 8, "nearest", 8192, False, 7,
            loopback_n=4)), sds((8 << 20,), jnp.float32, chip.one)) == 1


def test_loopback_all_gather_32mib(chip):
    n = kernels_in(on_one_chip(
        chip, lambda x: ring_pallas._ag_stream_segmented(
            x, None, BFPConfig(), 8192, False, 8, loopback_n=4)),
        sds((2 << 20,), jnp.float32, chip.one))
    assert n == len(ring_pallas.ag_stream_segments(2 << 20, 8192, 16)) == 2


# -- the names the kernels carry into the compiled program --------------------

NAMED_CALL_RE = re.compile(
    r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
    r'kernel_metadata=\{([^}]*)\}')


def named_calls(text):
    """[(instruction name, kernel_metadata as a dict)] of every Pallas call
    in a compiled program's text."""
    return [(instr, json.loads("{%s}" % meta))
            for instr, meta in NAMED_CALL_RE.findall(text)]


def _rs(chip, streaming):
    return (on_mesh4(chip, lambda x: ring_pallas.ring_reduce_scatter_fused(
        x, "dp", streaming=streaming, interpret=False)),
        sds((16 * 131_072,), jnp.float32, chip.dp))


def _ag(chip, streaming):
    return (on_mesh4(chip, lambda x: ring_pallas.ring_all_gather_fused(
        x, "dp", streaming=streaming, interpret=False)),
        sds((4 * (131_072 if streaming else 32_768),), jnp.float32, chip.dp))


N_CODEC = 64 * TILE
NAMED_SITES = {
    "ring.rs": lambda c: _rs(c, False),
    "ring.rs_stream": lambda c: _rs(c, True),
    "ring.rs_update": lambda c: _rs_update(c, False),
    "ring.rs_update_stream": lambda c: _rs_update(c, True, "adamw"),
    "ring.ag": lambda c: _ag(c, False),
    "ring.ag_stream": lambda c: _ag(c, True),
    "codec.bfp_encode": lambda c: (
        lambda x: bfp_pallas.bfp_encode_inline(x, interpret=False),
        sds((N_CODEC,), jnp.float32, c.one)),
    "codec.bfp_decode": lambda c: (
        lambda m, s: bfp_pallas.bfp_decode_inline(m, s, interpret=False),
        sds((N_CODEC,), jnp.int8, c.one),
        sds((N_CODEC // 16,), jnp.int8, c.one)),
    "codec.int8_encode": lambda c: (
        lambda x: int8.int8_encode_pallas(x, rounding="nearest",
                                          interpret=False),
        sds((N_CODEC,), jnp.float32, c.one)),
    "codec.int8_decode": lambda c: (
        lambda q, s: int8.int8_decode_pallas(q, s, interpret=False),
        sds((N_CODEC,), jnp.int8, c.one),
        sds((N_CODEC // 16,), jnp.bfloat16, c.one)),
    "attention.paged": lambda c: (
        lambda q, pk, pv, pt, pos: paged_attend_pallas.paged_gather_attend(
            q, pk, pv, pt, pos, page_size=128, interpret=False),
        sds((2, 4, 1, 128), jnp.float32, c.one),
        sds((16, 2, 128, 128), jnp.bfloat16, c.one),
        sds((16, 2, 128, 128), jnp.bfloat16, c.one),
        sds((2, 4), jnp.int32, c.one), sds((2,), jnp.int32, c.one)),
}


@pytest.mark.parametrize("name", sorted(NAMED_SITES))
def test_compiled_kernel_holds_its_own_name(chip, name):
    """`name=` names the instruction, `metadata=` rides in the
    instruction's frontend attributes — which is where the v5e's profiler
    prints it (seen on the chip, PR 25)."""
    fn, *args = NAMED_SITES[name](chip)
    calls = named_calls(compiled_text(fn, *args))
    assert calls and all(
        instr.startswith(meta["ainic_kernel"].replace(".", "_") + ".")
        and meta["ainic_kernel"] in obs_names.KERNELS
        for instr, meta in calls)
    metas = [meta for _, meta in calls if meta["ainic_kernel"] == name]
    assert metas
    if "update" in name:
        assert all(m["opt"] in ("sgd", "adamw") for m in metas)


def test_ablated_kernel_says_so_in_the_compiled_text(chip):
    (_, meta), = named_calls(compiled_text(on_one_chip(
        chip, lambda x: ring_pallas._rs_stream_call(
            x.reshape(-1, 128), None, 16, 8, "nearest", 8192, False, 7,
            loopback_n=4, ablate="rdma")),
        sds((4 * 131_072,), jnp.float32, chip.one)))
    assert meta == {"ainic_kernel": "ring.rs_stream", "ablate": "rdma"}


def test_flash_kernels_are_named_where_the_compiler_speaks_of_them(chip):
    """`flash_pallas` does not compile for the chip yet (PERF.md, S3): the
    refusal now names the kernel.  Once it compiles, all three kernels
    must stand in the text under their names."""
    qkv = [sds((2, 4, 256, 64), jnp.bfloat16, chip.one)] * 3

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_pallas.flash_attention(
            *a, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    try:
        text = compiled_text(grads, *qkv)
    except ValueError as e:
        assert "pallas_call attention_flash_" in str(e)
    else:
        assert {m["ainic_kernel"] for _, m in named_calls(text)} == {
            "attention.flash_fwd", "attention.flash_dq",
            "attention.flash_dkv"}


# -- the XLA attention route at the cells' shapes ----------------------------

@pytest.mark.parametrize("shape,block,biased", [
    ((32, 12, 512, 64), (4, 12, 512, 512), True),
    ((128, 12, 128, 64), (64, 12, 128, 128), True),
    ((2, 20, 4096, 256), (2, 20, 512, 512), False),
], ids=["bert-base-seq512", "bert-base-seq128", "glm47-flash-s4096"])
def test_xla_route_keeps_its_score_block_in_fast_memory(chip, shape, block,
                                                        biased):
    """What `ring_attention.SCORE_BLOCK_BYTES` is set for (PERF.md, PR 31
    and 33): in the route's compiled gradient the float32 score block of
    one group stands in the v5e's fast memory (`S(1)`), forward and
    backward, and no float32 array holds the scores of the whole batch."""
    from fpga_ai_nic_tpu.ops import ring_attention as ra
    x = sds(shape, jnp.bfloat16, chip.one)
    bias = sds((shape[0], shape[2]), jnp.float32, chip.one)
    B, H, S, _ = shape

    def grads(q, k, v, w, bias):
        return jax.grad(lambda q, k, v: jnp.sum((ra.flash_attention_remat(
            q, k, v, causal=not biased, impl="xla",
            key_bias=bias if biased else None) * w).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(grads, x, x, x, x, bias)
    assert ra._group(B, H, *block[2:]) == block[0]
    fast = re.findall(r"f32\[%d,%d,%d,%d\]\{[^}]*S\(1\)\}" % block, text)
    for scoped in ("ainic.attn.fwd", "ainic.attn.bwd"):
        assert any(scoped in line and "S(1)" in line
                   for line in text.splitlines()), scoped
    assert len(fast) >= 4, len(fast)
    assert not re.search(r"f32\[%d,%d,%d,%d\]" % (B, H, S, S), text)


# -- the semaphore bound, in plain Python ------------------------------------

@pytest.mark.parametrize("mib", [1, 32, 160])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_gather_slot_window_fits_the_semaphore_budget(n, mib):
    """Every segment of every payload: two windows of `ag_n_slots` DMA
    semaphores plus what the compiled program keeps beside them
    (`opstream.AG_SEM_RESERVED`, measured at 95 words) fit the core's 512
    words; the segments tile the chunk on tile boundaries."""
    owned = (mib << 20) // 4 // n
    owned -= owned % TILE
    segs = ring_pallas.ag_stream_segments(owned, 8192, 16)
    at = 0
    for off, size, slice_e in segs:
        assert off == at and size % slice_e == 0 and slice_e % TILE == 0
        slices = size // slice_e
        assert slices <= opstream.AG_MAX_SLICES
        assert (2 * opstream.ag_n_slots(n, slices)
                + opstream.AG_SEM_RESERVED <= opstream.AG_SEM_WORDS)
        at += size
    assert at == owned


def test_gather_slot_window_past_the_budget_is_refused_by_name():
    assert opstream.AG_MAX_SLICES < 239      # the v5e compiler's own limit
    with pytest.raises(ValueError, match="semaphore budget"):
        opstream.ag_n_slots(4, 256)


# -- the dropless expert layer at GLM-4.7-Flash's widths ----------------------

def grouped_products(text):
    """The result type of every `ragged-dot-none` product, sorted."""
    return sorted(re.findall(
        r"^\s*(?:ROOT )?%ragged-dot-none[.\d]* = (\w+\[[\d,]+\])", text, re.M))


def conditional_branches(text):
    """[(full, compact)] of every `conditional` of a compiled module: the
    text of each branch's computation with all it calls.  `lax.cond` puts
    the false branch first."""
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}

    def reach(name, seen):
        seen.add(name)
        called = [n for n in re.findall(r"%([\w.\-]+)", bodies[name])
                  if n in bodies]
        return bodies[name] + "".join(
            reach(n, seen) for n in called if n not in seen)

    return [tuple(reach(name, set()) for name in m.groups())
            for m in re.finditer(
                r" conditional\([^\n]*branch_computations="
                r"\{%([\w.\-]+), %([\w.\-]+)\}", text)]


def products_of(rows):
    """(forward, backward): the grouped products of a layer over `rows`
    sorted rows — three forward, and nine in the backward, which recomputes
    its forward (`_compact_or_full` keeps only its inputs): those three, one
    more by the experts' width, two by the model's, three of the matrices'
    shapes."""
    forward = [f"bf16[{rows},1536]"] * 2 + [f"bf16[{rows},2048]"]
    return forward, (
        forward + [f"bf16[{rows},1536]"] + [f"bf16[{rows},2048]"] * 2
        + ["bf16[8,2048,1536]"] * 2 + ["bf16[8,1536,2048]"])


def assert_compact_and_full_programs(text, capacity, assignments):
    """The expert layer's compiled value and gradient hold ONE conditional
    forward and ONE backward.  Each has the full program over `assignments`
    sorted rows and the compact one over `capacity`: 3 `ragged-dot-none`
    products a branch forward, 9 a branch backward (24 in all, of which a
    step runs 12); the compact branches hold no array of `assignments`
    rows by the model's or the experts' width, no branch scatters rows,
    and every Mosaic kernel is the compiler's own, under a name the table
    lists: the benchmark's rule reads the class from there."""
    branches = conditional_branches(text)
    assert len(branches) == len(re.findall(r" conditional\(", text)) == 2
    for (full, compact), want in zip(branches, (0, 1)):
        assert grouped_products(full) == sorted(
            products_of(assignments)[want])
        assert grouped_products(compact) == sorted(
            products_of(capacity)[want])
        wide = rf"\[{assignments},(?:2048|1536)\]"
        assert re.search(wide, full) and not re.search(wide, compact)
    assert len(grouped_products(text)) == 24
    assert not re.search(r"scatter\(\w+\[\d+,2048\]", text)
    made = set(re.findall(
        r"^\s*(?:ROOT )?%([\w-]+?)[.\d]* = [^\n]*tpu_custom_call", text,
        re.M))
    assert made == set(obs_names.EXTERNAL_KERNELS)


def test_dropless_expert_layer_compiles_to_grouped_kernels(chip):
    """`ops.moe.held_experts_ffn`, forward and backward, at the benchmark
    cell's sizes: 8,192 tokens, top-4 of 64 sigmoid-routed experts, the 8
    held here of width 2048 x 1536, a shared expert.  On the v5e
    `lax.ragged_dot` becomes the compiler's own grouped-matmul kernels
    (`ragged-dot-*` custom calls): twelve products (three forward; the
    same three and six more backward) over the first 8,192 sorted rows,
    twelve over all 32,768 for a batch that overflows them, no [experts,
    capacity, width] buffer, no scatter of rows back onto the tokens, and
    in the compact program no array of 32,768 rows by 2,048 or 1,536."""
    from fpga_ai_nic_tpu.ops import moe
    T, D, F, H, E = 8192, 2048, 1536, 8, 64
    bf = jnp.bfloat16
    params = {"wr": sds((D, E), jnp.float32, chip.one),
              "w1": sds((H, D, F), bf, chip.one),
              "w3": sds((H, D, F), bf, chip.one),
              "w2": sds((H, F, D), bf, chip.one),
              "sw1": sds((D, F), bf, chip.one),
              "sw3": sds((D, F), bf, chip.one),
              "sw2": sds((F, D), bf, chip.one)}

    def grads(params, x):
        return jax.value_and_grad(lambda p, y: moe.held_experts_ffn(
            p, y, num_experts=E, top_k=4, held=tuple(range(H)), scale=1.8
        ).astype(jnp.float32).sum(), argnums=(0, 1))(params, x)

    assert moe.compact_capacity(4 * T, H, E) == T
    assert_compact_and_full_programs(
        compiled_text(grads, params, sds((2, T // 2, D), bf, chip.one)),
        capacity=8192, assignments=32768)


# -- LFM2's mixers and its expert layer at the benchmark cell's sizes ---------

def _lfm2_layer(chip, mixer, ffn):
    """(cfg, one layer's leaves as shapes, x [1, 8192, 2048] bf16)."""
    from fpga_ai_nic_tpu.models import lfm2_moe
    cfg = lfm2_moe.Lfm2MoeConfig(vocab=8192, held=tuple(range(8)),
                                 layer_types=(mixer,), n_dense_layers=0)
    lyr = {k: sds(shape, jnp.float32 if k in ("wr", "expert_bias")
                  else jnp.bfloat16, chip.one)
           for k, shape in lfm2_moe._layer_shapes(cfg, mixer, ffn).items()}
    return cfg, lyr, sds((1, 8192, cfg.dim), jnp.bfloat16, chip.one)


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_lfm2_mixers_compile_without_a_kernel_of_their_own(chip, mixer):
    """The gated short convolution (W_in 2048 x 6144, three taps) and
    grouped-query attention (32 heads over 8 at width 64, the route's blocks
    of 512) at one 8,192-token sequence, forward and backward: XLA's own
    fusions, no Mosaic kernel, and attention's score block in fast memory
    (`S(1)`) as the other cells' is."""
    from fpga_ai_nic_tpu.models import lfm2_moe
    cfg, lyr, x = _lfm2_layer(chip, mixer, "dense")
    pos = jnp.arange(8192, dtype=jnp.int32)

    def grads(lyr, x):
        def f(lyr, x):
            y = (lfm2_moe.conv_mixer(lyr, x) if mixer == "conv"
                 else lfm2_moe.gqa(lyr, x, pos, cfg))
            return y.astype(jnp.float32).sum()
        return jax.grad(f, argnums=(0, 1))(lyr, x)

    text = compiled_text(grads, lyr, x)
    assert "tpu_custom_call" not in text
    if mixer == "conv":
        assert re.search(r"bf16\[1,8192,6144\]", text)
    else:
        # one sequence a group: the compiler drops the group's unit axis
        assert re.search(r"f32\[(?:1,)?32,512,512\]\{[^}]*S\(1\)\}", text)
        assert re.search(r"bf16\[2048,3072\]", text)


def test_lfm2_expert_layer_compiles_to_grouped_kernels(chip):
    """`ops.moe.held_experts_ffn` as LFM2 calls it — a selection bias, no
    shared expert — at 32,768 tokens: twelve grouped products over the
    first 32,768 sorted rows, twelve over all 131,072, no array of 131,072
    rows by 2,048 or 1,536 in the compact program, every Mosaic kernel one
    the table lists."""
    from fpga_ai_nic_tpu.ops import moe
    cfg, lyr, _ = _lfm2_layer(chip, "conv", "moe")
    params = {k: lyr[k] for k in ("wr", "expert_bias", "w1", "w3", "w2")}

    def grads(params, x):
        return jax.value_and_grad(lambda p, y: moe.held_experts_ffn(
            p, y, num_experts=64, top_k=4, held=cfg.held,
            bias=jax.lax.stop_gradient(p["expert_bias"])
        ).astype(jnp.float32).sum(), argnums=(0, 1))(params, x)

    assert_compact_and_full_programs(
        compiled_text(grads, params,
                      sds((4, 8192, cfg.dim), jnp.bfloat16, chip.one)),
        capacity=32768, assignments=131072)


# -- Nemotron-H's mixer and its expert layer at the benchmark cell's sizes ----

def _nemotron_block(chip, kind):
    """(cfg, one block's leaves as shapes, x [1, 8192, 2688] bf16)."""
    from fpga_ai_nic_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig(vocab=16384, held=tuple(range(8)),
                                     pattern=kind)
    like = jax.eval_shape(lambda: nemotron_h.init(jax.random.PRNGKey(0), cfg))
    lyr = {k: sds(v.shape[1:], v.dtype, chip.one)
           for k, v in like["blocks"][0].items()}
    return cfg, lyr, sds((1, 8192, cfg.dim), jnp.bfloat16, chip.one)


def test_nemotron_mixer_compiles_clear_of_the_other_cells_rules(chip):
    """The Mamba-2 mixer (W_in 2688 x 10304, 64 heads of 64 over 8 groups,
    state 128, chunks of 128) at one 8,192-token sequence, forward and
    backward: XLA's own fusions, no Mosaic kernel; the decay arrays are
    float32 [64 chunks, 64 heads, 128, 128]; and NO array of it has the
    shape 075-lfm2-moe.json reads as attention ([.., 8 or 32, n, 64 or
    512]) — inside a chunk the positions come last, so every array of the
    scan ends in 128 — nor 07's or 075's vocabulary-wide ones."""
    from fpga_ai_nic_tpu.models import nemotron_h
    cfg, lyr, x = _nemotron_block(chip, "M")

    def grads(lyr, x):
        return jax.grad(lambda l, y: nemotron_h.mamba_mixer(
            l, y, cfg).astype(jnp.float32).sum(), argnums=(0, 1))(lyr, x)

    text = compiled_text(grads, lyr, x)
    assert "tpu_custom_call" not in text
    assert re.search(r"f32\[64,64,128,128\]", text)
    assert re.search(r"bf16\[(?:1,)?2688,10304\]", text)
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "op_classes", "075-lfm2-moe.json")) as f:
        theirs = [r["regex"] for r in json.load(f)["rules"]
                  if r["class"] in ("attention", "head", "gqa", "conv")]
    shapes = set(re.findall(r"\w+\[[\d,]+\]", text))
    for regex in theirs:
        # the rule's shape part alone, on every array the mixer makes
        shape_part = regex[regex.rindex("[^\\n]*") + len("[^\\n]*"):]
        hit = [s for s in shapes if re.search(shape_part, s)]
        assert not hit, (regex, hit)


def test_nemotron_expert_layer_compiles_to_two_products_an_expert(chip):
    """`ops.moe.held_experts_ffn` as Nemotron-H calls it — relu2 experts of
    two matrices, a selection bias, a shared expert — at 8,192 tokens, top-6
    of 128: the compact program over C = 6,144 sorted rows and the full one
    over 49,152 in one conditional forward and one backward, TWO grouped
    products a program forward (three for SwiGLU) and six backward, every
    Mosaic kernel one the table lists."""
    from fpga_ai_nic_tpu.ops import moe
    cfg, lyr, x = _nemotron_block(chip, "E")
    params = {k: v for k, v in lyr.items() if k != "norm"}

    def grads(params, x):
        return jax.value_and_grad(lambda p, y: moe.held_experts_ffn(
            p, y, num_experts=128, top_k=6, held=cfg.held, scale=2.5,
            bias=jax.lax.stop_gradient(p["expert_bias"])
        ).astype(jnp.float32).sum(), argnums=(0, 1))(params, x)

    text = compiled_text(grads, params, x)
    branches = conditional_branches(text)
    assert len(branches) == len(re.findall(r" conditional\(", text)) == 2

    def products(rows):
        forward = [f"bf16[{rows},1856]", f"bf16[{rows},2688]"]
        return forward, (forward + [f"bf16[{rows},1856]",
                                    f"bf16[{rows},2688]",
                                    "bf16[8,2688,1856]", "bf16[8,1856,2688]"])

    # the forward conditional and the backward one, in either order
    branches.sort(key=lambda pair: len(grouped_products(pair[0])))
    for (full, compact), want in zip(branches, (0, 1)):
        assert grouped_products(full) == sorted(products(49152)[want])
        assert grouped_products(compact) == sorted(products(6144)[want])
        assert not re.search(r"\[49152,(?:2688|1856)\]", compact)
    assert len(grouped_products(text)) == 16
    made = set(re.findall(
        r"^\s*(?:ROOT )?%([\w-]+?)[.\d]* = [^\n]*tpu_custom_call", text,
        re.M))
    assert made == set(obs_names.EXTERNAL_KERNELS)


# -- the whole step (about a minute each: not tier-1) ------------------------

@pytest.mark.slow
@pytest.mark.parametrize("dp,padded", [(1, DP1_PADDED), (4, DP4_PADDED)])
def test_whole_fused_ring_step_compiles(monkeypatch, chip, dp, padded):
    """DPTrainer's step with the fused-ring config, from shapes alone, at
    the trainer's own padded length.  `_is_tpu` still sees the CPU here, so
    the test steers it — not an option of the program."""
    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
    from fpga_ai_nic_tpu.parallel.train import TrainState
    from fpga_ai_nic_tpu.utils.config import (
        CollectiveConfig, MeshConfig, MLPConfig, TrainConfig)

    monkeypatch.setattr(bfp_pallas, "_is_tpu", lambda: True)
    monkeypatch.setattr(ring_pallas, "_is_tpu", lambda: True)
    mcfg = MLPConfig(layer_sizes=(2048,) * 11, dtype="bfloat16")
    cfg = TrainConfig(
        global_batch=4096 * dp, mesh=MeshConfig(dp=dp),
        collective=CollectiveConfig(
            impl="ring", compression=BFPConfig(), fused_kernel=True,
            fused_optimizer=True),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    mesh = make_mesh(cfg.mesh, devices=chip.devices)
    tr = DPTrainer(lambda p, b: mlp.loss_fn(p, b, mcfg), mesh, cfg)
    like = jax.eval_shape(lambda: mlp.init(jax.random.PRNGKey(0), mcfg))
    tr._ensure_meta(like)
    assert tr._meta.padded_len == padded
    rep, shd = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    state = TrainState(
        params=jax.tree_util.tree_map(
            lambda l: sds(l.shape, l.dtype, rep), like),
        w_own=sds((padded,), jnp.float32, shd), opt_state={},
        step=sds((), jnp.int32, rep))
    batch = (sds((cfg.global_batch, 2048), jnp.bfloat16, shd),
             sds((cfg.global_batch,), jnp.int32, shd))
    hlo = tr.step_fn.lower(state, batch).compile().as_text()
    want = 2 if dp == 1 else 1 + len(ring_pallas.ag_stream_segments(
        padded // dp, 8192, 16))
    assert hlo.count("tpu_custom_call") == want
    got = sorted(meta["ainic_kernel"] for _, meta in named_calls(hlo))
    assert got == (["codec.bfp_decode", "codec.bfp_encode"] if dp == 1 else
                   ["ring.ag_stream"] * (want - 1)
                   + ["ring.rs_update_stream"])


# GiB of arguments and of temporaries of the expert cells' compiled steps with
# the full program alone (commit 768f32f, compiled the same way).  With both
# programs the LFM2 step holds 6.9942 GiB of temporaries; the GLM step 4.0936,
# 3.9 MiB (0.09%) more than before: its peak lies outside the expert layer,
# and what a conditional takes as operands lives until the conditional ends.
EXPERT_STEP_GIB = {"lfm2-24b-ep8share-s8192": (6.1201, 7.5041),
                   "glm47-flash-ep8share-s4096": (7.7107, 4.0897)}
CONDITIONAL_OPERANDS_GIB = 0.005


@pytest.mark.slow
@pytest.mark.parametrize("cell_name", sorted(EXPERT_STEP_GIB))
def test_expert_cells_step_holds_both_dispatch_programs(monkeypatch, chip,
                                                        cell_name):
    """A benchmark cell's whole `DPTrainer` step, from shapes alone: every
    scanned run of expert layers holds one conditional forward and one
    backward, each with the compact program and the full one — twelve
    grouped products a program, not fifteen, because the layer's recompute
    under `jax.checkpoint` needs nothing of the conditional — and the
    step's arguments are what they were with one program and its
    temporaries no more, but for the conditionals' operands."""
    from benchmark import loader, run
    monkeypatch.setattr(bfp_pallas, "_is_tpu", lambda: True)
    monkeypatch.setattr(ring_pallas, "_is_tpu", lambda: True)
    cell = loader.load_cell(loader.load_spec(), cell_name)
    config, job, family = cell["config"], cell["job"], cell["family"]
    tr, _, _ = run.build(jax, cell, 1, chip.devices)
    like = jax.eval_shape(family.program(config, job)[0],
                          jax.random.PRNGKey(0))
    rep = NamedSharding(tr.mesh, P())
    state = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype, rep),
        jax.eval_shape(tr.init_state, like))
    batch = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype, NamedSharding(tr.mesh,
                                                      tr.batch_spec)),
        jax.eval_shape(lambda k: family.make_batch(k, config, job),
                       jax.random.PRNGKey(0)))
    compiled = tr.step_fn.lower(state, batch).compile()
    text = compiled.as_text()
    runs = 2 if config["family"] == "lfm2_moe" else 1
    tokens = family.items_per_step(config, job)
    branches = conditional_branches(text)
    assert len(branches) == len(re.findall(r" conditional\(", text)) \
        == 2 * runs
    for full, compact in branches:
        assert re.search(rf"\[{4 * tokens},(?:2048|1536)\]", full)
        assert not re.search(rf"\[{4 * tokens},(?:2048|1536)\]", compact)
    assert grouped_products(text) == sorted(
        sum(products_of(tokens) + products_of(4 * tokens), []) * runs)
    mem = compiled.memory_analysis()
    arguments, temporaries = EXPERT_STEP_GIB[cell_name]
    assert mem.argument_size_in_bytes / 2 ** 30 <= arguments + 5e-5
    assert mem.temp_size_in_bytes / 2 ** 30 \
        <= temporaries + CONDITIONAL_OPERANDS_GIB, mem.temp_size_in_bytes
