"""The one table of names the program's Pallas kernels and step phases carry
into the compiled step and the device trace.

Every ``pl.pallas_call`` in the package is written
``pl.pallas_call(kern, ..., **kernel("ring.rs_update", opt="sgd"))``:
``name=`` (the table's name with ``.`` as ``_``) names the Mosaic kernel and
the HLO instruction, and ``metadata=`` rides into the instruction's
``frontend_attributes={kernel_metadata={...}}``, which the TPU's profiler
keeps in the event's name.  The benchmark's rules
(``benchmark/op_classes/05-named-kernels.json``) and PERF.md section 3 agree
with this table and with nothing else.  Names are compile-time only: nothing
is added to a step.  `EXTERNAL_KERNELS` lists the kernels the compiler makes
itself, under the names it gives them.
"""

from typing import Dict, Tuple

import jax

METADATA_KEY = "ainic_kernel"

# name -> (layer, what it is).  The layer is the part before the dot.
KERNELS: Dict[str, Tuple[str, str]] = {
    "ring.rs": ("ring", "resident reduce-scatter (ops/ring_pallas._rs_call)"),
    "ring.rs_update": (
        "ring", "resident reduce-scatter with the optimizer in its last hop"),
    "ring.rs_stream": (
        "ring", "streaming reduce-scatter (ops/ring_pallas._rs_stream_call)"),
    "ring.rs_update_stream": (
        "ring", "streaming reduce-scatter with the optimizer in its last hop"),
    "ring.ag": ("ring", "resident all-gather (ops/ring_pallas._ag_call)"),
    "ring.ag_stream": (
        "ring", "streaming all-gather, one launch per segment "
                "(ops/ring_pallas._ag_stream_call)"),
    "codec.bfp_encode": (
        "codec", "f32 -> BFP mantissas and scales (ops/bfp_pallas.py)"),
    "codec.bfp_decode": (
        "codec", "BFP mantissas and scales -> f32 (ops/bfp_pallas.py)"),
    "codec.int8_encode": (
        "codec", "f32 -> int8 and bf16 block scales (compress/int8.py)"),
    "codec.int8_decode": (
        "codec", "int8 and bf16 block scales -> f32 (compress/int8.py)"),
    "attention.flash_fwd": (
        "attention", "flash attention forward (ops/flash_pallas.py)"),
    "attention.flash_dq": (
        "attention", "flash attention backward, dQ (ops/flash_pallas.py)"),
    "attention.flash_dkv": (
        "attention", "flash attention backward, dK and dV "
                     "(ops/flash_pallas.py)"),
    "attention.paged": (
        "attention", "paged decode attention (ops/paged_attend_pallas.py)"),
}

# Mosaic kernels the TPU compiler makes of an XLA operation by itself.  No
# call site of ours can hand them a name or metadata, so the table holds the
# instruction names the compiler gives them ("%<name>.N = ... custom-call"):
# name -> (layer, what it is).  tests/test_tpu_compile.py compiles the expert
# layer for the chip and refuses a custom call that is neither in KERNELS
# nor here; benchmark/op_classes/08-glm-moe.json classes them by
# `external_kernel_regex()`, which tests/test_kernel_names.py holds it to.
EXTERNAL_KERNELS: Dict[str, Tuple[str, str]] = {
    "ragged-dot-none": (
        "moe", "lax.ragged_dot's grouped product (ops/moe._grouped_dot)"),
    "ragged-dot-metadata": (
        "moe", "the grouped product's tiles, from the rows of each group"),
}

# the phases of DPTrainer's step, as jax.named_scope: metadata on the
# instructions (op_name), the same program
SCOPES: Dict[str, str] = {
    "ainic.fwd_bwd": "loss forward and backward over the micro-batches",
    "ainic.flatten": "gradient tree -> flat vector (+ error-feedback encode)",
    "ainic.collective_update": "reduce-scatter, with the update where fused",
    "ainic.optimizer": "the optimizer's formula where XLA runs it",
    "ainic.gather": "owned shard -> replicated parameters",
    # inside ainic.fwd_bwd, where the model has them (models/glm_moe.py)
    "ainic.mla": "latent attention: compressed q and k/v, rotary key, heads",
    # likewise (models/lfm2_moe.py)
    "ainic.conv": "gated short convolution: in-projection, gates, taps, out",
    "ainic.gqa": "grouped-query attention: q/k/v, head norms, RoPE, output",
    # likewise (models/nemotron_h.py; its attention is ainic.gqa's, no norms)
    "ainic.ssm": "Mamba-2 mixer: in-projection, convolution, gate, norm, out",
    "ainic.ssm.scan": "its state-space recurrence, chunked, in matrix form",
    # inside a model's attention (ops/ring_attention.flash_attention)
    "ainic.attn.fwd": "the XLA route's forward: out and lse, block by block",
    "ainic.attn.bwd": "its backward: p from lse again, dQ, one dK and dV",
    "ainic.moe.route": "sigmoid scores over every expert, top-k, gates",
    "ainic.moe.experts": "dropless grouped product over the experts held",
    "ainic.moe.shared": "the shared expert, on every token",
}


def external_kernel_regex(layer: str) -> str:
    """The pattern that matches, at its start, the instruction text of the
    compiler's own kernels of `layer`."""
    own = sorted(n for n, (l, _) in EXTERNAL_KERNELS.items() if l == layer)
    return r"^%(?:" + "|".join(own) + r")[.\d]* = "


def kernel(name: str, **extra: object) -> dict:
    """``name=`` and ``metadata=`` for ``pl.pallas_call``, splatted into the
    call.  ``extra`` (``opt="sgd"``, ``ablate="rdma"``) joins the
    metadata; a value of None is left out.  An unknown name raises."""
    if name not in KERNELS:
        raise KeyError(f"{name!r} is not in obs.names.KERNELS: a kernel gets "
                       f"its name there before a pallas_call carries it")
    metadata = {METADATA_KEY: name}
    metadata.update((k, str(v)) for k, v in extra.items() if v is not None)
    return {"name": name.replace(".", "_"), "metadata": metadata}


def scope(name: str):
    """``jax.named_scope`` for a phase of the step; an unknown name raises."""
    if name not in SCOPES:
        raise KeyError(f"{name!r} is not in obs.names.SCOPES")
    return jax.named_scope(name)
