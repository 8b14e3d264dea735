"""The one table of names the program's Pallas kernels and step phases carry
into the compiled step and the device trace, and of the host spans of
set-up in the event stream.

Every ``pl.pallas_call`` in the package is written
``pl.pallas_call(kern, ..., **kernel("ring.rs_update", opt="sgd"))``:
``name=`` (the table's name with ``.`` as ``_``) names the Mosaic kernel and
the HLO instruction, and ``metadata=`` rides into the instruction's
``frontend_attributes={kernel_metadata={...}}``, which the TPU's profiler
keeps in the event's name.  The benchmark's rules
(``benchmark/op_classes/05-named-kernels.json``) and PERF.md section 3 agree
with this table and with nothing else.  Names are compile-time only: nothing
is added to a step.
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
    "moe.gmm": (
        "moe", "grouped product of rows sorted by expert, forward and the "
               "rows' cotangent (ops/grouped_pallas.py)"),
    "moe.tgmm": (
        "moe", "grouped product's weight cotangent, one matrix an expert "
               "(ops/grouped_pallas.py)"),
    "ssm_scan.fwd": (
        "ssm_scan", "Mamba-2's chunked scan, a chunk's decay block in fast "
                    "memory, the state carried (ops/ssd_pallas.py)"),
    "ssm_scan.states": (
        "ssm_scan", "the state each chunk starts from, the forward's carry "
                    "again for the backward (ops/ssd_pallas.py)"),
    "ssm_scan.bwd": (
        "ssm_scan", "its backward, the chunks in reverse, the state's "
                    "cotangent carried (ops/ssd_pallas.py)"),
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


# host spans of the event stream (obs.events), opened by Profiler.bucket or
# forwarded from jax's compile events into the innermost open bucket; each
# child carries `fun_name` and `parent`, and jax.compile also `cache`
SPANS: Dict[str, str] = {
    "trainer.init_state": "DPTrainer.init_state: the master shards' layout, "
                          "its compile and dispatch",
    "trainer.step": "DPTrainer.step, host side; `traced`: the call traced "
                    "the step anew",
    "jax.trace": "jax traces a function to a jaxpr (nested traces nest)",
    "jax.lower": "jaxpr -> MLIR module",
    "jax.compile": "compile_or_get_cached: the compile on a miss, the cache "
                   "read and load on a hit; `cache` hit | miss | none",
}


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
