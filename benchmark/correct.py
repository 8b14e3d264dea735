"""The comparison that decides `correct`.

Outside the measured window, on the window's own batch and seed: the
trainer's first step is run again from the initial state and held to the
family's plain float32 reference (families/<family>.py), which shares no
code with models/*, DPTrainer or any kernel.  What is compared, and why each
tolerance is what it is:

- loss: relative, the family's LOSS_RTOL;
- gradient: the program's first gradient is read back from what the step
  stored — (w_old - w_new) / lr for SGD, m / (1 - b1) for AdamW, whose first
  update is sign-like and says little — relative L2 against the reference's
  gradient, the family's GRAD_TOL, plus HOP_TOL for each hop of a ring that
  requantizes partial sums;
- params: what the gather handed every replica against the reference's
  updated master in the model's type, relative L2, PARAM_TOL;
- across chips, params bit-identical on every device;
- the compiled step holds the Pallas calls its route needs (TPU only).
"""

import jax
import jax.numpy as jnp
import numpy as np

# The gather hands every replica the BFP roundtrip of the updated master:
# 7.29e-3 relative L2 at 8 mantissa bits over blocks of 16
# (docs/BFP_CONVERGENCE.md, roundtrip table; 7.62e-3 on the chip, PR 22).
# Twice that is allowed; a 7-bit mantissa would double the error and miss.
PARAM_TOL = 1.5e-2
# Across chips each of the n-1 reduce-scatter hops quantizes a partial sum
# once more (docs/BFP_CONVERGENCE.md, "per-hop"): one roundtrip's error per
# hop, added linearly, bounds what independent errors add in quadrature.
HOP_TOL = 7.29e-3


def flat_f32(tree):
    """A pytree as one float32 vector in leaf order — the trainer's flat
    master layout, written out again so the check does not lean on it."""
    return jnp.concatenate([leaf.astype(jnp.float32).reshape(-1)
                            for leaf in jax.tree_util.tree_leaves(tree)])


def rel_l2(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def program_gradient(kind: str, opt: dict, w_old, w_new, opt_state):
    """The mean gradient the step applied, read back from its outputs."""
    if kind == "sgd":
        return (w_old - w_new) / opt["learning_rate"]
    if kind == "adamw":
        return opt_state["m"][:w_old.shape[0]] / (1.0 - opt.get("b1", 0.9))
    raise ValueError(f"no first-step check for optimizer {kind!r}")


def reference_update(kind: str, opt: dict, w_old, grad):
    """The master after one step of the plain optimizer, no weight decay."""
    lr = opt["learning_rate"]
    if kind == "sgd":
        return w_old - lr * grad
    if kind == "adamw":
        # bias-corrected first step: m_hat = g, v_hat = g^2
        return w_old - lr * grad / (jnp.abs(grad) + opt.get("eps", 1e-8))
    raise ValueError(f"no first-step check for optimizer {kind!r}")


def reference_loss_and_grad(family, params0, batch, config: dict, job: dict):
    """(loss, gradient pytree) of the first step from the family's plain
    float32 `reference_nll`, float32 matmuls at full precision.  The batch
    is taken in blocks of `reference_block` items from every chip's part at
    once — the float32 activations of a whole batch would not fit beside the
    program's state — so each block keeps the batch's sharding and XLA's own
    partitioner, not the program's ring, sums the gradient across chips."""
    dp, per_chip = job["dp"], job["batch_per_chip"]
    block = min(job.get("reference_block", per_chip), per_chip)
    if per_chip % block:
        raise ValueError(f"batch per chip {per_chip} is no multiple of the "
                         f"reference block {block}")
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params0)

    def take(leaf, i):
        parts = leaf.reshape((dp, per_chip) + leaf.shape[1:])
        parts = jax.lax.dynamic_slice_in_dim(parts, i * block, block, axis=1)
        return parts.reshape((dp * block,) + leaf.shape[1:])

    @jax.jit
    def one(p, whole, i):
        part = jax.tree_util.tree_map(lambda leaf: take(leaf, i), whole)
        with jax.default_matmul_precision("highest"):
            (total, count), g = jax.value_and_grad(
                family.reference_nll, has_aux=True)(p, part, config)
        return total, count, g

    total, count, grads = 0.0, 0, None
    for i in range(per_chip // block):
        t, n, g = one(p32, batch, i)
        total, count = total + t, count + n
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    denom = jnp.maximum(count, 1).astype(jnp.float32)
    return total / denom, jax.tree_util.tree_map(lambda a: a / denom, grads)


def needed_pallas_calls(tr) -> int:
    """Pallas calls the step must hold on the TPU for the configured route:
    none without the fused kernel; at dp=1 the codec's encode and decode
    (the wire is routed around); across chips the fused reduce-scatter+
    update and one gather per segment of the program's own plan."""
    coll = tr.cfg.collective
    if coll.impl != "ring" or not coll.fused_kernel:
        return 0
    if tr.n == 1:
        return 2
    from fpga_ai_nic_tpu.ops import ring_pallas
    owned = tr.obs_static_metrics()["padded_len"] // tr.n
    return 1 + len(ring_pallas.ag_stream_segments(
        owned, coll.slice_elems, coll.compression.block_size))


def replicas_identical(params) -> bool:
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], other) for other in shards[1:]):
            return False
    return True


def first_step(tr, family, config: dict, job: dict, params0, batch,
               on_tpu: bool, log) -> dict:
    """Run the first step again from `params0` and compare.  Returns
    {"ok", "pallas_calls" and the measured errors}; raises where the
    compiled step lacks its kernels."""
    opt = config["optimizer"]
    kind = opt["kind"]
    ref_loss, ref_grads = reference_loss_and_grad(family, params0, batch,
                                                  config, job)
    w_old, g_ref = flat_f32(params0), flat_f32(ref_grads)
    del ref_grads
    w_ref = reference_update(kind, opt, w_old, g_ref)
    model_dtype = jax.tree_util.tree_leaves(params0)[0].dtype
    live = w_old.shape[0]

    state = tr.init_state(params0)          # the step donates params0
    compiled = tr.step_fn.lower(state, batch).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    need = needed_pallas_calls(tr)
    if on_tpu and calls < need:
        raise SystemExit(f"the compiled step holds {calls} Pallas calls, "
                         f"its route needs {need}: the kernels did not run")
    state, loss = compiled(state, batch)
    w_new = state.w_own[:live]
    g_prog = program_gradient(kind, opt, w_old, w_new, state.opt_state)
    out = {
        "pallas_calls": calls, "pallas_calls_needed": need,
        "loss": float(loss), "ref_loss": float(ref_loss),
        "grad_err": rel_l2(g_prog, g_ref),
        "grad_tol": family.GRAD_TOL + (tr.n - 1) * HOP_TOL,
        "param_err": rel_l2(flat_f32(state.params),
                            w_ref.astype(model_dtype).astype(jnp.float32)),
        "param_tol": PARAM_TOL,
        "replicas_identical": tr.n == 1 or replicas_identical(state.params),
    }
    out["loss_err"] = abs(out["loss"] - out["ref_loss"]) / abs(out["ref_loss"])
    out["ok"] = bool(out["loss_err"] <= family.LOSS_RTOL
                     and out["grad_err"] <= out["grad_tol"]
                     and out["param_err"] <= out["param_tol"]
                     and out["replicas_identical"])
    log("first step vs float32 reference: loss %.5f vs %.5f (rel %.2e, tol "
        "%.0e); gradient rel L2 %.2e (tol %.2e); params rel L2 %.2e (tol "
        "%.1e); replicas identical %s; %d Pallas calls (needs %d) -> %s"
        % (out["loss"], out["ref_loss"], out["loss_err"], family.LOSS_RTOL,
           out["grad_err"], out["grad_tol"], out["param_err"],
           out["param_tol"], out["replicas_identical"], calls, need,
           "ok" if out["ok"] else "NOT ok"))
    return out
