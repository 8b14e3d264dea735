"""What the decoders of the expert-model families share (`models/glm_moe.py`,
`models/lfm2_moe.py`): how a dict of shapes becomes initial leaves, what a
layer's checkpoint keeps, the head with its recomputed logits, and the
next-token loss as a token-weighted mean over `dp`.  The layers themselves
stay with their family."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ring_attention
from .llama import _rmsnorm, _token_nll

# what a layer's checkpoint keeps beside its input: the attention route's
# output and logsumexp, which are all its backward needs of its forward, so
# that the layer's recompute does not run the attention again
KEEP = jax.checkpoint_policies.save_only_these_names(ring_attention.SAVED)


def init_leaves(key: jax.Array, shapes: Dict[str, Tuple[int, ...]],
                lead: Tuple[int, ...], dt) -> Dict:
    """Norms at one; a selection bias (`*_bias`) at zero, float32; matrices
    normal with variance 1/fan_in (the dimension before the last); the
    router stays float32 (ops/moe.py)."""
    out = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(lead + shape, dt)
        elif name.endswith("_bias"):
            out[name] = jnp.zeros(lead + shape, jnp.float32)
        else:
            w = (jax.random.normal(k, lead + shape, jnp.float32)
                 * shape[-2] ** -0.5)
            out[name] = w if name == "wr" else w.astype(dt)
    return out


def head_nll(norm_w: jax.Array, head_w: jax.Array, x: jax.Array,
             safe: jax.Array, eps: float, tied: bool = False) -> jax.Array:
    """Per-row negative log-likelihood of x [N, D] against labels [N] under
    logits = RMSNorm(x) W, W = `head_w` [D, V], or its transpose where the
    head is `tied` to the embedding [V, D]; the logits are recomputed in the
    backward pass, so no [N, vocab] array is kept for it."""
    def block(xb, lb):
        h = _rmsnorm(xb, norm_w, eps)
        return _token_nll(h @ head_w.T if tied else h @ head_w, lb, None)

    return jax.checkpoint(block)(x, safe)


def next_token_loss(nll: jax.Array, valid: jax.Array, *,
                    dp_axis: Optional[str] = None) -> jax.Array:
    """Mean of `nll` over the `valid` rows.  dp_axis: as in
    models.bert.loss_fn — the value is the global token-weighted mean, and
    the gradient rides the local sum with the n_dp factor that cancels the
    trainer's uniform /n_dp."""
    local_sum = jnp.sum(jnp.where(valid, nll, 0.0))
    count = jnp.sum(valid)
    if dp_axis is None:
        return local_sum / jnp.maximum(count, 1)
    total = lax.psum(local_sum, dp_axis)
    denom = lax.stop_gradient(
        jnp.maximum(lax.psum(count, dp_axis), 1).astype(jnp.float32))
    n_dp = lax.axis_size(dp_axis)
    return lax.stop_gradient(total / denom) + (
        n_dp * (local_sum - lax.stop_gradient(local_sum)) / denom)
