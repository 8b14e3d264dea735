"""The expert layer's grouped product (`ops/grouped_pallas.grouped_dot`, in
Pallas's interpreter here) against `lax.ragged_dot` and its autodiff: the
forward, the rows' cotangent and the weights', for uneven groups, an empty
group, every row in one group, rows past the groups, and widths that no
tile divides; and the expert layer built on it against the same layer
built on `lax.ragged_dot`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from fpga_ai_nic_tpu.ops import grouped_pallas, moe


def _ragged(x, w, sizes):
    """`lax.ragged_dot` with rows past sum(sizes) zeroed going in and coming
    out: the expert layer's product before it had a kernel of its own."""
    live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    y = lax.ragged_dot(jnp.where(live, x, jnp.zeros((), x.dtype)), w, sizes)
    return jnp.where(live, y, jnp.zeros((), y.dtype))


# rows, K, N, rows of each group
CASES = {
    "uneven": (48, 128, 256, (20, 5, 17)),
    "empty-group": (48, 128, 256, (20, 0, 12)),
    "one-group": (64, 128, 128, (0, 64, 0)),
    "rows-past-the-groups": (64, 128, 128, (10, 3, 0, 7)),
    "untiled-widths": (40, 200, 136, (11, 0, 25)),
    "groups-across-tiles": (96, 256, 128, (1, 40, 2, 33, 20)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_dot_matches_ragged_dot_and_its_autodiff(case, dtype):
    rows, k, n, sizes = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(rows * k + n), 3)
    x = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
         * k ** -0.5).astype(dtype)
    dy = jax.random.normal(keys[2], (rows, n), jnp.float32).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)

    def value_and_pull(dot):
        y, pull = jax.vjp(lambda x, w: dot(x, w, sizes), x, w)
        return (y,) + pull(dy)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda: value_and_pull(grouped_pallas.grouped_dot))()
        want = jax.jit(lambda: value_and_pull(_ragged))()
    held = int(jnp.sum(sizes))
    for name, g, r in zip(("y", "dx", "dw"), got, want):
        assert g.dtype == r.dtype == dtype, name
        assert g.shape == r.shape, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        scale = float(np.max(np.abs(r)))
        assert scale > 0.1, name
        bound = 1e-5 if dtype == jnp.float32 else 1e-2
        assert float(np.max(np.abs(g - r))) <= bound * scale, name
    y, dx, dw = (np.asarray(a, np.float32) for a in got)
    assert not y[held:].any() and not dx[held:].any()    # exactly zero
    for group, size in enumerate(np.asarray(sizes)):
        assert dw[group].any() == (size > 0), group


def test_rows_past_the_groups_take_no_part_in_either_cotangent():
    """Whatever the rows past sum(sizes) hold, the value and both
    cotangents are those of the same rows zeroed."""
    rows, k, n, sizes = CASES["rows-past-the-groups"]
    sizes = jnp.asarray(sizes, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (rows, k), jnp.float32)
    w = jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
    dy = jax.random.normal(keys[2], (rows, n), jnp.float32)
    dead = x.at[20:].set(1e4)

    def run(x):
        y, pull = jax.vjp(
            lambda x, w: grouped_pallas.grouped_dot(x, w, sizes), x, w)
        return (y,) + pull(dy)

    for a, b in zip(run(x), run(dead)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_operands_of_two_types_are_refused():
    with pytest.raises(TypeError, match="one type"):
        grouped_pallas.grouped_dot(jnp.zeros((16, 128), jnp.bfloat16),
                                   jnp.zeros((2, 128, 128), jnp.float32),
                                   jnp.asarray([8, 8]))


# -- the expert layer on the kernel against the layer on lax.ragged_dot -----

D, F = 16, 32


def _layer(rng, n, held, swiglu):
    h = len(held)
    p = {"wr": rng.standard_normal((D, n)) * D ** -0.5,
         "w1": rng.standard_normal((h, D, F)) * D ** -0.5,
         "w2": rng.standard_normal((h, F, D)) * F ** -0.5,
         "sw1": rng.standard_normal((D, F)) * .3,
         "sw2": rng.standard_normal((F, D)) * .3}
    if swiglu:
        p.update(w3=rng.standard_normal((h, D, F)) * D ** -0.5,
                 sw3=rng.standard_normal((D, F)) * .3)
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


# experts of the layer, held, top_k, SwiGLU (else relu2), tokens: 512 tokens
# fit the compact program at 2 of 8 held, 64 overflow none of it
LAYERS = {
    "swiglu-compact": (8, (5, 2), 2, True, 512),
    "relu2-compact": (8, (5, 2), 2, False, 512),
    "swiglu-all-held": (8, tuple(range(8)), 3, True, 64),
    "relu2-share": (8, (1, 4, 6), 3, False, 64),
}


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_expert_layer_matches_the_layer_on_ragged_dot(rng, monkeypatch,
                                                      name):
    n, held, top_k, swiglu, tokens = LAYERS[name]
    params = _layer(rng, n, held, swiglu)
    x = jnp.asarray(rng.standard_normal((1, tokens, D)), jnp.float32)

    def run():
        def loss(params, x):
            y = moe.held_experts_ffn(params, x, num_experts=n, top_k=top_k,
                                     held=held, scale=1.8)
            return jnp.sum(jnp.sin(y)), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
                params, x)

    (got_p, got_x), got_y = run()
    monkeypatch.setattr(moe, "grouped_dot", _ragged)
    (want_p, want_x), want_y = run()
    tol = dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), **tol)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x), **tol)
    assert sorted(got_p) == sorted(params)
    for leaf in sorted(params):
        np.testing.assert_allclose(np.asarray(got_p[leaf]),
                                   np.asarray(want_p[leaf]), err_msg=leaf,
                                   **tol)
        assert float(jnp.max(jnp.abs(want_p[leaf]))) > 1e-3, leaf
