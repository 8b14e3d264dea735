"""ops/ssd_pallas.py — Mamba-2's chunked scan as Pallas kernels, run in the
interpreter at tiny widths — against the recurrence taken one position
after the other: the values, and the gradients with respect to x, the
steps, the decay rates, B and C.  Groups of several heads, several groups,
a state carried over three chunks and more, a sequence that is no multiple
of the chunk, bf16 operands, and inside shard_map."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fpga_ai_nic_tpu.models import nemotron_h
from fpga_ai_nic_tpu.ops import ssd_pallas

HIGHEST = lax.Precision.HIGHEST


def recurrence(x, step, a, b, c):
    """S_t = exp(D_t a) S_{t-1} + D_t x_t (x) B_t, y_t = C_t . S_t, one
    position after the other in float32: no chunk, no matrix form."""
    f32 = jnp.float32
    per = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(t.astype(f32), per, axis=2) for t in (b, c))

    def one(state, t):
        x_t, d_t, b_t, c_t = t
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=HIGHEST)

    empty = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], f32)
    _, y = lax.scan(one, empty, tuple(
        jnp.swapaxes(t, 0, 1) for t in (x.astype(f32), step, b, c)))
    return jnp.swapaxes(y, 0, 1)


def inputs(seq, heads, groups, dtype=jnp.float32, seed=0, p=8, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, seq, heads, p)).astype(dtype)
    step = jax.nn.softplus(jax.random.normal(ks[1], (2, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)))
    b = (0.5 * jax.random.normal(ks[3], (2, seq, groups, n))).astype(dtype)
    c = (0.5 * jax.random.normal(ks[4], (2, seq, groups, n))).astype(dtype)
    return x, step, a, b, c


def rel_l2(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


def grads(fn, args):
    """Every input's gradient of sum(sin(y) * w): w makes each position's
    cotangent its own."""
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(*t):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.sin(fn(*t)) * w)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)


# (positions, heads, groups, chunk): R = heads / groups heads a group
SHAPES = {
    "two-groups-of-two-five-chunks": (40, 4, 2, 8),
    "one-group-of-four-three-chunks": (24, 4, 1, 8),
    "a-head-a-group": (32, 4, 4, 8),
    "four-groups-of-two-chunks-of-16": (64, 8, 4, 16),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_values_are_the_recurrence_s(shape):
    seq, heads, groups, chunk = SHAPES[shape]
    args = inputs(seq, heads, groups)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *t: ssd_pallas.ssd(*t, chunk))(*args)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    assert rel_l2(got, recurrence(*args)) < 1e-5


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gradients_are_the_recurrence_s(shape):
    seq, heads, groups, chunk = SHAPES[shape]
    args = inputs(seq, heads, groups, seed=1)
    got = grads(lambda *t: ssd_pallas.ssd(*t, chunk), args)
    want = grads(recurrence, args)
    for name, g, w, arg in zip("x step a b c".split(), got, want, args):
        assert g.shape == arg.shape and g.dtype == arg.dtype, name
        assert rel_l2(g, w) < 1e-4, (name, rel_l2(g, w))


@pytest.mark.parametrize("seq", [29, 21, 5])
def test_a_ragged_last_chunk_is_padded_with_steps_of_zero(seq):
    """Through `nemotron_h.ssd_scan`, which pads the sequence to a whole
    number of chunks: values and every gradient."""
    args = inputs(seq, 4, 2, seed=2)

    def scan(*t):
        return nemotron_h.ssd_scan(*t, 8)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(scan)(*args)
    assert got.shape == args[0].shape
    assert rel_l2(got, recurrence(*args)) < 1e-5
    for name, g, w in zip("x step a b c".split(), grads(scan, args),
                          grads(recurrence, args)):
        assert rel_l2(g, w) < 1e-4, (name, rel_l2(g, w))


def test_the_kernels_take_whole_chunks_only():
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_pallas.ssd(*inputs(12, 4, 2), 8)


def test_bf16_operands_keep_their_type_and_float32_accumulation():
    """x, B and C in bf16: the products take bf16 operands and accumulate
    in float32, so the result is the recurrence's on the same (rounded)
    inputs to within bf16's rounding of mix, D x and the carried state, and
    every cotangent comes back in its input's type."""
    args = inputs(40, 4, 2, jnp.bfloat16, seed=3)
    got = jax.jit(lambda *t: ssd_pallas.ssd(*t, 8))(*args)
    assert got.dtype == jnp.float32
    assert 1e-4 < rel_l2(got, recurrence(*args)) < 1.5e-2
    got = grads(lambda *t: ssd_pallas.ssd(*t, 8), args)
    for name, g, w, arg in zip("x step a b c".split(), got,
                               grads(recurrence, args), args):
        assert g.dtype == arg.dtype, name
        assert rel_l2(g, w) < 4e-2, (name, rel_l2(g, w))


def test_the_state_reaches_across_chunks_and_never_back():
    """Position t reads nothing after t; a slow decay carries x at position
    2 to the end of the fourth chunk."""
    x, step, a, b, c = inputs(32, 4, 2, seed=4)
    step = 0.05 * step
    scan = jax.jit(lambda *t: ssd_pallas.ssd(*t, 8))
    base = scan(x, step, a, b, c)
    later = scan(x.at[:, 20:].add(1.0), step, a, b, c)
    np.testing.assert_allclose(np.asarray(later[:, :20]),
                               np.asarray(base[:, :20]), atol=1e-6)
    earlier = scan(x.at[:, 2].add(1.0), step, a, b, c)
    assert float(jnp.max(jnp.abs(earlier[:, 31] - base[:, 31]))) > 1e-3


def test_inside_shard_map_each_rank_scans_its_own_sequences():
    """Over two devices, a sequence each, as the trainer runs it: values and
    gradients those of the scan over both sequences at once."""
    mesh = jax.make_mesh((2,), ("dp",), devices=jax.devices()[:2])
    args = inputs(24, 4, 2, seed=5)
    spec = (P("dp"), P("dp"), P(), P("dp"), P("dp"))
    sharded = jax.shard_map(lambda *t: ssd_pallas.ssd(*t, 8), mesh=mesh,
                            in_specs=spec, out_specs=P("dp"))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(sharded)(*args)
    assert rel_l2(got, recurrence(*args)) < 1e-5
    for name, g, w in zip("x step a b c".split(), grads(sharded, args),
                          grads(recurrence, args)):
        assert rel_l2(g, w) < 1e-4, (name, rel_l2(g, w))
