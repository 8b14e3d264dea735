"""Mamba-2's chunked scan (state-space duality, arXiv:2405.21060) as Pallas
kernels that keep a chunk's decay block in fast memory.

`ssd(x, step, a, b, c, chunk)`: y_t = C_t . S_t of S_t = exp(D_t a) S_{t-1}
+ D_t x_t (x) B_t, S zero before the sequence; x [B, S, H, P], the steps
D = `step` [B, S, H] float32, a [H], b and c [B, S, G, N], head h reading
group h // (H / G), S a multiple of `chunk` -> y [B, S, H, P] float32.

The kernels run over a grid of (sequence, group, chunk), the chunks in
order: a grid step takes the Q = `chunk` positions of one chunk for a
group's R = H / G heads, so C B^T is made once a group and chunk; x and y
are read and written in their own layout ([Q, R P] of [B, S, H P]), B and
C as [Q, N] blocks; the state is kept as [N, R P].

- `ssm_scan.fwd`: for each head, in fast memory, the float32 decay block
  L = exp(cs_l - cs_s) for l >= s (cs the log-decay from the chunk's start,
  the cumulative sum of D a), mix = C B^T * L in x's type, y = mix (D x)
  plus the carried state's read-out (C . S) * exp(cs); then the state the
  chunk ends with, exp(cs_last) S + B^T (exp(cs_last - cs) * D x), kept in a
  float32 scratch across the chunks.  y is its one output.
- `ssm_scan.states`: the same carry again, for the backward: the state each
  chunk starts from, [N, H P] float32 a chunk.  Rebuilt rather than kept
  from the forward, which would write it on both of its passes (the
  blocks' checkpoint runs the forward twice) for one read.
- `ssm_scan.bwd`: the chunks in reverse, the cotangent of the state carried
  in float32 fast memory, each decay block rebuilt: dx, dB and dC (summed
  over a group's heads), dD through D x, and the cotangent of cs a position
  and head, which XLA turns into dD and da: a reverse cumulative sum within
  the chunks and the chain through D a.

The rounding points are those of the matrix form written in XLA: the
operands of every product take x's type (mix, D x, the states read out,
exp(cs_last - cs) * D x) and accumulate in float32; the decay and the
carried state stay float32.  The backward's cotangents stay float32 but
where they enter a product.  cs is computed outside the kernels, in
float32, as jnp.cumsum does; D and cs reach the kernels a row a head
([B, G, R, S]: no lane padding) and a kernel transposes its block.  The
entry points are jitted on their own with only the chunk and the
interpreter static, so a step that runs the scan at many sites (each
mixer, the recompute under the blocks' checkpoint) traces and lowers each
distinct kernel once a jaxpr, however many mixers there are (jax keeps two
jaxprs of the forward under the checkpoint).  Off the TPU the same kernels
run in Pallas's interpreter (`grouped_pallas._launch`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.names import kernel
from .bfp_pallas import _is_tpu
from .grouped_pallas import _launch, _vma

f32 = jnp.float32
# the chunks in order, one after the other: the state is carried
PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _dot(a, b, ta=False, tb=False):
    """a @ b (a^T where `ta`, b^T where `tb`), accumulated in float32."""
    dims = (((0,) if ta else (1,), (1,) if tb else (0,)), ((), ()))
    return lax.dot_general(a, b, dims, preferred_element_type=f32)


def _reach(q):
    """[Q, Q]: l >= s, position s reaching position l of a chunk."""
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _decay(col, row, reach):
    """L [Q, Q]: exp(col_l - row_s) where l >= s, else 0."""
    return jnp.exp(jnp.where(reach, col - row, -jnp.inf))


def _slabs(heads, p):
    """(heads a slab, masks [1, per P] of each of its heads' lanes): a slab
    is as many heads side by side as fill 128 lanes, so that what a head
    does with its [Q, P] columns runs on whole vregs, at lane offset 0."""
    per = max(k for k in range(1, heads + 1)
              if heads % k == 0 and (k * p <= 128 or k == 1))
    lane = lax.broadcasted_iota(jnp.int32, (1, per * p), 1)
    return per, [(lane >= i * p) & (lane < (i + 1) * p) for i in range(per)]


def _spread(cols, masks):
    """Columns [rows, 1], one a head of a slab -> [rows, per P], each head's
    column over its own lanes."""
    out = jnp.broadcast_to(cols[0], (cols[0].shape[0], masks[0].shape[1]))
    for col, mask in zip(cols[1:], masks[1:]):
        out = jnp.where(mask, col, out)
    return out


def _ends(cs, ends_ref):
    """ends_ref [N, R] <- exp(cs_last) a head, the decay over the whole
    chunk, on each of a state's N rows: read back a head's column at a
    time, it broadcasts over lanes alone."""
    ends_ref[...] = jnp.exp(jnp.broadcast_to(cs[cs.shape[0] - 1:, :],
                                             ends_ref.shape))
    return ends_ref


def _slab_inputs(x_ref, d, cs, j, per, masks):
    """Slab j's lanes, and x, D, D x and exp(cs_last - cs), [Q, per P]."""
    q, width = x_ref.shape
    p = width // d.shape[1]
    lanes = slice(j * per * p, (j + 1) * per * p)
    heads = range(j * per, (j + 1) * per)
    x = x_ref[:, lanes].astype(f32)
    steps = _spread([d[:, h:h + 1] for h in heads], masks)
    to_end = jnp.exp(_spread([cs[q - 1:, h:h + 1] - cs[:, h:h + 1]
                              for h in heads], masks))
    return lanes, x, steps, x * steps, to_end


def _advance(b, prev, cs, state_ref, to_end_ref, ends_ref, per, masks):
    """state_ref <- the state the chunk ends with, exp(cs_last) S +
    B^T (exp(cs_last - cs) * D x), S = `prev` the one it starts from and
    to_end_ref the last factor, in x's type."""
    own = _dot(b, to_end_ref[...], ta=True)              # [N, R P]
    ends = _ends(cs, ends_ref)
    width = per * prev.shape[1] // cs.shape[1]
    for j in range(cs.shape[1] // per):
        lanes = slice(j * width, (j + 1) * width)
        scale = _spread([ends[:, h:h + 1]
                         for h in range(j * per, (j + 1) * per)], masks)
        state_ref[:, lanes] = scale * prev[:, lanes] + own[:, lanes]


def _select(masks, per_head):
    """[Q, per P] from `per_head`, one such array a head of the slab: each
    head's own lanes."""
    out = per_head[0]
    for mask, arr in zip(masks[1:], per_head[1:]):
        out = jnp.where(mask, arr, out)
    return out


def _fwd_kernel(x_ref, d_ref, cs_ref, b_ref, c_ref, y_ref, state_ref,
                to_end_ref, ends_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    rows = cs_ref[...]                                   # cs [R, Q]
    cs, d = rows.T, d_ref[...].T                         # [Q, R]
    heads, dt = rows.shape[0], x_ref.dtype
    per, masks = _slabs(heads, x_ref.shape[1] // heads)
    b, c = b_ref[...], c_ref[...]
    prev = state_ref[...]                                # S [N, R P]
    cb = _dot(c, b, tb=True)                             # C B^T [Q, Q]
    carried = _dot(c, prev.astype(dt))                   # C . S [Q, R P]
    reach = _reach(cb.shape[0])
    for j in range(heads // per):
        lanes, _, _, xd, to_end = _slab_inputs(x_ref, d, cs, j, per, masks)
        heads_j = range(j * per, (j + 1) * per)
        # a head's product fills the slab; its own lanes are kept
        y = _select(masks, [
            _dot((cb * _decay(cs[:, h:h + 1], rows[h:h + 1, :], reach)
                  ).astype(dt), xd.astype(dt)) for h in heads_j])
        read = jnp.exp(_spread([cs[:, h:h + 1] for h in heads_j], masks))
        y_ref[:, lanes] = y + carried[:, lanes] * read
        to_end_ref[:, lanes] = (xd * to_end).astype(dt)
    _advance(b, prev, cs, state_ref, to_end_ref, ends_ref, per, masks)


def _states_kernel(x_ref, d_ref, cs_ref, b_ref, start_ref, state_ref,
                   to_end_ref, ends_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    cs, d = cs_ref[...].T, d_ref[...].T                  # [Q, R]
    heads = cs.shape[1]
    per, masks = _slabs(heads, x_ref.shape[1] // heads)
    prev = state_ref[...]
    start_ref[...] = prev
    for j in range(heads // per):
        lanes, _, _, xd, to_end = _slab_inputs(x_ref, d, cs, j, per, masks)
        to_end_ref[:, lanes] = (xd * to_end).astype(x_ref.dtype)
    _advance(b_ref[...], prev, cs, state_ref, to_end_ref, ends_ref, per,
             masks)


def _bwd_kernel(x_ref, d_ref, cs_ref, b_ref, c_ref, start_ref, dy_ref,
                dx_ref, dd_ref, dcs_ref, db_ref, dc_ref, dstate_ref,
                to_end_ref, ends_ref, dcarried_ref, cols_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    rows = cs_ref[...]                                   # cs [R, Q]
    cs, d = rows.T, d_ref[...].T                         # [Q, R]
    (q, width), heads, dt = x_ref.shape, rows.shape[0], x_ref.dtype
    per, masks = _slabs(heads, width // heads)
    b, c = b_ref[...], c_ref[...]
    prev = start_ref[...]              # the state the chunk starts from
    ds = dstate_ref[...]               # the cotangent of the one it ends with
    cb = _dot(c, b, tb=True)
    carried = _dot(c, prev.astype(dt))
    d_to_end = _dot(b, ds.astype(dt))                    # [Q, R P]
    ends = _ends(cs, ends_ref)
    reach = _reach(q)
    at_end = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcb = jnp.zeros((q, q), f32)

    def head_sum(mask, a):                               # -> [rows, 1]
        return jnp.sum(jnp.where(mask, a, 0.0), axis=1, keepdims=True)

    for j in range(heads // per):
        lanes, x, steps, xd, to_end = _slab_inputs(x_ref, d, cs, j, per,
                                                   masks)
        heads_j = range(j * per, (j + 1) * per)
        dy = dy_ref[:, lanes]
        dyb, xdb = dy.astype(dt), xd.astype(dt)
        read = jnp.exp(_spread([cs[:, h:h + 1] for h in heads_j], masks))
        dcarried_ref[:, lanes] = (dy * read).astype(dt)
        to_end_ref[:, lanes] = (xd * to_end).astype(dt)
        dte = d_to_end[:, lanes]
        # the state the chunk ends with, through exp(cs_last - cs) * D x
        dxd = dte * to_end
        pulled = dte * xd * to_end
        # the carried state's read-out, (C . S) * exp(cs)
        readout = dy * carried[:, lanes] * read
        kept = prev[:, lanes] * ds[:, lanes] * _spread(
            [ends[:, h:h + 1] for h in heads_j], masks)
        for mask, h in zip(masks, heads_j):
            decay = _decay(cs[:, h:h + 1], rows[h:h + 1, :], reach)
            mix = cb * decay
            # inside the chunk, y = mix (D x): the head's own lanes of dy
            dmix = _dot(jnp.where(mask, dyb, jnp.zeros((), dt)), xdb,
                        tb=True)
            dxd = jnp.where(mask, dxd + _dot(mix.astype(dt), dyb, ta=True),
                            dxd)
            dcb = dcb + dmix * decay
            w = dmix * mix               # d(cs_l - cs_s) through L
            out = head_sum(mask, pulled)
            # cs's cotangent: a column's part (cs_l of L, the read-out,
            # the state) and a row's (cs_s of L)
            cols_ref[:, h:h + 1] = (
                jnp.sum(w, axis=1, keepdims=True) + head_sum(mask, readout)
                - out + jnp.where(at_end, jnp.sum(out, axis=0, keepdims=True)
                                  + jnp.sum(head_sum(mask, kept), axis=0,
                                            keepdims=True), 0.0))
            dcs_ref[h:h + 1, :] = -jnp.sum(w, axis=0, keepdims=True)
            cols_ref[:, heads + h:heads + h + 1] = head_sum(mask, dxd * x)
        dx_ref[:, lanes] = (dxd * steps).astype(dx_ref.dtype)
    cols = cols_ref[...].T                               # [2 R, Q]
    dcs_ref[...] += cols[:heads]
    dd_ref[...] = cols[heads:]
    dcb = dcb.astype(dt)
    dcarried = dcarried_ref[...]
    dc_ref[...] = (_dot(dcb, b) + _dot(dcarried, prev.astype(dt), tb=True)
                   ).astype(dc_ref.dtype)
    db_ref[...] = (_dot(dcb, c, ta=True) + _dot(to_end_ref[...],
                                                ds.astype(dt), tb=True)
                   ).astype(db_ref.dtype)
    back = _dot(c, dcarried, ta=True)                    # [N, R P]
    width_j = per * width // heads
    for j in range(heads // per):
        lanes = slice(j * width_j, (j + 1) * width_j)
        scale = _spread([ends[:, h:h + 1]
                         for h in range(j * per, (j + 1) * per)], masks)
        dstate_ref[:, lanes] = scale * ds[:, lanes] + back[:, lanes]


def _grid(x, b, chunk, reverse):
    """The grid (sequence, group, chunk) and the blocks a grid step takes:
    of x or y [B, S, H P], of a row [B, G, R, S] a head (D, cs and their
    cotangents), of B or C [B, S, G N], of a state [B, nc, N, H P].
    Where `reverse`, the chunks are visited last first."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    r, nc = heads // groups, seq // chunk

    def at(k):
        return nc - 1 - k if reverse else k

    return (batch, groups, nc), dict(
        x=pl.BlockSpec((None, chunk, r * p), lambda i, g, k: (i, at(k), g)),
        row=pl.BlockSpec((None, None, r, chunk),
                         lambda i, g, k: (i, g, 0, at(k))),
        bc=pl.BlockSpec((None, chunk, n), lambda i, g, k: (i, at(k), g)),
        state=pl.BlockSpec((None, None, n, r * p),
                           lambda i, g, k: (i, at(k), 0, g)))


def _flat(t):
    """x [B, S, H, P] or b [B, S, G, N] -> [B, S, H P] or [B, S, G N], the
    layouts the kernels read."""
    return t.reshape(t.shape[:2] + (-1,))


def _scratch(x, b, chunk):
    """The carried state [N, R P] float32, exp(cs_last - cs) * D x
    [Q, R P] in x's type, and exp(cs_last) [N, R]."""
    heads, p = x.shape[2:]
    groups, n = b.shape[2:]
    r = heads // groups
    return [pltpu.VMEM((n, r * p), f32), pltpu.VMEM((chunk, r * p), x.dtype),
            pltpu.VMEM((n, r), f32)]


@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(x, d, cs, b, c, chunk, interpret):
    """y [B, S, H, P] float32."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    r = heads // groups
    grid, spec = _grid(x, b, chunk, False)
    steps = batch * groups * (seq // chunk)
    item = x.dtype.itemsize

    def call(*args):
        return pl.pallas_call(
            _fwd_kernel,
            out_shape=jax.ShapeDtypeStruct((batch, seq, heads * p), f32,
                                           vma=_vma(*args)),
            grid=grid,
            in_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                      spec["bc"]],
            out_specs=spec["x"],
            scratch_shapes=_scratch(x, b, chunk),
            compiler_params=PARAMS,
            cost_estimate=pl.CostEstimate(
                flops=steps * (2 * chunk * chunk * (n + r * p)
                               + 4 * chunk * n * r * p),
                transcendentals=steps * r * (chunk * chunk + 2 * chunk + n),
                bytes_accessed=(x.size * (item + 4) + 2 * d.size * 4
                                + 2 * b.size * item)),
            interpret=interpret,
            **kernel("ssm_scan.fwd"),
        )(*args)

    y = _launch(call, interpret, _flat(x), d, cs, _flat(b), _flat(c))
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _states(x, d, cs, b, chunk, interpret):
    """The state each chunk starts from, [B, nc, N, H P] float32: the
    forward's carry again, for the backward."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    r = heads // groups
    grid, spec = _grid(x, b, chunk, False)
    steps = batch * groups * (seq // chunk)

    def call(*args):
        return pl.pallas_call(
            _states_kernel,
            out_shape=jax.ShapeDtypeStruct(
                (batch, seq // chunk, n, heads * p), f32, vma=_vma(*args)),
            grid=grid,
            in_specs=[spec["x"], spec["row"], spec["row"], spec["bc"]],
            out_specs=spec["state"],
            scratch_shapes=_scratch(x, b, chunk),
            compiler_params=PARAMS,
            cost_estimate=pl.CostEstimate(
                flops=steps * 2 * chunk * n * r * p,
                transcendentals=steps * r * (chunk + n),
                bytes_accessed=(x.size * x.dtype.itemsize + 2 * d.size * 4
                                + b.size * b.dtype.itemsize
                                + steps // groups * n * heads * p * 4)),
            interpret=interpret,
            **kernel("ssm_scan.states"),
        )(*args)

    return _launch(call, interpret, _flat(x), d, cs, _flat(b))


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(x, d, cs, b, c, starts, dy, chunk, interpret):
    """(dx, dD through D x and dcs, both [B, G, R, S], dB, dC) for the
    cotangent dy [B, S, H, P] of y."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    r = heads // groups
    grid, spec = _grid(x, b, chunk, True)
    steps = batch * groups * (seq // chunk)
    item = x.dtype.itemsize

    def call(*args):
        vma = _vma(*args)
        return pl.pallas_call(
            _bwd_kernel,
            out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, vma=vma)
                            for a in args[:5]),
            grid=grid,
            in_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                      spec["bc"], spec["state"], spec["x"]],
            out_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                       spec["bc"]],
            scratch_shapes=_scratch(x, b, chunk) + [
                pltpu.VMEM((chunk, r * p), x.dtype),
                pltpu.VMEM((chunk, 2 * r), f32)],
            compiler_params=PARAMS,
            cost_estimate=pl.CostEstimate(
                flops=steps * (2 * chunk * chunk * (3 * n + 2 * r * p)
                               + 10 * chunk * n * r * p),
                transcendentals=steps * r * (chunk * chunk + 3 * chunk + n),
                bytes_accessed=(2 * x.size * (item + 4) + 4 * d.size * 4
                                + 4 * b.size * item + starts.size * 4)),
            interpret=interpret,
            **kernel("ssm_scan.bwd"),
        )(*args)

    dx, dd, dcs, db, dc = _launch(call, interpret, _flat(x), d, cs, _flat(b),
                                  _flat(c), starts, _flat(dy))
    return (dx.reshape(x.shape), dd, dcs, db.reshape(b.shape),
            dc.reshape(c.shape))


def _by_group(t, groups):
    """[B, S, H] -> [B, G, R, S]: a row a head, a group's heads together."""
    batch, seq, heads = t.shape
    return t.reshape(batch, seq, groups, heads // groups).transpose(
        0, 2, 3, 1)


def _by_head(t):
    """[B, G, R, S] -> [B, S, H]."""
    batch, groups, r, seq = t.shape
    return t.transpose(0, 3, 1, 2).reshape(batch, seq, groups * r)


def _operands(step, a, chunk, groups):
    """D, and cs — the log of the decay from the chunk's start through each
    position, the cumulative sum of D a in float32 — as the kernels read
    them, a row a head."""
    batch, seq, heads = step.shape
    cs = jnp.cumsum((step * a).reshape(batch, seq // chunk, chunk, heads),
                    axis=2).reshape(step.shape)
    return _by_group(step, groups), _by_group(cs, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, step, a, b, c, chunk, interpret):
    return _forward(x, *_operands(step, a, chunk, b.shape[2]), b, c, chunk,
                    interpret)


def _ssd_fwd(x, step, a, b, c, chunk, interpret):
    return _ssd(x, step, a, b, c, chunk, interpret), (x, step, a, b, c)


def _ssd_bwd(chunk, interpret, res, dy):
    x, step, a, b, c = res
    batch, seq, heads = step.shape
    d, cs = _operands(step, a, chunk, b.shape[2])
    starts = _states(x, d, cs, b, chunk, interpret)
    dx, dd, dcs, db, dc = _backward(x, d, cs, b, c, starts, dy, chunk,
                                    interpret)
    # cs_l sums D a over the chunk's positions up to l: D_t a's cotangent
    # is the sum of cs's from t to the chunk's end
    dsa = lax.cumsum(_by_head(dcs).reshape(batch, seq // chunk, chunk, heads),
                     axis=2, reverse=True).reshape(step.shape)
    return (dx, _by_head(dd) + dsa * a, jnp.sum(dsa * step, axis=(0, 1)), db,
            dc)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x: jax.Array, step: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int) -> jax.Array:
    """The chunked scan (module docstring): x [B, S, H, P], step [B, S, H]
    float32, a [H], b and c [B, S, G, N], S a multiple of `chunk` -> y
    [B, S, H, P] float32."""
    if x.shape[1] % chunk:
        raise ValueError(f"{x.shape[1]} positions are no multiple of the "
                         f"chunk {chunk}")
    vma = _vma(x, step, a, b, c)
    x, step, a, b, c = (
        lax.pcast(t, tuple(sorted(vma - jax.typeof(t).vma)), to="varying")
        if vma - jax.typeof(t).vma else t for t in (x, step, a, b, c))
    # traced under the mesh that the backward pass sets from the cotangent
    # (the operands' own), so that a forward here and the recompute in a
    # backward share one trace and one lowering of each shape
    with jax.sharding.use_abstract_mesh(jax.typeof(x).sharding.mesh):
        return _ssd(x, step, a, b, c, chunk, not _is_tpu())
