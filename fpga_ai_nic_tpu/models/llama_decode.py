"""Incremental (KV-cache) decoding for the Llama family.

The reference is a training-only system (SURVEY.md: an MLP trainer with a
hardware all-reduce; no inference path exists to mirror), but a framework
whose flagship model is a decoder owes its users generation.  TPU-first
shape of the problem:

- **Static shapes everywhere.**  The cache is allocated at ``max_seq`` up
  front and written with ``dynamic_update_slice``; attention always scores
  against the full cache with an ``iota <= pos`` mask.  Nothing recompiles
  as the sequence grows — the XLA contract (one trace, one binary) that
  data-dependent cache growth would break.
- **The decode loop is a ``lax.scan``** over generated positions: one
  compiled program for the whole generation, host round-trip free.
- **tp composes** exactly as in training: heads shard over tp, the cache
  shards with them ([B, n_kv/tp, max_seq, hd] per rank), and the same
  row-parallel psum closes each block (call inside shard_map with
  ``llama.param_specs`` shardings).  kv-head replication (tp > n_kv)
  works the same way training's does (llama._block): wk/wv arrive
  replicated, each rank slices the ONE kv head serving its query group,
  and the cache holds that single head per rank — a config that trains
  can always generate.

Layer-stack params use the same pytree as ``llama.init``; weights trained
by any trainer in `parallel/` drop straight in.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import llama
from .llama import LlamaConfig


def kv_local_heads(cfg: LlamaConfig, tp_size: int = 1) -> int:
    """Per-rank KV head count: n_kv/tp, or 1 under kv-head replication
    (tp > n_kv — each rank slices the ONE head serving its query group)."""
    if cfg.n_kv_heads % tp_size == 0:
        return cfg.n_kv_heads // tp_size
    if tp_size % cfg.n_kv_heads == 0:
        return 1                      # replicated-kv: one sliced head/rank
    raise ValueError(
        f"tp={tp_size} must divide n_kv_heads={cfg.n_kv_heads}, or be "
        f"a multiple of it (kv-head replication)")


def init_cache(cfg: LlamaConfig, batch: int, max_seq: int, *,
               tp_size: int = 1, dtype=None) -> List[Dict]:
    """Per-layer K/V cache [B, kv_local, max_seq, head_dim], zero-filled;
    kv_local = n_kv/tp, or 1 under kv-head replication (tp > n_kv).

    HBM cost caveat: the WHOLE [B, kv_local, max_seq, hd] extent is
    allocated and zero-filled up front, per layer, per K and V — a batch
    of short sequences pays for max_seq anyway, and B concurrent
    sequences cannot share a byte.  That is the right trade for a single
    fixed-shape generate() call; it is the wrong one for a serving plane
    multiplexing thousands of requests (see `serve.paged.init_pool` +
    `forward_paged`: one shared page pool, per-sequence page tables;
    `tools/serve_bench.py` banks the byte comparison)."""
    kv_local = kv_local_heads(cfg, tp_size)
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (batch, kv_local, max_seq, cfg.head_dim)
    return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for _ in range(cfg.n_layers)]


def _cached_attend(q, ck, cv, pos, n_heads, n_kv, sm_scale):
    """q: [B,H,T,hd] (T = tokens this call, ending at position pos+T-1);
    ck/cv: [B,Hkv,Smax,hd] cache AFTER this call's keys were written.
    Scores the full static cache with a two-sided mask: key j visible to
    query t iff j <= pos + t (causal) and j < pos + T (written).

    ``pos`` is a scalar (whole batch at one position — the generate()
    path) or a [B] vector (each sequence at its own position — the
    serving plane's continuous-batching decode, where slots advance
    independently).  The scalar path is untouched: a uniform [B] vector
    computes the identical mask, so the two agree bitwise."""
    B, H, T, hd = q.shape
    Smax = ck.shape[2]
    # GQA via a grouped einsum — the cache is read ONCE per kv head
    # instead of jnp.repeat materializing a G-times copy every decode
    # step (decode is cache-bandwidth-bound, so the repeat was a direct
    # G-times throughput tax).  G == 1 (MHA) takes the same path with
    # identical contractions.
    G = n_heads // n_kv
    qg = q.astype(jnp.float32).reshape(B, n_kv, G, T, hd)
    s = jnp.einsum("bkgtd,bkjd->bkgtj", qg, ck.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    j = lax.broadcasted_iota(jnp.int32, (T, Smax), 1)
    t = lax.broadcasted_iota(jnp.int32, (T, Smax), 0)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        visible = j <= (pos + t)                   # causal + written bound
        s = jnp.where(visible[None, None, None], s, jnp.float32(-1e30))
    else:                                          # per-sequence positions
        visible = j[None] <= (pos[:, None, None] + t[None])  # [B,T,Smax]
        s = jnp.where(visible[:, None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgtj,bkjd->bkgtd", p, cv.astype(jnp.float32))
    return out.reshape(B, H, T, hd)


def forward(params: Dict, tokens: jax.Array, cache: List[Dict],
            pos: jax.Array, cfg: LlamaConfig, *,
            tp_axis: Optional[str] = None
            ) -> Tuple[jax.Array, List[Dict]]:
    """Run ``tokens [B, T]`` (their global positions are pos..pos+T-1)
    through the decoder, reading and extending the cache.

    T is static: call once with the whole prompt (prefill), then with
    T == 1 per generated token.  Returns (logits [B, T, vocab], cache').
    pos is a traced scalar — one compiled program serves every step.
    """
    B, T = tokens.shape
    Hd = cfg.head_dim
    n_heads, n_kv = llama._shard_counts(cfg, tp_axis)
    kv_rep = n_kv == 0
    if kv_rep:
        # kv-head replication (tp > n_kv), same mechanism as training
        # (llama._block): wk/wv arrive replicated over tp; each rank
        # slices the ONE kv head serving its query group and caches just
        # that head
        n_kv = 1
    sm_scale = Hd ** -0.5
    positions = pos + llama._positions(T, None)

    x = params["tok_emb"][tokens]
    new_cache: List[Dict] = []
    for lyr, c in zip(params["layers"], cache):
        if kv_rep:
            wk, wv = llama._kv_rep_slice(lyr, cfg, tp_axis)
        else:
            wk, wv = lyr["wk"], lyr["wv"]
        h = llama._rmsnorm(x, lyr["attn_norm"], cfg.norm_eps)
        q = (h @ lyr["wq"]).reshape(B, T, n_heads, Hd).transpose(0, 2, 1, 3)
        k = (h @ wk).reshape(B, T, n_kv, Hd).transpose(0, 2, 1, 3)
        v = (h @ wv).reshape(B, T, n_kv, Hd).transpose(0, 2, 1, 3)
        q = llama._rope(q, positions, cfg)
        k = llama._rope(k, positions, cfg)
        ck = lax.dynamic_update_slice(c["k"], k.astype(c["k"].dtype),
                                      (0, 0, pos, 0))
        cv = lax.dynamic_update_slice(c["v"], v.astype(c["v"].dtype),
                                      (0, 0, pos, 0))
        new_cache.append({"k": ck, "v": cv})
        att = _cached_attend(q, ck, cv, pos, n_heads, n_kv, sm_scale)
        att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
            B, T, n_heads * Hd)
        x = x + llama._psum_if(att @ lyr["wo"], tp_axis)

        h = llama._rmsnorm(x, lyr["mlp_norm"], cfg.norm_eps)
        if "moe" in lyr:
            from ..ops import moe as moe_ops
            ff, _ = moe_ops.moe_ffn(lyr["moe"], h, cfg.moe)
        else:
            gate = jax.nn.silu((h @ lyr["w1"]).astype(jnp.float32)
                               ).astype(x.dtype)
            ff = (gate * (h @ lyr["w3"])) @ lyr["w2"]
        x = x + llama._psum_if(ff, tp_axis)

    x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]                  # [B, T, V/tp]
    if tp_axis is not None:
        logits = lax.all_gather(logits, tp_axis, axis=2, tiled=True)
    return logits, new_cache


def _rope_rows(x: jax.Array, pos: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Rotate-half rope with PER-SEQUENCE positions: x [B,H,T,dh],
    pos [B,T] global positions.  Same formula as llama._rope (which
    takes one shared [T] vector); a row-constant grid runs the identical
    elementwise ops, so the two agree bitwise — the parity seam between
    generate()'s uniform batch and the serving plane's mixed-position
    decode."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = llama._rope_freqs(cfg, half)
    ang = pos.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # [B,1,T,half]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def forward_paged(params: Dict, tokens: jax.Array, pool: List[Dict],
                  page_table: jax.Array, pos: jax.Array, cfg: LlamaConfig,
                  *, page_size: int, tp_axis: Optional[str] = None,
                  active: Optional[jax.Array] = None,
                  attend_impl: str = "reference"
                  ) -> Tuple[jax.Array, List[Dict]]:
    """Paged-KV forward — the serving plane's decode path.

    ``tokens [R, T]``: R request slots, T tokens each (T == 1 for decode,
    T == chunk for chunked prefill); ``pos [R]``: each slot's global
    position for its first token this call; ``pool``: per-layer
    ``{"k","v"}`` pages ``[n_pages, kv_local, page_size, hd]`` shared by
    every slot (``serve.paged.init_pool``); ``page_table [R, P]`` int32:
    ``page_table[r, i]`` is the pool page holding slot r's positions
    ``[i*page_size, (i+1)*page_size)``; ``active [R]`` bool (None = all)
    gates K/V writes — empty slots write zeros into the reserved null
    page 0 and their logits are garbage the host ignores.

    Bit-parity contract (pinned by tests/test_serve.py): for the same
    token stream and chunk schedule, with Smax == P*page_size, logits
    are BITWISE identical to ``forward()`` over the contiguous
    ``init_cache`` — for ANY page assignment, even into a dirty
    (recycled) pool.  Unwritten/garbage positions sit behind the same
    -1e30 mask in both paths; their exact-zero softmax weights multiply
    the garbage away in f32 (0 * finite == ±0, and a ±0 term never moves
    an f32 sum).

    Every shape is static in (R, T, P, page_size): admissions, evictions
    and page re-assignments change VALUES only, so a jitted step is
    trace-stable across any admit/evict schedule (frozen as graftlint
    J10).

    ``attend_impl`` picks how the pool is scored: ``"reference"``
    (default) materializes the gathered view below — the portable XLA
    path and the bitwise oracle; ``"pallas"`` runs
    `ops.paged_attend_pallas.paged_gather_attend`, which walks the page
    table and DMAs live pages HBM->VMEM inside the kernel instead.  The
    two are bitwise-identical on a given backend
    (tests/test_paged_attend.py), so the contract above holds for
    both."""
    if attend_impl not in ("reference", "pallas"):
        raise ValueError(
            f"forward_paged: unknown attend_impl={attend_impl!r}; "
            "expected 'reference' or 'pallas'")
    R, T = tokens.shape
    Hd = cfg.head_dim
    P = page_table.shape[1]
    n_heads, n_kv = llama._shard_counts(cfg, tp_axis)
    kv_rep = n_kv == 0
    if kv_rep:
        n_kv = 1
    sm_scale = Hd ** -0.5
    pos = jnp.asarray(pos, jnp.int32)
    pos_grid = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    # scatter coordinates for this call's K/V rows: (page, in-page
    # offset) per (slot, token); the page index is clamped defensively —
    # the scheduler's bound is pos + T <= P*page_size for active slots,
    # and inactive slots sit at pos 0 in the null page
    page_of = jnp.take_along_axis(
        page_table, jnp.minimum(pos_grid // page_size, P - 1), axis=1)
    if active is None:
        act = jnp.ones((R,), bool)
    else:
        act = jnp.asarray(active, bool)
    # two classes of writes must be REDIRECTED to the null page, not
    # merely value-masked — their clamped/aliased page index would land
    # in a LIVE page otherwise: (a) inactive slots, whose table row may
    # hold a co-resident's pages; (b) positions beyond the table's range
    # (a final prefill chunk's zero-padding when pos+T overruns
    # P*page_size — the clamp above would alias them onto the LAST live
    # page and corrupt its K/V at the same in-page offsets)
    in_range = pos_grid < P * page_size
    page_of = jnp.where(act[:, None] & in_range, page_of, 0)
    flat_pages = page_of.reshape(-1)
    flat_offs = (pos_grid % page_size).reshape(-1)
    gate = act[:, None, None, None]

    x = params["tok_emb"][tokens]
    new_pool: List[Dict] = []
    for lyr, pl in zip(params["layers"], pool):
        if kv_rep:
            wk, wv = llama._kv_rep_slice(lyr, cfg, tp_axis)
        else:
            wk, wv = lyr["wk"], lyr["wv"]
        h = llama._rmsnorm(x, lyr["attn_norm"], cfg.norm_eps)
        q = (h @ lyr["wq"]).reshape(R, T, n_heads, Hd).transpose(0, 2, 1, 3)
        k = (h @ wk).reshape(R, T, n_kv, Hd).transpose(0, 2, 1, 3)
        v = (h @ wv).reshape(R, T, n_kv, Hd).transpose(0, 2, 1, 3)
        q = _rope_rows(q, pos_grid, cfg)
        k = _rope_rows(k, pos_grid, cfg)
        dt = pl["k"].dtype
        # inactive slots write zeros (all aimed at the null page, so the
        # duplicate scatter indices all carry the same value and the
        # result is deterministic regardless of write order)
        kw = jnp.where(gate, k, 0).astype(dt).transpose(0, 2, 1, 3)
        vw = jnp.where(gate, v, 0).astype(dt).transpose(0, 2, 1, 3)
        pk = pl["k"].at[flat_pages, :, flat_offs, :].set(
            kw.reshape(R * T, n_kv, Hd))
        pv = pl["v"].at[flat_pages, :, flat_offs, :].set(
            vw.reshape(R * T, n_kv, Hd))
        new_pool.append({"k": pk, "v": pv})
        if attend_impl == "pallas":
            # Pallas gather-attend: the gathered view is never formed —
            # the kernel walks page_table and DMAs each LIVE page
            # HBM->VMEM, so decode bytes/token follow the live KV
            # rather than the allocated page span (docs/SERVING.md).
            from ..ops import paged_attend_pallas as _paged_pallas
            att = _paged_pallas.paged_gather_attend(
                q, pk, pv, page_table, pos, page_size=page_size,
                sm_scale=sm_scale)
        else:
            # reference: gather each slot's paged view
            # [R, kv, P*page_size, hd] — the array forward() reads
            # straight out of the contiguous cache.  XLA materializes
            # it; bytes scale with the ALLOCATED span, which is why
            # this stays the portable oracle rather than the fast path.
            ck = pk[page_table].transpose(0, 2, 1, 3, 4).reshape(
                R, n_kv, P * page_size, Hd)
            cv = pv[page_table].transpose(0, 2, 1, 3, 4).reshape(
                R, n_kv, P * page_size, Hd)
            att = _cached_attend(q, ck, cv, pos, n_heads, n_kv, sm_scale)
        att = att.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
            R, T, n_heads * Hd)
        x = x + llama._psum_if(att @ lyr["wo"], tp_axis)

        h = llama._rmsnorm(x, lyr["mlp_norm"], cfg.norm_eps)
        if "moe" in lyr:
            from ..ops import moe as moe_ops
            ff, _ = moe_ops.moe_ffn(lyr["moe"], h, cfg.moe)
        else:
            gate_act = jax.nn.silu((h @ lyr["w1"]).astype(jnp.float32)
                                   ).astype(x.dtype)
            ff = (gate_act * (h @ lyr["w3"])) @ lyr["w2"]
        x = x + llama._psum_if(ff, tp_axis)

    x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]                  # [R, T, V/tp]
    if tp_axis is not None:
        logits = lax.all_gather(logits, tp_axis, axis=2, tiled=True)
    return logits, new_pool


def generate(params: Dict, prompt: jax.Array, n_new: int,
             cfg: LlamaConfig, *, max_seq: Optional[int] = None,
             tp_axis: Optional[str] = None,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """Greedy (temperature=0) or sampled generation.

    prompt: [B, S0] int32.  Returns [B, S0 + n_new].  One prefill call
    plus one scanned decode program; everything stays on device.
    """
    B, S0 = prompt.shape
    if n_new <= 0:
        return prompt
    max_seq = max_seq or (S0 + n_new)
    assert max_seq >= S0 + n_new, (max_seq, S0, n_new)
    tp = lax.axis_size(tp_axis) if tp_axis is not None else 1
    cache = init_cache(cfg, B, max_seq, tp_size=tp)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    logits, cache = forward(params, prompt, cache, jnp.int32(0), cfg,
                            tp_axis=tp_axis)

    def pick(logits_last, key):
        if temperature == 0.0:
            return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits_last.astype(jnp.float32) / temperature,
            axis=-1).astype(jnp.int32)

    first = pick(logits[:, -1], rng)

    def step(carry, key):
        tok, cache, pos = carry
        logits, cache = forward(params, tok[:, None], cache, pos, cfg,
                                tp_axis=tp_axis)
        nxt = pick(logits[:, -1], key)
        return (nxt, cache, pos + 1), tok

    keys = jax.random.split(jax.random.fold_in(rng, 1), max(n_new - 1, 1))
    (last, _, _), toks = lax.scan(step, (first, cache, jnp.int32(S0)),
                                  keys[:n_new - 1])
    out = jnp.concatenate([prompt, toks.T, last[:, None]], axis=1)
    return out
