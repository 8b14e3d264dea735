#!/usr/bin/env python
"""On-chip A/B of the expert layer's grouped product: `lax.ragged_dot` (the
compiler's own grouped kernels) against `ops/grouped_pallas.grouped_dot`
at the tile sizes given, at the compact programs' shapes of the three
expert cells, forward alone and forward with backward.

Rows: C sorted rows, of which the experts held take half (the compact
program holds SLACK = 2 times what a uniform router sends them), split
over the 8 experts held by a fixed multinomial draw.  A time is the best
of three runs of 50 back-to-back dispatches over 50, on the device; the
numerics are each result's largest difference from `ragged_dot`'s over
the largest magnitude of `ragged_dot`'s.

Usage: python tools/grouped_kernel_probe.py [ROW_TILE,LANE_TILE,TGMM_K_TILE ...]
(TPU required; prints one JSON line a measurement)
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

# cell -> (C, model width D, expert width F, relu2: two products, else three)
SHAPES = {"glm47-flash-ep8share-s4096": (8192, 2048, 1536),
          "lfm2-24b-ep8share-s8192": (32768, 2048, 1536),
          "nemotron-twotower-ep16share-s8192": (6144, 2688, 1856)}
HELD = 8


def main():
    from bench_common import enable_compile_cache, require_tpu
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    require_tpu("grouped_kernel_probe")
    enable_compile_cache()
    from fpga_ai_nic_tpu.ops import grouped_pallas as gp

    variants = [tuple(int(t) for t in v.split(",")) for v in sys.argv[1:]] \
        or [(gp.ROW_TILE, gp.LANE_TILE, gp.TGMM_K_TILE)]

    def ragged(x, w, sizes):
        live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
        y = lax.ragged_dot(jnp.where(live, x, 0), w, sizes)
        return jnp.where(live, y, 0)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        best = 9e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(50):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / 50)
        return best * 1e3

    rng = np.random.default_rng(0)
    for cell, (rows, d, f) in SHAPES.items():
        sizes = jnp.asarray(rng.multinomial(rows // 2, [1 / HELD] * HELD),
                            jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(1), 4)
        for k, n in ((d, f), (f, d)):
            x = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
            w = (jax.random.normal(keys[1], (HELD, k, n), jnp.bfloat16)
                 / np.sqrt(k)).astype(jnp.bfloat16)
            dy = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)

            def fwd_bwd(dot):
                def run(x, w, dy):
                    y, pull = jax.vjp(lambda x, w: dot(x, w, sizes), x, w)
                    return y, pull(dy)
                return jax.jit(run)

            want = fwd_bwd(ragged)(x, w, dy)
            base = {"fwd_ms": timed(jax.jit(lambda x, w: ragged(x, w, sizes)),
                                    x, w),
                    "fwd_bwd_ms": timed(fwd_bwd(ragged), x, w, dy)}
            print(json.dumps({"cell": cell, "rows": rows, "k": k, "n": n,
                              "sizes": np.asarray(sizes).tolist(),
                              "kernel": "ragged_dot", **base}), flush=True)
            for tm, tn, tk in variants:
                gp.ROW_TILE, gp.LANE_TILE, gp.TGMM_K_TILE = tm, tn, tk
                jax.clear_caches()
                got = fwd_bwd(gp.grouped_dot)(x, w, dy)
                err = {}
                for name, a, b in (("y", got[0], want[0]),
                                   ("dx", got[1][0], want[1][0]),
                                   ("dw", got[1][1], want[1][1])):
                    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
                    err[name] = float(np.max(np.abs(a - b))
                                      / np.max(np.abs(b)))
                print(json.dumps({
                    "cell": cell, "rows": rows, "k": k, "n": n,
                    "kernel": "grouped_pallas", "tiles": [tm, tn, tk],
                    "fwd_ms": timed(jax.jit(
                        lambda x, w: gp.grouped_dot(x, w, sizes)), x, w),
                    "fwd_bwd_ms": timed(fwd_bwd(gp.grouped_dot), x, w, dy),
                    "rel_err": err}), flush=True)


if __name__ == "__main__":
    main()
