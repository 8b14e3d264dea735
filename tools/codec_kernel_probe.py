#!/usr/bin/env python
"""On-hardware A/B of BFP codec kernel variants (round-5 verdict item 2).

Measures slope-based (K/2K chained, fixed dispatch cost cancels — see
bench_common.slope_timeit) encode and decode rates for every combination
of broadcast strategy ("repeat" = jnp.repeat on sublanes vs "reshape" =
3D-register broadcast) and grid tile count, at 64 MiB.  The winner's
settings become bfp_pallas defaults; the whole table is banked as an
artifact so the choice is evidenced, not asserted.

Targets: >= 25 GB/s per direction is the minimum
ticket for the wire path to win a 12.5 GB/s link; >= 90 GB/s covers
v5p-class links; the HBM roofline at ~820 GB/s and 5.06 traffic bytes
per payload f32 byte allows ~650 GB/s.

Usage: python tools/codec_kernel_probe.py [mb] [K]   (TPU required)
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def main():
    from bench_common import (enable_compile_cache, log, require_tpu,
                              save_artifact, slope_timeit)
    import jax
    import jax.numpy as jnp
    from jax import lax
    platform = require_tpu("codec_kernel_probe")[0].platform
    enable_compile_cache()
    from fpga_ai_nic_tpu.ops import bfp_pallas as bp

    mb = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    n_elems = mb * (1 << 20) // 4
    gb = n_elems * 4 / 1e9
    x = jax.random.normal(jax.random.PRNGKey(0), (n_elems,), jnp.float32)

    _scalar = jax.jit(lambda t: sum(
        jnp.sum(jnp.asarray(l).astype(jnp.float32))
        for l in jax.tree_util.tree_leaves(t)))

    def sync(t):
        return float(_scalar(t))

    out = {"probe": "codec_kernel_variants", "platform": platform,
           "mb": mb, "k": K, "rows": []}
    mant0, se0 = jax.jit(lambda v: bp.bfp_encode_inline(v))(x)

    for broadcast in ("repeat", "reshape"):
        for tiles in (32, 64, 128, 256):
            def make_enc(k):
                @jax.jit
                def chain(v):
                    def body(i, carry):
                        v, acc = carry
                        v = v.at[0].add(acc.astype(jnp.float32) * 1e-40)
                        m, s = bp.bfp_encode_inline(
                            v, tiles_per_step=tiles, broadcast=broadcast)
                        return v, s[0].astype(jnp.int32)
                    return lax.fori_loop(0, k, body, (v, jnp.int32(0)))[1]
                return chain

            def make_dec(k):
                @jax.jit
                def chain(mant, se):
                    def body(i, acc):
                        o = bp.bfp_decode_inline(
                            mant, jnp.roll(se, i),
                            tiles_per_step=tiles, broadcast=broadcast)
                        return acc + o[0]
                    return lax.fori_loop(0, k, body, jnp.float32(0))
                return chain

            row = {"broadcast": broadcast, "tiles_per_step": tiles}
            try:
                t_e, de = slope_timeit(make_enc, (x,), K, sync)
                t_d, dd = slope_timeit(make_dec, (mant0, se0), K, sync)
                row["encode_gbps"] = round(gb / t_e, 2) if t_e > 0 else None
                row["decode_gbps"] = round(gb / t_d, 2) if t_d > 0 else None
                row["diag"] = {"enc": de, "dec": dd}
            except Exception as e:  # noqa: BLE001 — probe rows are
                row["error"] = repr(e)[:200]         # independent
            out["rows"].append(row)
            log(f"{broadcast}/tiles={tiles}: enc={row.get('encode_gbps')} "
                f"dec={row.get('decode_gbps')} GB/s")

    good = [r for r in out["rows"] if r.get("encode_gbps")]
    if good:
        best = max(good, key=lambda r: min(r["encode_gbps"],
                                           r.get("decode_gbps") or 0))
        out["best"] = {k: best[k] for k in ("broadcast", "tiles_per_step",
                                            "encode_gbps", "decode_gbps")}
    save_artifact("codec_kernel_probe", out)
    print(json.dumps(out.get("best", out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
