"""Shared machinery for the benchmark drivers (bench_collective.py, tools/).

A chip belongs to one process at a time, so a driver that spawns measured
children imports no jax itself: the parent here only supervises child
attempts under an *activity watchdog* — children print `[bench] phase=...`
progress lines; the parent kills a child when the total budget expires or
no line arrives within the silence limit, so a hang is always localized to
a phase (the diagnosability the reference's infinite `wait()` spin lacked,
sw/mlp_mpi_example_f32.cpp:157-180, hw/README:3).
"""

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def is_tpu_platform(platform: str) -> bool:
    """One predicate for 'this backend is the TPU'."""
    return platform == "tpu"


def require_tpu(who: str):
    """`jax.devices()` where jax found a TPU; elsewhere the process ends
    with code 1 and a line on stderr, before anything is run or printed —
    a number from another platform is not a device metric.  For the one
    process that is meant to hold the chip."""
    import jax
    dev = jax.devices()
    if not is_tpu_platform(dev[0].platform):
        raise SystemExit(f"{who}: jax found no TPU (platform "
                         f"{dev[0].platform!r}); nothing was run")
    return dev


def run_attempt(name: str, cmd, *, env=None, budget_s: float,
                silence_s: float, cwd=None) -> dict:
    """Run one child attempt; returns its parsed result JSON (the last line
    starting with '{') or raises RuntimeError carrying the forensic tail.

    A result that printed before an unclean exit is kept and annotated:
    runtime teardown is where a post-result hang happens."""
    import subprocess
    import threading

    log(f"attempt={name} budget={budget_s:.0f}s silence={silence_s:.0f}s")
    t0 = time.time()
    proc = subprocess.Popen(cmd, env=env or dict(os.environ), cwd=cwd,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, bufsize=1)
    last_line_at = [time.time()]
    deadline = t0 + budget_s
    kill_reason = [None]

    def _watch():
        while proc.poll() is None:
            now = time.time()
            if now > deadline:
                kill_reason[0] = f"total budget {budget_s:.0f}s"
            elif now - last_line_at[0] > silence_s:
                kill_reason[0] = (f"silent for {now - last_line_at[0]:.0f}s "
                                  f"(limit {silence_s:.0f}s)")
            if kill_reason[0]:
                proc.kill()
                return
            time.sleep(1.0)

    threading.Thread(target=_watch, daemon=True).start()
    lines, result = [], None
    try:
        for line in proc.stdout:
            last_line_at[0] = time.time()
            lines.append(line)
            sys.stderr.write(line)
            sys.stderr.flush()
            if line.startswith("{"):
                try:
                    result = json.loads(line)
                except json.JSONDecodeError:
                    pass
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    if result is not None:
        if rc != 0:
            result["unclean_exit"] = kill_reason[0] or f"rc={rc}"
        return result
    why = kill_reason[0] or f"rc={rc}"
    raise RuntimeError(
        f"attempt {name} failed ({why}); last output: "
        + " | ".join(l.strip() for l in lines[-4:]))


# Published peaks of one chip, keyed by what jax reports as
# `jax.devices()[0].device_kind` (Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s in bf16, 819 GB/s of HBM).  A device that is not in the table
# is an error, not a default: a utilization against the wrong peak is worse
# than none.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _peak(device_kind: str, what: str) -> float:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"bench_common.CHIP_PEAKS with its source (known: "
            f"{sorted(CHIP_PEAKS)})")
    return CHIP_PEAKS[device_kind][what]


def bf16_peak(device_kind: str):
    """(peak FLOP/s, label) of a chip — the MFU denominator."""
    peak = _peak(device_kind, "bf16_flops")
    return peak, f"{device_kind} bf16 {peak / 1e12:.0f} TFLOP/s"


def hbm_peak(device_kind: str):
    """(peak bytes/s, label) of a chip — the denominator of decode's
    HBM-roofline accounting (decode is bandwidth-bound: every generated
    token re-reads the weights and the KV cache, so bytes/token over HBM
    peak is its MFU analogue)."""
    peak = _peak(device_kind, "hbm_bytes_per_s")
    return peak, f"{device_kind} HBM {peak / 1e9:.0f} GB/s"


def chain_kernel_calls(call, k: int = 8):
    """jit(k chained invocations of a side-effecting kernel `call`) —
    divide the elapsed time of one dispatch by k.  The adds only order
    *consumption* of the results; what keeps the k identical invocations
    distinct and ordered is pallas `has_side_effects=True` (no CSE, no
    reordering across side effects).  This exists because a device dispatch
    has a fixed cost that floors any one-kernel-per-dispatch measurement.
    For a *fixed-floor-free* rate use `slope_timeit`, which differences
    two chain lengths so even the residual in-dispatch constant cancels."""
    import jax

    def chained(v):
        acc = call(v)
        for _ in range(k - 1):
            acc = acc + call(v)
        return acc
    return jax.jit(chained)


def slope_timeit(make_chain, args, k, sync, reps: int = 3):
    """Fixed-cost-free per-iteration time by slope: build chains of k and
    2k data-dependent iterations (``make_chain(k)`` must return a jitted
    callable), time each inside ONE dispatch, and difference:

        t_iter = (t_2k - t_k) / k

    Any per-dispatch constant — dispatch floor, sync fetch, loop setup —
    appears in both terms and cancels exactly.  This is the
    round-5 replacement for the naive `t_k / k` quotient whose r04 codec
    numbers were provably dispatch-floored (roundtrip measured ~2x the
    harmonic sum of its own stages).  Returns (t_iter_seconds, diag dict);
    t_iter <= 0 means noise swamped the slope — callers must treat the
    measurement as invalid, not report a negative rate."""
    def run(fn):
        out = fn(*args)
        sync(out)
        best = 9e9
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(*args)
            sync(out)
            best = min(best, time.perf_counter() - t0)
        return best

    t_k = run(make_chain(k))
    t_2k = run(make_chain(2 * k))
    t_iter = (t_2k - t_k) / k
    diag = {"k": k, "t_k_s": round(t_k, 4), "t_2k_s": round(t_2k, 4),
            "naive_t_iter_s": round(t_k / k, 6),
            "slope_t_iter_s": round(t_iter, 6)}
    return t_iter, diag


def git_sha(repo_dir=None) -> str:
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=repo_dir or os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip()
        return out or "unknown"
    except Exception:  # noqa: BLE001
        return "unknown"


def save_artifact(prefix: str, result: dict) -> str:
    """Write a timestamped raw-evidence JSON under artifacts/ (round-2
    verdict: a number without a committed artifact is asserted, not
    measured)."""
    here = os.path.dirname(os.path.abspath(__file__))
    art_dir = os.path.join(here, "artifacts")
    os.makedirs(art_dir, exist_ok=True)
    ts = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = os.path.join(art_dir, f"{prefix}_{ts}.json")
    payload = dict(result)
    payload["_provenance"] = {
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(here),
        "argv": sys.argv,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    log(f"artifact saved: {os.path.relpath(path, here)}")
    return path


def cpu_env(n_devices: int = 8) -> dict:
    """Env overrides forcing an n-device virtual CPU mesh."""
    import re
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    flags = (flags.strip() +
             f" --xla_force_host_platform_device_count={n_devices}").strip()
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)


def enable_compile_cache() -> dict:
    """JAX's persistent compile cache, placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and nothing is set
    here; otherwise the cache is `<checkout>/.jax_cache` — a fixed path,
    because the path is part of the cache key and a directory that moves
    never hits.  Returns a live {"hits", "misses"} count of this process's
    cache reads and writes."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"))
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts
