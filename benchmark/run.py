#!/usr/bin/env python3
"""One run of one benchmark cell: one process, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse] [--keep-trace]

The cell is found by name in BENCHMARK.json, and everything that belongs to
it in files of its own (benchmark/loader.py).  The run builds the trainer as
examples/train_mlp.py does, makes weights and batch on the device from
--seed, warms up until the step no longer compiles (all of that is
`setup_s`), measures for --seconds, then — outside the window — runs the
first step again against the family's plain float32 reference
(benchmark/correct.py).  With --trace 0 the last line carries the cell's
end-to-end metrics, with --trace 1 its per-layer metrics, read from a
profiler trace of the window's last seconds and from the program's
counters by the readers under benchmark/metrics/.

Without a TPU, or with fewer chips than the cell asks for, the run ends
non-zero and prints no result.  --rehearse runs the tiny cells of
benchmark/rehearse.json on virtual CPU devices and prints every time as
null: a number from the CPU is never a device metric.
"""

import time

T_PROCESS = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import math                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402
import types                        # noqa: E402
import warnings                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import loader        # noqa: E402  (imports no jax)

TRACE_SECONDS = 3.0                 # of steady steps, at the window's end
WARM_STEPS = 3                      # the first two compile (see PERF.md)
OUT_DIR = os.path.join(ROOT, ".benchmark_out")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TIMED_SOURCES = ("host_clock", "device_trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's own files under "
                         ".benchmark_out/<cell>/trace")
    return ap.parse_args(argv)


def start_jax(chips: int, rehearse: bool):
    """Import jax, place the compile cache, register the counters, and
    return (jax, the devices the cell uses, counters)."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    import jax
    # where JAX_COMPILATION_CACHE_DIR is set jax reads it itself; otherwise
    # a fixed path in the checkout, because the path is part of the key
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # every program of a run is cached, not only the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: jax found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), jax "
                         f"reports {len(devices)}; nothing was run")
    counters = types.SimpleNamespace(compiles=[], cache_hits=0,
                                     cache_misses=0)

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            counters.compiles.append((time.perf_counter(), secs))

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counters.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counters.cache_misses += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return jax, devices, counters


def collective_config(preset: dict):
    """A job's `collective` as the program's CollectiveConfig."""
    from fpga_ai_nic_tpu.utils.config import BFPConfig, CollectiveConfig
    kw = dict(preset)
    codec = kw.pop("compression", None)
    if codec == "bfp":
        kw["compression"] = BFPConfig()
    elif codec is not None:
        raise loader.SpecError(f"job: unknown compression {codec!r}")
    return CollectiveConfig(**kw)


def build(jax, cell: dict, seed: int, devices):
    """(trainer, make_params(), make_batch()) through the calls
    examples/train_mlp.py makes.  Weights are made on the device in one
    jitted call, in the type they are trained in; the batch is made already
    sharded, each chip its own part."""
    from jax.sharding import NamedSharding

    from fpga_ai_nic_tpu.parallel import DPTrainer, make_mesh
    from fpga_ai_nic_tpu.utils.config import (MeshConfig, OptimizerConfig,
                                              TrainConfig)
    config, job, family = cell["config"], cell["job"], cell["family"]
    init, loss_fn = family.program(config, job)
    cfg = TrainConfig(
        global_batch=family.global_batch(config, job),
        mesh=MeshConfig(dp=job["dp"]), seed=seed,
        collective=collective_config(job["collective"]),
        optimizer=OptimizerConfig(**config["optimizer"]))
    tr = DPTrainer(loss_fn, make_mesh(cfg.mesh, devices=devices[:job["dp"]]),
                   cfg)
    key = jax.random.PRNGKey(seed)
    init_jit = jax.jit(init)
    batch_jit = jax.jit(
        lambda k: family.make_batch(k, config, job),
        out_shardings=NamedSharding(tr.mesh, tr.batch_spec))
    return (tr, lambda: init_jit(key),
            lambda: batch_jit(jax.random.fold_in(key, 1)))


def run_chunk(jax, tr, state, batch, n: int, dispatch_ms):
    """Dispatch n steps without waiting, then wait for the last: one sync a
    chunk keeps the queue bounded.  With `dispatch_ms` (traced runs) every
    dispatch and the sync are spans in the profiler's trace."""
    if dispatch_ms is None:
        for _ in range(n):
            state, loss = tr.step(state, batch)
        jax.block_until_ready((loss, state.w_own))
        return state, float(loss)
    with jax.profiler.TraceAnnotation("bench.chunk"):
        for _ in range(n):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, loss = tr.step(state, batch)
            dispatch_ms.append((time.perf_counter() - t0) * 1e3)
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready((loss, state.w_own))
            loss = float(loss)
    return state, loss


def measure(jax, run, state, seconds: float, trace_dir):
    """The window.  Returns the state; fills run.chunks [(steps, seconds)],
    run.losses, run.attempted, run.failed, run.window_s, run.dispatch_ms."""
    tr, batch, n = run.trainer, run.batch, run.job["steps_per_sync"]
    run.chunks, run.losses = [], []
    run.attempted = run.failed = 0
    run.dispatch_ms = [] if trace_dir else None
    trace_s = min(TRACE_SECONDS, seconds / 2)
    tracing, dispatch_ms = False, None
    t_begin = time.perf_counter()
    while True:
        if trace_dir and not tracing \
                and time.perf_counter() - t_begin >= seconds - trace_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # spans, not every Python call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, dispatch_ms = True, run.dispatch_ms
        t0 = time.perf_counter()
        run.attempted += n
        try:
            state, loss = run_chunk(jax, tr, state, batch, n, dispatch_ms)
        except Exception as e:  # noqa: BLE001 — counted, reported, and the
            # window ends: the donated state cannot be stepped again
            log(f"a step raised: {type(e).__name__}: {e}")
            run.failed += n
            break
        t1 = time.perf_counter()
        run.chunks.append((n, t1 - t0))
        run.losses.append(loss)
        if not math.isfinite(loss):
            run.failed += n
        if t1 - t_begin >= seconds:
            break
    run.window = (t_begin, time.perf_counter())
    run.window_s = run.window[1] - run.window[0]
    if tracing:
        jax.profiler.stop_trace()
    return state


def peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip: live buffers plus what loaded
    programs reserve for their temporaries, which the TPU's allocator
    counts apart (`peak_bytes_reserved`; 5.38 GB beside 0.73 GB of buffers
    for the MLP step at 131,072 samples, equal to the compiler's
    temp_size_in_bytes, PR 23).  0 where the backend does not say (CPU)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


def quartiles(xs):
    if len(xs) < 2:
        return xs[0], xs[0], xs[0]
    q = statistics.quantiles(xs, n=4, method="inclusive")
    return q[0], q[1], q[2]


def main(argv=None) -> int:
    args = parse(argv)
    spec = loader.load_spec(args.rehearse)
    cell = loader.load_cell(spec, args.workload)
    seconds = float(spec["run_seconds"] if args.seconds is None
                    else args.seconds)
    config, job, family = cell["config"], cell["job"], cell["family"]
    chips = job["chips"]
    jax, devices, counters = start_jax(chips, args.rehearse)
    on_tpu = devices[0].platform == "tpu"
    kind = devices[0].device_kind
    peaks = None if args.rehearse else loader.peaks(kind)
    log(f"cell {cell['name']}: config {cell['workload']['config']}, job "
        f"{cell['workload']['traffic']}, seed {args.seed}, {seconds:g} s, "
        f"trace {args.trace}; {len(devices)} x {kind!r}; compile cache at "
        f"{jax.config.jax_compilation_cache_dir} (max size "
        f"{jax.config.jax_compilation_cache_max_size})")

    # what the per-layer readers are handed (benchmark/README.md)
    run = types.SimpleNamespace(config=config, job=job, family=family,
                                peaks=peaks, counters=counters, trace=None)
    phases, t_phase = [], time.perf_counter()

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases.append((name, now - t_phase))
        t_phase = now

    # setup_s starts here: the interpreter, jax's import and the TPU
    # runtime coming up are the machine's (10 to 19 s from run to run on the
    # v5e host, PR 23), and would bury what the program does in set-up
    t_setup = t_phase
    log(f"runtime start-up {t_setup - T_PROCESS:.2f} s (not in setup_s)")
    with warnings.catch_warnings():
        if on_tpu:
            # a reroute off the configured kernels is an error, not a note
            warnings.filterwarnings("error", message=".*fused_kernel.*")
        tr, make_params, make_batch = build(jax, cell, args.seed, devices)
        run.trainer = tr
        run.batch = jax.block_until_ready(make_batch())
        params = jax.block_until_ready(make_params())
        phase("weights+batch")
        state = tr.init_state(params)
        del params
        phase("init_state")
        warm = []
        for i in range(WARM_STEPS):
            state, loss = tr.step(state, run.batch)
            warm.append(float(loss))
            phase(f"step{i + 1}")
        jax.block_until_ready(state)
        setup_s = time.perf_counter() - t_setup
        log("set-up %.2f s: " % setup_s
            + ", ".join(f"{n} {s:.2f}" for n, s in phases)
            + f"; {len(counters.compiles)} compiles, cache {counters.cache_hits} "
              f"hits {counters.cache_misses} misses; warm-up losses "
            + " ".join(f"{v:.4f}" for v in warm))

        trace_dir = None
        if args.trace:
            trace_dir = os.path.join(OUT_DIR, cell["name"], "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir)
        state = measure(jax, run, state, seconds, trace_dir)
    run.step_traces = tr.step_traces
    run.compiles_in_window = sum(
        run.window[0] <= t <= run.window[1] for t, _ in counters.compiles)
    run.memory_peak_bytes = peak_bytes(devices[:chips])
    del state                   # room for the reference

    steps = sum(n for n, _ in run.chunks)
    items = family.items_per_step(config, job)
    rates = [n * items / s / chips for n, s in run.chunks]
    if rates:
        q1, q2, q3 = quartiles(rates)
        log(f"window {run.window_s:.3f} s, {steps} steps in "
            f"{len(run.chunks)} chunks; {family.ITEM}/s/chip per chunk: "
            + " ".join(f"{r:.0f}" for r in rates)
            + f"; quartiles {q1:.0f} {q2:.0f} {q3:.0f}; over the whole "
            f"window {steps * items / run.window_s / chips:.0f}")
    log(f"loss {warm[-1]:.4f} at the window's start, "
        f"{run.losses[-1] if run.losses else float('nan'):.4f} at its end; "
        f"{run.compiles_in_window} compiles inside the window; peak "
        f"{run.memory_peak_bytes / 2**30:.2f} GiB")

    if trace_dir:
        from benchmark import trace_reduce
        reduced = trace_reduce.reduce_xplane(
            trace_reduce.find_xplane(trace_dir))
        with open(os.path.join(OUT_DIR, cell["name"], "trace_reduced.json"),
                  "w") as f:
            json.dump(reduced, f)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = trace_reduce.Trace(reduced)

    from benchmark import correct
    run.check = correct.first_step(tr, family, config, job, make_params(),
                                   run.batch, on_tpu, log)
    fell = bool(run.losses) and run.losses[-1] < warm[-1]
    ok = (run.check["ok"] and fell and run.failed == 0
          and run.compiles_in_window == 0)
    if not ok:
        log(f"NOT correct: first step ok {run.check['ok']}, loss fell "
            f"{fell}, failed steps {run.failed}, compiles in the window "
            f"{run.compiles_in_window}")

    # the median of the chunks' rates, not work over wall time: a one-chip
    # machine shares its host's cores, and one host stall of a second (2 of
    # 22 runs, PR 23) would otherwise take a tenth off a device-bound rate
    rate = statistics.median(rates) if rates else 0.0
    values = {family.THROUGHPUT: rate, "setup_s": setup_s}
    if peaks:
        values["mfu_pct"] = (100.0 * family.flops_per_item(config, job)
                             * rate / peaks["bf16_flops"])
    metrics = {}
    if args.trace:
        for name, entry in cell["metrics"]["per_layer"].items():
            value = loader.load_module("metrics", name).read(run)
            if value is not None:
                metrics[name] = (entry, value)
    else:
        for name, entry in cell["metrics"]["end_to_end"].items():
            if name not in values and not args.rehearse:
                raise SystemExit(f"benchmark: the {config['family']} family "
                                 f"does not produce {name}")
            metrics[name] = (entry, values.get(name))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": ok, "attempted": run.attempted,
              "failed": run.failed, "metrics": {}, "device": device}
    for name, (entry, value) in metrics.items():
        if args.rehearse and entry["source"] in TIMED_SOURCES:
            value = None            # a time from the CPU is no device metric
        result["metrics"][name] = {"value": value, "unit": entry["unit"]}
    if args.trace and run.trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        result["breakdown"] = {"device_ops": run.trace.device_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
