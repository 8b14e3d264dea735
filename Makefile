# Top-level targets mirroring CI (.github/workflows/ci.yml).
.PHONY: ci test codec chip-smoke collective chaos-bench codec-bench fused-opt-bench reshard-bench tune-bench serve-bench fleet-bench integrity-bench slo-bench adapt-bench ckpt-bench obs-gate lint lint-fixtures modelcheck

codec:
	$(MAKE) -C fpga_ai_nic_tpu/csrc

test:
	python -m pytest tests/ -q

# fast inner loop: skip the marked long-running tests (full suite stays
# the CI gate)
test-fast:
	python -m pytest tests/ -q -m "not slow"

# telemetry regression gate: diff the banked benchmark artifacts against
# a run summary (self-diff here — trivially green on an unchanged tree;
# bench drivers / CI runs pass --summary to gate fresh numbers).  Exits
# nonzero on any per-metric regression beyond threshold.
obs-gate:
	python tools/obs_gate.py

# graftlint static analysis (docs/LINT.md): AST rules R1-R5 over the
# package/tools/bench tree, ruff+mypy on the strict typed core (when
# installed), and the jaxpr invariant sweep J1-J6 (codec x trainer x obs
# grid traced abstractly on the 8-device virtual CPU mesh — no TPU).
# Runs AHEAD of obs-gate in `make ci`: structural regressions fail before
# any benchmark artifact is consulted.
lint:
	python tools/graftlint.py

# graftmc protocol model check (docs/MODELCHECK.md): exhaustive
# explicit-state exploration of all six collective op streams (flat,
# streaming, streaming-AG, hier, reshard, handoff — integrity variants
# included) for n<=6, S<=6, D<=4 — deadlock freedom, slot overwrite,
# decode ordering, credit safety, termination, DMA discipline, and the
# M2 static checksum-weight pass — plus the n=8 randomized fuzz sweep
# and the H1 happens-before/lockset pass.  Plain-Python state
# exploration, no jax APIs, <60 s, CPU-platform env pinned before
# import (never reaches for a chip); violations leave pretty-printed +
# Perfetto counterexamples under artifacts/.  Every run banks its
# envelope (per-route cells/states, POR reduction, wall time) as
# artifacts/mc_envelope_*.json; the newest is snapshotted as the
# round's committed record, which obs-gate's mc.* keys hold future
# runs to TWO-SIDED (a silent envelope shrink fails CI) with a wall-
# time budget so state-explosion regressions fail loudly.  Runs
# BETWEEN lint and obs-gate in `make ci`.
modelcheck:
	@start=$$(date +%s); \
	  GRAFTMC_NO_BANK= python tools/graftlint.py --mc || exit $$?; \
	  latest=$$(ls -t artifacts/mc_envelope_*.json 2>/dev/null | head -1); \
	  if [ -z "$$latest" ] || [ $$(stat -c %Y "$$latest") -lt $$start ]; then \
	    echo "modelcheck: no FRESH envelope artifact to bank (found: '$$latest')" >&2; exit 1; \
	  fi; \
	  cp $$latest MC_ENVELOPE_$(ROUND).json; \
	  echo "saved $$latest -> MC_ENVELOPE_$(ROUND).json"

# fast fixture-corpus loop (<30 s, CPU-only): every rule fires on its bad
# fixture / stays silent on the good one, suppression hygiene, and the
# copied-into-the-package exit-code demonstration — without the jaxpr grid
lint-fixtures:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py -q \
	    -k "not Jaxpr" -p no:cacheprovider

ci: codec test lint modelcheck obs-gate

# needs the chip: run it through the chip tool, one process per chip.
# The measured surface is the benchmark (BENCHMARK.json, benchmark/run.py;
# numbers in the root PERF.md and PERF_LEDGER.jsonl)
chip-smoke:
	python chip_smoke.py

# run the collective/codec benchmark and snapshot its newest artifact as
# the round's committed record (the round-2 review's item 3: the
# first-named BASELINE metric must land in a committed file every round)
ROUND ?= r20
collective:
	python bench_collective.py
	@latest=$$(ls -t artifacts/collective_tpu_*.json artifacts/collective_2*.json 2>/dev/null | head -1); \
	  cp $$latest COLLECTIVE_$(ROUND).json; \
	  echo "saved $$latest -> COLLECTIVE_$(ROUND).json"

# the codec x {vmem, streaming} matrix: every registered compression
# codec's encode/decode/roundtrip slope rates at both payload classes,
# plus per-codec compression ratio and serial-VPU break-even
# (bench_collective.codec_matrix_child); snapshot the newest artifact as
# the round's committed record, same contract as `make collective`
codec-bench:
	python bench_collective.py --codec-matrix
	@latest=$$(ls -t artifacts/codec_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest CODEC_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> CODEC_BENCH_$(ROUND).json"

# fused decode+accumulate+optimizer vs ring-then-optimizer: per optimizer
# kind, slope-timed fused step vs the two-pass baseline + the standalone
# optimizer HBM roofline (bench_collective.fused_opt_child); snapshot the
# newest artifact as the round's committed record, same contract as
# `make codec-bench`.  obs-gate consumes the committed row
# (tools/obs_gate.py FUSED_OPT_GATE_KEYS).
fused-opt-bench:
	python bench_collective.py --fused-optimizer
	@latest=$$(ls -t artifacts/fused_opt_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest FUSED_OPT_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> FUSED_OPT_BENCH_$(ROUND).json"

# the chaos fault matrix: every fault class x injection site x wire
# format, each cell a real supervised run that must recover (or absorb)
# on the 8-device virtual CPU mesh — docs/CHAOS.md.  Per wire it also
# runs the preempt-shrink cell: live reshard (dp8->dp4, no checkpoint)
# vs checkpoint-restore MTTR, side by side.
chaos-bench:
	python tools/chaos_bench.py --fast

# autotune matrix (docs/TUNING.md): the tuned plan vs every fixed
# (codec, depth, bucket, topology) config per payload regime, scored by
# the calibrated ring_cost model and measured on the live mesh; snapshot
# the newest artifact as the round's committed record (obs-gate consumes
# it — dryrun CPU rows gate only the exact plan accounting, tune.* keys)
tune-bench:
	python bench_collective.py --autotune-matrix
	@latest=$$(ls -t artifacts/tune_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest TUNE_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> TUNE_BENCH_$(ROUND).json"

# serving bench (docs/SERVING.md): throughput-vs-latency curve over the
# paged continuous-batching engine at increasing concurrency, the
# contiguous-init_cache-vs-paged-pool HBM comparison, token-exactness
# under batching, and the zero-recompile gate; snapshot the newest
# artifact as the round's committed record (obs-gate consumes it —
# dryrun CPU rows gate only the exact byte accounting + recompiles==0,
# serve.* keys)
serve-bench:
	python tools/serve_bench.py
	@latest=$$(ls -t artifacts/serve_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest SERVE_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> SERVE_BENCH_$(ROUND).json"

# fleet bench (docs/SERVING.md "The fleet"): the disaggregated
# prefill/KV-handoff/decode pipeline at steady state + the replica-kill
# row (a decode replica preempted mid-run, surviving streams
# byte-identical with zero replay); snapshot the newest artifact as the
# round's committed record (obs-gate consumes it — dryrun CPU rows gate
# only the exact handoff accounting, fleet.* keys)
fleet-bench:
	python tools/serve_bench.py --fleet
	@latest=$$(ls -t artifacts/fleet_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest FLEET_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> FLEET_BENCH_$(ROUND).json"

# SLO observatory bench (docs/OBSERVABILITY.md "The serving SLO
# observatory"): alias of the fleet bench — the same artifact carries
# the per-scenario `slo` blocks (windowed tick-domain percentiles,
# autoscaler decision ledger) obs-gate pins exactly as fleet.slo.* keys
# on ANY surface, dryrun included
slo-bench: fleet-bench

# wire-integrity bench (docs/CHAOS.md "Exact wire integrity"): checksum
# on/off overhead per ppermute-bearing route (flat/hier rings per codec,
# reshard transfer, KV handoff, serve decode tick) + the wirebit
# trip->recovery MTTR rows; snapshot the newest artifact as the round's
# committed record (obs-gate consumes it — dryrun CPU rows gate only
# the exact byte/counter keys: wire_bytes_delta==0 means no checksum
# ever rides the wire, trips==0 means no false trips, integrity.* keys)
integrity-bench:
	python tools/integrity_bench.py
	@latest=$$(ls -t artifacts/integrity_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest INTEGRITY_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> INTEGRITY_BENCH_$(ROUND).json"

# adaptive-tuning bench (docs/TUNING.md "Online plan adaptation"): the
# drift observatory's switch events banked — the forced
# slowdown@collective regime shift detected from measured-vs-modeled
# residuals and answered by a step-boundary switch to a pre-compiled
# plan (recompiles_across_switch == 0, the J13 contract), plus the
# zero-switch steady guard; snapshot the newest artifact as the round's
# committed record (obs-gate consumes it — dryrun CPU rows gate only
# the exact switch/trace counters, adapt.* keys)
adapt-bench:
	python tools/adapt_bench.py
	@latest=$$(ls -t artifacts/adapt_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest ADAPT_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> ADAPT_BENCH_$(ROUND).json"

# durable-state bench (docs/DURABILITY.md): the checkpoint plane's
# save-stall (sync vs async with the BFP encode in the background
# thread), audit overhead, and restore-MTTR with/without peer repair —
# plus the exact storage/repair accounting (bytes, shard/mirror files,
# repair_wire_bytes == shard bytes, walk-back steps_lost, refusal);
# snapshot the newest artifact as the round's committed record
# (obs-gate consumes it — dryrun CPU rows gate only the exact
# byte/counter keys, ckpt.* keys)
ckpt-bench:
	python tools/ckpt_bench.py
	@latest=$$(ls -t artifacts/ckpt_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest CKPT_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> CKPT_BENCH_$(ROUND).json"

# reshard-vs-restore MTTR per trainer x codec (docs/RESHARD.md):
# the same mid-run preemption recovered by the live-reshard tier and by
# checkpoint-restore; snapshot the newest artifact as the round's
# committed record (obs-gate consumes it — dryrun CPU rows gate only the
# exact plan wire-byte accounting)
reshard-bench:
	python tools/chaos_bench.py --fast --reshard-bench
	@latest=$$(ls -t artifacts/reshard_bench_*.json 2>/dev/null | head -1); \
	  cp $$latest RESHARD_BENCH_$(ROUND).json; \
	  echo "saved $$latest -> RESHARD_BENCH_$(ROUND).json"
