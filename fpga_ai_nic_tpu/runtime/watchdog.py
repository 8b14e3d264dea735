"""Failure detection and recovery — the subsystem the reference lacks.

The reference documents a nondeterministic infinite hang (OPAE reads/writes
to on-board memory that never complete, hw/README:3-5) and ships no recovery:
its `kill_syn_e0` CSR is declared but never used (hw/all_reduce.sv:83) and
the only remedy is a full shell reset (`iko areset`/`reset`,
sw/mlp_mpi_example_f32.cpp:54-57).  SURVEY.md §5 calls this out as a gap to
fill, not replicate.  Here:

- ``Watchdog.run`` bounds any device-touching call with a wall-clock
  timeout; a wedged dispatch raises ``DeviceHangError`` instead of
  spinning forever the way the reference's ``wait()`` poll loop does
  (sw/mlp_mpi_example_f32.cpp:157-180).
- ``Heartbeat`` is the training-loop liveness probe: steps beat it, a
  monitor (or the loop itself) checks staleness.
- ``run_with_recovery`` retries a step from the last known-good state with
  exponential backoff — elastic recovery for transient failures
  (preempted chip, flaky host link), composing with utils.checkpoint for
  cross-process restarts.

A hung XLA dispatch cannot be cancelled from Python (the thread leaks until
the runtime returns) — same physics as the FPGA: detection and restart is
the recovery model, matching how production TPU jobs handle preemption.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax


class DeviceHangError(RuntimeError):
    """A device-touching call exceeded its watchdog timeout."""


class Watchdog:
    """Run device-touching callables under a wall-clock timeout.

    One DAEMON thread per call: a wedged call must not keep the interpreter
    alive at exit (concurrent.futures workers are non-daemon and its atexit
    hook joins them — a hung dispatch would then hang process shutdown too,
    turning a detected failure back into the reference's undetected one).
    """

    def __init__(self, timeout_s: float = 300.0):
        self.timeout_s = timeout_s

    def run(self, fn: Callable, *args, timeout_s: Optional[float] = None,
            **kwargs) -> Any:
        result: dict = {}
        done = threading.Event()

        def target():
            try:
                result["value"] = fn(*args, **kwargs)
            except BaseException as e:      # noqa: BLE001 — re-raised below
                result["error"] = e
            finally:
                done.set()

        limit = timeout_s if timeout_s is not None else self.timeout_s
        threading.Thread(target=target, daemon=True,
                         name="watchdog").start()
        if not done.wait(limit):
            raise DeviceHangError(
                f"{getattr(fn, '__name__', fn)!r} exceeded "
                f"{limit:.1f}s — device "
                "presumed hung (reference analogue: hw/README:3 hang with "
                "no kill path)")
        if "error" in result:
            raise result["error"]
        return result["value"]


@dataclass
class Heartbeat:
    """Liveness probe for a training loop: the loop calls ``beat()`` every
    step; anyone may call ``stalled()``/``assert_alive()``."""

    stall_after_s: float = 600.0

    def __post_init__(self):
        self._last = time.monotonic()
        self._beats = 0
        self._lock = threading.Lock()

    def beat(self) -> None:
        with self._lock:
            self._last = time.monotonic()
            self._beats += 1

    @property
    def beats(self) -> int:
        return self._beats

    def age_s(self) -> float:
        with self._lock:
            return time.monotonic() - self._last

    def stalled(self) -> bool:
        return self.age_s() > self.stall_after_s

    def assert_alive(self) -> None:
        age = self.age_s()
        if age > self.stall_after_s:
            raise DeviceHangError(
                f"no heartbeat for {age:.1f}s (> {self.stall_after_s:.1f}s)")


def run_with_recovery(step_fn: Callable[[Any, Any], Tuple[Any, Any]],
                      state: Any, batch: Any, *,
                      max_retries: int = 2,
                      backoff_s: float = 1.0,
                      watchdog: Optional[Watchdog] = None,
                      restore_fn: Optional[Callable[[], Any]] = None,
                      on_failure: Optional[Callable[[Exception], None]] = None,
                      ) -> Tuple[Any, Any]:
    """Run one training step with retries from known-good state.

    On failure (including DeviceHangError from the watchdog), restores
    state via restore_fn (e.g. a checkpoint load; defaults to reusing the
    pre-step state, valid for non-donating steps because they are
    functional) and retries with exponential backoff.  Raises the last
    error after max_retries.

    Donation caveat: the framework's trainers jit their step with
    ``donate_argnums=(0,)``, so a dispatched-then-failed attempt may have
    consumed the input state's buffers — retrying with the same pytree
    would crash on deleted arrays.  Pass restore_fn (checkpoint restore)
    for those; the retry loop checks and raises a clear error otherwise.
    """

    def _deleted(tree) -> bool:
        return any(getattr(l, "is_deleted", lambda: False)()
                   for l in jax.tree_util.tree_leaves(tree))

    err: Optional[Exception] = None
    for attempt in range(max_retries + 1):
        src = state if restore_fn is None or attempt == 0 else restore_fn()
        if attempt > 0 and restore_fn is None and _deleted(src):
            raise RuntimeError(
                "cannot retry: the failed step donated the state buffers "
                "(trainer steps use donate_argnums); pass restore_fn="
                "<checkpoint restore> to run_with_recovery") from err
        try:
            if watchdog is not None:
                return watchdog.run(step_fn, src, batch)
            return step_fn(src, batch)
        except Exception as e:      # noqa: BLE001 — retry boundary
            err = e
            if on_failure is not None:
                on_failure(e)
            if attempt < max_retries:
                time.sleep(backoff_s * (2 ** attempt))
    raise err
