"""Single kernels of the program, read from a trace by the name the program
gave them.

Since PR 25 every `pl.pallas_call` of the program carries a name from one
table (`fpga_ai_nic_tpu/obs/names.py`) into its HLO instruction's
`frontend_attributes={kernel_metadata={"ainic_kernel":"<name>", ...}}`, and
the v5e's profiler names an `XLA Ops` event by that whole instruction.  The
classes (`op_classes/05-named-kernels.json`) read the part before the dot;
the functions here read the whole name, for the metrics that tell one of a
layer's kernels from another.  The arithmetic is `trace_reduce.Trace`'s:
union of the intervals in the steady window over the steps in it, median of
the devices.  A trace of a program without names holds no such event, and
every function returns None.
"""

import functools
import re
import statistics
from typing import Optional

from benchmark import trace_reduce

NAME_RE = re.compile(r'kernel_metadata=\{[^}]*"ainic_kernel":"([\w.]+)"')


@functools.lru_cache(maxsize=None)
def kernel_name(event_name: str) -> Optional[str]:
    """The program's name for the kernel an event ran, or None."""
    m = NAME_RE.search(event_name)
    return m.group(1) if m else None


def _per_device(trace, prefix: str):
    """For each device that ran one: (intervals of the events whose kernel
    name starts with `prefix`, steps in the window)."""
    for d in trace.devices:
        ivs = [(s, e) for name, _, s, e in d["ops"]
               if (kernel_name(name) or "").startswith(prefix)]
        if ivs:
            yield ivs, d["steps"]


def ms_per_step(trace, prefix: str) -> Optional[float]:
    """Busy time per step, in ms, of the kernels whose name starts with
    `prefix`; None where none ran."""
    vals = [trace_reduce.total_len(trace_reduce.merge_intervals(ivs)) / 1e6
            / steps for ivs, steps in _per_device(trace, prefix)]
    return statistics.median(vals) if vals else None


def launches_per_step(trace, prefix: str) -> Optional[float]:
    """Events per step on one device of the kernels whose name starts with
    `prefix`; None where none ran."""
    vals = [len(ivs) / steps for ivs, steps in _per_device(trace, prefix)]
    return statistics.median(vals) if vals else None
