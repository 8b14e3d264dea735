"""Host clock around `tr.step`'s return, no sync: the median over the traced
part of the window, in ms.  It bounds throughput only where the device waits
for the host (device.idle_pct)."""

import statistics


def read(run):
    if not run.dispatch_ms:
        return None
    return statistics.median(run.dispatch_ms)
