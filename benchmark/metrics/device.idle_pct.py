"""Share of the traced steady window in which no operation ran on the
device, in %, mean of the devices.  A ring kernel waiting for its neighbour
is busy here and shows in ring.kernel_ms_per_step."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s())
