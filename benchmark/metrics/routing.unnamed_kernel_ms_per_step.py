"""Device time per step of Mosaic kernels that no rule knows by name (class
`pallas_unknown`), in ms.  model.xla_ms_per_step is everything that is
neither ring nor codec and so takes such a kernel's time in silence; here
it shows as a number.  0.0, not None, where a trace was read and none ran."""


def read(run):
    if not run.trace:
        return None
    return run.trace.class_ms_per_step("pallas_unknown") or 0.0
