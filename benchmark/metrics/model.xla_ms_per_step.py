"""Device time per step of everything that is neither a ring nor a codec
kernel, in ms: forward, backward, the flat gradient, the optimizer where it
is not in the ring, the unflatten.  Attention is part of it and has its own
metric beside."""


def read(run):
    if not run.trace:
        return None
    return run.trace.ms_per_step(lambda c: c not in ("ring", "codec"))
