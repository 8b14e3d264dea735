"""Device time of the codec's own kernels per step, in ms: at dp=1 the
program routes around the wire and hands the replica the BFP roundtrip of
the updated master, one encode and one decode custom call."""


def read(run):
    if not run.trace or run.trainer.n != 1:
        return None
    return run.trace.class_ms_per_step("codec")
