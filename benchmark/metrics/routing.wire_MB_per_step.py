"""Bytes each device puts on the wire for one step's all-reduce, in MB
(1e6): `DPTrainer.obs_static_metrics()["wire_bytes_per_allreduce"]`, a count
from shapes and the codec's rate, not a measurement.  Nothing where there
is no wire (dp=1)."""


def read(run):
    if run.trainer.n == 1:
        return None
    return run.trainer.obs_static_metrics()["wire_bytes_per_allreduce"] / 1e6
