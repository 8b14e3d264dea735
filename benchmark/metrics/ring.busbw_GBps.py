"""Bus bandwidth of the ring all-reduce, in GB/s: 2(n-1)/n times the
gradient's float32 bytes, over ring.kernel_ms_per_step.  The uncompressed
bytes are counted, so the codec's rate shows as bandwidth; the chip's
interconnect peak is in peaks.json."""


def read(run):
    if not run.trace or run.trainer.n == 1:
        return None
    ms = run.trace.class_ms_per_step("ring")
    if not ms:
        return None
    n = run.trainer.n
    grad_bytes = 4 * run.trainer.obs_static_metrics()["padded_len"]
    return 2 * (n - 1) / n * grad_bytes / (ms * 1e-3) / 1e9
