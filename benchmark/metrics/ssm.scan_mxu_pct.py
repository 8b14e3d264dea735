"""The chunked scans' share of the chip's bf16 peak, in %: the operations
the matrix form needs a step, forward and backward (`ssm_flops` of the
family: per token and mixer 2 Q G N + 2 Q H P + 4 H P N with chunks of Q
positions, x 3), over the peak, over the time of class `ssm_scan`.  The
class holds those products and, beside them, the bandwidth-bound decay
arrays and the carry, so the share says how far the scan is from a kernel
that keeps a chunk in fast memory; it counts no recomputation although the
blocks' checkpoints run the forward twice, so it cannot read above 100.
Nothing where the family counts no `ssm_flops` or the class did not run."""


def read(run):
    if not run.trace or not hasattr(run.family, "ssm_flops"):
        return None
    ms = run.trace.class_ms_per_step("ssm_scan")
    if not ms:
        return None
    need_s = run.family.ssm_flops(run.config, run.job) \
        / run.peaks["bf16_flops"]
    return 100.0 * need_s / (ms * 1e-3)
