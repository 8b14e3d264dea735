"""The convolution mixers' in-projections' share of the chip's bf16 peak,
in %: the operations W_in's products need a step, forward and backward
(`conv_flops` of the family without W_out: 3 x 2 x 2048 x 6144 a token and
convolution layer), over the peak, over the time of class `conv`.  The class
holds exactly those products and, beside them, the bandwidth-bound gating
and filter, so the share says how much of the mixer's time its products
are and cannot read above 100."""


def read(run):
    if not run.trace or not hasattr(run.family, "conv_flops"):
        return None
    ms = run.trace.class_ms_per_step("conv")
    if not ms:
        return None
    need_s = run.family.conv_flops(run.config, run.job, with_out=False) \
        / run.peaks["bf16_flops"]
    return 100.0 * need_s / (ms * 1e-3)
