"""The convolution mixers' in-projections' share of the chip's bf16 peak,
in %: the operations W_in's products need a step, forward and backward
(`conv_flops` of the family without W_out: 3 x 2 x 2048 x 6144 a token and
convolution layer), over the peak, over the time of class `conv`.  The class
holds those products whole and, beside them, the bandwidth-bound gates and
filter and those of W_out's products XLA fused a gate into (a third as many
operations as W_in's, not counted: W_out's product alone is in no class), so
the share says how much of the class's time W_in's products are, reads low
by up to a quarter, and cannot read above 100."""


def read(run):
    if not run.trace:
        return None
    ms = run.trace.class_ms_per_step("conv")
    if not ms:
        return None
    need_s = run.family.conv_flops(run.config, run.job, with_out=False) \
        / run.peaks["bf16_flops"]
    return 100.0 * need_s / (ms * 1e-3)
