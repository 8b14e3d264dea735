"""The codec roundtrip's share of its HBM roofline, in %: the bytes the
two kernels must move over the chip's HBM peak, over the time they took.

Per element of the padded flat master (block 16, 8-bit mantissas): encode
reads 4 B of float32 and writes 1 B of mantissa and 1/16 B of scale; decode
reads those and writes 4 B of float32."""


def roundtrip_bytes(elems: int, block: int) -> float:
    wire = 1.0 + 1.0 / block
    return elems * ((4.0 + wire) + (wire + 4.0))


def read(run):
    if not run.trace or run.trainer.n != 1:
        return None
    ms = run.trace.class_ms_per_step("codec")
    if not ms:
        return None
    coll = run.trainer.cfg.collective
    need_s = roundtrip_bytes(
        run.trainer.obs_static_metrics()["padded_len"],
        coll.compression.block_size) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (ms * 1e-3)
