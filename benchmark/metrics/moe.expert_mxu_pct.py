"""The routed experts' share of the chip's bf16 peak, in %: the operations
the rows ROUTED to the experts held need, forward and backward
(`expert_flops` of the family: 3 x 2 x three matrices a row, rows summed
over the expert layers from the program's `routing_stats`), over the peak,
over the time of class `moe`.  It counts rows routed, not rows padded, so a
grouped product that computes its padded bound reads low and none can read
above 100.  The rows are those the SEED's weights route (the family's
`routing`), the time is the trained window's: the harness keeps no trained
state for a reader (PERF.md section 7)."""


def read(run):
    if not run.trace:
        return None
    ms = run.trace.class_ms_per_step("moe")
    if not ms:
        return None
    rows = float(run.family.routing(run)["rows"].sum())
    need_s = run.family.expert_flops(run.config, rows) \
        / run.peaks["bf16_flops"]
    return 100.0 * need_s / (ms * 1e-3)
