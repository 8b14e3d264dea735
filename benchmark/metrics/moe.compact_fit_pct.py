"""Share of the expert layers whose rows on the experts held lay within the
compact program's capacity C (`ops.moe.compact_capacity`: `SLACK` = twice a
uniform router's rows), in %: 100 where every layer ran the compact program,
less where a layer's `lax.cond` took the full program over all assignment
rows.  The mean of `fit` of the program's `routing_stats` on the seed's
weights and the run's batch (the family's `routing`); nothing where the
program counts no `fit` (a program from before the compact dispatch)."""


def read(run):
    fit = run.family.routing(run).get("fit")
    return None if fit is None else 100.0 * float(fit.mean())
