"""Share of all routed assignments (tokens x experts per token) that landed
on an expert this chip holds, in %, mean over the expert layers: 12.5 under
a uniform router with 8 of 64 held.  From the program's `routing_stats` on the
seed's weights and the run's batch (the family's `routing`)."""


def read(run):
    return 100.0 * float(run.family.routing(run)["held_share"].mean())
