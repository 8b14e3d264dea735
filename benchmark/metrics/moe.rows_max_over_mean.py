"""Rows routed to the fullest expert held over the mean of the experts
held, of the expert layer where that is largest (1.0: even).  From the
program's `routing_stats` on the seed's weights and the run's batch (the
family's `routing`)."""


def read(run):
    return float(run.family.routing(run)["max_over_mean"].max())
