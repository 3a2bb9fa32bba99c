"""``drain_ms_per_round``'s reading in the sealed aggregation cell, under a
name of its own because that cell reports ``updates_per_s.sealed``."""


def read(view):
    rounds = view.run.get("rounds")
    if view.run.get("kind") != "agg" or not rounds:
        return None
    ns = view.trace.span_ns("bench.drain")
    return ns / 1e6 / rounds if ns > 0 else None
