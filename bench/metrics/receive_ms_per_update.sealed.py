"""``receive_ms_per_update``'s reading in the sealed aggregation cell, under
a name of its own because that cell reports ``updates_per_s.sealed``."""


def read(view):
    updates = view.run.get("updates")
    if view.run.get("kind") != "agg" or not updates:
        return None
    ns = view.trace.span_ns("bench.receive")
    return ns / 1e6 / updates if ns > 0 else None
