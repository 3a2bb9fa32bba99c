"""Host milliseconds the aggregation server spent answering frames
(``ingest_frame``: parse, validate, reassemble, fold) per accepted client
update, from the benchmark's ``bench.receive`` spans on the profiler's
clock."""


def read(view):
    updates = view.run.get("updates")
    if view.run.get("kind") != "agg" or not updates:
        return None
    ns = view.trace.span_ns("bench.receive")
    return ns / 1e6 / updates if ns > 0 else None
