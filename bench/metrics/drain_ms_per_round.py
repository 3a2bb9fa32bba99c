"""Host milliseconds from closing a round to its published mean (``seal``,
``tick``, ``published``: the drain and the final mean) per round, from the
benchmark's ``bench.drain`` spans on the profiler's clock."""


def read(view):
    rounds = view.run.get("rounds")
    if view.run.get("kind") != "agg" or not rounds:
        return None
    ns = view.trace.span_ns("bench.drain")
    return ns / 1e6 / rounds if ns > 0 else None
