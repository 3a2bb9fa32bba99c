"""The aggregation server's phases, read from a profiler trace.

The server times its phases with ``repro.obs.span``: ``agg.parse``,
``agg.reassemble``, ``agg.fold`` (around ``agg.fold.residuals``),
``agg.commit``, ``agg.drain`` (around ``agg.stage`` and ``agg.decode``)
and ``agg.respond``.  They land on the trace's host lines, on the clock of the
device's operations.  :func:`load` reads a trace as ``bench.xplane.load``
does and keeps those spans beside the benchmark's ``bench.*`` spans, so
``Trace.idle_gaps`` charges device idle time to the innermost server phase.
:func:`readings` turns a traced window into per-phase numbers, and
:func:`coverage` says how much of the benchmark's ``bench.receive`` and
``bench.drain`` the phases account for.

    python bench/agg_spans.py --workload fl-xdevice.stream --seed <n> --seconds <s>

runs one traced window of an aggregation cell on its chips and prints the
readings as one JSON line.
"""
from __future__ import annotations

import bisect
import os
import sys

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import xplane as X  # noqa: E402

PREFIX = "agg."

# reading -> (how, span, per): "span" sums the span's time, "self" its time
# outside the spans nested in it, "count" the spans; per update or round
READINGS = {
    "parse_ms_per_update": ("span", "agg.parse", "updates"),
    "reassemble_ms_per_update": ("span", "agg.reassemble", "updates"),
    "fold_host_ms_per_update": ("self", "agg.fold", "updates"),
    "fold_device_ms_per_update": ("span", "agg.fold.residuals", "updates"),
    "fold_dispatches_per_update": ("count", "agg.fold.residuals", "updates"),
    "commit_ms_per_update": ("span", "agg.commit", "updates"),
    "stage_ms_per_round": ("span", "agg.stage", "rounds"),
    "decode_ms_per_round": ("span", "agg.decode", "rounds"),
}


def host_spans(pd, prefix: str = PREFIX) -> list[tuple[str, float, float]]:
    """Every host event of ``jax.profiler.ProfileData`` whose name starts
    with ``prefix``, as (name, start_ns, end_ns)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return out


def load(path: str, devices: "set[int] | None" = None) -> X.Trace:
    """``bench.xplane.load``, with the server's spans among ``spans``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = X.from_profile(pd, devices)
    tr.spans = tr.spans + host_spans(pd)
    return tr


def span_count(trace: X.Trace, name: str) -> int:
    """The spans of that name that overlap the window."""
    lo, hi = trace.window
    return sum(1 for n, a, b in trace.spans if n == name and b > lo and a < hi)


def self_ns(trace: X.Trace, name: str) -> float:
    """The span's time in the window minus the part that spans nested in it
    cover: its self time.  A span is nested when it starts and ends inside
    one of the named spans (the spans come from one thread)."""
    lo, hi = trace.window
    spans = sorted(trace.spans, key=lambda s: (s[1], -s[2]))
    starts = [a for _, a, _ in spans]
    own, inner = [], []
    for i, (n, a, b) in enumerate(spans):
        if n != name:
            continue
        own.append((a, b))
        for j in range(i + 1, bisect.bisect_left(starts, b)):
            if spans[j][2] <= b:
                inner.append(spans[j][1:])
    own = X.union(X.clip(own, lo, hi))
    return X.total(X.subtract(own, X.union(X.clip(inner, lo, hi))))


def covered_share(trace: X.Trace, base: str, names) -> "float | None":
    """Share of the ``base`` spans' time in the window that the spans
    ``names`` cover; None where ``base`` has no time."""
    lo, hi = trace.window
    b = X.union(X.clip(((a, e) for n, a, e in trace.spans if n == base),
                       lo, hi))
    whole = X.total(b)
    if whole <= 0:
        return None
    parts = X.union(X.clip(((a, e) for n, a, e in trace.spans
                            if n in names), lo, hi))
    return (whole - X.total(X.subtract(b, parts))) / whole


def readings(trace: X.Trace, run: dict) -> dict:
    """The per-phase readings of a traced aggregation window (``run`` is
    the generator's record of it); a phase with no spans is left out."""
    out = {}
    for metric, (how, span, per) in READINGS.items():
        if not run.get(per):
            continue
        if how == "count":
            v = span_count(trace, span)
        elif how == "self":
            v = self_ns(trace, span) / 1e6
        else:
            v = trace.span_ns(span) / 1e6
        if v > 0:
            out[metric] = v / run[per]
    return out


def coverage(trace: X.Trace) -> dict:
    """How much of the benchmark's spans the server's phases cover, with
    each base in seconds."""
    phases = {n for n, _, _ in trace.spans if n.startswith(PREFIX)}
    out = {}
    for base, names in (("bench.receive", phases),
                        ("bench.drain", {"agg.stage", "agg.decode"})):
        share = covered_share(trace, base, names)
        if share is not None:
            out[base] = {"share": share, "base_s": trace.span_ns(base) / 1e9}
    return out


def traced_window(drv, devs, seconds: float) -> "tuple[X.Trace, dict]":
    """One traced window of a set-up generator on ``devs``."""
    import shutil

    from jax.profiler import TraceAnnotation

    from bench import harness
    with harness.profiled(True) as prof:
        with TraceAnnotation("bench.window"):
            res = drv.window(seconds)
    tr = load(prof.path, {d.id for d in devs})
    shutil.rmtree(prof.dir, ignore_errors=True)
    return tr, res


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, cfg, traffic = harness.cell_parts(spec, args.workload)
    devs = harness.check_devices(int(cell["chips"]))
    drv = harness.generator_module(traffic).Run(cfg, traffic, args.seed,
                                                len(devs))
    drv.setup()
    tr, res = traced_window(drv, devs, args.seconds)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": devs[0].device_kind, "window_s": res["window_s"],
        "updates_per_s": res["end_to_end"]["updates_per_s"],
        "idle_share": tr.idle_share(), "readings": readings(tr, res),
        "coverage": coverage(tr), "idle_gaps": tr.idle_gaps(10)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
