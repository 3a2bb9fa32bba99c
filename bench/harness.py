"""The benchmark's machinery, driven by data.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration, whose
file is listed under ``configs``, and a traffic mix, read from
``bench/traffic/<traffic>.json``.  The mix names the generator that makes
its traffic (``bench/drive_<generator>.py``).  A per-layer metric is read by
``bench/metrics/<name>.py``.  A new cell therefore needs new files and
entries only.

A run: check the chips, set up (build, load, warm every shape up), measure
``seconds`` of traffic (traced in a run of its own with ``trace``), read
the device's peak memory, release the program's state, compare what the
window produced with the plain reference, and print one JSON line.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    pass


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_parts(spec: dict, workload: str, root: Path = ROOT
               ) -> "tuple[dict, dict, dict]":
    """(cell, configuration, traffic) of one workload, found by name."""
    cell = find(spec["workloads"], workload, "workload")
    centry = find(spec["configs"], cell["config"], "configuration")
    cfg = json.loads((root / centry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def generator_module(traffic: dict):
    return importlib.import_module(f"bench.drive_{traffic['generator']}")


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, workload: str, section: str) -> list:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in spec[section]
            if workload in m.get("workloads", [workload])]


def check_devices(chips: int):
    """The chips of the cell, or NoChip: never a fall-back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts traces and backend compiles while active."""
    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax.monitoring as mon
        self.counts = {"compiles": 0, "traces": 0}
        self.active = False
        mon.register_event_duration_secs_listener(self._on)

    def close(self):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


@contextlib.contextmanager
def profiled(enabled: bool):
    """Traces the block with the JAX profiler into a scratch directory
    under TMPDIR; yields a holder whose ``path`` is the .xplane.pb."""
    holder = type("T", (), {"path": None, "dir": None})()
    if not enabled:
        yield holder
        return
    import jax
    holder.dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        found = sorted(Path(holder.dir).rglob("*.xplane.pb"))
        holder.path = str(found[-1]) if found else None


def memory_peak(devs) -> "int | None":
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_chip: bool = True,
             spec: "dict | None" = None, cfg: "dict | None" = None,
             traffic: "dict | None" = None, log=print) -> dict:
    """One run of one cell; returns the result object (without printing).

    ``cfg``/``traffic`` override the files (the CPU tests run the same path
    at a small size); ``require_chip=False`` skips the look for a chip."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    spec = spec or load_spec(root)
    cell, cfg_file, traffic_file = cell_parts(spec, workload, root)
    cfg = cfg or cfg_file
    traffic = traffic or traffic_file
    chips = int(cell["chips"])
    devs = check_devices(chips) if require_chip else jax.devices()[:chips]
    log(f"[bench] {workload}: {devs[0].device_kind} x{len(devs)}, seed "
        f"{seed}, compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    drv = generator_module(traffic).Run(cfg, traffic, seed, chips)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[bench] set-up {setup_s:.3f} s")

    from jax.profiler import TraceAnnotation
    counter.active = True
    with profiled(trace) as prof:
        with TraceAnnotation("bench.window"):
            res = drv.window(seconds)
    counter.active = False
    counter.close()
    log(f"[bench] window {res['window_s']:.3f} s; compiles in window "
        f"{counter.counts['compiles']}, traces {counter.counts['traces']}")
    peak = memory_peak(devs)
    drv.release()
    checks = drv.check()
    correct = all(v <= lim for _, v, lim in checks)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    if trace:
        from bench import xplane
        from bench.peaks import peaks
        tr = xplane.load(prof.path, {d.id for d in devs})
        shutil.rmtree(prof.dir, ignore_errors=True)
        view = RunView(tr, res, cfg, chips, peaks(devs[0].device_kind))
        metrics = {}
        for m in cell_metrics(spec, workload, "per_layer"):
            v = metric_reader(m["name"], root)(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {}
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        for m in cell_metrics(spec, workload, "end_to_end"):
            name = m["name"]
            if name == "setup_s":
                metrics[name] = {"value": setup_s, "unit": units[name]}
            # a quantity split by cells (updates_per_s.sealed) is the
            # generator's quantity named before the first dot
            elif name.split(".")[0] in res["end_to_end"]:
                quantity = res["end_to_end"][name.split(".")[0]]
                metrics[name] = {"value": quantity, "unit": units[name]}
        out["metrics"] = metrics
        out["device"] = device
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


class RunView:
    """What a per-layer metric reader sees: the traced window's reduction,
    the generator's counts of that window, the configuration, the chips and
    their peaks."""

    def __init__(self, trace, run: dict, cfg: dict, chips: int, peaks: dict):
        self.trace, self.run, self.cfg = trace, run, cfg
        self.chips, self.peaks = chips, peaks


def main(argv=None, t_start: "float | None" = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start, log=log)
    except NoChip as e:
        log(f"bench: {e}; refusing to run without the chips")
        return 3
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
