"""Run paper-table benchmarks.  Prints ``name,us_per_call,derived`` CSV rows
plus one machine-readable ``BENCH_JSON {...}`` summary line (parsed by
scripts/bench_ci.py for the CI regression gate), and exits nonzero when any
module fails.

    PYTHONPATH=src python benchmarks/run.py [--modules bench_kernels,bench_dme]
"""
import argparse
import json
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "bench_norms", "bench_variance", "bench_convergence", "bench_sublinear",
    "bench_multimachine", "bench_localsgd", "bench_nn",
    "bench_power_iteration", "bench_lower_bound", "bench_dme",
    "bench_kernels", "bench_agg",
]


def run_modules(names: "list[str]") -> dict:
    """Run the named benchmark modules; returns the BENCH_JSON summary."""
    import importlib

    from benchmarks import common

    failed = []
    results = {}
    print("name,us_per_call,derived")
    for name in names:
        before = len(common.ROWS)
        try:
            importlib.import_module(f"benchmarks.{name}").main()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        for row in common.ROWS[before:]:
            rname, us, derived = row.split(",", 2)
            results[rname] = {"module": name, "us_per_call": float(us),
                              "derived": derived}
    return {"ok": not failed, "failed": failed, "results": results}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--modules", default=",".join(MODULES),
                   help="comma-separated benchmark module names")
    args = p.parse_args(argv)
    names = [m for m in args.modules.split(",") if m]
    enable_compile_cache()
    summary = run_modules(names)
    if set(names) == set(MODULES):
        # roofline table (prints a note and returns when there are no
        # dry-run results; a failure fails the run like any module)
        try:
            from benchmarks import roofline
            roofline.main()
        except Exception:
            summary["failed"].append("roofline")
            summary["ok"] = False
            traceback.print_exc()
    print("BENCH_JSON " + json.dumps(summary))
    if summary["failed"]:
        print(f"FAILED: {summary['failed']}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
