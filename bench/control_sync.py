"""Readings of a data-parallel training cell (``bench.drive_train``, dp > 1)
that set and test the limits of its ``correct``, at the cell's own size on
the chip (the benchmark's runs do not run this).

    python bench/control_sync.py --workload granite.dp4-fp32 --seeds 11 \
        --fault
    python bench/control_sync.py --workload granite.dp4-fp32 --seeds 11,12 \
        --controls

``--fault`` (on the cell's chips), for every seed: the program's readings
of ``exchange_left_out``, the fault the cell exists to catch: the
gradient's reduce-scatter over ``data`` keeps each chip's segment of its own
gradient and adds nothing of the other chips'.  (The sound readings of a
seed are what the cell's own run prints.)

``--controls`` (on one chip: only the plain reference runs), for every seed:
each control of ``bench.control.CONTROL_DTYPES``, the reference one
precision below the configuration's in the program's place, at the cell's
batch.

Prints one JSON line per seed and kind.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def local_segment(psum_scatter):
    """jax.lax.psum_scatter that, over ``data``, returns this chip's tiled
    segment of its own operand, unsummed."""
    import jax

    def fn(x, axis_name, *, scatter_dimension=0, tiled=False, **kw):
        if axis_name != "data" or not tiled:
            return psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled, **kw)
        n = x.shape[scatter_dimension] // jax.lax.psum(1, axis_name)
        return jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(axis_name) * n, n, scatter_dimension)
    return fn


def fault_readings(make, kinds=("sound", "exchange_left_out")) -> dict:
    import jax
    from bench import drive_train as TD
    from bench.control import patched
    out, progs = {}, {}
    for kind in kinds:
        drv = make()
        with (patched(jax.lax, "psum_scatter",
                      local_segment(jax.lax.psum_scatter))
              if kind != "sound" else contextlib.nullcontext()):
            drv.setup()
        progs[kind] = drv.program_readings()
        drv.release()
        del drv
        gc.collect()
    ref = make().reference()
    for kind, prog in progs.items():
        out[kind] = TD.gaps(prog, ref)
    return out


def control_readings(make) -> dict:
    import jax.numpy as jnp
    from bench import control
    from bench import drive_train as TD
    drv = make()
    ref = drv.reference()
    return {f"control.{dt}": TD.gaps(
        dict(drv.reference(dtype=jnp.dtype(dt)), fails=0.0), ref)
        for dt in control.CONTROL_DTYPES}


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--fault", action="store_true")
    what.add_argument("--controls", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    cell, cfg, traffic = harness.cell_parts(harness.load_spec(),
                                            args.workload)
    chips = int(cell["chips"])
    if traffic["generator"] != "train" or int(traffic["dp"]) < 2:
        raise SystemExit(f"{args.workload} is not a data-parallel training "
                         f"cell")
    devs = harness.check_devices(chips if args.fault else 1)
    enable_compile_cache()
    mod = harness.generator_module(traffic)
    for s in (int(x) for x in args.seeds.split(",")):
        make = lambda: mod.Run(cfg, traffic, s, chips)  # noqa: E731
        res = fault_readings(make, ("exchange_left_out",)) if args.fault \
            else control_readings(make)
        for kind, rs in res.items():
            print(json.dumps({"seed": s, "kind": kind, **rs,
                              "device": f"{devs[0].device_kind} x{len(devs)}",
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
