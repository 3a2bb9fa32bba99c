"""Readings that set and test the limits of a Moonlight cell's ``correct``,
at the cell's own size on the chip (the benchmark's runs do not run this):
``bench/control.py``'s training readings with the router's own
(``bench.drive_moonlight.gaps``).

    python bench/control_moonlight.py --workload moonlight.ep8-8k \
        --seeds 11,12,13 [--controls 11,12]

For every seed: the program's readings of a sound run.  For the seeds given
to ``--controls`` also, on the same seed, each control of
``bench.control.CONTROL_DTYPES`` (the plain reference one precision below
the configuration's, put in the program's place), and ``bias_frozen``: the
sound run's readings with the selection bias left at zero, as a trainer that
never moves it leaves it (only its ``bias_differ`` is printed, since the
other readings of such a run are not these).

Prints one JSON line per seed and kind, then the largest sound reading and
the smallest control and fault reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def seed_readings(make, controls: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from bench import control
    from bench import drive_moonlight as M
    drv = make()
    rate = drv.cfg["bias_update_speed"]
    drv.setup()
    prog = drv.program_readings()
    drv.release()
    ref = drv.reference()
    out = {"sound": M.gaps(prog, ref, rate)}
    if not controls:
        return out
    for dt in control.CONTROL_DTYPES:
        low = dict(drv.reference(dtype=jnp.dtype(dt)), fails=0.0)
        out[f"control.{dt}"] = M.gaps(low, ref, rate)
        del low
    frozen = dict(prog, bias=np.zeros_like(prog["bias"]))
    out["bias_frozen"] = {
        "bias_differ": M.gaps(frozen, ref, rate)["bias_differ"]}
    return out


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="",
                    help="the seeds, of --seeds, that also read the controls")
    args = ap.parse_args(argv)
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    cell, cfg, traffic = harness.cell_parts(harness.load_spec(),
                                            args.workload)
    devs = harness.check_devices(int(cell["chips"]))
    enable_compile_cache()
    mod = harness.generator_module(traffic)
    with_controls = {int(x) for x in args.controls.split(",") if x}
    worst: dict = {}
    for s in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        res = seed_readings(
            lambda: mod.Run(cfg, traffic, s, int(cell["chips"])),  # noqa
            s in with_controls)
        gc.collect()
        for kind, rs in res.items():
            print(json.dumps({"seed": s, "kind": kind, **rs,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
            for n, v in rs.items():
                k = (kind, n)
                pick = max if kind == "sound" else min
                worst[k] = v if k not in worst else pick(worst[k], v)
    summary = {f"{'max' if k == 'sound' else 'min'}.{k}.{n}": v
               for (k, n), v in sorted(worst.items())}
    print(json.dumps({"summary": summary,
                      "device": f"{devs[0].device_kind} x{len(devs)}",
                      "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
