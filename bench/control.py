"""Readings that set and test the limits of ``correct``, at a cell's own
size on the chip (the benchmark's runs do not run this).

    python bench/control.py --workload <cell> --seeds 11,12,13 [--controls]
    python bench/control.py --cell <config>/<traffic>/<chips> --seeds ... \
        [--set grad_sync='"fp32"']

For every seed: the program's readings of a sound run.  With ``--controls``
also, on the same seed, the control (the plain reference one precision
below the configuration's, put in the program's place: for a training cell
each of ``CONTROL_DTYPES``) and each fault the cell can have, planted in
the program's path:

- aggregation: half of the cohort's uploads dropped by the server, which
  publishes the mean of the rest; one element of the published mean
  altered where the server produces it;
- training: half of the batch's rows masked out, the loss taken as the mean
  over the rest.  (A step that returns its state unchanged reads 1 on
  ``delta_gap`` by definition and needs no run.)

Prints one JSON line per seed and kind, then the largest sound reading and
the smallest control and fault reading of each number.
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


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def drop_half(ingest):
    """AggServer.ingest_frame that drops every odd client's frames."""
    def fn(self, data, now=0.0):
        from repro.agg.transport import frame as wire
        h, _ = wire.decode_frame(data)
        return [] if h.client_id % 2 else ingest(self, data, now)
    return fn


def alter_answer(finalize):
    """AggServer.finalize that publishes a mean with one element moved by
    one unit in the last place."""
    import numpy as np

    def fn(self):
        mean, stats = finalize(self)
        mean = mean.copy()
        mean[0] = np.nextafter(mean[0], np.float32(np.inf))
        return mean, stats
    return fn


def agg_readings(drv, seconds: float, controls: bool) -> dict:
    from bench import drive_agg as A
    from repro.agg.server import AggServer
    cfg = drv.cfg
    drv.setup()
    drv.window(seconds)
    ref = drv.reference()
    exact = drv.exact_mean()
    def gaps(*a):
        return {n: v for n, v, _ in A.readings(cfg, *a)}
    out = {"sound": gaps(drv.means, drv.accepted, drv.failed_clients, ref,
                         exact)}
    if not controls:
        return out
    low = drv.reference(low_precision=True)
    out["control"] = gaps([low], [cfg["cohort"]], 0, ref, exact)
    for kind, attr, fault in (("half_batch", "ingest_frame", drop_half),
                              ("answer_altered", "finalize", alter_answer)):
        drv.means, drv.accepted, drv.failed_clients = [], [], 0
        with patched(AggServer, attr, fault(getattr(AggServer, attr))):
            drv._round()
        out[kind] = gaps(drv.means, drv.accepted, drv.failed_clients, ref,
                         exact)
    return out


# The configuration computes in bfloat16; the steps below it are fp8 and
# int8 (int8 runs at 393 TOP/s on a TPU v5e, which has no fp8 unit).  Each
# has to come out not correct.
CONTROL_DTYPES = ("float8_e4m3fn", "int8")


def train_readings(make, controls: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from bench import drive_train as TD
    drv = make()
    drv.setup()
    prog = drv.program_readings()
    drv.release()
    ref = drv.reference()
    out = {"sound": TD.gaps(prog, ref)}
    if not controls:
        return out
    for dt in CONTROL_DTYPES:
        low = dict(drv.reference(dtype=jnp.dtype(dt)), fails=0.0)
        out[f"control.{dt}"] = TD.gaps(low, ref)
        del low
    half = make()
    keep = np.arange(half.batch) % 2 == 0      # every other row
    half.mask_fault = jnp.asarray(keep, jnp.float32)[:, None]
    half.setup()
    hp = half.program_readings()
    half.release()
    out["half_batch"] = TD.gaps(hp, ref)
    return out


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--cell", help="CONFIG/TRAFFIC/CHIPS: a pairing that is "
                                   "not (or not yet) a cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="override a traffic key, KEY=JSON (a variant of the "
                         "cell, e.g. grad_sync=\"fp32\" as a witness)")
    args = ap.parse_args(argv)
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    spec = harness.load_spec()
    if args.cell:
        cname, tname, chips = args.cell.split("/")
        cell = {"config": cname, "traffic": tname, "chips": int(chips)}
        spec = dict(spec, workloads=[dict(cell, name="cell")])
        cell, cfg, traffic = harness.cell_parts(spec, "cell")
    else:
        cell, cfg, traffic = harness.cell_parts(spec, args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        traffic[k] = json.loads(v)
    devs = harness.check_devices(int(cell["chips"]))
    enable_compile_cache()
    mod = harness.generator_module(traffic)
    worst: dict = {}
    for s in (int(x) for x in args.seeds.split(",")):
        make = lambda: mod.Run(cfg, traffic, s, int(cell["chips"]))  # noqa
        if traffic["generator"] == "agg":
            res = agg_readings(make(), args.seconds, args.controls)
        else:
            res = train_readings(make, args.controls)
        gc.collect()
        for kind, rs in res.items():
            print(json.dumps({"seed": s, "kind": kind, **rs}), flush=True)
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
