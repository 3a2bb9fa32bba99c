import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the first import side effect: 512 placeholder CPU devices so
``jax.make_mesh`` can build the production meshes (16x16 single-pod,
2x16x16 multi-pod).  Do not move the os.environ lines.

Per cell, records:
  * compiled.memory_analysis()  — per-device bytes (proves it fits),
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes parsed from the compiled HLO text (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute operand
    sizes) — the roofline's third term,
to JSON under --out (default results/dryrun).

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--shapes train_4k,...]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import registry, shapes as SH
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as ST
from repro.launch.hlo_analysis import analyze, audit_overlap
from repro.dist.collectives import QSyncConfig


COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\S+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(shape_str: str) -> int:
    """'bf16[16,1024]{1,0}' -> byte count (per participating device)."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op, by kind.

    Tuple-shaped outputs ((f32[...], f32[...])) are summed over elements.
    This counts bytes *entering the interconnect* once per device (the
    standard roofline convention).
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.match(
            r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]+?)\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        shape_str, kind = m.groups()
        total = sum(_shape_bytes(s) for s in
                    re.findall(r"\w+\[[\d,]*\](?:\{[\d,]*\})?", shape_str))
        out[kind] = out.get(kind, 0) + total
        out[f"{kind}_count"] = out.get(f"{kind}_count", 0) + 1
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             grad_sync: str = "lq", qcfg=None, seq_parallel=None,
             microbatch: int = 0, tag: str = "",
             kv_quant: bool = False) -> dict:
    cfg0 = registry.config(arch)
    if not SH.applicable(cfg0.family, shape_name):
        return {"arch": arch, "shape": shape_name,
                "multi_pod": multi_pod, "skipped": True,
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    step_fn, args, cfg, ctx = ST.build_cell(
        arch, shape_name, mesh, grad_sync=grad_sync, qcfg=qcfg,
        seq_parallel=seq_parallel, microbatch=microbatch) \
        if SH.SHAPES[shape_name].kind == "train" else ST.build_cell(
            arch, shape_name, mesh, kv_quant=kv_quant)
    lowered = step_fn.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # flops/collectives from the PRE-optimization HLO (dots are still dots;
    # the CPU backend rewrites big matmuls into oneDNN custom-calls in the
    # post-opt text); HBM-traffic proxy from the POST-opt (fused) HLO.
    pre_txt = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    pre = analyze(pre_txt)            # loop-trip-expanded (hlo_analysis.py)
    post_txt = compiled.as_text()
    post = analyze(post_txt)
    # overlap audit on the post-opt (scheduled) HLO: fraction of loop-
    # collective wire bytes whose result feeds same-iteration compute
    # (1.0 = fully serialized; the prefetched scan should sit well below)
    overlap = audit_overlap(post_txt)
    coll = pre.coll
    if os.environ.get("DRYRUN_SAVE_HLO"):
        import zstandard as zstd
        hdir = os.environ["DRYRUN_SAVE_HLO"]
        os.makedirs(hdir, exist_ok=True)
        nm = f"{arch}__{shape_name}__{'2pod' if multi_pod else '1pod'}"
        if tag:
            nm += f"__{tag}"
        with open(os.path.join(hdir, nm + ".hlo.zst"), "wb") as f:
            f.write(zstd.ZstdCompressor(level=6).compress(post_txt.encode()))

    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "tag": tag, "grad_sync": grad_sync, "skipped": False,
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "flops_raw": float(cost.get("flops", 0.0)),
        "bytes_accessed_raw": float(cost.get("bytes accessed", 0.0)),
        "flops": pre.dot_flops,             # trip-expanded dot flops
        "traffic_bytes": post.traffic,      # trip-expanded HBM proxy (fused)
        "traffic_bytes_pre": pre.traffic,
        "collectives": coll,
        "collective_exposed_fraction": overlap.exposed_fraction,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
                          + getattr(mem, "argument_size_in_bytes", 0),
        },
        "params_B": cfg.param_count() / 1e9,
        "active_params_B": cfg.active_param_count() / 1e9,
        "seq_parallel": ctx.seq_parallel,
        "mesh": dict(mesh.shape),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--archs", default="")
    ap.add_argument("--grad-sync", default="lq")
    ap.add_argument("--q", type=int, default=16)
    ap.add_argument("--bucket", type=int, default=4096)
    ap.add_argument("--rotate", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    qcfg = QSyncConfig(q=args.q, bucket=args.bucket, rotate=args.rotate)
    sp = False if args.no_seq_parallel else None

    cells = []
    archs = (args.archs.split(",") if args.archs
             else ([args.arch] if args.arch else list(registry.ARCHS)))
    shape_list = (args.shapes.split(",") if args.shapes
                  else ([args.shape] if args.shape else list(SH.SHAPES)))
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for mp in meshes:
        for a in archs:
            for s in shape_list:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    ok = fail = 0
    for arch, shape, mp in cells:
        name = f"{arch}__{shape}__{'2pod' if mp else '1pod'}"
        if args.tag:
            name += f"__{args.tag}"
        path = os.path.join(args.out, name + ".json")
        if os.path.exists(path):
            print(f"[dryrun] {name}: cached", flush=True)
            ok += 1
            continue
        print(f"[dryrun] {name}: lowering...", flush=True)
        try:
            rec = run_cell(arch, shape, mp, grad_sync=args.grad_sync,
                           qcfg=qcfg, seq_parallel=sp,
                           microbatch=args.microbatch, tag=args.tag,
                           kv_quant=args.kv_quant)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("skipped"):
                print(f"[dryrun] {name}: SKIP ({rec['reason']})", flush=True)
            else:
                print(f"[dryrun] {name}: OK flops={rec['flops']:.3e} "
                      f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                      f"coll={ {k: round(v/2**20, 1) for k, v in rec['collectives'].items() if not k.endswith('_count')} }MiB "
                      f"compile={rec['compile_s']}s", flush=True)
            ok += 1
        except Exception as e:
            fail += 1
            print(f"[dryrun] {name}: FAIL {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
            with open(path + ".fail", "w") as f:
                f.write(traceback.format_exc())
    print(f"[dryrun] done: {ok} ok, {fail} failed", flush=True)
    sys.exit(1 if fail else 0)


if __name__ == "__main__":
    main()
