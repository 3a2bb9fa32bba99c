"""End-to-end training driver.

Examples:
  # CPU sanity (smoke config, 1 device):
  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke --steps 20

  # ~100M LM for a few hundred steps (examples/train_lm.py wraps this):
  PYTHONPATH=src python -m repro.launch.train --preset 100m --steps 300

  # production pod (on real hardware; mesh axes = data x model):
  python -m repro.launch.train --arch qwen3-32b --mesh 16x16 --steps 1000
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import registry
from repro.models.config import ModelConfig
from repro.models.sharding import ShardCtx
from repro.dist.collectives import QSyncConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.train.trainer import Trainer, TrainConfig
from repro.train.optim import OptConfig
from repro.train.data import DataConfig


PRESETS = {
    # ~100M-parameter decoder LM (examples/train_lm.py)
    "100m": ModelConfig(arch="lm-100m", family="dense", n_layers=12,
                        d_model=768, n_heads=12, n_kv=4, head_dim=64,
                        d_ff=2048, vocab=32768, act="swiglu"),
    "25m": ModelConfig(arch="lm-25m", family="dense", n_layers=8,
                       d_model=384, n_heads=6, n_kv=2, head_dim=64,
                       d_ff=1024, vocab=16384, act="swiglu"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=list(PRESETS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1x1", help="DPxTP, e.g. 16x16")
    ap.add_argument("--grad-sync", default="lq",
                    choices=["lq", "fp32"])
    ap.add_argument("--q", type=int, default=16)
    ap.add_argument("--bucket", type=int, default=4096)
    ap.add_argument("--rotate", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    if args.preset:
        cfg = PRESETS[args.preset]
    elif args.arch:
        cfg = (registry.smoke_config(args.arch) if args.smoke
               else registry.config(args.arch))
    else:
        raise SystemExit("pass --arch or --preset")

    dp, tp = (int(v) for v in args.mesh.split("x"))
    if dp * tp > len(jax.devices()):
        raise SystemExit(f"mesh {args.mesh} needs {dp*tp} devices, "
                         f"have {len(jax.devices())}")
    mesh = make_mesh((dp, tp), ("data", "model"))
    ctx = ShardCtx(tp=tp, dp=dp,
                   qcfg=QSyncConfig(q=args.q, bucket=args.bucket,
                                    rotate=args.rotate),
                   grad_sync=args.grad_sync,
                   seq_parallel=tp > 1 and cfg.family != "encdec")
    tc = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir, log_every=args.log_every,
                     microbatch=args.microbatch)
    opt = OptConfig(lr=args.lr, warmup=min(50, args.steps // 10 + 1),
                    decay_steps=args.steps)
    data = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    extra = None
    if cfg.family == "vlm":
        from repro.train.data import frames_at
        extra = lambda step: {"img": frames_at(data, step, cfg.img_tokens,
                                               cfg.d_model)}
    if cfg.family == "encdec":
        from repro.train.data import frames_at
        extra = lambda step: {"frames": frames_at(data, step, cfg.enc_seq,
                                                  cfg.d_model)}
        raise SystemExit("encdec training driver: use tests/benchmarks "
                         "(frames batch wiring differs)")

    print(f"[train] arch={cfg.arch} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={args.mesh} sync={args.grad_sync}(q={args.q}) "
          f"steps={args.steps}", flush=True)
    tr = Trainer(cfg, ctx, mesh, opt, tc, data, extra_batch=extra)
    state = tr.train()
    if tr.history:
        first, last = tr.history[0], tr.history[-1]
        print(f"[train] loss {first['loss']:.4f} -> {last['loss']:.4f} over "
              f"{int(state['step'])} steps", flush=True)


if __name__ == "__main__":
    main()
