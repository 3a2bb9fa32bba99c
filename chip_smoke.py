"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py             # one chip: kernels, an aggregation round,
                                     # granite-moe-1b-a400m training steps
    python chip_smoke.py --chips 4   # four chips: granite on a dp=4 ZeRO-3
                                     # mesh, grad_sync "lq" against "fp32"

Every phase drives the entry points a user calls (``repro.kernels.ops``,
``AggClient``/``AggServer``, ``Trainer``/``make_train_step``) and checks what
comes out against the repo's own references.  Phases print their own lines;
the last line of stdout is one JSON object naming the device, printed only
when every phase passed.  The script exits nonzero, and prints no result,
when JAX finds no TPU: it never falls back to the CPU.  Timings printed here
come from a smoke run, not from a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

KERNEL_N = 1 << 22           # kernel phase: coordinates per vector
KERNEL_SENDERS = 16          # kernel phase: batched-decode senders
AGG_CLIENTS = 256            # aggregation phase: one round's cohort
AGG_D = 1 << 20              # aggregation phase: update size
AGG_MTU = 1 << 16            # aggregation phase: chunk bytes (~9 per client)
AGG_WINDOW = 2               # aggregation phase: credit window, in chunks
Q = 16
BUCKET = 4096
TRAIN_ARCH = "granite-moe-1b-a400m"
TRAIN_LAYERS = 8             # cut from 24 so weights + Adam fit 16 GB
TRAIN_SEQ = 2048
TRAIN_BATCH_PER_CHIP = 5     # the largest that fits: the step holds the
                             # state twice (in and out, 11.8 GB at 8 layers)
TRAIN_STEPS = 4
LOSS_RTOL = 0.02             # four chips: |lq - fp32| / fp32, every step


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def kernel_names(compiled) -> "set[str]":
    """The jitted wrappers (``lattice_encode_pallas``, ``_fwht_2d``, ...)
    whose Pallas kernels the compiled program launches as Mosaic custom
    calls; empty under interpret mode and on the jnp fallback of
    repro.kernels.ops."""
    return {m.group(1) for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r"jit\((\w+)\)+/pallas_call", line)]
            if m}


def same_bits(a, b) -> "tuple[bool, int]":
    """(bit-identical, number of differing elements)."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False, -1
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jax.lax.bitcast_convert_type(a, jnp.int32)
        b = jax.lax.bitcast_convert_type(b, jnp.int32)
    diff = int(jnp.sum(a != b))
    return diff == 0, diff


def timed_call(fn, *args) -> float:
    """Seconds of one steady call (the first call compiled/warmed up)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


# ------------------------------------------------------------------ kernels
def kernel_phase(seed: int, n: int = KERNEL_N,
                 senders: int = KERNEL_SENDERS) -> None:
    """Encode, decode, batched decode and FWHT through repro.kernels.ops,
    each compiled to a Mosaic kernel and bit-identical to kernels/ref.py."""
    from repro.core import lattice as L
    from repro.kernels import ops as K
    from repro.kernels import ref as R

    bits = L.bits_for_q(Q)
    nb = n // BUCKET
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = 3.0 * jax.random.normal(ks[0], (n,))
    anchor = x + 0.3 * jax.random.normal(ks[1], (n,))   # QState anchor
    u = jax.random.uniform(ks[2], (n,), minval=-0.5, maxval=0.5)
    y_b = jax.random.uniform(ks[3], (nb,), minval=0.5, maxval=1.5)
    s = jnp.repeat(2.0 * y_b / (Q - 1), BUCKET)           # per-bucket sides
    x_rx = x + 0.05 * jax.random.normal(ks[4], (n,))     # receiver's estimate

    xs = x[None] + 0.05 * jax.random.normal(ks[5], (senders, n))
    y_sb = jax.random.uniform(ks[6], (senders, nb), minval=0.5, maxval=1.5)
    s_sb = jnp.repeat(2.0 * y_sb / (Q - 1), BUCKET, axis=-1)
    enc_ref = jax.jit(lambda x, s: R.lattice_encode_ref(
        x, u, s, q=Q, bits=bits, anchor=anchor))
    words_sb = jnp.stack([enc_ref(xs[i], s_sb[i]) for i in range(senders)])
    words = enc_ref(x, s)
    h = jax.random.randint(ks[7], (n // BUCKET, BUCKET), -8, 9
                           ).astype(jnp.float32)        # exact in any order

    cases = [
        ("encode q=16 per-coord sides+anchor+coords",
         lambda x, u, s, a: K.lattice_encode(x, u, s, q=Q, return_coords=True,
                                             anchor=a),
         lambda x, u, s, a: R.lattice_encode_ref(x, u, s, q=Q, bits=bits,
                                                 return_coords=True, anchor=a),
         (x, u, s, anchor)),
        ("encode q=16 scalar side",
         lambda x, u, s: K.lattice_encode(x, u, s, q=Q),
         lambda x, u, s: R.lattice_encode_ref(x, u, s, q=Q, bits=bits),
         (x, u, jnp.float32(0.1))),
        ("decode point +ref",
         lambda w, a, u, s, r: K.lattice_decode(w, a, u, s, q=Q, mode="point",
                                                ref=r),
         lambda w, a, u, s, r: R.lattice_decode_ref(w, a, u, s, q=Q, bits=bits,
                                                    n=n, mode="point", ref=r),
         (words, x_rx, u, s, anchor)),
        ("decode coords +ref",
         lambda w, a, u, s, r: K.lattice_decode(w, a, u, s, q=Q,
                                                mode="coords", ref=r),
         lambda w, a, u, s, r: R.lattice_decode_ref(w, a, u, s, q=Q, bits=bits,
                                                    n=n, mode="coords", ref=r),
         (words, x_rx, u, s, anchor)),
        (f"batched decode {senders} senders per-sender sides",
         lambda w, a, u, s, r: K.lattice_decode_batched(
             w, a, u, s, q=Q, mode="coords", ref=r),
         lambda w, a, u, s, r: R.lattice_decode_batched_ref(
             w, a, u, s, q=Q, bits=bits, n=n, mode="coords", ref=r),
         (words_sb, x_rx, u, s_sb, anchor)),
        (f"fwht d={BUCKET}", K.fwht, R.fwht_ref, (h,)),
    ]
    for name, fn, ref_fn, args in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        out = jax.tree.leaves(compiled(*args))
        want = jax.tree.leaves(jax.jit(ref_fn)(*args))
        kernel = bool(kernel_names(compiled))
        verdicts = [same_bits(o, w) for o, w in zip(out, want)]
        call_us = timed_call(compiled, *args) * 1e6
        print(f"[kernels] {name} N={n}: tpu_custom_call={kernel} "
              f"bit_identical={all(v for v, _ in verdicts)} "
              f"differing={[d for _, d in verdicts]} "
              f"compile_s={compile_s:.2f} call_us={call_us:.1f}", flush=True)
        check(kernel, f"{name}: no Mosaic kernel in the compiled program")
        check(len(out) == len(want) and all(v for v, _ in verdicts),
              f"{name}: output differs from kernels/ref.py")


# -------------------------------------------------------------- aggregation
def agg_phase(seed: int, clients: int = AGG_CLIENTS, d: int = AGG_D,
              mtu: int = AGG_MTU, window: int = AGG_WINDOW) -> None:
    """One anchored, chunked, windowed round: AggClient -> AggServer.

    The streaming server's mean must be bit-identical to the sealed
    batched-decode drain of the same frames and to a jnp-reference
    decode-and-sum of the same payloads, and within the lattice bound of
    the exact mean."""
    from repro.agg import rounds as AR
    from repro.agg.client import AggClient
    from repro.agg.server import AggServer
    from repro.agg.transport import frame as wire
    from repro.core import error_detect as ED
    from repro.core import lattice as L
    from repro.dist.collectives import QSyncConfig
    from repro.kernels import ref as R

    key = jax.random.PRNGKey(seed + 1)
    base = 3.0 * jax.random.normal(key, (d,))
    anchor = np.asarray(base)          # the previous round's published mean

    def x_of(i):
        return base + 0.05 * jax.random.normal(jax.random.fold_in(key, i),
                                               (d,))

    spec = wire.RoundSpec(round_id=1, d=d, cfg=QSyncConfig(q=Q, bucket=BUCKET),
                          y0=0.5, seed=seed,
                          anchor_digest=AR.anchor_digest(anchor),
                          mtu=mtu, window=window)
    t0 = time.perf_counter()
    cl = [AggClient(spec, cid, x_of(cid), anchor=anchor)
          for cid in range(clients)]
    frames = {c.client_id: c.frames() for c in cl}
    encode_s = time.perf_counter() - t0

    # windowed streaming round: credit-paced clients, lossless wire
    t0 = time.perf_counter()
    server = AggServer(spec, anchor)
    outbox = [(c, f) for c in cl for f in c.send_frames()]
    n_frames = 0
    while outbox:
        nxt = []
        for c, f in outbox:
            n_frames += 1
            nxt.extend((c, g) for g in c.handle_response(server.receive(f)))
        outbox = nxt
    server.seal()
    server.tick()
    pub = server.published()
    stream_s = time.perf_counter() - t0
    check(pub, "streaming round did not publish")
    mean, stats = pub[0].mean, pub[0].stats
    check(stats.accepted == clients,
          f"streaming round accepted {stats.accepted} of {clients}")

    # the same frames through the sealed batched-decode drain
    t0 = time.perf_counter()
    sealed = AggServer(spec, anchor, streaming=False)
    for cid in range(clients):
        for f in frames[cid]:
            sealed.receive(f)
    mean_sealed, stats_sealed = sealed.finalize()
    sealed_s = time.perf_counter() - t0
    check(stats_sealed.accepted == clients,
          f"sealed drain accepted {stats_sealed.accepted} of {clients}")

    # jnp reference: reassemble each payload, decode it with kernels/ref.py
    # against the anchor-relative zero reference, sum the integer coords
    n = spec.padded
    u = AR.dither(spec)
    weights = AR.checksum_weights(spec)
    dec = jax.jit(lambda w, s: R.lattice_decode_batched_ref(
        w, jnp.zeros((n,), jnp.float32), u.reshape(-1),
        jnp.repeat(s, BUCKET, axis=-1), q=Q, bits=L.bits_for_q(Q), n=n,
        mode="coords"))
    ksum = jnp.zeros((n,), jnp.int32)
    for lo in range(0, clients, 32):
        ps = []
        for cid in range(lo, min(lo + 32, clients)):
            parts = [wire.decode_frame(f) for f in frames[cid]]
            parts.sort(key=lambda p: p[0].chunk_index)
            ps.append(wire.payload_from_body(
                parts[0][0], b"".join(chunk for _, chunk in parts)))
        k = dec(jnp.asarray(np.stack([p.words for p in ps])),
                jnp.asarray(np.stack([p.sides for p in ps])))
        got = np.asarray(ED.coord_checksum(k, weights, axis=-1))
        check(np.array_equal(got, np.array([p.check for p in ps], np.uint32)),
              "reference decode failed a payload checksum")
        ksum = ksum + jnp.sum(k, axis=0, dtype=jnp.int32)
    # the server's float epilogue, in one program of the same ops (a
    # runtime count, so the divide stays a true division), then the anchor
    mean_b = jax.jit(lambda k, c, u, s: (
        jax.lax.optimization_barrier(k).astype(jnp.float32)
        / c.astype(jnp.float32) + u) * s)(
        ksum.reshape(spec.nb, BUCKET), jnp.int32(clients), u,
        AR.sides(spec)[:, None])
    mean_b = mean_b + AR.bucketize(jnp.asarray(anchor), spec)
    ref_mean = np.asarray(AR.unbucketize(mean_b, spec))

    exact = np.zeros((d,), np.float64)
    for i in range(clients):
        exact += np.asarray(x_of(i), np.float64)
    err = float(np.abs(mean - exact / clients).max())
    bound = 2 * wire.y_at_attempt(spec, 0)
    ident_sealed = np.array_equal(mean.view(np.uint32),
                                  mean_sealed.view(np.uint32))
    ident_ref = np.array_equal(mean.view(np.uint32), ref_mean.view(np.uint32))
    print(f"[agg] anchored round: {clients} clients d={d} q={Q} "
          f"bucket={BUCKET} mtu={mtu} window={window}: "
          f"{spec.n_chunks()} chunks/client, {n_frames} frames; "
          f"accepted={stats.accepted} nacks={stats.nacks_sent} "
          f"resends={stats.resends_sent}", flush=True)
    print(f"[agg] mean bit-identical: streaming==sealed {ident_sealed}, "
          f"streaming==jnp reference {ident_ref}; max_err={err:.6f} "
          f"(bound {bound:.6f})", flush=True)
    print(f"[agg] host seconds: encode {encode_s:.2f}, streaming round "
          f"{stream_s:.2f}, sealed drain {sealed_s:.2f}", flush=True)
    check(ident_sealed, "streaming mean != sealed batched-drain mean")
    check(ident_ref, "published mean != jnp reference decode-and-sum")
    check(err <= bound, f"round error {err} exceeds the lattice bound {bound}")


# ----------------------------------------------------------------- training
def _train_run(cfg, dp: int, grad_sync: str, seed: int,
               steps: int = TRAIN_STEPS) -> dict:
    """``steps`` steps of ``Trainer``'s compiled step from a seeded init."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.collectives import QSyncConfig
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import ShardCtx
    from repro.train import data as D
    from repro.train.optim import OptConfig
    from repro.train.trainer import TrainConfig, Trainer, init_state

    mesh = make_mesh((dp, 1), ("data", "model"))
    ctx = ShardCtx(tp=1, dp=dp, qcfg=QSyncConfig(q=Q, bucket=BUCKET),
                   grad_sync=grad_sync)
    # max_restarts=0: any error fails the phase instead of a silent retry
    tc = TrainConfig(steps=steps, max_restarts=0)
    opt = OptConfig(lr=3e-4, warmup=min(50, steps // 10 + 1),
                    decay_steps=steps)
    data = D.DataConfig(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                        global_batch=TRAIN_BATCH_PER_CHIP * dp)
    tr = Trainer(cfg, ctx, mesh, opt, tc, data)
    devices = set(mesh.devices.flat)
    check(len(devices) == dp, f"mesh spans {len(devices)} devices, not {dp}")

    def batch_at(step):
        b = D.batch_at(tr.data_cfg, step)
        return {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                for k, v in b.items()}

    shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                             tr.state_spec,
                             is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(init_state(cfg, tr.ctx, tr.opt_cfg, tr.tc,
                                      jax.random.PRNGKey(seed)), shardings)
    leaf = jax.tree.leaves(state["params"])[0]
    check(leaf.sharding.device_set == devices,
          f"params live on {len(leaf.sharding.device_set)} devices, "
          f"not the mesh's {dp}")
    batch = batch_at(0)
    t0 = time.perf_counter()
    step_fn = tr.step_fn.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    losses, fails, step_s = [], [], []
    for step in range(steps):
        if step:
            batch = batch_at(step)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])          # waits for the step
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
        fails.append(float(metrics["fails"]))
        print(f"[train] {grad_sync} dp={dp} step={step} loss={loss:.4f} "
              f"gnorm={float(metrics['gnorm']):.3f} fails={fails[-1]:.0f} "
              f"step_s={step_s[-1]:.3f}", flush=True)
    del state
    return {"losses": losses, "fails": fails, "compile_s": compile_s,
            "steady_step_s": float(np.median(step_s[1:])),
            "kernels": sorted(kernel_names(step_fn)),
            "tokens": TRAIN_BATCH_PER_CHIP * dp * TRAIN_SEQ}


def _granite():
    from repro.configs import registry
    full = registry.config(TRAIN_ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    print(f"[train] {TRAIN_ARCH}: published widths d{cfg.d_model} "
          f"{cfg.n_heads}H/kv{cfg.n_kv} {cfg.n_experts} experts top-"
          f"{cfg.top_k} ff{cfg.d_ff} V{cfg.vocab}; depth cut "
          f"{full.n_layers} -> {cfg.n_layers} layers "
          f"({full.param_count() / 1e6:.0f}M -> "
          f"{cfg.param_count() / 1e6:.0f}M params); seq {TRAIN_SEQ}, "
          f"batch {TRAIN_BATCH_PER_CHIP} per chip", flush=True)
    return cfg


def train_phase(seed: int) -> None:
    """granite-moe-1b-a400m at published widths, grad_sync="lq", 1x1 mesh."""
    cfg = _granite()
    r = _train_run(cfg, 1, "lq", seed)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[train] lq dp=1: compile_s={r['compile_s']:.1f} "
          f"steady_step_s={r['steady_step_s']:.4f} "
          f"tokens/step={r['tokens']} fails={sum(r['fails']):.0f} "
          f"peak_bytes_in_use={peak}", flush=True)
    ln_v = math.log(cfg.vocab)
    check(all(math.isfinite(v) for v in r["losses"]), "non-finite loss")
    check(abs(r["losses"][0] - ln_v) < 1.0,
          f"first loss {r['losses'][0]:.4f} is not near ln V = {ln_v:.4f}")
    check(sum(r["fails"]) == 0, "decode failures in a 1x1 run")


def four_chip_phase(seed: int) -> None:
    """granite on a dp=4 ZeRO-3 mesh: quantized lq sync against fp32."""
    cfg = _granite()
    res = {}
    for sync in ("lq", "fp32"):
        res[sync] = r = _train_run(cfg, 4, sync, seed)
        gc.collect()
        print(f"[train4] {sync} dp=4: compile_s={r['compile_s']:.1f} "
              f"steady_step_s={r['steady_step_s']:.4f} "
              f"tokens/step={r['tokens']} kernels={r['kernels']} "
              f"fails={sum(r['fails']):.0f}", flush=True)
    lq, fp = res["lq"]["losses"], res["fp32"]["losses"]
    rel = [abs(a - b) / abs(b) for a, b in zip(lq, fp)]
    print(f"[train4] |lq - fp32| / fp32 per step: "
          f"{[f'{v:.5f}' for v in rel]} (tolerance {LOSS_RTOL})", flush=True)
    check({"lattice_encode_pallas", "lattice_decode_pallas"}
          <= set(res["lq"]["kernels"]),
          "the lq program lacks the encode/decode kernels")
    check(all(math.isfinite(v) for v in lq + fp), "non-finite loss")
    check(sum(res["lq"]["fails"]) == 0, "decode failures in the lq run")
    check(max(rel) <= LOSS_RTOL, "lq loss does not track fp32")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); refusing to fall back", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    phases = ([("four_chip", four_chip_phase)] if args.chips == 4 else
              [("kernels", kernel_phase), ("agg", agg_phase),
               ("train", train_phase)])
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        gc.collect()
        print(f"[smoke] phase {name}: "
              f"{'FAIL' if name in failed else 'ok'} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
