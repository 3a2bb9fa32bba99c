"""Hierarchical sum-without-decode tree (ISSUE 7): tree-vs-flat bit-parity
under chunk loss / reordering / duplicates / straggling tiers, saturation
rejection at the q cap, the no-tier-decodes dispatch gate and the AggNode
protocol surface."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.agg import sim
from repro.agg.api import AggNode, PublishedRound
from repro.agg.client import AggClient
from repro.agg.engine import AggEngine, EngineConfig
from repro.agg.server import AggServer
from repro.agg.service import AggService, ServiceConfig
from repro.agg.transport import frame as wire
from repro.agg.tree import TIER_ID_BASE, AggTree, TierAggregator
from repro.dist.collectives import QSyncConfig
from repro.kernels import ops as K

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(d=1024, bucket=128, q=16, mtu=0, y0=0.5, seed=3, round_id=1,
          max_attempts=4):
    return wire.RoundSpec(round_id=round_id, d=d,
                          cfg=QSyncConfig(q=q, bucket=bucket), y0=y0,
                          seed=seed, max_attempts=max_attempts, mtu=mtu)


def _fleet(spec, n, seed=0, spread=0.02, scale=2.0):
    rng = np.random.RandomState(seed)
    base = scale * rng.randn(spec.d).astype(np.float32)
    xs = base[None] + spread * rng.randn(n, spec.d).astype(np.float32)
    return base, xs, sim.fleet_frames(spec, xs)


def _flat_publish(spec, base, frames):
    srv = AggServer(spec, base)
    for fs in frames:
        for f in fs:
            srv.ingest_frame(f)
    srv.tick()
    srv.seal()
    return srv.published()[0]


def _run_tree(tree, frames, max_ticks=16):
    for fs in frames:
        for f in fs:
            tree.ingest_frame(f)
    tree.tick()
    tree.seal()
    for _ in range(max_ticks):
        tree.tick()
        prs = tree.published()
        if prs:
            return prs[0]
    raise AssertionError("tree never published within the tick budget")


def _assert_parity(pt: PublishedRound, pf: PublishedRound):
    assert pt.accepted == pf.accepted
    assert np.array_equal(pt.mean.view(np.uint32), pf.mean.view(np.uint32))


# ---------------------------------------------------------------------------
# Bit-parity: tree mean == flat mean over the same accepted clients
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fanout,tiers,mtu", [(4, 1, 0), (4, 2, 0),
                                              (4, 2, 160), (8, 1, 256)])
def test_tree_flat_bit_parity(fanout, tiers, mtu):
    spec = _spec(mtu=mtu)
    base, _, frames = _fleet(spec, 24)
    pf = _flat_publish(spec, base, frames)
    pt = _run_tree(AggTree(spec, base, fanout=fanout, tiers=tiers), frames)
    _assert_parity(pt, pf)


def test_tree_parity_under_chunk_loss_and_reordering():
    """Drop internal frames (once each) AND deliver every client's chunks
    in reversed interleaved order; the selective-retransmit path must
    restore bit-parity with the clean flat round."""
    spec = _spec(mtu=160)
    base, _, frames = _fleet(spec, 20)
    pf = _flat_publish(spec, base, frames)

    lost = {"n": 0}

    def loss(src, dst, data):
        if data[:4] == wire.MAGIC_PAYLOAD and lost["n"] < 5:
            lost["n"] += 1
            return None
        return data

    tree = AggTree(spec, base, fanout=4, tiers=2, loss=loss)
    # reordered: chunk-interleaved, reversed client order
    nc = len(frames[0])
    for k in range(nc - 1, -1, -1):
        for i in range(len(frames) - 1, -1, -1):
            tree.ingest_frame(frames[i][k])
    tree.tick()
    tree.seal()
    for _ in range(24):
        tree.tick()
        if tree.published():
            break
    assert lost["n"] == 5, "loss hook never fired"
    assert tree.published(), "tree did not recover from internal loss"
    _assert_parity(tree.published()[0], pf)


def test_tree_parity_with_duplicate_clients():
    """Every frame delivered twice (client retransmit storm) plus a late
    full replay: duplicates ACK idempotently at the edge and are never
    double-counted in any tier's fold."""
    spec = _spec(mtu=0)
    base, _, frames = _fleet(spec, 16)
    pf = _flat_publish(spec, base, frames)
    tree = AggTree(spec, base, fanout=4, tiers=1)
    for fs in frames:
        for f in fs:
            tree.ingest_frame(f)
            tree.ingest_frame(f)           # immediate duplicate
    for fs in frames:                      # and a late full replay
        for f in fs:
            tree.ingest_frame(f)
    pt = _run_tree(tree, [])
    _assert_parity(pt, pf)
    assert sum(t.duplicates for t in tree.tier_stats()) > 0


def test_tree_straggling_tier_resend_path():
    """A tier whose ENTIRE combined payload is lost upstream (every chunk,
    first transmissions) must recover via its idle re-send timer plus the
    parent's RESEND chase — the straggling-tier drain path."""
    spec = _spec(mtu=160)
    base, _, frames = _fleet(spec, 12)
    pf = _flat_publish(spec, base, frames)

    victim = {"id": None, "dropped": 0}

    def loss(src, dst, data):
        if data[:4] != wire.MAGIC_PAYLOAD:
            return data
        if victim["id"] is None:
            victim["id"] = src
        if src == victim["id"] and victim["dropped"] < 6:
            victim["dropped"] += 1
            return None                      # black-hole the whole payload
        return data

    tree = AggTree(spec, base, fanout=4, tiers=1, loss=loss)
    pt = _run_tree(tree, frames, max_ticks=32)
    assert victim["dropped"] >= 1
    _assert_parity(pt, pf)
    resends = sum(t.up_resends + t.resends_sent for t in tree.tier_stats())
    assert resends >= 1, "straggling tier never exercised a resend path"


def test_tree_parity_with_escalating_clients():
    """An out-of-bound client escalates against its EDGE tier with the same
    q <- q^2 handshake it would run against a flat server, and the
    recovered round stays bit-identical to flat."""
    spec = _spec(mtu=0)
    rng = np.random.RandomState(4)
    base = 2.0 * rng.randn(spec.d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(10, spec.d).astype(np.float32)
    xs[7] += 6.0 * spec.y0 * rng.choice([-1.0, 1.0], spec.d
                                        ).astype(np.float32)

    def drive(node):
        clients = [AggClient(spec, i, xs[i]) for i in range(len(xs))]
        inflight = [f for c in clients for f in c.frames()]
        for _ in range(2 * spec.max_attempts):
            outs = []
            for f in inflight:
                outs.extend(node.ingest_frame(f))
            outs.extend(node.tick())
            inflight = []
            for rb in outs:
                r = wire.decode_response(rb)
                if r.client_id < len(clients):
                    inflight.extend(clients[r.client_id].handle_response(rb))
            if not inflight:
                break
        node.seal()
        for _ in range(16):
            node.tick()
            if node.published():
                return node.published()[0]
        raise AssertionError("did not publish")

    pf = drive(AggServer(spec, base))
    pt = drive(AggTree(spec, base, fanout=4, tiers=1))
    assert 7 in pt.accepted                  # escalation recovered it
    _assert_parity(pt, pf)


# ---------------------------------------------------------------------------
# Saturation: the overflow guard at the widest color space
# ---------------------------------------------------------------------------

def test_tier_saturation_rejects_at_q_cap():
    """With q0 = 2^16 and no escalation headroom, a fold that would push
    |R| past q_max/2 draws a terminal REJECT and is counted saturated —
    never a silent wraparound of the combined coordinates."""
    spec = _spec(d=256, bucket=64, q=1 << 16, y0=0.5, max_attempts=1)
    rng = np.random.RandomState(0)
    base = np.zeros(spec.d, np.float32)
    # every client ~0.3 * (q/2) coordinate units from the anchor with the
    # SAME sign: the 4th fold would exceed the centered q_max/2 range
    side = float(np.max(spec.sides_np()))
    xs = np.full((6, spec.d), 0.3 * side * float(1 << 15), np.float32)
    xs += 0.01 * side * rng.randn(6, spec.d).astype(np.float32)
    frames = sim.fleet_frames(spec, xs)
    tier = TierAggregator(spec, base, TIER_ID_BASE)
    outs = []
    for fs in frames:
        for f in fs:
            outs.extend(tier.ingest_frame(f))
    outs.extend(tier.tick())
    tier.seal()
    outs.extend(tier.tick())
    st = tier.stats
    assert st.saturated >= 1, "no fold was saturation-rejected"
    assert st.clients_summed >= 1
    assert st.clients_summed + st.saturated == 6
    assert len(tier.accepted_clients) == st.clients_summed
    assert tier.n_summed == st.clients_summed
    # the guarded accumulator still forwards, with the honest summed count
    fwd = [o for o in outs if o[: len(wire.MAGIC_PAYLOAD)]
           == wire.MAGIC_PAYLOAD]
    assert fwd, "tier did not forward its combined payload"
    h, _ = wire.decode_frame(fwd[0])
    assert h.n_summed == st.clients_summed


# ---------------------------------------------------------------------------
# The dispatch gate: tiers never decode; the root decodes once per q
# ---------------------------------------------------------------------------

def test_no_tier_decodes_root_decodes_once_per_color_space():
    fanout = 4
    spec = _spec(mtu=0)
    base, _, frames = _fleet(spec, 24)
    tree = AggTree(spec, base, fanout=fanout, tiers=2)
    for fs in frames:
        for f in fs:
            tree.ingest_frame(f)
    tree.tick()
    import jax

    jax.clear_caches()          # the dispatch counter fires at trace time:
    K.reset_dispatch_counts()   # force the root drain to retrace here
    tree.seal()
    for _ in range(16):
        tree.tick()
        if tree.published():
            break
    decodes = K.DISPATCH_COUNTS["lattice_decode_batched"]
    spaces = {t.forwarded_q for t in tree.layers[0]
              if t.forwarded_q is not None}
    assert tree.published()
    assert decodes == len(spaces) >= 1
    assert K.DISPATCH_COUNTS["lattice_decode"] == 0
    assert tree.root.stats.drains == 1
    assert tree.root_ingress_payloads <= fanout


# ---------------------------------------------------------------------------
# AggNode protocol
# ---------------------------------------------------------------------------

def test_aggnode_protocol_is_satisfied_by_all_endpoints():
    spec = _spec(d=256, bucket=64)
    base = np.zeros(spec.d, np.float32)
    svc = AggService(ServiceConfig(d=256, bucket=64))
    eng = AggEngine(svc, EngineConfig(), now=0.0)
    for node in (AggServer(spec, base), eng,
                 TierAggregator(spec, base, TIER_ID_BASE),
                 AggTree(spec, base, fanout=2)):
        assert isinstance(node, AggNode), type(node)
        assert isinstance(node.published(), list)


def test_tree_behind_protocol_matches_flat_server_driver():
    """One driver function, two AggNode implementations, byte-for-byte the
    same outcome — the API-redesign headline."""
    spec = _spec(mtu=0)
    base, _, frames = _fleet(spec, 12)

    def drive(node):
        for fs in frames:
            for f in fs:
                node.ingest_frame(f)
        node.tick()
        node.seal()
        for _ in range(16):
            node.tick()
            if node.published():
                return node.published()[0]
        raise AssertionError("no publish")

    _assert_parity(drive(AggTree(spec, base, fanout=4)),
                   drive(AggServer(spec, base)))


# ---------------------------------------------------------------------------
# 8-device subprocess parity: tree mean == shard_map star-collective mean
# ---------------------------------------------------------------------------

def _run_8dev(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_tree_mean_bit_identical_to_star_8dev():
    """ISSUE 7 acceptance: the 2-tier tree's published mean over 8 clients
    equals the 8-device allgather_allreduce_mean star bitwise — tiers sum
    packed words without decoding, the root issues the batched decode."""
    out = _run_8dev("""
        from functools import partial
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import (QSyncConfig,
            allgather_allreduce_mean, flat_size_padded)
        from repro.agg import rounds
        from repro.agg.transport import frame as wire
        from repro.agg.client import AggClient
        from repro.agg.tree import AggTree
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n, bucket = 8192, 1024
        cfg = QSyncConfig(q=16, bucket=bucket)
        spec = wire.RoundSpec(round_id=11, d=n, cfg=cfg, y0=2.0, seed=5)
        base = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 50.0
        xs = base + 0.05 * jax.random.normal(jax.random.PRNGKey(1), (8, n))
        nb = flat_size_padded(n, cfg) // bucket
        y_b = jnp.full((nb,), spec.y0)
        key = rounds.round_key(spec)
        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                 out_specs=P("data"), check_vma=False)
        def f(xl):
            out, _ = allgather_allreduce_mean(xl.reshape(-1), y_b, key,
                                              "data", cfg)
            return out.reshape(1, -1)
        star = np.asarray(jax.jit(f)(xs))
        assert np.all(star == star[0])
        tree = AggTree(spec, np.asarray(xs[3]), fanout=2, tiers=2)
        for i in np.random.RandomState(1).permutation(8):
            tree.ingest_frame(AggClient(spec, int(i),
                                        np.asarray(xs[i])).payload())
        tree.tick()
        tree.seal()
        for _ in range(8):
            tree.tick()
            if tree.published():
                break
        pr = tree.published()[0]
        assert pr.accepted == frozenset(range(8)), pr.accepted
        assert np.array_equal(pr.mean, star[0])
        print("TREE_STAR_PARITY_OK")
    """)
    assert "TREE_STAR_PARITY_OK" in out
