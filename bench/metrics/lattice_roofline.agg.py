"""Roofline share of the lattice decode kernels in an aggregation cell.

The least time of the round's logical work (the bytes of decoding every
payload and summing its coordinates, ``bench.counts.agg_decode_bytes``, at
the chip's HBM bandwidth; the decode does a few integer operations a byte,
so memory bounds it) over the device time of the ``lattice_*`` Pallas
kernels in the traced window."""


def read(view):
    if view.run.get("kind") != "agg":
        return None
    ns = view.trace.kernel_ns(r"^lattice_(encode|decode)")
    if ns <= 0:
        return None
    least_s = (view.run["decode_bytes_per_round"] * view.run["rounds"]
               / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)
