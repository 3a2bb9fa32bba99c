"""Roofline share of the held experts' grouped matmuls in a Moonlight
training cell: the least time of their work (``gmm_flops`` a step, from
``bench.counts_moonlight`` and the step's loads, times the traced window's
steps, at the chip's bf16 peak; the products are compute-bound) over the
device time of the ``expert_gmm``/``expert_tgmm`` Pallas kernels
(``kernels/grouped_matmul.py``).  The forward products the layer's
rematerialisation recomputes count in the time and not in the work."""


def read(view):
    run = view.run
    if run.get("kind") != "train" or not run.get("gmm_flops"):
        return None
    ns = view.trace.kernel_ns(r"^expert_t?gmm")
    if ns <= 0:
        return None
    least_s = run["gmm_flops"] * run["steps"] / view.peaks["bf16_flops"]
    return 100.0 * least_s / (ns / 1e9)
