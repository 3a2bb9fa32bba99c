"""Model FLOP utilization of the training step: model FLOPs per token
(``bench.counts.granite_flops_per_token``, forward and backward, no
recomputation) times the tokens the traced window completed, over the
window's time, the chips and their bf16 peak."""


def read(view):
    run = view.run
    if run.get("kind") != "train" or not run.get("tokens"):
        return None
    rate = run["flops_per_token"] * run["tokens"] / run["window_s"]
    return 100.0 * rate / (view.chips * view.peaks["bf16_flops"])
