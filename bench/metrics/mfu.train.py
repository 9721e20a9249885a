"""Model FLOP/s utilisation of a training window: the operations one step
requires (forward and backward, causal attention at half, recomputation
not counted) times steps per second, over the chips' bf16 peak."""


def read(run):
    k = run.counters
    if k.get("kind") != "train" or run.peaks is None:
        return None
    rate = k["flops_per_step"] * k["steps"] / k["window_s"]
    return 100.0 * rate / (run.peaks["bf16_flops_per_s"] * len(run.devices))
