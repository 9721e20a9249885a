"""Model FLOP/s utilisation of a closed-loop serving window: the
operations the window's work requires (each prompt's prefill at its true
length with causal attention at half and the head at its last position
only; each decoded token over its row's live length), per second, over
the chips' bf16 peak."""


def read(run):
    k = run.counters
    if k.get("kind") != "serve_closed" or run.peaks is None:
        return None
    rate = k["flops"] / k["window_s"]
    return 100.0 * rate / (run.peaks["bf16_flops_per_s"] * len(run.devices))
