"""Mean rows decoded per decode step, over the engine's slots."""


def read(run):
    k = run.counters
    if k.get("kind") != "serve_closed" or not k.get("decode_steps"):
        return None
    return 100.0 * k["rows"] / (k["decode_steps"] * k["slots"])
