"""The serving engine's host time per engine step, over the traced
closed-loop window: each benchmark ``engine.step`` span (around
``ServeEngine.step``) less the engine's own ``engine.prefill_wait`` and
``engine.decode_wait`` spans inside it, the time the host sat blocked on
the chip (copying the tokens to the host, ``engine.fetch``, is host
time).  None where the engine emits no wait span.  Its first reading
also writes the engine's phases per step, the host-to-device clock
offset and the chip's idle time inside ``engine.step`` per innermost
span into the run's notes."""

from bench import spans

STEP = "engine.step"
WAITS = ("engine.prefill_wait", "engine.decode_wait")
PHASES = ("engine.expire", "engine.admit", "engine.prefill", "engine.prefill_wait",
          "engine.decode", "engine.decode_wait", "engine.fetch", "engine.retire")


def read(run):
    k, t = run.counters, run.trace_data
    if k.get("kind") != "serve_closed" or t is None:
        return None
    lo, hi = t.window
    steps = [(s, e) for n, s, e in t.host_spans if n == STEP and s >= lo and e <= hi]
    inner = [(n, s, e) for n, s, e in t.host_spans if n in PHASES]
    if not steps or not any(n in WAITS for n, _, _ in inner):
        return None
    per_phase = dict.fromkeys(PHASES, 0.0)
    host = 0.0
    for s0, e0 in steps:
        waits = 0.0
        for n, s, e in inner:
            if s >= s0 and e <= e0:
                per_phase[n] += e - s
                if n in WAITS:
                    waits += e - s
        host += (e0 - s0) - waits
    if "idle_notes" not in k:
        k["idle_notes"] = True
        n = len(steps)
        run.notes.append(f"engine spans per {STEP} over {n} traced steps "
                         f"(ms, nested spans counted in their parents too): "
                         + ", ".join(f"{p} {v / n * 1e-6:.3f}" for p, v in per_phase.items())
                         + f", host {host / n * 1e-6:.3f}")
        run.notes.extend(spans.notes(t, k["kind"], within=STEP))
    return host / len(steps) * 1e-6
