"""The decode program against its roofline: the bytes a decode step must
move (every weight once, the cached K/V of each active row, the new
token's K/V) over HBM bandwidth, per step, over the device time of one
execution of the engine's decode program, both over the traced window."""

DECODE_MODULE = "jit_decode_step"


def read(run):
    k, t = run.counters, run.trace_data
    if k.get("kind") != "serve_closed" or t is None or run.peaks is None:
        return None
    runs = t.module_runs(DECODE_MODULE)
    if not runs or not k.get("traced_decode_steps"):
        return None
    device_s = sum(e - s for s, e in runs) * 1e-9 / len(runs)
    need_s = k["traced_decode_bytes"] / k["traced_decode_steps"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / device_s
