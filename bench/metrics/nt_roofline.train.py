"""The step's NT products against their roofline: the least time the chip
needs for the NT products one step requires (each FCN layer's ``y = x
W^T``; each LM layer's seven dense projections and the tied head; each at
the larger of its operations over the bf16 peak and its bytes over HBM
bandwidth), over the device time per step of every op under an ``NT``
dispatch scope, the arm's transposes and copies included.  Remat's
recomputed forward products count in the time, not in the need.  None
where no op carries an NT scope.  Its first reading also writes the
traced step's device time per dispatch scope, and the host's idle time
per innermost span, into the run's notes."""

from bench import counts, scopes, spans


def read(run):
    k = run.counters
    red = scopes.reduce_train(run)
    if red is None or run.peaks is None:
        return None
    if "idle_notes" not in k:
        k["idle_notes"] = True
        run.notes.extend(spans.notes(run.trace_data, "train", k["step_module"]))
    nt_s = sum(v for s, v in red["times"].items() if s is not None and s.op == "NT")
    if nt_s <= 0:
        return None
    need_s = counts.roofline_s(scopes.required_nt(run.config, run.workload),
                                      run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s * red["steps"] / nt_s
