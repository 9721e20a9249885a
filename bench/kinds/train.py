"""A training cell: set-up builds the compiled step with its state and
drives it through its first three steps, which the plain reference then
follows, and then through the cell's ``warm_steps``, so that the chip has
left its first, slower steps behind; the window runs further steps through
the same call.

``train_step_ms`` is the window's time over the steps completed in it: the
host keeps one step queued behind the running one, and the last step is
waited for."""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from bench import checks, counts, harness, trace
from bench.numerics import Numerics

CHECKED_STEPS = 3


def _drive(prog, first: int, seconds: float):
    """Steps ``first, first+1, ...`` until ``seconds`` have passed; returns
    (steps, seconds, losses)."""
    losses, prev = [], None
    t0 = time.perf_counter()
    i = first
    while True:
        with harness.span("train.step"):
            loss = prog.step(i)
        i += 1
        if prev is not None:
            with harness.span("train.wait"):
                prev.block_until_ready()
        losses.append(loss)
        prev = loss
        if time.perf_counter() - t0 >= seconds:
            break
    prev.block_until_ready()
    return i - first, time.perf_counter() - t0, losses


def run(run: harness.Run) -> None:
    c, w = run.config, run.workload
    fam = harness.family(c)
    prog = fam.TrainProgram(c, w, run.seed, fault=run.fault)

    first = []
    for i in range(CHECKED_STEPS):
        with harness.span("train.step"):
            first.append(prog.step(i))
        if i == 0:
            g1 = prog.first_grad_norms()
    change = prog.change_norms()
    first = [float(x) for x in first]
    g1, change = np.asarray(g1), np.asarray(change)
    start = CHECKED_STEPS + w["warm_steps"]
    warm = []
    for i in range(CHECKED_STEPS, start):
        with harness.span("train.step"):
            warm.append(prog.step(i))
    jax.block_until_ready(warm)

    run.mark_window_start()
    steps, window_s, losses = _drive(prog, start, run.seconds)
    run.mark_window_end()
    run.attempted = steps
    run.failed = sum(1 for x in losses if not math.isfinite(float(x)))
    run.end_to_end["train_step_ms"] = window_s / steps * 1e3

    gemms = fam.train_gemms(c, w)
    run.counters.update(
        kind="train", window_s=window_s, steps=steps,
        flops_per_step=counts.total_flops(gemms),
    )
    if run.peaks is not None:
        run.counters["gemm_roofline_s_per_step"] = counts.roofline_s(
            gemms, run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])

    if run.trace:
        log_dir = f"{run.out_dir}/trace"
        with harness.traced(log_dir):
            _drive(prog, start + steps, w["trace_seconds"])
        run.trace_data = trace.load(trace.find_xplane(log_dir), chips=range(len(run.devices)))
        hlo = prog.hlo_text()
        run.counters["step_module"] = hlo.split(None, 2)[1].rstrip(",")
        run.counters["matmul_ops"] = trace.matmul_instructions(hlo)

    run.read_memory_peak()
    ref_params, batches = prog.reference_inputs()
    prog.free()
    del prog
    ref = fam.reference_train(c, w, ref_params, batches, Numerics("f32"), CHECKED_STEPS)
    run.counters.update(reference=ref, first_losses=first)
    for name, value in readings((first, g1, change), ref, w["loss_steps"]).items():
        run.check(name, value, w["limits"][name])
    jax.clear_caches()


def readings(got, ref, loss_steps: int) -> dict:
    """The numbers compared: the worst relative loss gap of the first
    ``loss_steps`` steps, and the worst leaf's gap of the first gradient's
    norm and of the change's norm (leaves whose reference gradient is
    nought to rounding left out of the change)."""
    (losses, g1, change), (ref_losses, ref_g1, ref_change) = got, ref
    return {
        "loss_gap": max(checks.rel_gap(a, b) for a, b in
                        zip(losses[:loss_steps], ref_losses[:loss_steps])),
        "grad_gap": checks.worst_leaf_gap(g1, ref_g1),
        "change_gap": checks.worst_leaf_gap(change, ref_change,
                                            checks.moving_leaves(ref_g1)),
    }
