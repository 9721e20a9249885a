#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU, at full published widths.

    python chip_smoke.py               # one chip: the six phases below
    python chip_smoke.py --chips 4     # four chips: smollm-135m training on
                                       # a 2x2 mesh against a 1x1 mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

Phases (one process; weights are random, made from seeds):

  1. device     the first device is a TPU and Pallas kernels compile
                (``should_interpret()`` is False)
  2. fcn        the paper's synthetic FCN (26752-4096-4096-26752) trains 3
                steps at batch 1024 under the default MTNN ModelPolicy and
                under FixedPolicy("XLA_NT"); losses finite and equal per step
  3. lm-train   smollm-135m (30 layers, d_model 576, vocab 49152, bf16)
                trains 3 steps at seq 2048 through ``repro.launch.train``
                under the default policy, the all-XLA reference and a policy
                forcing the fused attention kernel; step-0 losses agree and
                sit near ln(vocab); the fused run's program holds the kernel
  4. serve      ``repro.launch.serve`` serves 8 seeded requests (prompts up
                to 512 tokens, 32 new tokens, 8 slots); every request
                finishes whole, no step crashes, nothing is evicted or
                rejected; one prompt's first-token logits match the
                all-XLA reference
  5. autotune   the FCN trains 2 steps at batch 128 under
                ``autotune:<checkout>/.smoke/autotune.json``; every key is
                measured on this device and none falls back
  6. health     the quarantine ledger and the fallback counts are empty

Each phase prints its wall and compile seconds and its result.  Any failure
exits non-zero.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
without a TPU the script exits non-zero and prints no such line (``--tiny``
runs the phases at toy sizes on any backend as a rehearsal, and never
reports a result).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Tolerances.  FCN runs in f32 (XLA's default TPU matmul precision); the
# two policies differ only in which XLA formulation computes each GEMM.
# On a v5e the two runs agreed exactly.
FCN_LOSS_ATOL = 1e-3  # per step, loss ~ ln(26752) = 10.2
# smollm-135m computes in bf16: the policies differ in GEMM formulation
# and, for the fused run, in where the softmax is rounded to bf16 (8.2e-5
# apart at step 0 on a v5e); meshes differ in reduction order.
LM_LOSS_ATOL = 5e-3  # loss ~ ln(49152) = 10.8
LM_LOSS_NEAR_LN_VOCAB = 1.0  # |step-0 loss - ln(vocab)| at random init
LOGIT_RTOL = 1e-2  # max |logit diff| / max |reference logit|

XLA_REFERENCE = "fixed:nt=XLA_NT,nn=XLA_NN,tn=XLA_TN,bnt=XLA_BNT,bnn=XLA_BNN,attn=unfused"
FUSED_ATTN_FORCED = "fixed:nt=XLA_NT,nn=XLA_NN,tn=XLA_TN,bnt=XLA_BNT,bnn=XLA_BNN,attn=fused"

# Warnings that mean a silent degradation happened somewhere.
DEGRADATION_MARKERS = (
    "quarantined",
    "not timed",
    "no usable measurement",
    "crashed",
    "reference instead",
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    fcn_batch: int
    lm_args: tuple  # size arguments for repro.launch.train
    serve_args: tuple  # size arguments for repro.launch.serve
    autotune_batch: int
    tiny: bool


FULL = Sizes(
    fcn_batch=1024,
    lm_args=("--batch", "8", "--seq", "2048"),
    serve_args=(
        "--requests", "8", "--prompt-len", "512", "--gen", "32",
        "--slots", "8", "--max-seq", "768", "--len-step", "256",
    ),
    autotune_batch=128,
    tiny=False,
)
TINY = Sizes(
    fcn_batch=32,
    lm_args=("--smoke", "--batch", "4", "--seq", "64"),
    serve_args=(
        "--smoke", "--requests", "4", "--prompt-len", "24", "--gen", "4",
        "--slots", "4", "--max-seq", "32", "--len-step", "16",
    ),
    autotune_batch=16,
    tiny=True,
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- compile-time accounting --------------------------------------------------

_COMPILE_S = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def run_phase(name: str, fn, failures: list):
    """Run one phase; print its line; record a failure instead of raising."""
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            result = fn()
            bad = [
                str(w.message) for w in caught
                if any(m in str(w.message) for m in DEGRADATION_MARKERS)
            ]
            check(not bad, f"degradation warnings: {bad}")
            status = "PASS"
        except (Exception, SystemExit) as e:  # every failure is reported
            traceback.print_exc()
            result = f"{type(e).__name__}: {e}"
            failures.append(name)
            status = "FAIL"
    for w in caught:
        print(f"[{name}] warning: {w.category.__name__}: {w.message}")
    print(
        f"[phase] {name}: {status} wall {time.perf_counter() - t0:.1f}s "
        f"compile {_COMPILE_S[0] - c0:.1f}s — {result}",
        flush=True,
    )


# -- phases -------------------------------------------------------------------


def phase_device(sizes: Sizes):
    import importlib.metadata

    import jax

    from repro.kernels import should_interpret

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    interp = should_interpret()
    if not sizes.tiny:
        check(dev.platform == "tpu", f"first device is {dev.platform}, not tpu")
        check(not interp, "Pallas would run in interpret mode on this device")
    return (
        f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())} jax={jax.__version__} libtpu={libtpu} "
        f"should_interpret={interp}"
    )


def _fcn_config(sizes: Sizes):
    from repro.configs.fcn_paper import SYNTHETIC_FCNS
    from repro.models.fcn import FCNConfig

    if sizes.tiny:
        return FCNConfig("fcn-tiny", 256, 192, (128, 128))
    return SYNTHETIC_FCNS[2]


def _train_fcn(cfg, policy, batch: int, steps: int):
    """The FCN driver's step (``launch.steps.make_fcn_train_step``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import make_fcn_batch
    from repro.launch.steps import make_fcn_train_step
    from repro.models.fcn import init_fcn
    from repro.optim import adamw_init, warmup_cosine

    params = init_fcn(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    step_fn = jax.jit(
        make_fcn_train_step(policy, warmup_cosine(1e-3, 20, steps)),
        donate_argnums=(0, 1),
    )
    rng = np.random.RandomState(0)
    w_true = rng.randn(cfg.input_dim, 8).astype(np.float32)
    losses = []
    for step in range(steps):
        b = make_fcn_batch(rng, cfg, batch, w_true)
        params, opt, loss, _ = step_fn(params, opt, jnp.asarray(step), b)
        losses.append(float(loss))
    return losses


def phase_fcn(sizes: Sizes):
    from repro import core

    cfg = _fcn_config(sizes)
    runs = {}
    for label, policy in (
        ("model", core.ModelPolicy()),
        ("XLA_NT", core.FixedPolicy("XLA_NT")),
    ):
        runs[label] = _train_fcn(cfg, policy, sizes.fcn_batch, 3)
        print(f"[fcn] {cfg.name} dims {cfg.dims} batch {sizes.fcn_batch} "
              f"policy {label}: losses {runs[label]}")
        print(core.dispatch_report(policy))
    a, b = runs["model"], runs["XLA_NT"]
    check(all(map(math.isfinite, a + b)), f"non-finite FCN loss: {a} {b}")
    diffs = [abs(x - y) for x, y in zip(a, b)]
    check(max(diffs) <= FCN_LOSS_ATOL,
          f"FCN losses differ by {diffs} > {FCN_LOSS_ATOL}")
    return f"losses model={a} XLA_NT={b} max|diff|={max(diffs):.3g}"


def phase_lm_train(sizes: Sizes):
    import jax

    from repro.configs import get_config
    from repro.launch import train

    vocab = get_config("smollm-135m").vocab
    losses = {}
    fused_in_program = None
    for label, spec in (
        ("model", "model"),
        ("xla", XLA_REFERENCE),
        ("fused", FUSED_ATTN_FORCED),
    ):
        res = train.main([
            "--arch", "smollm-135m", *sizes.lm_args, "--steps", "3",
            "--mesh", "1x1", "--log-every", "1", "--policy", spec,
        ])
        losses[label] = res.losses
        print(f"[lm-train] policy {label}: losses {res.losses} "
              f"(compile {res.compile_s:.1f}s)")
        if label == "fused":
            attn = res.policy.stats.by_op.get("ATTN", {})
            check(any(k.startswith("FUSED_ATTN") for k in attn),
                  f"fused run dispatched no FUSED_ATTN: {attn}")
            fused_in_program = "tpu_custom_call" in res.compiled.as_text()
            if jax.default_backend() == "tpu":
                check(fused_in_program,
                      "fused run's compiled step holds no tpu_custom_call")
        del res
    flat = [x for v in losses.values() for x in v]
    check(all(map(math.isfinite, flat)), f"non-finite LM loss: {losses}")
    ref0 = losses["xla"][0]
    d_model = abs(losses["model"][0] - ref0)
    d_fused = abs(losses["fused"][0] - ref0)
    check(d_model <= LM_LOSS_ATOL and d_fused <= LM_LOSS_ATOL,
          f"step-0 loss diffs model={d_model:.3g} fused={d_fused:.3g} "
          f"> {LM_LOSS_ATOL}")
    if not sizes.tiny:
        check(abs(ref0 - math.log(vocab)) <= LM_LOSS_NEAR_LN_VOCAB,
              f"step-0 loss {ref0} not near ln({vocab})={math.log(vocab):.2f}")
    return (
        f"step-0 loss xla={ref0:.5f} |model-xla|={d_model:.3g} "
        f"|fused-xla|={d_fused:.3g} ln(vocab)={math.log(vocab):.3f} "
        f"tpu_custom_call={fused_in_program} losses={losses}"
    )


def phase_serve(sizes: Sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import core
    from repro.launch import serve
    from repro.launch.steps import make_prefill_step
    from repro.serving import RequestState

    gen = int(sizes.serve_args[sizes.serve_args.index("--gen") + 1])
    engine = serve.main([
        "--arch", "smollm-135m", *sizes.serve_args, "--mesh", "1x1",
        "--policy", "model", "--classes", "interactive",
    ])
    reqs = list(engine.requests.values())
    health = engine.health()
    short = [
        (r.rid, r.state.value, len(r.generated)) for r in reqs
        if r.state is not RequestState.FINISHED or len(r.generated) != gen
    ]
    check(not short, f"requests not finished whole: {short}")
    for counter in ("crashed_steps", "evicted", "deadline_exceeded",
                    "rejected_submits"):
        check(health[counter] == 0, f"{counter}={health[counter]}")

    # first-token logits of one prompt: served policy vs all-XLA reference
    cfg, req = engine.cfg, reqs[0]
    tokens = jnp.asarray(req.tokens[None, :], jnp.int32)
    logits = {}
    for label, policy in (
        ("served", engine.policies["interactive"]),
        ("xla", core.policy_from_spec(XLA_REFERENCE)),
    ):
        step = jax.jit(make_prefill_step(cfg, engine.max_seq, policy))
        out, _ = step(engine.params, {"tokens": tokens})
        logits[label] = np.asarray(out[0, -1, : cfg.vocab], np.float32)
    ref = logits["xla"]
    rel = float(np.max(np.abs(logits["served"] - ref)) / np.max(np.abs(ref)))
    check(np.all(np.isfinite(logits["served"])), "non-finite served logits")
    check(rel <= LOGIT_RTOL, f"first-token logits differ: rel {rel:.3g}")
    same_top = int(np.argmax(logits["served"])) == int(np.argmax(ref))
    return (
        f"{len(reqs)} requests x {gen} tokens finished; health {health}; "
        f"prompt {req.prompt_len} tokens first-token logits rel diff "
        f"{rel:.3g} (top-1 equal: {same_top})"
    )


def phase_autotune(sizes: Sizes):
    import jax

    from repro import core
    from repro.core.measure import best_times

    path = os.path.join(ROOT, ".smoke", "autotune.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path):
        os.remove(path)  # measure afresh on this device
    policy = core.policy_from_spec(f"autotune:{path}")
    cfg = _fcn_config(sizes)
    losses = _train_fcn(cfg, policy, sizes.autotune_batch, 2)
    print(core.dispatch_report(policy))
    check(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    check(policy.n_measured > 0, "autotune measured nothing")
    check(policy.n_fallbacks == 0, f"{policy.n_fallbacks} analytic fallbacks")
    here = (jax.default_backend(), core.device_spec().name)
    records = list(policy.cache.records())
    for key, times in records:
        check(tuple(key[:2]) == here, f"record {key} not measured on {here}")
        name, (ck, t) = min(best_times(times).items(), key=lambda kv: kv[1][1])
        print(f"[autotune] {key[3]} g={key[4]} {key[5]}x{key[6]}x{key[7]} "
              f"{key[2]}: {name}@{ck} {t * 1e3:.4f} ms "
              f"({sum(len(c) for c in times.values())} arms timed)")
    return (
        f"losses {losses}; n_measured={policy.n_measured} "
        f"n_fallbacks={policy.n_fallbacks} {len(records)} keys on {here}"
    )


def phase_health(sizes: Sizes):
    from repro import core
    from repro.core import faults

    print(core.health_report())
    quarantined = faults.quarantine_entries()
    fallbacks = faults.fallback_counts()
    check(not quarantined, f"quarantined arms: {[e.label() for e in quarantined]}")
    check(not fallbacks, f"fallbacks taken: {dict(fallbacks)}")
    return "quarantine ledger empty, no fallbacks"


def phase_mesh(sizes: Sizes):
    """smollm-135m: 3 steps on a 2x2 mesh, then on 1x1, same global batch."""
    import jax

    from repro.launch import train

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    losses = {}
    for mesh in ("2x2", "1x1"):
        res = train.main([
            "--arch", "smollm-135m", *sizes.lm_args, "--steps", "3",
            "--mesh", mesh, "--log-every", "1", "--policy", "model",
        ])
        losses[mesh] = res.losses
        if mesh == "2x2":
            held = {d: 0 for d in jax.devices()}
            for leaf in jax.tree.leaves(res.state):
                for shard in leaf.addressable_shards:
                    held[shard.device] += shard.data.nbytes
            for d, n in held.items():
                stats = d.memory_stats() or {}
                print(f"[mesh] device {d.id}: train state {n / 2**20:.1f} MiB, "
                      f"bytes_in_use {stats.get('bytes_in_use', 'n/a')}")
            spread = min(held.values()) / max(held.values())
            check(spread > 0.5, f"train state piled up: {held}")
        del res
    flat = losses["2x2"] + losses["1x1"]
    check(all(map(math.isfinite, flat)), f"non-finite loss: {losses}")
    diffs = [abs(a - b) for a, b in zip(losses["2x2"], losses["1x1"])]
    check(max(diffs) <= LM_LOSS_ATOL, f"mesh losses differ by {diffs}")
    return f"losses {losses} max|diff|={max(diffs):.3g} spread={spread:.2f}"


# -- main -----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-vs-1x1 mesh training check")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any backend; a rehearsal, never a result")
    args = ap.parse_args(argv)
    sizes = TINY if args.tiny else FULL

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    from repro.launch.common import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: first device is {dev.platform}, not a TPU",
              file=sys.stderr)
        return 2
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    failures: list = []
    run_phase("device", lambda: phase_device(sizes), failures)
    if failures:
        return 1
    if args.chips == 4:
        phases = (("mesh", phase_mesh),)
    else:
        phases = (
            ("fcn", phase_fcn),
            ("lm-train", phase_lm_train),
            ("serve", phase_serve),
            ("autotune", phase_autotune),
            ("health", phase_health),
        )
    for name, fn in phases:
        run_phase(name, lambda fn=fn: fn(sizes), failures)
    if failures:
        print(f"[smoke] FAILED phases: {failures}")
        return 1
    if args.tiny or dev.platform != "tpu":
        print("[smoke] rehearsal passed; not a chip run, no result reported")
        return 3
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
