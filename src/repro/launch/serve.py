"""Serving driver: a thin client of the continuous-batching engine.

Default mode builds a ``ServeEngine`` (``repro.serving``), submits a
seeded batch of mixed-length requests across the request classes, runs
the autotune warmup pass over every decode/prefill bucket, drains the
queue, and prints per-class throughput + dispatch reports:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 8 --prompt-len 32 --gen 16 --slots 4 --mesh 1x1 \
      --policy autotune --class-policy bulk=analytic

``--legacy`` keeps the original fixed-batch prefill/decode demo (one
jit_prefill + token-by-token jit_serve over a rectangular batch).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.engine import (
    POLICY_SPEC_HELP,
    add_policy_argument,
    dispatch_report,
    health_report,
    policy_from_spec,
)
from repro.core.faults import add_chaos_argument, chaos_scope
from repro.distributed import named, param_specs
from repro.launch.common import (
    add_mesh_argument,
    enable_compile_cache,
    resolve_mesh_and_policy,
)
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import lm

DEFAULT_CLASSES = ("interactive", "bulk")


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--legacy", action="store_true",
                    help="fixed-batch prefill/decode demo (pre-engine path)")
    # engine mode
    ap.add_argument("--requests", type=int, default=8,
                    help="number of synthetic requests to submit")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV cache slots (max concurrent requests)")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache extent per slot (default: prompt-len + gen)")
    ap.add_argument("--budget-tokens", type=int, default=0,
                    help="max-tokens admission budget (default: slots * max-seq)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission queue bound (default: 8 * slots); "
                         "submits beyond it are rejected")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds; overdue requests "
                         "are evicted as DEADLINE_EXCEEDED")
    ap.add_argument("--classes", default=",".join(DEFAULT_CLASSES),
                    help="comma-separated request classes; requests are "
                         "assigned round-robin, each class compiles its own "
                         "steps")
    ap.add_argument("--len-step", type=int, default=0,
                    help="prefill bucket grid step (default: 16, raised to "
                         "the attention window); every bucket is compiled "
                         "at warmup")
    ap.add_argument("--class-policy", action="append", default=[],
                    metavar="CLS=SPEC",
                    help=f"per-class policy override, e.g. bulk=analytic; "
                         f"SPEC is {POLICY_SPEC_HELP}")
    # shared / legacy
    ap.add_argument("--batch", type=int, default=4, help="legacy batch size")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="prompt length (legacy: exact; engine: maximum)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    add_mesh_argument(ap)
    add_policy_argument(ap)
    add_chaos_argument(ap)
    return ap


def _class_policies(args, parser, distributed: bool):
    """One *fresh* policy instance per request class (stats must not mix
    across classes), honouring ``--class-policy CLS=SPEC`` overrides."""
    classes = [c.strip() for c in args.classes.split(",") if c.strip()]
    if not classes:
        parser.error(f"--classes needs at least one class, got {args.classes!r}")
    specs = {cls: args.policy for cls in classes}
    for entry in args.class_policy:
        cls, eq, spec = entry.partition("=")
        cls, spec = cls.strip(), spec.strip()
        if not eq or not cls or not spec:
            parser.error(
                f"malformed --class-policy {entry!r}; expected CLS=SPEC"
            )
        specs[cls] = spec
    try:
        return {
            cls: policy_from_spec(spec, distributed=distributed)
            for cls, spec in specs.items()
        }
    except ValueError as e:
        parser.error(str(e))


def _engine_main(args, parser):
    from repro.serving import QueueFullError, ServeEngine, default_buckets
    from repro.serving.engine import HOST_PHASES, WAIT_PHASES

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh, _ = resolve_mesh_and_policy(args, parser)
    policies = _class_policies(args, parser, distributed=mesh.size > 1)
    max_seq = args.max_seq or (args.prompt_len + args.gen)

    params = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
    with mesh:
        params = jax.device_put(params, named(mesh, param_specs(params, mesh)))

    bucket_spec = None
    if args.len_step:
        windows = [b.window for _, blocks in cfg.segments for b in blocks
                   if b.window is not None]
        bucket_spec = default_buckets(
            args.slots, max_seq, window=max(windows, default=0),
            len_step=args.len_step,
        )
    engine = ServeEngine(
        cfg, params, n_slots=args.slots, max_seq=max_seq,
        policies=policies, mesh=mesh, bucket_spec=bucket_spec,
        budget_tokens=args.budget_tokens or None,
        max_queue=args.max_queue or None,
    )
    t0 = time.perf_counter()
    warm = engine.warmup()
    t_warm = time.perf_counter() - t0
    print(f"[serve] warmup: {warm['shapes_traced']} bucketed shapes "
          f"({t_warm:.1f}s) — buckets batch={engine.buckets.decode_batches} "
          f"len_step={engine.buckets.len_step}")

    rng = np.random.RandomState(args.seed)
    classes = sorted(policies)
    for i in range(args.requests):
        p_len = int(rng.randint(1, args.prompt_len + 1))
        prompt = rng.randint(0, cfg.vocab, (p_len,)).astype(np.int32)
        try:
            engine.submit(prompt, max_new=args.gen,
                          cls=classes[i % len(classes)],
                          deadline_s=args.deadline_s)
        except QueueFullError:
            print(f"[serve] request {i} rejected: admission queue full "
                  f"(max_queue={engine.max_queue})")
    t0 = time.perf_counter()
    engine.run()
    t_run = time.perf_counter() - t0

    reqs = list(engine.requests.values())
    # as a client sees them: submit to first token, then token to token
    ttft = [r.token_times[0] - r.submit_time for r in reqs if r.token_times]
    itl = [b - a for r in reqs for a, b in zip(r.token_times, r.token_times[1:])]
    queued = [r.admit_time - r.submit_time for r in reqs if r.admit_time is not None]
    n_tok = sum(len(r.generated) for r in reqs)
    print(f"[serve] {args.requests} requests, {n_tok} tokens in "
          f"{t_run:.2f}s ({n_tok / max(t_run, 1e-9):.1f} tok/s)")
    for what, xs in (("time to first token", ttft), ("inter-token latency", itl),
                     ("queue wait", queued)):
        if xs:
            p50, p95 = np.percentile(xs, [50, 95]) * 1e3
            print(f"[serve] {what}: p50 {p50:.2f} ms, p95 {p95:.2f} ms")
    st = engine.stats()
    steps = max(st["steps"], 1)
    print(f"[serve] engine: {st['steps']} steps, {st['decode_steps']} decode steps, "
          f"{st['decoded_rows']} rows decoded, {st['prefills']} prefills of "
          f"{st['prompt_tokens']} prompt tokens padded to {st['prefill_tokens']}; "
          f"{st['overlapped_steps']} decode launches overlapped a fetch, "
          f"{st['discarded_rows']} tokens discarded")
    print("[serve] engine ms per step: "
          + ", ".join(f"{p} {st[p + '_s'] / steps * 1e3:.3f}"
                      for p in HOST_PHASES + WAIT_PHASES)
          + f"; host {st['host_s'] / steps * 1e3:.3f} of {st['step_s'] / steps * 1e3:.3f}")
    misses = engine.cold_misses()
    print(f"[serve] post-warmup cold-miss measurements: {misses}")
    health = engine.health()
    print(f"[serve] health: finished={health.get('finished', 0)} "
          f"deadline_exceeded={health.get('deadline_exceeded', 0)} "
          f"evicted={health.get('evicted', 0)} "
          f"crashed_steps={health['crashed_steps']} "
          f"rejected_submits={health['rejected_submits']}")
    for cls, report in sorted(engine.class_reports().items()):
        print(f"[serve] class {cls!r}:")
        print(report)
    print(health_report())
    return engine


def _legacy_main(args, parser):
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh, policy = resolve_mesh_and_policy(args, parser)

    max_seq = args.prompt_len + args.gen
    rng = np.random.RandomState(args.seed)
    B = args.batch

    params = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
    p_specs = param_specs(params, mesh)
    with mesh:
        params = jax.device_put(params, named(mesh, p_specs))

    if cfg.input_mode == "frames":
        prompt = {"frames": jnp.asarray(
            rng.randn(B, args.prompt_len, cfg.d_model).astype(np.float32) * 0.02
        )}
    else:
        prompt = {"tokens": jnp.asarray(
            rng.randint(0, cfg.vocab, (B, args.prompt_len)), jnp.int32
        )}

    prefill = make_prefill_step(cfg, max_seq=max_seq, policy=policy)
    serve = make_serve_step(cfg, policy=policy)
    with mesh:
        jit_prefill = jax.jit(prefill)
        jit_serve = jax.jit(serve, donate_argnums=(1,))  # in-place cache
        t0 = time.perf_counter()
        logits, cache = jit_prefill(params, prompt)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        outs = []
        tok = jnp.argmax(logits[:, -1, : cfg.vocab], axis=-1)[:, None]
        t0 = time.perf_counter()
        for i in range(args.gen):
            outs.append(np.asarray(tok))
            if cfg.input_mode == "frames":
                step_in = {"frames": jnp.zeros((B, 1, cfg.d_model), jnp.float32)}
            else:
                step_in = {"tokens": tok.astype(jnp.int32)}
            logits, cache = jit_serve(params, cache, step_in)
            tok = jnp.argmax(logits[:, -1, : cfg.vocab], axis=-1)[:, None]
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0

    gen = np.concatenate(outs, axis=1)
    print(f"[serve] prefill {args.prompt_len} tok x {B}: {t_prefill*1e3:.1f} ms")
    print(
        f"[serve] decode {args.gen} steps: {t_decode*1e3:.1f} ms "
        f"({t_decode/args.gen*1e3:.2f} ms/tok)"
    )
    print("[serve] sample generations:", gen[:2, :8].tolist())
    print(dispatch_report(policy))
    print(health_report())
    return gen


def main(argv=None):
    """Run the driver; a run in which any engine step crashed exits
    non-zero (``SystemExit``) after its reports are printed."""
    parser = _build_parser()
    args = parser.parse_args(argv)
    with chaos_scope(args.chaos):
        if args.legacy:
            return _legacy_main(args, parser)
        engine = _engine_main(args, parser)
    if engine.crashed_steps:
        raise SystemExit(
            f"[serve] {engine.crashed_steps} engine step(s) crashed; their "
            "requests were evicted"
        )
    return engine


if __name__ == "__main__":
    enable_compile_cache()
    main()
