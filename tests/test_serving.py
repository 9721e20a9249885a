"""Continuous-batching serving engine: bucket math, paged KV slot
lifecycle, admit/evict mid-stream with slot reuse, ragged-length decode
equivalence against the unbatched reference, warmup covering every
bucketed OpKey (zero post-warmup autotune measurements), per-request
deadlines + bounded-queue backpressure, and the shared launcher
mesh-spec parsing."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.arch import ArchConfig, BlockCfg
from repro.core.policy import AutotunePolicy, FixedPolicy
from repro.kernels import attention_decode
from repro.launch.common import parse_mesh, resolve_mesh_and_policy
from repro.models import lm
from repro.serving import (
    BucketSpec,
    PagedKVCache,
    QueueFullError,
    RequestState,
    ServeEngine,
    default_buckets,
)
from repro.serving.engine import HOST_PHASES

TINY = ArchConfig(
    name="tiny-serve",
    family="dense",
    d_model=32,
    n_heads=2,
    n_kv=2,
    d_head=16,
    d_ff=64,
    vocab=64,
    segments=((2, (BlockCfg("attn", "mlp"),)),),
    param_dtype="float32",
    compute_dtype="float32",
    attn_chunk=16,
    remat="none",
)
TINY_WINDOWED = TINY.replace(
    name="tiny-serve-windowed",
    segments=((2, (BlockCfg("attn", "mlp", window=8),)),),
)


@pytest.fixture(scope="module")
def tiny_params():
    return lm.init_lm(jax.random.PRNGKey(0), TINY)


def make_engine(params, cfg=TINY, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(cfg, params, **kw)


def reference_generate(cfg, params, prompt, max_new, max_seq=32):
    """Unbatched greedy generation — the fixed-batch legacy semantics the
    engine's bucketed ragged batching must reproduce token-for-token."""
    logits, cache = lm.lm_prefill(
        params, cfg, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
        max_seq=max_seq, cache_dtype=jnp.float32,
    )
    toks = [int(jnp.argmax(logits[0, -1, : cfg.vocab]))]
    for _ in range(max_new - 1):
        step = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = lm.lm_decode(params, cfg, cache, {"tokens": step})
        toks.append(int(jnp.argmax(logits[0, -1, : cfg.vocab])))
    return toks


def mixed_prompts(lens, vocab=TINY.vocab, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


# -- bucket math --------------------------------------------------------------


class TestBucketSpec:
    def test_bucket_batch_rounds_up(self):
        spec = BucketSpec(batch_buckets=(1, 2, 4, 8), len_step=16,
                          max_prompt_len=64)
        assert [spec.bucket_batch(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]

    def test_bucket_batch_rejects_oversize(self):
        spec = BucketSpec(batch_buckets=(1, 2), len_step=16, max_prompt_len=64)
        with pytest.raises(ValueError):
            spec.bucket_batch(3)

    def test_bucket_len_rounds_to_grid(self):
        spec = BucketSpec(batch_buckets=(1,), len_step=16, max_prompt_len=48)
        assert [spec.bucket_len(n) for n in (1, 16, 17, 48)] == [16, 16, 32, 48]
        with pytest.raises(ValueError):
            spec.bucket_len(49)

    def test_default_buckets_cover_slots(self):
        spec = default_buckets(6, 64)
        assert spec.batch_buckets[-1] == 6  # largest bucket fills the pool
        assert all(b <= 6 for b in spec.batch_buckets)

    def test_default_buckets_len_step_respects_window(self):
        spec = default_buckets(4, 64, window=24)
        assert spec.len_step % 24 == 0


# -- paged KV cache -----------------------------------------------------------


class TestPagedKVCache:
    def test_allocate_until_full_then_none(self):
        kv = PagedKVCache(TINY, n_slots=2, max_seq=16, dtype=jnp.float32)
        a, b = kv.allocate("r0"), kv.allocate("r1")
        assert {a, b} == {0, 1} and kv.n_free == 0
        assert kv.allocate("r2") is None

    def test_free_recycles_slot(self):
        kv = PagedKVCache(TINY, n_slots=2, max_seq=16, dtype=jnp.float32)
        a, b = kv.allocate("r0"), kv.allocate("r1")
        kv.lengths[a] = 7
        kv.free(a)
        assert kv.n_free == 1 and kv.lengths[a] == 0
        assert kv.allocate("r2") == a  # freed slot comes back
        with pytest.raises(KeyError):
            kv.free(kv.null_slot)  # never allocatable
        kv.free(b)
        assert kv.n_free == 1

    def test_null_slot_is_outside_the_pool(self):
        kv = PagedKVCache(TINY, n_slots=3, max_seq=16, dtype=jnp.float32)
        assert kv.null_slot == 3
        leaf = jax.tree.leaves(kv.data)[0]
        assert leaf.shape[1] == 4  # pool + scratch row on the sequence axis

    def test_insert_requires_allocation_and_records_length(self, tiny_params):
        kv = PagedKVCache(TINY, n_slots=2, max_seq=16, dtype=jnp.float32)
        _, cache = lm.lm_prefill(
            tiny_params, TINY, {"tokens": jnp.zeros((1, 4), jnp.int32)},
            max_seq=16, cache_dtype=jnp.float32,
        )
        token = jnp.asarray([9], jnp.int32)
        with pytest.raises(KeyError):
            kv.insert(cache, 0, 4, token)
        slot = kv.allocate("r0")
        kv.insert(cache, slot, 4, token)
        assert kv.lengths[slot] == 4
        # the first token lands in the slot's entry of the token vector
        assert np.asarray(kv.tokens).tolist() == [9 if s == slot else 0 for s in range(3)]
        kv.advance([slot])
        assert kv.lengths[slot] == 5


# -- the engine ---------------------------------------------------------------


class TestServeEngine:
    def test_ragged_batch_matches_unbatched_reference(self, tiny_params):
        """Mixed-length requests decoded together in one bucketed batch
        produce exactly the tokens each would produce alone."""
        engine = make_engine(tiny_params)
        prompts = mixed_prompts([3, 7, 5, 9])
        reqs = [
            engine.submit(p, max_new=6, cls=("interactive", "bulk")[i % 2])
            for i, p in enumerate(prompts)
        ]
        engine.run()
        for req, prompt in zip(reqs, prompts):
            assert req.state is RequestState.FINISHED
            expect = reference_generate(TINY, tiny_params, prompt, 6)
            assert req.generated == expect, f"rid={req.rid}"

    def test_windowed_arch_ragged_decode(self):
        """Same equivalence on a sliding-window arch: the per-slot ring
        write positions must agree with the bucketed (padded) prefill."""
        params = lm.init_lm(jax.random.PRNGKey(1), TINY_WINDOWED)
        engine = make_engine(params, cfg=TINY_WINDOWED)
        prompts = mixed_prompts([13, 6, 21])
        reqs = [engine.submit(p, max_new=5) for p in prompts]
        engine.run()
        for req, prompt in zip(reqs, prompts):
            expect = reference_generate(TINY_WINDOWED, params, prompt, 5)
            assert req.generated == expect, f"rid={req.rid}"

    @pytest.mark.parametrize("cfg", [TINY, TINY_WINDOWED], ids=["global", "windowed"])
    def test_decode_step_changes_only_the_rows_new_positions(self, cfg):
        """After one decode step the pool is bit-identical outside the
        batch's slots, and inside them outside each row's write position
        (``pos % window`` in a ring)."""
        params = lm.init_lm(jax.random.PRNGKey(5), cfg)
        engine = make_engine(params, cfg=cfg)
        keys = iter(jax.random.split(jax.random.PRNGKey(6), 16))
        pool = jax.tree.map(lambda leaf: jax.random.normal(next(keys), leaf.shape, leaf.dtype),
                            engine.kv.data)
        before = jax.tree.map(np.asarray, pool)
        null = engine.kv.null_slot
        slot_ids = np.array([2, 0, null, null], np.int32)
        lengths = np.array([5, 17, 0, 0], np.int32)
        _, after = engine._decode_steps["interactive"](
            params, pool, engine.kv.tokens, jnp.asarray(slot_ids),
            jnp.asarray(lengths))
        window = cfg.segments[0][1][0].window
        for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            new = np.asarray(new)  # (layers, slots+1, positions, lanes)
            for s in (1, 3):
                np.testing.assert_array_equal(new[:, s], old[:, s])
            for s, n in zip(slot_ids[:2], lengths[:2]):
                keep = np.arange(old.shape[2]) != (n % window if window else n)
                np.testing.assert_array_equal(new[:, s, keep], old[:, s, keep])
                assert not np.array_equal(new[:, s, ~keep], old[:, s, ~keep])

    def test_admit_evict_midstream_reuses_slot(self, tiny_params):
        """Evicting an active request mid-stream frees its slot for the
        queue head, and the survivors' outputs stay exact."""
        engine = make_engine(tiny_params, n_slots=2)
        prompts = mixed_prompts([4, 6, 5])
        r0, r1, r2 = [engine.submit(p, max_new=8) for p in prompts]
        engine.step()
        assert (r0.state, r1.state) == (RequestState.ACTIVE, RequestState.ACTIVE)
        assert r2.state is RequestState.QUEUED  # pool is full
        victim_slot = r0.slot
        engine.evict(r0.rid)
        assert r0.state is RequestState.EVICTED
        assert len(r0.generated) < 8  # stopped mid-stream
        engine.step()
        assert r2.state is RequestState.ACTIVE
        assert r2.slot == victim_slot  # evicted slot reused immediately
        engine.run()
        for req, prompt in ((r1, prompts[1]), (r2, prompts[2])):
            expect = reference_generate(TINY, tiny_params, prompt, 8)
            assert req.generated == expect, f"rid={req.rid}"

    def test_evict_queued_request_leaves_queue(self, tiny_params):
        engine = make_engine(tiny_params, n_slots=1)
        r0 = engine.submit(mixed_prompts([4])[0], max_new=4)
        r1 = engine.submit(mixed_prompts([4])[0], max_new=4)
        engine.step()
        engine.evict(r1.rid)
        assert r1.state is RequestState.EVICTED and not engine.queue
        engine.run()
        assert r0.state is RequestState.FINISHED

    def test_fcfs_budget_blocks_head_of_line(self, tiny_params):
        """Strict FCFS under the max-tokens budget: the head waits for
        capacity, later arrivals never skip ahead of it."""
        engine = make_engine(tiny_params, n_slots=4, budget_tokens=14)
        r0 = engine.submit(mixed_prompts([6])[0], max_new=4)  # reserve 10
        r1 = engine.submit(mixed_prompts([5])[0], max_new=4)  # reserve 9
        r2 = engine.submit(mixed_prompts([2])[0], max_new=2)  # reserve 4: fits!
        engine.step()
        assert r0.state is RequestState.ACTIVE
        # r1 doesn't fit next to r0 — and r2, which would fit, must not
        # skip ahead of it
        assert r1.state is RequestState.QUEUED
        assert r2.state is RequestState.QUEUED
        engine.run()
        assert r1.admit_step >= r0.finish_step
        assert r2.admit_step >= r1.admit_step
        for r in (r0, r1, r2):
            assert r.state is RequestState.FINISHED

    def test_submit_validation(self, tiny_params):
        engine = make_engine(tiny_params)
        with pytest.raises(KeyError):
            engine.submit(np.zeros(4, np.int32), max_new=2, cls="nope")
        with pytest.raises(ValueError):
            engine.submit(np.zeros(0, np.int32), max_new=2)
        with pytest.raises(ValueError):
            engine.submit(np.zeros(4, np.int32), max_new=0)
        with pytest.raises(ValueError):
            engine.submit(np.zeros(30, np.int32), max_new=8)  # > max_seq

    def test_warmup_covers_every_bucket_no_cold_misses(
        self, tiny_params, tmp_path
    ):
        """After warmup the bucketed serve loop only hits pre-measured
        OpKeys: AutotunePolicy.n_measured stays flat through real traffic,
        for every class independently."""
        policies = {
            "interactive": AutotunePolicy(
                cache_path=str(tmp_path / "warm_a.json")
            ),
            "bulk": AutotunePolicy(cache_path=str(tmp_path / "warm_b.json")),
        }
        engine = make_engine(tiny_params, policies=policies)
        warm = engine.warmup()
        assert warm["shapes_traced"] == 2 * (
            len(engine.buckets.decode_batches) + len(engine.buckets.prefill_lens)
        )
        measured = {cls: p.n_measured for cls, p in policies.items()}
        assert all(n > 0 for n in measured.values())  # warmup did measure
        for i, p in enumerate(mixed_prompts([3, 9, 14, 6, 11])):
            engine.submit(p, max_new=4, cls=("interactive", "bulk")[i % 2])
        engine.run()
        assert engine.cold_misses() == {"interactive": 0, "bulk": 0}
        for cls, p in policies.items():
            assert p.n_measured == measured[cls], cls

    def test_per_class_dispatch_rows_are_separate(self, tiny_params):
        """Each class's GEMMs land in its own policy's report — batched
        attention ops (BNT/BNN) included — with no cross-class bleed."""
        policies = {
            "interactive": FixedPolicy("XLA_NT"),
            "bulk": FixedPolicy("XLA_TNN"),
        }
        engine = make_engine(tiny_params, policies=policies)
        for i, p in enumerate(mixed_prompts([4, 6, 5, 8])):
            engine.submit(p, max_new=3, cls=("interactive", "bulk")[i % 2])
        engine.run()
        rows = engine.class_dispatch_rows()
        for cls in ("interactive", "bulk"):
            assert rows[cls].get("BNT") and rows[cls].get("BNN"), cls
        assert set(rows["interactive"]["NT"]) == {"XLA_NT"}
        assert set(rows["bulk"]["NT"]) == {"XLA_TNN"}

    def test_rejects_non_token_arch(self, tiny_params):
        frames = TINY.replace(input_mode="frames")
        with pytest.raises(ValueError):
            ServeEngine(frames, tiny_params, n_slots=2, max_seq=16)


# -- deadlines + backpressure (the fault-tolerance layer) ---------------------


class TestDeadlinesAndBackpressure:
    def test_queued_request_past_deadline_expires(self, tiny_params):
        """A request whose deadline lapses while waiting in the queue is
        evicted as DEADLINE_EXCEEDED before a slot is ever spent on it."""
        engine = make_engine(tiny_params, n_slots=1)
        r0 = engine.submit(mixed_prompts([4])[0], max_new=4)
        r1 = engine.submit(mixed_prompts([4])[0], max_new=4, deadline_s=0.0)
        engine.run()
        assert r0.state is RequestState.FINISHED
        assert r1.state is RequestState.DEADLINE_EXCEEDED
        assert r1.slot is None and not engine.queue
        assert engine.health()["deadline_evictions"] == 1
        assert engine.health()["deadline_exceeded"] == 1

    def test_active_request_past_deadline_evicted_midstream(self, tiny_params):
        """An admitted request is expired between decode steps: it stops
        mid-generation and its slot returns to the pool."""
        engine = make_engine(tiny_params, n_slots=2)
        req = engine.submit(mixed_prompts([4])[0], max_new=24, deadline_s=0.05)
        engine.step()
        assert req.state is RequestState.ACTIVE
        time.sleep(0.06)
        engine.step()
        assert req.state is RequestState.DEADLINE_EXCEEDED
        assert len(req.generated) < 24
        assert engine.kv.n_free == 2  # slot released
        engine.run()  # the drained engine is still healthy

    def test_no_deadline_never_expires(self, tiny_params):
        engine = make_engine(tiny_params)
        req = engine.submit(mixed_prompts([4])[0], max_new=4)
        assert not req.overdue(time.monotonic() + 1e6)
        engine.run()
        assert req.state is RequestState.FINISHED

    def test_negative_deadline_rejected(self, tiny_params):
        engine = make_engine(tiny_params)
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(mixed_prompts([4])[0], max_new=4, deadline_s=-1.0)

    def test_full_queue_rejects_submit(self, tiny_params):
        engine = make_engine(tiny_params, n_slots=1, max_queue=2)
        engine.submit(mixed_prompts([4])[0], max_new=2)
        engine.submit(mixed_prompts([4])[0], max_new=2)
        with pytest.raises(QueueFullError):
            engine.submit(mixed_prompts([4])[0], max_new=2)
        assert engine.health()["rejected_submits"] == 1
        # draining the queue re-opens admission
        engine.run()
        r = engine.submit(mixed_prompts([4])[0], max_new=2)
        engine.run()
        assert r.state is RequestState.FINISHED

    def test_default_queue_bound_scales_with_slots(self, tiny_params):
        engine = make_engine(tiny_params, n_slots=4)
        assert engine.max_queue == 32

    def test_health_counts_terminal_states(self, tiny_params):
        engine = make_engine(tiny_params, n_slots=2)
        r0 = engine.submit(mixed_prompts([4])[0], max_new=4)
        r1 = engine.submit(mixed_prompts([4])[0], max_new=4)
        engine.step()
        engine.evict(r1.rid)
        engine.run()
        health = engine.health()
        assert health["finished"] == 1 and health["evicted"] == 1
        assert health["crashed_steps"] == 0
        assert r0.state is RequestState.FINISHED


# -- launcher mesh-spec parsing (shared CLI setup) ----------------------------


class TestMeshParsing:
    def test_valid_spec(self):
        mesh = parse_mesh("1x1")
        assert mesh.size == 1

    @pytest.mark.parametrize(
        "spec", ["4", "axb", "", "2x", "x2", "0x2", "2x0", "-1x2", "1x1x1"]
    )
    def test_malformed_specs_raise_value_error(self, spec):
        with pytest.raises(ValueError, match="mesh spec"):
            parse_mesh(spec)

    def test_oversubscribed_mesh_raises(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="devices"):
            parse_mesh(f"{n + 1}x2")

    def test_resolver_routes_to_parser_error(self):
        import argparse

        ap = argparse.ArgumentParser()
        args = argparse.Namespace(mesh="bogus", policy="model")
        with pytest.raises(SystemExit):
            resolve_mesh_and_policy(args, ap)

    def test_resolver_without_parser_raises(self):
        import argparse

        args = argparse.Namespace(mesh="bogus", policy="model")
        with pytest.raises(ValueError, match="mesh spec"):
            resolve_mesh_and_policy(args)


# -- the serve driver -----------------------------------------------------------

_DRIVER_ARGS = [
    "--arch", "smollm-135m", "--smoke", "--requests", "2",
    "--prompt-len", "8", "--gen", "8", "--slots", "2", "--mesh", "1x1",
    "--policy", "fixed:XLA_NT", "--classes", "interactive", "--len-step", "8",
]


class TestServeDriver:
    def test_classes_and_len_step_reach_the_engine(self):
        from repro.launch import serve

        engine = serve.main(_DRIVER_ARGS)
        assert set(engine.policies) == {"interactive"}
        assert engine.buckets.len_step == 8
        assert engine.health()["finished"] == 2

    def test_reports_client_times_and_the_engines_counters(self, capsys):
        from repro.launch import serve
        from repro.serving.engine import WAIT_PHASES

        engine = serve.main(_DRIVER_ARGS)
        out = capsys.readouterr().out
        for what in ("time to first token", "inter-token latency", "queue wait"):
            assert f"[serve] {what}: p50 " in out
        st = engine.stats()
        assert (f"[serve] engine: {st['steps']} steps, {st['decode_steps']} decode steps, "
                f"{st['decoded_rows']} rows decoded, 2 prefills") in out
        per_step = next(line for line in out.splitlines()
                        if line.startswith("[serve] engine ms per step: "))
        for p in HOST_PHASES + WAIT_PHASES:
            assert f" {p} " in per_step

    def test_crashed_step_exits_nonzero(self, monkeypatch):
        from repro.launch import serve

        def boom(self, cls, reqs):
            raise RuntimeError("decode step lost")

        monkeypatch.setattr(ServeEngine, "_decode_class", boom)
        with pytest.warns(UserWarning, match="crashed"):
            with pytest.raises(SystemExit, match="crashed"):
                serve.main(_DRIVER_ARGS)


class TestStepAccounting:
    """Phase counters, spans and the client-side times of each token."""

    def test_token_times_are_step_returns_on_the_submit_clock(self, tiny_params):
        engine = make_engine(tiny_params, n_slots=2)
        reqs = [engine.submit(p, max_new=3) for p in mixed_prompts([4, 6, 5])]
        returns = []
        while engine.has_work():
            engine.step()
            returns.append(time.monotonic())
        for r in reqs:
            assert r.state is RequestState.FINISHED
            assert len(r.token_times) == len(r.generated) == 3
            assert r.submit_time <= r.admit_time <= r.token_times[0]
            assert r.token_times == sorted(r.token_times)
            # each token is stamped at the return of the step that made it
            assert all(any(0 <= t1 - t <= 0.5 for t1 in returns) for t in r.token_times)
        # the third request waited for a slot: admitted a step later
        assert reqs[2].admit_time > reqs[0].token_times[0]

    def test_stats_count_the_steps_work_and_split_its_time(self, tiny_params, monkeypatch):
        monkeypatch.setattr(attention_decode, "DECODE_BLOCK", 8)  # 8 positions a grid step
        engine = make_engine(tiny_params, n_slots=2)
        assert engine.stats()["steps"] == 0
        prompts = mixed_prompts([4, 9, 5])
        for p in prompts:
            engine.submit(p, max_new=3)
        t0 = time.perf_counter()
        engine.run()
        wall = time.perf_counter() - t0
        st = engine.stats()
        assert st["steps"] == engine.clock
        assert st["prefills"] == 3 and st["prompt_tokens"] == sum(len(p) for p in prompts)
        assert st["prefill_tokens"] == sum(engine.buckets.bucket_len(len(p)) for p in prompts)
        # every request's first token comes from prefill, the rest from decode
        assert st["decoded_rows"] == 3 * 2
        # buckets (1, 2) on 2 slots: no padding rows, every row in the pool
        # kernel; row at position p reads cdiv(p + 1, 8) blocks of each layer
        assert st["decoded_rows_inplace"] == st["decoded_rows"]
        blocks = sum(-(-(len(p) + k + 1) // 8) for p in prompts for k in range(2))
        assert st["kv_blocks_read"] == 2 * blocks  # TINY's two attention layers
        assert 0 < st["decode_steps"] <= st["steps"]
        waits = st["prefill_wait_s"] + st["decode_wait_s"]
        phases = sum(st[f"{p}_s"] for p in HOST_PHASES)
        assert st["host_s"] == pytest.approx(st["step_s"] - waits)
        assert phases + waits <= st["step_s"] + 1e-9
        assert 0 < st["step_s"] <= wall

    def test_phases_run_inside_profiler_spans(self, tiny_params, monkeypatch):
        names = []

        class Span:
            def __init__(self, name):
                names.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        engine = make_engine(tiny_params, n_slots=2)
        engine.submit(mixed_prompts([4])[0], max_new=2)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
        engine.step()
        # the decode launches before any wait; the wait for the previous
        # step's decode tokens (none yet) comes before this step's first
        # tokens, then one fetch and one retire for all of them
        assert names == ["engine.expire", "engine.admit", "engine.prefill",
                         "engine.decode", "engine.decode_wait", "engine.prefill_wait",
                         "engine.fetch", "engine.retire"]


class TestOverlappedStep:
    """Each step launches its decode before it fetches the previous one's
    tokens: tokens arrive a step late, rows leave the batch by count, and
    the host walks only live requests."""

    @pytest.mark.parametrize("how", ["evict", "deadline"])
    def test_token_in_flight_for_a_dropped_request_is_discarded(self, tiny_params, how):
        """A request evicted, or expired, while its decode is in flight:
        the token is discarded and counted, its slot goes to the queue
        head, and every token served stays exact."""
        engine = make_engine(tiny_params, n_slots=2)
        prompts = mixed_prompts([4, 6, 5])
        deadline = 0.2 if how == "deadline" else None
        r0 = engine.submit(prompts[0], max_new=8, deadline_s=deadline)
        r1, r2 = (engine.submit(p, max_new=8) for p in prompts[1:])
        engine.step()
        assert (r0.launched, len(r0.generated)) == (2, 1)  # one token in flight
        victim_slot = r0.slot
        if how == "evict":
            engine.evict(r0.rid)
        else:
            time.sleep(deadline)
        engine.step()
        expected_state = {"evict": RequestState.EVICTED,
                          "deadline": RequestState.DEADLINE_EXCEEDED}[how]
        assert r0.state is expected_state and len(r0.generated) == 1
        assert engine.stats()["discarded_rows"] == 1
        assert r2.state is RequestState.ACTIVE and r2.slot == victim_slot
        engine.run()
        assert r0.generated == reference_generate(TINY, tiny_params, prompts[0], 1)
        for req, prompt in ((r1, prompts[1]), (r2, prompts[2])):
            assert req.state is RequestState.FINISHED
            assert req.generated == reference_generate(TINY, tiny_params, prompt, 8)
        assert engine.stats()["discarded_rows"] == 1

    def test_finished_means_every_token_arrived(self, tiny_params):
        """``FINISHED`` comes with all ``max_new`` tokens and their times,
        one step after the last decode's launch; ``run()`` drains that
        step, and a one-token request is done with its prefill."""
        engine = make_engine(tiny_params, n_slots=2)
        lens, news = [4, 7, 5, 9, 3], [1, 2, 5, 3, 4]
        prompts = mixed_prompts(lens)
        reqs = [engine.submit(p, max_new=n) for p, n in zip(prompts, news)]
        seen_finished = set()
        for _ in range(4):
            engine.step()
            for r in reqs:
                if r.state is RequestState.FINISHED:
                    assert len(r.generated) == len(r.token_times) == r.max_new
                    seen_finished.add(r.rid)
                else:
                    assert len(r.generated) < r.max_new
        assert seen_finished  # some finished while others decoded
        # a row's slot goes back once its last token is launched, before
        # that token has arrived: the engine still has work without slots
        while engine.kv.owner or engine.queue:
            engine.step()
        assert engine.has_work() and any(r.state is RequestState.ACTIVE for r in reqs)
        engine.run()
        assert not engine.has_work()
        for r, p in zip(reqs, prompts):
            assert r.state is RequestState.FINISHED
            assert len(r.generated) == len(r.token_times) == r.max_new
            assert r.generated == reference_generate(TINY, tiny_params, p, r.max_new)

    def test_step_walks_only_live_requests(self, tiny_params, monkeypatch):
        """After many finished requests, a step never walks the map of
        every request, and checks a deadline only of the live ones."""
        engine = make_engine(tiny_params, n_slots=2)
        for p in mixed_prompts([3, 5, 4, 6, 7, 3, 5, 4, 6, 2, 8, 5]):
            engine.submit(p, max_new=3, deadline_s=60.0)
        engine.run()

        class NoWalk(dict):
            def _walk(self, *a):
                raise AssertionError("the step walked every request")

            __iter__ = values = items = keys = _walk

        engine.requests = NoWalk(engine.requests)
        checked = []
        real_overdue = engine.requests[0].overdue.__func__
        monkeypatch.setattr(type(engine.requests[0]), "overdue",
                            lambda self, now: checked.append(self.rid) or real_overdue(self, now))
        late = [engine.submit(p, max_new=4, deadline_s=60.0) for p in mixed_prompts([4, 6, 5])]
        while engine.has_work():
            live = sum(r.state not in (RequestState.FINISHED,) for r in late)
            checked.clear()
            engine.step()
            assert len(checked) == live <= len(late)
        assert all(r.state is RequestState.FINISHED for r in late)

    def test_overlapped_steps_count_every_decode_but_the_first(self, tiny_params):
        """In a steady run of one class every decode launch but the first
        finds the previous decode's tokens still in flight."""
        engine = make_engine(tiny_params, n_slots=2)
        for p in mixed_prompts([4, 6, 5, 7]):
            engine.submit(p, max_new=5)
        engine.run()
        st = engine.stats()
        assert st["decode_steps"] == 2 * 4  # two waves of four decodes each
        assert st["overlapped_steps"] == st["decode_steps"] - 1
        assert st["discarded_rows"] == 0

    @pytest.mark.parametrize("where", ["launch", "fetch"])
    def test_crashed_decode_evicts_only_its_batch(self, tiny_params, monkeypatch, where):
        """One class's decode raising at its launch, or when its tokens
        are fetched, evicts that class's requests; the other class
        finishes exact, and the evicted rows' tokens still in flight are
        discarded."""
        engine = make_engine(tiny_params, n_slots=4)
        prompts = mixed_prompts([4, 6, 5, 7])
        classes = ["interactive", "bulk"] * 2
        reqs = [engine.submit(p, max_new=6, cls=c) for p, c in zip(prompts, classes)]
        real_step, real_block = engine._decode_steps["bulk"], jax.block_until_ready
        calls, lost = [], []

        def flaky(*args):
            calls.append(1)
            if len(calls) == 2 and where == "launch":
                raise RuntimeError("decode step lost")
            tok, segments = real_step(*args)
            if len(calls) == 2:
                lost.append(tok)
            return tok, segments

        def block(x):
            if any(x is t for t in lost):
                raise RuntimeError("tokens lost")
            return real_block(x)

        engine._decode_steps["bulk"] = flaky
        monkeypatch.setattr(jax, "block_until_ready", block)
        with pytest.warns(UserWarning, match="decode step for class 'bulk' crashed"):
            engine.run()
        assert engine.crashed_steps == 1
        assert engine.stats()["discarded_rows"] == 2
        for req, prompt, cls in zip(reqs, prompts, classes):
            if cls == "bulk":
                assert req.state is RequestState.EVICTED and len(req.generated) < 6
            else:
                assert req.state is RequestState.FINISHED
                assert req.generated == reference_generate(TINY, tiny_params, prompt, 6)
