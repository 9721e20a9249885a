"""The on-device measurement subsystem: cache persistence + schema
versioning (v1 -> v2 migration), the timing harness' per-(candidate, tile
config) sweep and admissibility guards, AutotunePolicy two-level
cold-miss/warm-hit semantics with analytic fallback, the autotune policy
spec, and retraining the paper's GBDT from autotune-collected records."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.hardware import HardwareSpec, host_spec
from repro.core.measure import (
    MEASURE_SCHEMA_VERSION,
    MeasurementCache,
    best_times,
    default_cache_path,
    bench_fn,
    measure_candidates,
    top_configs_by_candidate,
)

def _nt(m, n, k, dsize=4):
    return core.OpKey("NT", m, n, k, dsize)


TINY_HW = HardwareSpec(
    name="tiny_mem",
    mem_gib=1e-6,  # nothing extra-memory fits
    num_cores=1,
    clock_mhz=1000.0,
    mem_bw_gbps=100.0,
    sram_kib=1024.0,
    peak_tflops_bf16=1.0,
    peak_tflops_f32=1.0,
)


# -- cache persistence --------------------------------------------------------


class TestMeasurementCache:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "cache.json")
        cache = MeasurementCache(p)
        key = ("cpu", "host_cpu", "float32", 128, 256, 512)
        cache.put(
            key,
            {
                "XLA_NT": {"default": 1.5e-4},
                "PALLAS_NT": {"128x128x128": 2.5e-4, "256x256x256": 2.0e-4},
            },
        )
        cache.save()
        cache2 = MeasurementCache.load(p)
        assert len(cache2) == 1 and key in cache2
        assert cache2.get(key) == {
            "XLA_NT": {"default": 1.5e-4},
            "PALLAS_NT": {"128x128x128": 2.5e-4, "256x256x256": 2.0e-4},
        }

    def test_flat_put_normalises_under_default_config(self):
        """v1-style flat {name: seconds} dicts keep working (hand-built
        caches, old call sites): they land under the 'default' config."""
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 8, 8, 8)
        cache.put(key, {"XLA_NT": 1e-5})
        assert cache.get(key) == {"XLA_NT": {"default": 1e-5}}

    def test_v1_file_migrates_on_load(self, tmp_path):
        """A v1 cache (flat per-candidate timings) must keep answering warm
        hits after the schema bump — no silent misread, no data loss."""
        p = str(tmp_path / "v1.json")
        with open(p, "w") as fh:
            json.dump(
                {
                    "schema_version": 1,
                    "entries": {
                        "cpu|host_cpu|float32|64|64|64": {
                            "XLA_NT": 2.0e-5, "XLA_TNN": 1.0e-5,
                        }
                    },
                },
                fh,
            )
        cache = MeasurementCache.load(p)
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        assert cache.get(key) == {
            "XLA_NT": {"default": 2.0e-5},
            "XLA_TNN": {"default": 1.0e-5},
        }
        # and the migrated cache drives selection
        pol = core.AutotunePolicy(cache=cache, measure=False)
        assert pol.select(_nt(64, 64, 64)) == core.Decision("XLA_TNN", None)

    def test_v2_file_migrates_op_less_keys_as_nt(self, tmp_path):
        """A v2 cache (per-config timings, op-less keys) must keep
        answering warm hits after the op-space schema bump: its keys could
        only describe the forward op, so they migrate as op="NT"."""
        p = str(tmp_path / "v2.json")
        with open(p, "w") as fh:
            json.dump(
                {
                    "schema_version": 2,
                    "entries": {
                        "cpu|host_cpu|float32|64|64|64": {
                            "XLA_NT": {"default": 2.0e-5},
                            "PALLAS_NT": {"128x128x128": 1.0e-5},
                        }
                    },
                },
                fh,
            )
        cache = MeasurementCache.load(p)
        key = ("cpu", "host_cpu", "float32", "NT", 64, 64, 64)
        assert cache.get(key) == {
            "XLA_NT": {"default": 2.0e-5},
            "PALLAS_NT": {"128x128x128": 1.0e-5},
        }
        # legacy op-less 6-tuple lookups see the same entry
        assert cache.get(("cpu", "host_cpu", "float32", 64, 64, 64)) is not None
        # and the migrated cache answers NT dispatches (not NN/TN ones)
        pol = core.AutotunePolicy(cache=cache, measure=False)
        assert pol.select(_nt(64, 64, 64)) == core.Decision(
            "PALLAS_NT", (128, 128, 128)
        )
        assert pol.n_cache_hits == 1
        nn = pol.select(core.OpKey("NN", 64, 64, 64, 4))
        assert "NN" in core.get_candidate(nn.name).ops  # analytic fallback

    def test_v3_roundtrip_with_op_keys(self, tmp_path):
        """Distinct ops of one shape are distinct cache entries."""
        p = str(tmp_path / "v3.json")
        cache = MeasurementCache(p)
        nt_key = ("cpu", "host_cpu", "float32", "NT", 8, 8, 8)
        tn_key = ("cpu", "host_cpu", "float32", "TN", 8, 8, 8)
        cache.put(nt_key, {"XLA_NT": 1e-5})
        cache.put(tn_key, {"XLA_TN": 2e-5})
        cache.save()
        cache2 = MeasurementCache.load(p)
        assert len(cache2) == 2
        assert cache2.get(nt_key) == {"XLA_NT": {"default": 1e-5}}
        assert cache2.get(tn_key) == {"XLA_TN": {"default": 2e-5}}

    def test_malformed_key_rejected(self):
        cache = MeasurementCache()
        with pytest.raises(ValueError, match="unknown op kind"):
            cache.put(("cpu", "hw", "float32", "XX", 8, 8, 8), {"XLA_NT": 1.0})
        with pytest.raises(ValueError, match="measurement key"):
            cache.put(("cpu", "hw", 8, 8, 8), {"XLA_NT": 1.0})

    def test_missing_file_starts_empty(self, tmp_path):
        cache = MeasurementCache.load(str(tmp_path / "absent.json"))
        assert len(cache) == 0

    def test_missing_file_strict(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            MeasurementCache.load(str(tmp_path / "absent.json"), missing_ok=False)

    def test_carries_schema_version(self, tmp_path):
        p = str(tmp_path / "cache.json")
        cache = MeasurementCache(p)
        cache.put(("cpu", "host_cpu", "float32", 8, 8, 8), {"XLA_NT": 1e-5})
        cache.save()
        with open(p) as fh:
            payload = json.load(fh)
        assert payload["schema_version"] == MEASURE_SCHEMA_VERSION

    def test_future_schema_rejected(self, tmp_path):
        p = str(tmp_path / "future.json")
        with open(p, "w") as fh:
            json.dump(
                {"schema_version": MEASURE_SCHEMA_VERSION + 1, "entries": {}}, fh
            )
        with pytest.raises(ValueError, match="newer than supported"):
            MeasurementCache.load(p)

    def test_default_cache_path_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "/tmp/custom_cache.json")
        assert default_cache_path() == "/tmp/custom_cache.json"

    def test_hardware_name_with_separator_roundtrips(self, tmp_path):
        p = str(tmp_path / "cache.json")
        cache = MeasurementCache(p)
        key = ("cpu", "gpu|a100-sxm", "float32", 8, 8, 8)
        cache.put(key, {"XLA_NT": 1e-5})
        cache.save()
        assert MeasurementCache.load(p).get(key) == {"XLA_NT": {"default": 1e-5}}

    def test_save_merges_concurrent_writers(self, tmp_path):
        """Two processes sharing one cache file must not clobber each
        other's measurements (last-writer-wins data loss)."""
        p = str(tmp_path / "shared.json")
        a = MeasurementCache(p)
        b = MeasurementCache(p)  # both loaded the same (empty) snapshot
        ka = ("cpu", "host_cpu", "float32", 8, 8, 8)
        kb = ("cpu", "host_cpu", "float32", 16, 16, 16)
        a.put(ka, {"XLA_NT": 1e-5})
        a.save()
        b.put(kb, {"XLA_NT": 2e-5})
        b.save()
        merged = MeasurementCache.load(p)
        assert ka in merged and kb in merged


# -- timing harness -----------------------------------------------------------


class TestMeasureHarness:
    def test_measures_admissible_candidates(self):
        times = measure_candidates(32, 24, 16, reps=1)
        assert "XLA_NT" in times and "XLA_TNN" in times
        assert all(
            t > 0.0 for cfgs in times.values() for t in cfgs.values()
        )
        assert set(times) <= set(core.CANDIDATES)
        # non-tunable candidates are timed once, under the default key
        assert set(times["XLA_NT"]) == {"default"}

    def test_tunable_candidates_swept_over_configs(self):
        """A shape with real tile choice: every tunable candidate gets
        several explicit config timings, each key parseable."""
        from repro.kernels.tiling import parse_config_key

        times = measure_candidates(256, 256, 256, reps=1, max_tile_configs=3)
        assert "PALLAS_NT" in times
        cfgs = times["PALLAS_NT"]
        assert len(cfgs) > 1
        for ck in cfgs:
            cfg = parse_config_key(ck)
            assert cfg is not None and len(cfg) == 3

    def test_tune_false_restricts_to_default_tiling(self):
        times = measure_candidates(256, 256, 256, reps=1, tune=False)
        assert set(times["PALLAS_NT"]) == {"default"}

    def test_best_times_folds_top_config(self):
        nested = {
            "PALLAS_NT": {"128x128x128": 3.0, "256x256x256": 1.0},
            "XLA_NT": {"default": 2.0},
        }
        assert best_times(nested) == {
            "PALLAS_NT": ("256x256x256", 1.0),
            "XLA_NT": ("default", 2.0),
        }

    def test_top_configs_by_candidate_is_modal(self):
        cache = MeasurementCache()
        for i, winner in enumerate(["256x256x256", "256x256x256", "128x128x128"]):
            cache.put(
                ("cpu", "host_cpu", "float32", 8 * (i + 1), 8, 8),
                {"PALLAS_NT": {winner: 1.0, "512x512x512": 2.0}},
            )
        assert top_configs_by_candidate(cache) == {"PALLAS_NT": "256x256x256"}

    def test_top_configs_skip_default_pseudo_tiles(self):
        """Non-tunable candidates always 'win' at 'default'; that is not a
        learned tile and must not pollute v2 artifacts."""
        cache = MeasurementCache()
        cache.put(
            ("cpu", "host_cpu", "float32", 8, 8, 8),
            {
                "XLA_NT": {"default": 1.0},
                "PALLAS_NT": {"128x128x128": 2.0},
            },
        )
        assert top_configs_by_candidate(cache) == {"PALLAS_NT": "128x128x128"}

    def test_measures_per_op_candidate_sets(self):
        """measure_candidates(op=...) builds operands in the op's storage
        layout and only times candidates implementing the op."""
        for op in ("NN", "TN"):
            times = measure_candidates(32, 24, 16, op=op, reps=1)
            assert times, op
            for name in times:
                assert op in core.get_candidate(name).ops
        nn = measure_candidates(32, 24, 16, op="NN", reps=1)
        assert "XLA_NN" in nn and "XLA_NT" not in nn

    def test_tile_tables_from_cache_are_per_op_and_per_shape(self):
        from repro.core.measure import tile_tables_from_cache

        cache = MeasurementCache()
        cache.put(
            ("cpu", "host_cpu", "float32", "NT", 128, 128, 128),
            {"PALLAS_NT": {"128x128x128": 1.0, "256x256x256": 2.0}},
        )
        cache.put(
            ("cpu", "host_cpu", "float32", "NT", 1000, 1000, 1000),
            {"PALLAS_NT": {"512x512x1024": 1.0, "128x128x128": 2.0}},
        )
        cache.put(
            ("cpu", "host_cpu", "float32", "TN", 128, 128, 128),
            {"PALLAS_TN": {"256x256x256": 1.0}, "XLA_TN": {"default": 2.0}},
        )
        tables = tile_tables_from_cache(cache)
        assert tables["NT"]["PALLAS_NT"]["by_shape"] == {
            "128x128x128": "128x128x128",
            "1000x1000x1000": "512x512x1024",
        }
        assert tables["NT"]["PALLAS_NT"]["modal"] in (
            "128x128x128", "512x512x1024",
        )
        assert tables["TN"]["PALLAS_TN"]["by_shape"] == {
            "128x128x128": "256x256x256"
        }
        # default-key wins (XLA_TN) never enter the table
        assert "XLA_TN" not in tables["TN"]

    def test_oom_guard_skips_extra_memory_candidates(self):
        times = measure_candidates(32, 24, 16, hardware=TINY_HW, reps=1)
        assert times, "non-extra-memory candidates must still be measured"
        assert all(not core.get_candidate(n).extra_memory for n in times)

    def test_distributed_filter(self):
        times = measure_candidates(32, 24, 16, distributed=True, reps=1)
        assert times
        assert all(core.get_candidate(n).distributed_safe for n in times)



# -- measurement inside a jit trace -------------------------------------------


_SLEEP_S = 0.05


def _slow_nt(a, b):
    """XLA_NT plus a host callback that sleeps while the program *runs*:
    only executed code costs the sleep, tracing it costs nothing."""
    import time

    def wait(x):
        time.sleep(_SLEEP_S)
        return x

    out = core.get_candidate("XLA_NT").fn(a, b)
    return jax.pure_callback(wait, jax.ShapeDtypeStruct(out.shape, out.dtype), out)


@pytest.fixture
def slow_candidate():
    core.register_candidate("TEST_SLOW_NT", sim_algo="NT_DIRECT")(_slow_nt)
    try:
        yield "TEST_SLOW_NT"
    finally:
        core.unregister_candidate("TEST_SLOW_NT")


class TestMeasureInsideTrace:
    def test_times_executed_code_not_tracing(self, slow_candidate):
        """Selection runs while jit traces; the recorded time must be the
        executable's run time (>= the sleep), not the trace time."""
        got = {}

        def traced(a):
            got.update(
                measure_candidates(
                    8, 8, 8, candidates=(slow_candidate,), reps=1, warmup=1
                )
            )
            return a

        jax.jit(traced)(jnp.ones(3))
        assert got[slow_candidate]["default"] >= _SLEEP_S

    def test_timed_callable_gets_concrete_arrays(self, monkeypatch):
        seen = []
        real = bench_fn

        def spy(fn, *operands, **kw):
            seen.extend(operands)
            return real(fn, *operands, **kw)

        monkeypatch.setattr("repro.core.measure.bench_fn", spy)
        jax.jit(
            lambda a: (
                measure_candidates(8, 8, 8, candidates=("XLA_NT",), reps=1),
                a,
            )[1]
        )(jnp.ones(3))
        assert seen
        assert all(
            isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
            for x in seen
        )

    def test_bench_fn_refuses_tracers(self):
        def traced(a):
            with pytest.raises(TypeError, match="concrete"):
                bench_fn(lambda x: x + 1, a)
            return a

        jax.jit(traced)(jnp.ones(3))

    def test_autotune_inside_jit_records_run_time(self, slow_candidate):
        pol = core.AutotunePolicy(candidates=(slow_candidate,), reps=1)
        a, b = jnp.ones((8, 8)), jnp.ones((8, 8))
        with core.use_policy(pol):
            jax.jit(lambda a, b: core.dispatch("NT", a, b))(a, b)
        assert pol.n_measured == 1 and pol.n_fallbacks == 0
        (times,) = [t for _, t in pol.cache.records()]
        assert times[slow_candidate]["default"] >= _SLEEP_S

    def test_fallback_is_announced(self):
        pol = core.AutotunePolicy(measure=False)
        with pytest.warns(UserWarning, match="measurement is disabled"):
            pol.select(_nt(64, 64, 64))
        assert pol.n_fallbacks == 1


# -- AutotunePolicy -----------------------------------------------------------


class TestAutotunePolicy:
    def test_cold_miss_measures_then_warm_hits(self, tmp_path):
        p = str(tmp_path / "cache.json")
        pol = core.AutotunePolicy(cache_path=p, reps=1)
        decision = pol.select(_nt(64, 48, 32))
        assert decision.name in core.CANDIDATES
        assert (pol.n_measured, pol.n_cache_hits) == (1, 0)
        assert pol.select(_nt(64, 48, 32)) == decision
        assert (pol.n_measured, pol.n_cache_hits) == (1, 1)
        # a fresh policy over the same file performs zero new measurements
        pol2 = core.AutotunePolicy(cache_path=p)
        assert pol2.select(_nt(64, 48, 32)) == decision
        assert (pol2.n_measured, pol2.n_cache_hits) == (0, 1)

    def test_select_is_cached_argmin_of_admissible(self):
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(key, {"XLA_NT": 2.0, "XLA_TNN": 1.0, "NOT_REGISTERED": 0.1})
        pol = core.AutotunePolicy(cache=cache)
        # stale/unregistered names never dispatch; fastest admissible wins
        assert pol.select(_nt(64, 64, 64)) == core.Decision("XLA_TNN", None)
        assert pol.n_cache_hits == 1 and pol.n_measured == 0

    def test_select_is_two_level_argmin_over_configs(self):
        """The decision space is (candidate x tile config): the winning
        pair wins even when another *candidate* has a better default."""
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(
            key,
            {
                "XLA_NT": {"default": 2.0},
                "PALLAS_NT": {"128x128x128": 3.0, "256x256x512": 1.0},
            },
        )
        pol = core.AutotunePolicy(cache=cache)
        assert pol.select(_nt(64, 64, 64)) == core.Decision(
            "PALLAS_NT", (256, 256, 512)
        )

    def test_vmem_infeasible_cached_config_refiltered(self):
        """A cached config that busts the VMEM budget (foreign cache,
        changed budget) must never dispatch — config-aware admissibility."""
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(
            key,
            {
                "PALLAS_NT": {"8192x8192x8192": 0.1, "128x128x128": 1.0},
                "XLA_NT": {"default": 2.0},
            },
        )
        pol = core.AutotunePolicy(cache=cache)
        assert pol.select(_nt(64, 64, 64)) == core.Decision(
            "PALLAS_NT", (128, 128, 128)
        )

    def test_malformed_config_key_never_dispatches(self):
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(
            key,
            {"PALLAS_NT": {"garbage": 0.1}, "XLA_NT": {"default": 2.0}},
        )
        pol = core.AutotunePolicy(cache=cache)
        assert pol.select(_nt(64, 64, 64)) == core.Decision("XLA_NT", None)

    def test_distributed_refilters_cached_entries(self):
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(key, {"PALLAS_NT": 1e-6, "XLA_NT": 2e-6})
        pol = core.AutotunePolicy(cache=cache, distributed=True)
        # fastest cached candidate is pjit-unsafe -> next admissible wins
        assert pol.select(_nt(64, 64, 64)).name == "XLA_NT"

    def test_candidate_restriction_respected_on_warm_hit_and_fallback(self):
        cache = MeasurementCache()
        key = ("cpu", "host_cpu", "float32", 64, 64, 64)
        cache.put(key, {"XLA_TNN": 1e-6, "XLA_NT": 2e-6})
        # warm hit: the fastest cached name is outside the restriction
        pol = core.AutotunePolicy(cache=cache, candidates=("XLA_NT",))
        assert pol.select(_nt(64, 64, 64)).name == "XLA_NT"
        # fallback path: the analytic fallback is restricted the same way
        pol2 = core.AutotunePolicy(measure=False, candidates=("XLA_TNN",))
        assert pol2.select(_nt(256, 256, 256)).name == "XLA_TNN"

    def test_cache_object_with_path_persists(self, tmp_path):
        p = str(tmp_path / "cache.json")
        pol = core.AutotunePolicy(cache=MeasurementCache(), cache_path=p, reps=1)
        pol.select(_nt(16, 16, 16))
        assert pol.n_measured == 1
        assert len(MeasurementCache.load(p)) == 1

    def test_measure_disabled_falls_back_to_analytic(self):
        pol = core.AutotunePolicy(measure=False)
        ana = core.AnalyticPolicy(hardware=pol.hardware)
        assert pol.select(_nt(256, 256, 256)) == ana.select(_nt(256, 256, 256))
        assert pol.n_fallbacks == 1 and len(pol.cache) == 0

    def test_analytic_fallback_is_not_blind_to_tiling(self):
        """The fallback attaches a roofline-ranked tile for tunable
        candidates instead of always running the default block."""
        pol = core.AutotunePolicy(measure=False, candidates=("PALLAS_NT",))
        decision = pol.select(_nt(129, 1000, 1000))
        assert decision.name == "PALLAS_NT"
        assert decision.config is not None
        from repro.kernels.tiling import enumerate_tile_configs

        assert decision.config in enumerate_tile_configs(129, 1000, 1000, 4)

    def test_distributed_disables_measurement(self):
        pol = core.AutotunePolicy(distributed=True)
        pol.select(_nt(128, 128, 128))
        assert pol.n_measured == 0 and pol.n_fallbacks == 1

    def test_flops_cap_disables_measurement(self):
        pol = core.AutotunePolicy(max_measure_flops=1.0)
        pol.select(_nt(64, 64, 64))
        assert pol.n_measured == 0 and pol.n_fallbacks == 1

    def test_measures_at_trace_time_inside_jit(self, tmp_path):
        p = str(tmp_path / "trace_cache.json")
        pol = core.AutotunePolicy(cache_path=p, reps=1)
        a, b = jnp.ones((8, 16), jnp.float32), jnp.ones((4, 16), jnp.float32)
        with core.use_policy(pol):
            out = jax.jit(lambda a, b: core.dispatch("NT", a, b))(a, b)
        np.testing.assert_allclose(np.asarray(out), 16.0)
        assert pol.n_measured == 1
        # the measurement persisted: a later eager run warm-hits it
        pol2 = core.AutotunePolicy(cache_path=p)
        pol2.select(_nt(8, 4, 16))
        assert (pol2.n_measured, pol2.n_cache_hits) == (0, 1)

    def test_is_selection_policy(self):
        assert isinstance(core.AutotunePolicy(measure=False), core.SelectionPolicy)

    def test_unmeasurable_shape_not_retried(self, monkeypatch):
        """A shape where measurement yields nothing must fall back once and
        be remembered, not re-attempt measurement on every select."""
        calls = []

        def empty_measurement(*a, **kw):
            calls.append(a)
            return {}

        # select() imports measure_candidates lazily from the module
        monkeypatch.setattr(
            "repro.core.measure.measure_candidates", empty_measurement
        )
        pol = core.AutotunePolicy()
        assert pol.select(_nt(8, 8, 8)).name in core.CANDIDATES  # analytic fallback
        pol.select(_nt(8, 8, 8))
        assert len(calls) == 1, "empty measurement must not be retried"
        assert pol.n_fallbacks == 2 and len(pol.cache) == 0


# -- spec parsing -------------------------------------------------------------


class TestAutotuneSpec:
    def test_autotune_spec_with_path(self, tmp_path):
        p = str(tmp_path / "c.json")
        pol = core.policy_from_spec(f"autotune:{p}")
        assert isinstance(pol, core.AutotunePolicy)
        assert pol.cache.path == p

    def test_autotune_spec_default_path(self, monkeypatch, tmp_path):
        p = str(tmp_path / "default.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", p)
        pol = core.policy_from_spec("autotune")
        assert pol.cache.path == p

    def test_autotune_spec_distributed_disables_measurement(self, tmp_path):
        pol = core.policy_from_spec(
            f"autotune:{tmp_path / 'c.json'}", distributed=True
        )
        pol.select(_nt(64, 64, 64))
        assert pol.n_measured == 0 and pol.n_fallbacks == 1

    def test_spec_help_mentions_autotune(self):
        from repro.core.engine import POLICY_SPEC_HELP

        assert "autotune" in POLICY_SPEC_HELP


# -- retraining from the cache ------------------------------------------------


class TestDatasetFromMeasurements:
    def _cache_from_dataset(self, ds) -> MeasurementCache:
        """Rebuild the cache an autotune run over ds's shapes would hold."""
        cache = MeasurementCache()
        hw = host_spec()
        for i, (m, n, k) in enumerate(np.asarray(ds.mnk)):
            key = ("cpu", hw.name, "float32", int(m), int(n), int(k))
            cache.put(
                key,
                {
                    "XLA_NT": float(ds.times["NT"][i]),
                    "XLA_TNN": float(ds.times["TNN"][i]),
                },
            )
        return cache

    def test_labels_agree_with_collect_measured(self):
        ds_m = core.collect_measured(sizes=[16, 32], reps=1)
        ds_c = core.dataset_from_measurements(self._cache_from_dataset(ds_m))
        assert len(ds_c) == len(ds_m)
        assert ds_c.source == "autotune-measured"
        by_mnk = {tuple(mnk): y for mnk, y in zip(ds_c.mnk.tolist(), ds_c.y)}
        for mnk, y in zip(ds_m.mnk.tolist(), ds_m.y):
            assert by_mnk[tuple(mnk)] == y
        # features rebuild identically from the hardware descriptor
        np.testing.assert_allclose(
            np.sort(ds_c.X, axis=0), np.sort(ds_m.X, axis=0)
        )

    def test_skips_records_missing_pair_member(self):
        cache = MeasurementCache()
        hw = host_spec()
        cache.put(("cpu", hw.name, "float32", 8, 8, 8), {"XLA_NT": 1e-5})
        cache.put(
            ("cpu", hw.name, "float32", 16, 16, 16),
            {"XLA_NT": 1e-5, "XLA_TNN": 2e-5},
        )
        ds = core.dataset_from_measurements(cache)
        assert len(ds) == 1 and ds.y[0] == 1

    def test_empty_cache_raises(self):
        with pytest.raises(ValueError, match="no usable float32 records"):
            core.dataset_from_measurements(MeasurementCache())

    def test_mixed_platform_same_shape_raises(self):
        """Same hw/dtype/shape under two jax backends would give identical
        features with possibly contradictory labels — refuse unless the
        caller filters to one platform."""
        cache = MeasurementCache()
        hw = host_spec()
        cache.put(
            ("cpu", hw.name, "float32", 8, 8, 8),
            {"XLA_NT": 1e-5, "XLA_TNN": 2e-5},
        )
        cache.put(
            ("gpu", hw.name, "float32", 8, 8, 8),
            {"XLA_NT": 2e-5, "XLA_TNN": 1e-5},
        )
        with pytest.raises(ValueError, match="multiple.*platforms"):
            core.dataset_from_measurements(cache)
        ds = core.dataset_from_measurements(cache, platform="gpu")
        assert len(ds) == 1 and ds.y[0] == -1

    def test_unknown_hardware_named_in_error(self):
        cache = MeasurementCache()
        cache.put(
            ("cpu", "some_future_chip", "float32", 8, 8, 8),
            {"XLA_NT": 1e-5, "XLA_TNN": 2e-5},
        )
        with pytest.raises(ValueError, match="some_future_chip"):
            core.dataset_from_measurements(cache)

    def test_dtype_filter_keeps_features_unambiguous(self):
        """bf16 and f32 timings of one shape would give the learner
        identical 8-dim features with contradictory labels; the converter
        keeps one dtype (default float32)."""
        cache = MeasurementCache()
        hw = host_spec()
        cache.put(
            ("cpu", hw.name, "float32", 8, 8, 8),
            {"XLA_NT": 1e-5, "XLA_TNN": 2e-5},  # NT wins -> +1
        )
        cache.put(
            ("cpu", hw.name, "bfloat16", 8, 8, 8),
            {"XLA_NT": 2e-5, "XLA_TNN": 1e-5},  # TNN wins -> -1
        )
        ds = core.dataset_from_measurements(cache)
        assert len(ds) == 1 and ds.y[0] == 1
        ds_bf16 = core.dataset_from_measurements(cache, dtype="bfloat16")
        assert len(ds_bf16) == 1 and ds_bf16.y[0] == -1
        assert len(core.dataset_from_measurements(cache, dtype=None)) == 2

    def test_trains_paper_model_end_to_end(self, tmp_path):
        """The acceptance loop: autotune-measure shapes, convert, train,
        save a versioned selector artifact (with the learned tiles),
        reload, select."""
        p = str(tmp_path / "cache.json")
        pol = core.AutotunePolicy(cache_path=p, reps=1)
        for m in (16, 32):
            for n in (16, 32):
                for k in (16, 32):
                    pol.select(_nt(m, n, k))
        assert pol.n_measured == 8
        cache = MeasurementCache.load(p)
        ds = core.dataset_from_measurements(cache)
        assert len(ds) == 8
        clf, report = core.train_paper_model(ds)
        art = str(tmp_path / "selector.json")
        tiles = core.top_configs_by_candidate(cache, dtype="float32")
        core.MTNNSelector(clf, tile_configs=tiles).save(art)
        sel = core.MTNNSelector.load(art)
        assert sel.select(_nt(32, 32, 32)) in core.CANDIDATES
        assert sel.tile_configs == tiles
        # ModelPolicy attaches the learned tile to its decisions
        mp = core.ModelPolicy(sel)
        decision = mp.select(_nt(32, 32, 32))
        assert decision.config == sel.tile_config_for(decision.name)
