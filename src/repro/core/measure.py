"""On-device measurement subsystem — the autotune backend of the policy zoo.

The paper's pipeline is *measure NT vs TNN on real hardware -> train a
selector -> dispatch*.  This module closes the measurement end of that loop
for dispatch itself (AutoTVM-style): a timing harness that benchmarks every
admissible *(candidate, tile config)* pair for one (op, m, n, k) key — the
forward NT or a backward NN/TN gradient GEMM — on the *current* backend,
and a persistent, versioned JSON cache of those timings keyed by
``(platform, hardware, dtype, op, m, n, k)``.  Tunable (Pallas)
candidates are swept over their roofline-pruned config shortlist
(``kernels/tiling.py``); non-tunable (XLA) candidates are timed once under
the ``"default"`` config key.

``AutotunePolicy`` (core/policy.py) answers ``select()`` from the cache and
measures-and-caches cold shapes; ``dataset_from_measurements``
(core/dataset.py) turns a populated cache into a ``SelectionDataset`` so
the paper's GBDT can be retrained from autotune-collected records.

``select()`` fires while a ``jit`` traces, where any JAX call would be
staged rather than run.  So every sweep runs on a thread of its own
(``run_outside_trace``), where nothing is traced: it compiles each
candidate ahead of time for concrete operands and times the executable
on the device (``bench_fn``).
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import tempfile
import threading
import time
from typing import Dict, Iterator, Optional, Sequence, Tuple

from . import faults
from .candidates import (
    CANDIDATES,
    candidate_allowed,
    candidate_fits_memory,
    get_candidate,
)
from .hardware import HardwareSpec, device_spec
from .opkey import check_op, shape_key

__all__ = [
    "MEASURE_SCHEMA_VERSION",
    "MeasurementKey",
    "MeasurementCache",
    "bench_fn",
    "measure_candidates",
    "measure_transpose_configs",
    "best_transpose_config",
    "run_outside_trace",
    "default_cache_path",
    "best_times",
    "top_configs_by_candidate",
    "tile_tables_from_cache",
    "DTYPE_BY_DSIZE",
]

# Cache schema history:
#   v1: {"schema_version": 1, "entries": {"plat|hw|dtype|m|n|k": {name: s}}}
#   v2: entry values gain a tile-config level:
#       {"plat|hw|dtype|m|n|k": {name: {"default"|"BMxBNxBK": s}}}
#       v1 records migrate on load as {name: {"default": s}}.
#   v3: keys gain the op kind ("plat|hw|dtype|op|m|n|k") so the cache
#       spans the whole (op x shape x candidate x config) selection space.
#       v1/v2 keys — which could only describe the forward op — migrate on
#       load with op="NT".
#   v4: keys gain the batch extent ("plat|hw|dtype|op|g|m|n|k") so the
#       batched attention contractions (BNT/BNN) are first-class entries.
#       v3 keys — necessarily unbatched — migrate on load with g=1.
#       v4 files may additionally carry a top-level "attempts" map
#       ({key: {name: {config_key: n}}} — how many bench tries each
#       measurement took, retry-with-backoff observability).  Optional and
#       schema-neutral: readers without the field ignore it.
#   v5: the attention subgraph op — the key grammar is unchanged but the
#       op slot admits "ATTN" (paired fused-vs-unfused rows keyed on the
#       whole subgraph: m queries, n keys, k head-dim per slice) and
#       entry values may carry 2-part "BQxBK" config keys for the fused
#       kernel's (bq, bk) space.  v4 files load unchanged (their op slots
#       simply never say ATTN); files newer than v5 are rejected.
MEASURE_SCHEMA_VERSION = 5

# select() receives an element size, not a dtype; measurement needs a real
# dtype to build operands.  Sizes outside this map are not measurable (the
# policy falls back to the analytic model for them).
DTYPE_BY_DSIZE: Dict[int, str] = {2: "bfloat16", 4: "float32"}

# (platform, hardware, dtype, op, g, m, n, k)
MeasurementKey = Tuple[str, str, str, str, int, int, int, int]


def default_cache_path() -> str:
    """Where ``--policy autotune`` persists measurements by default."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune_cache.json"
    )


def _normalize_mkey(key) -> MeasurementKey:
    """Canonical 8-tuple key.  Legacy 6-tuples (no op component — the
    pre-op-space cache API) mean the forward NT op; legacy 7-tuples (no
    batch component) mean g=1 — both keep working at ``get``/``put``."""
    key = tuple(key)
    if len(key) == 6:
        platform, hw, dtype, m, n, k = key
        op, g = "NT", 1
    elif len(key) == 7:
        platform, hw, dtype, op, m, n, k = key
        g = 1
    elif len(key) == 8:
        platform, hw, dtype, op, g, m, n, k = key
    else:
        raise ValueError(
            f"measurement key {key!r} must be (platform, hardware, dtype, "
            "op, g, m, n, k)"
        )
    return (
        str(platform), str(hw), str(dtype), check_op(op),
        int(g), int(m), int(n), int(k),
    )


def _key_str(key: MeasurementKey) -> str:
    return "|".join(str(p) for p in key)


def _file_sig(path: str) -> Optional[Tuple[int, int]]:
    """(mtime_ns, size) change signature, or None when unreadable/absent."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (st.st_mtime_ns, st.st_size)


@contextlib.contextmanager
def _file_lock(path: str):
    """Advisory lock serialising read-merge-replace across processes.

    Uses flock on a sibling ``.lock`` file (the data file itself is
    replaced atomically, so it cannot hold the lock).  On platforms
    without fcntl this degrades to unlocked atomic-replace semantics.
    """
    try:
        import fcntl
    except ImportError:
        yield
        return
    lock_path = path + ".lock"
    with open(lock_path, "a") as fh:
        fcntl.flock(fh.fileno(), fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(fh.fileno(), fcntl.LOCK_UN)


def _parse_key(s: str, version: int = MEASURE_SCHEMA_VERSION) -> MeasurementKey:
    # split from both ends: hardware names may themselves contain '|';
    # platform, dtype, op and the ints never do
    if version >= 4:
        head, op, g, m, n, k = s.rsplit("|", 5)
    elif version == 3:  # v3 keys carry no batch component: g=1
        head, op, m, n, k = s.rsplit("|", 4)
        g = 1
    else:  # v1/v2 keys carry no op component: they meant the forward op
        head, m, n, k = s.rsplit("|", 3)
        op, g = "NT", 1
    platform, rest = head.split("|", 1)
    hardware, dtype = rest.rsplit("|", 1)
    return (
        platform, hardware, dtype, check_op(op), int(g), int(m), int(n), int(k)
    )


def _normalize_times(times: Dict) -> Dict[str, Dict[str, float]]:
    """Canonical nested form ``{name: {config_key: seconds}}``.

    Accepts the v1 flat form ``{name: seconds}`` (migrated under the
    ``"default"`` config key) so old files and hand-built dicts keep
    working.
    """
    from repro.kernels.tiling import DEFAULT_CONFIG_KEY

    out: Dict[str, Dict[str, float]] = {}
    for name, val in times.items():
        if isinstance(val, dict):
            out[str(name)] = {str(c): float(t) for c, t in val.items()}
        else:
            out[str(name)] = {DEFAULT_CONFIG_KEY: float(val)}
    return out


def best_times(times: Dict[str, Dict[str, float]]) -> Dict[str, Tuple[str, float]]:
    """Per candidate, the winning ``(config_key, seconds)`` — the top-config
    fold used by selection and by ``dataset_from_measurements``."""
    out: Dict[str, Tuple[str, float]] = {}
    for name, cfgs in times.items():
        if cfgs:
            ck = min(cfgs, key=cfgs.get)
            out[name] = (ck, cfgs[ck])
    return out


class MeasurementCache:
    """Persistent ``(platform, hardware, dtype, op, g, m, n, k) ->
    {candidate: {config_key: seconds}}``.

    Versioned like selector artifacts: v1 files (flat per-candidate
    timings), v2 files (op-less keys — migrated as the forward NT op) and
    v3 files (batch-less keys — migrated with g=1) migrate on load; files
    newer than ``MEASURE_SCHEMA_VERSION`` are rejected rather than
    misread.  Legacy op-less 6-tuple and batch-less 7-tuple keys are
    accepted by ``get``/``put`` and normalised the same way.  ``save``
    writes atomically (tmp + rename) so a crash mid-write cannot corrupt a
    warm cache.

    ``load(..., recover=True)`` is the production posture (AutotunePolicy
    uses it): a corrupt/truncated/newer-schema file is moved aside to
    ``<path>.corrupt`` with a warning and the cache rebuilds empty, and a
    malformed individual entry is skipped — intact entries survive.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        # in-process counterpart of the cross-process _file_lock: policies
        # share one cache across serving threads
        self._lock = threading.Lock()
        self._entries: Dict[MeasurementKey, Dict[str, Dict[str, float]]] = {}  # guarded-by: _lock
        # per-measurement bench attempt counts (retry observability):
        # {key: {name: {config_key: attempts}}} — parallel to _entries
        self._attempts: Dict[MeasurementKey, Dict[str, Dict[str, int]]] = {}  # guarded-by: _lock
        # (mtime_ns, size) of the file state we last loaded/wrote
        self._synced_sig: Optional[Tuple[int, int]] = None

    @classmethod
    def load(
        cls, path: str, missing_ok: bool = True, recover: bool = False
    ) -> "MeasurementCache":
        cache = cls(path)
        if not os.path.exists(path):
            if missing_ok:
                return cache  # cold cache: starts empty, persists to `path`
            raise FileNotFoundError(f"measurement cache {path!r} does not exist")
        try:
            with open(path, "rb") as fh:
                raw = faults.corrupt_on_read("cache", fh.read())
            payload = json.loads(raw.decode("utf-8"))
            if not isinstance(payload, dict):
                raise ValueError(
                    f"measurement cache {path!r} is not a JSON object"
                )
            version = payload.get("schema_version", 0)
            if version > MEASURE_SCHEMA_VERSION:
                raise ValueError(
                    f"measurement cache schema v{version} is newer than "
                    f"supported v{MEASURE_SCHEMA_VERSION}; upgrade the code "
                    "or re-measure"
                )
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            if not recover:
                raise
            _move_aside_cache(path, e)
            return cache  # rebuilt empty; next save repopulates the path
        cache._synced_sig = _file_sig(path)
        # v1 (and unversioned v0-era) entries hold flat {name: seconds}
        # values; _normalize_times folds them under the "default" config
        # key — a v1 cache keeps answering warm hits after the upgrade.
        # Pre-v3 keys carry no op component and migrate as op="NT";
        # pre-v4 keys carry no batch component and migrate as g=1.
        n_bad = 0
        for ks, times in payload.get("entries", {}).items():
            try:
                cache._entries[_parse_key(ks, version)] = _normalize_times(
                    times
                )
            except (ValueError, TypeError, AttributeError):
                # recover: one rotten entry must not void the warm ones
                if not recover:
                    raise
                n_bad += 1
        for ks, per_cand in (payload.get("attempts") or {}).items():
            try:
                cache._attempts[_parse_key(ks, version)] = {
                    str(name): {str(ck): int(n) for ck, n in cfgs.items()}
                    for name, cfgs in per_cand.items()
                }
            except (ValueError, TypeError, AttributeError):
                if not recover:
                    raise
                n_bad += 1
        if n_bad:
            import warnings

            warnings.warn(
                f"measurement cache {path!r}: skipped {n_bad} malformed "
                f"entr{'y' if n_bad == 1 else 'ies'}; "
                f"{len(cache._entries)} intact entries loaded",
                UserWarning,
                stacklevel=2,
            )
        return cache

    def save(self, path: Optional[str] = None) -> None:
        path = path or self.path
        if path is None:
            raise ValueError("MeasurementCache has no path to save to")
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # merge-on-save under an advisory lock: concurrent processes sharing
        # one cache file each loaded their own snapshot — fold in shapes
        # others persisted since (ours win on conflict) and publish
        # atomically, so no writer clobbers another's measurements.  The
        # re-read is skipped when the file is still at the (mtime_ns, size)
        # state we last loaded/wrote — single-writer runs stay O(1) reads.
        with _file_lock(path):
            disk_sig = _file_sig(path)
            with self._lock:
                if disk_sig is not None and disk_sig != (
                    self._synced_sig if path == self.path else None
                ):
                    try:
                        on_disk = MeasurementCache.load(path)
                    except (ValueError, OSError, json.JSONDecodeError):
                        on_disk = None  # unreadable/foreign file: overwrite
                    if on_disk is not None:
                        for k, v in on_disk._entries.items():
                            self._entries.setdefault(k, v)
                        for k, v in on_disk._attempts.items():
                            self._attempts.setdefault(k, v)
                payload = {
                    "schema_version": MEASURE_SCHEMA_VERSION,
                    "entries": {
                        _key_str(k): times
                        for k, times in sorted(self._entries.items())
                    },
                }
                if self._attempts:
                    payload["attempts"] = {
                        _key_str(k): per_cand
                        for k, per_cand in sorted(self._attempts.items())
                    }
            # unique tmp per writer: a fixed sibling name would let two
            # unlocked writers truncate each other's half-written file
            fd, tmp = tempfile.mkstemp(
                prefix=os.path.basename(path) + ".", dir=parent or "."
            )
            try:
                with os.fdopen(fd, "w") as fh:
                    json.dump(payload, fh)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            if path == self.path:
                self._synced_sig = _file_sig(path)

    def get(self, key) -> Optional[Dict[str, Dict[str, float]]]:
        return self._entries.get(_normalize_mkey(key))

    def put(self, key, times: Dict, attempts: Optional[Dict] = None) -> None:
        """Store timings for one (op, shape).  Accepts the canonical nested
        times form or the flat v1 form (normalised under ``"default"``),
        and legacy op-less 6-tuple keys (normalised to op="NT").
        ``attempts`` optionally records the bench try count per
        (candidate, config) alongside the entry."""
        mkey = _normalize_mkey(key)
        with self._lock:
            self._entries[mkey] = _normalize_times(times)
            if attempts:
                self._attempts[mkey] = {
                    str(name): {str(ck): int(n) for ck, n in cfgs.items()}
                    for name, cfgs in attempts.items()
                }

    def get_attempts(self, key) -> Optional[Dict[str, Dict[str, int]]]:
        """Bench attempt counts recorded with an entry (None when the
        entry predates retry tracking)."""
        return self._attempts.get(_normalize_mkey(key))

    def records(
        self,
    ) -> Iterator[Tuple[MeasurementKey, Dict[str, Dict[str, float]]]]:
        """All (key, times) pairs, sorted for deterministic iteration."""
        return iter(sorted(self._entries.items()))

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return _normalize_mkey(key) in self._entries

    def __repr__(self):
        return f"MeasurementCache({len(self)} shapes, path={self.path!r})"


def _move_aside_cache(path: str, reason: BaseException) -> None:
    """Quarantine a corrupt cache file as ``<path>.corrupt`` (warns; a
    rename failure is itself only warned — recovery must not raise)."""
    import warnings

    corrupt = path + ".corrupt"
    try:
        os.replace(path, corrupt)
        moved = f"moved aside to {corrupt!r}"
    except OSError as e:
        moved = f"could not be moved aside ({e})"
    warnings.warn(
        f"measurement cache {path!r} is unreadable "
        f"({type(reason).__name__}: {reason}); {moved} — rebuilding empty",
        UserWarning,
        stacklevel=3,
    )


def run_outside_trace(fn, *args, **kw):
    """Call ``fn(*args, **kw)`` on a fresh thread and return its result.

    Selection runs while a ``jit`` traces, and there every JAX call is
    staged into the traced program: a timing taken in place measures
    tracing, not the device.  JAX keeps its trace state per thread, so on
    a new thread nothing is being traced — operands are concrete device
    arrays and a compiled executable runs on the device.  The caller's
    contextvars (fault rules, the policy scope) go along."""
    import concurrent.futures
    import contextvars

    ctx = contextvars.copy_context()
    with concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="repro-measure"
    ) as pool:
        return pool.submit(ctx.run, fn, *args, **kw).result()


def bench_fn(
    fn, *operands, reps: int = 3, warmup: int = 1, stat: str = "median"
) -> float:
    """Compile ``fn`` for ``operands`` ahead of time, run the executable
    ``warmup`` times, then return the ``stat`` of ``reps`` wall-clock runs
    — two operands for the GEMM ops, three (q, k, v) for the attention
    subgraph op.  Compilation is never inside a timing.

    The one timing loop in the codebase: ``measure_candidates`` uses the
    median (robust to scheduler noise in small-rep autotuning),
    ``dataset.collect_measured`` the min (paper-style best-case).
    Operands must be concrete arrays: tracers mean the caller is inside a
    trace, where a timing would measure tracing (``run_outside_trace``).
    """
    import jax

    if any(isinstance(x, jax.core.Tracer) for x in operands):
        raise TypeError(
            "bench_fn needs concrete operands, got tracers: call it outside "
            "the jit trace (measure.run_outside_trace)"
        )
    compiled = jax.jit(fn).lower(*operands).compile()
    for _ in range(max(1, warmup)):
        jax.block_until_ready(compiled(*operands))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*operands))
        ts.append(time.perf_counter() - t0)
    return float(statistics.median(ts) if stat == "median" else min(ts))


def operand_shapes(op: str, m: int, n: int, k: int, g: int = 1):
    """Storage-layout operand shapes of one op (``core/opkey.py``).
    Batched ops get 3-D shapes with the leading batch extent ``g``; the
    attention subgraph op gets three (q, k, v) shapes with the OpKey's
    extents read as (m queries, n keys, k head-dim) per slice."""
    check_op(op)
    if op == "ATTN":
        return (g, m, k), (g, n, k), (g, n, k)
    if op == "BNT":
        return (g, m, k), (g, n, k)
    if op == "BNN":
        return (g, m, k), (g, k, n)
    if op == "NT":
        return (m, k), (n, k)
    if op == "NN":
        return (m, k), (k, n)
    return (k, m), (k, n)  # TN


def measure_candidates(
    m: int,
    n: int,
    k: int,
    dtype: str = "float32",
    op: str = "NT",
    g: int = 1,
    candidates: Optional[Sequence[str]] = None,
    hardware: Optional[HardwareSpec] = None,
    distributed: bool = False,
    mem_budget_frac: float = 0.9,
    warmup: int = 1,
    reps: int = 3,
    seed: int = 0,
    tune: bool = True,
    max_tile_configs: int = 4,
    retries: int = 1,
    retry_backoff_s: float = 0.02,
    attempts: Optional[Dict[str, Dict[str, int]]] = None,
) -> Dict[str, Dict[str, float]]:
    """Time every admissible (candidate, tile config) for one
    (op, g, shape) on this backend; returns ``{name: {config_key:
    seconds}}``.

    Operands are built in ``op``'s storage layout — batched ops get 3-D
    operands with the leading batch extent ``g`` — and only candidates
    implementing the op are considered.  Tunable candidates are swept over
    their roofline-pruned config shortlist (``tune=False`` restricts them
    to the default tiling); non-tunable candidates are timed once under
    ``"default"``.  Admissibility is the shared guard set from
    ``candidates.py`` — the paper's OOM check (extra-memory candidates must
    fit the budget), the distributed/platform filter, and the VMEM budget
    per config — so an autotune run can never execute a pair the dispatch
    engine would refuse.  Inadmissible pairs are skipped, not timed; the
    result may be empty.

    A pair that raises is retried up to ``retries`` more times with
    exponential backoff (transient allocation/compile hiccups recover); a
    pair that keeps failing is not a measurement, and says so with a
    warning.  ``KeyboardInterrupt``/``SystemExit`` always propagate.  When
    the caller passes an ``attempts`` dict, the try count of every
    successful measurement is recorded into it as ``{name: {config_key:
    n}}`` — AutotunePolicy persists that beside the cache entry.

    The sweep runs on its own thread (``run_outside_trace``), so a call
    made while a ``jit`` traces — where selection happens — still times
    compiled code executing on the device.
    """
    import functools
    import warnings

    import jax
    import jax.numpy as jnp

    from repro.kernels.tiling import DEFAULT_CONFIG_KEY, config_key

    hw = hardware or device_spec()
    names = tuple(candidates or CANDIDATES)
    dt = jnp.dtype(dtype)
    dsize = dt.itemsize
    shapes = operand_shapes(op, m, n, k, g)

    def sweep_all() -> Dict[str, Dict[str, float]]:
        times: Dict[str, Dict[str, float]] = {}
        op_keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
        operands = tuple(
            jax.random.normal(kk, s, dtype=dt)
            for kk, s in zip(op_keys, shapes)
        )
        for name in names:
            cand = get_candidate(name)
            if not candidate_fits_memory(
                cand, m, n, k, dsize, hw.mem_gib, mem_budget_frac, op=op, g=g
            ):
                continue  # OOM guard: never materialise an over-budget transpose
            if not candidate_allowed(cand, distributed, op=op):
                continue
            if cand.tunable and tune:
                sweep = [
                    (config_key(cfg), cfg)
                    for cfg in cand.config_space(
                        m, n, k, dsize, max_configs=max_tile_configs, hardware=hw
                    )
                ]
            else:
                sweep = [(DEFAULT_CONFIG_KEY, None)]
            entry: Dict[str, float] = {}
            entry_tries: Dict[str, int] = {}
            for ck, cfg in sweep:
                # Candidate.run is the dispatch engine's invocation path —
                # time exactly what a dispatch at this config would execute
                fn = functools.partial(cand.run, config=cfg)
                n_try = 0
                while n_try <= retries:
                    n_try += 1
                    try:
                        faults.check_measure_fault(name, op)
                        entry[ck] = bench_fn(
                            fn, *operands, reps=reps, warmup=warmup
                        )
                        entry_tries[ck] = n_try
                        break
                    except (KeyboardInterrupt, SystemExit):
                        raise  # user/runtime interrupts are never a retry
                    except Exception as e:
                        # a pair that cannot run here (compile refusal,
                        # allocation failure, ...): back off and retry a
                        # bounded number of times; a persistent failure is
                        # not a measurement — selection proceeds over those
                        # that ran
                        if n_try <= retries:
                            time.sleep(retry_backoff_s * (2 ** (n_try - 1)))
                        else:
                            warnings.warn(
                                f"measurement of {name}@{ck} on {op} "
                                f"g={g} {m}x{n}x{k} {dtype} failed "
                                f"({type(e).__name__}: {e}); not timed",
                                UserWarning,
                            )
            if entry:
                times[name] = entry
                if attempts is not None:
                    attempts[name] = entry_tries
        return times

    return run_outside_trace(sweep_all)


def top_configs_by_candidate(
    cache: "MeasurementCache",
    dtype: Optional[str] = None,
    platform: Optional[str] = None,
    op: Optional[str] = None,
) -> Dict[str, str]:
    """Per candidate, the *modal* winning config key across all matching
    cache records — the shape-independent tile summary (v2 artifacts
    carried exactly this; v3 artifacts keep it as the ``"modal"`` fallback
    of their per-shape tables).  Only explicit tiles count: candidates
    whose wins are all at the ``"default"`` tiling (non-tunable XLA arms,
    ``tune=False`` sweeps) carry no entry — an artifact should list
    *learned* tiles, not restate the default."""
    from repro.kernels.tiling import DEFAULT_CONFIG_KEY

    wins: Dict[str, Dict[str, int]] = {}
    for (rec_platform, _hw, rec_dtype, rec_op, *_mnk), times in cache.records():
        if platform is not None and rec_platform != platform:
            continue
        if dtype is not None and rec_dtype != dtype:
            continue
        if op is not None and rec_op != op:
            continue
        for name, (ck, _t) in best_times(times).items():
            if ck == DEFAULT_CONFIG_KEY:
                continue
            wins.setdefault(name, {})
            wins[name][ck] = wins[name].get(ck, 0) + 1
    # deterministic tie-break: highest count, then lexicographic key
    return {
        name: min(counts, key=lambda ck: (-counts[ck], ck))
        for name, counts in wins.items()
    }


def tile_tables_from_cache(
    cache: "MeasurementCache",
    dtype: Optional[str] = None,
    platform: Optional[str] = None,
) -> Dict[str, Dict[str, Dict]]:
    """Per-op, per-candidate tile tables for a v3 selector artifact:
    ``{op: {name: {"modal": key, "by_shape": {"MxNxK": key}}}}``.

    ``by_shape`` holds each measured shape's winning explicit tile (the
    per-shape table the ROADMAP asked for — a ``ModelPolicy`` dispatches
    the exact tuned tile on shapes the cache saw, and the nearest recorded
    shape's tile otherwise); ``"modal"`` is the shape-independent summary
    (``top_configs_by_candidate``) kept as the terminal fallback.  Default
    ("default"-key) wins are omitted, as in the modal summary."""
    from repro.kernels.tiling import DEFAULT_CONFIG_KEY

    tables: Dict[str, Dict[str, Dict]] = {}
    # one pass: per-shape winners and the modal tally come from the same
    # best_times() fold of each record
    wins: Dict[Tuple[str, str], Dict[str, int]] = {}
    for (rec_platform, _hw, rec_dtype, rec_op, _g, m, n, k), times in cache.records():
        if platform is not None and rec_platform != platform:
            continue
        if dtype is not None and rec_dtype != dtype:
            continue
        for name, (ck, _t) in best_times(times).items():
            if ck == DEFAULT_CONFIG_KEY:
                continue
            entry = tables.setdefault(rec_op, {}).setdefault(
                name, {"modal": None, "by_shape": {}}
            )
            entry["by_shape"][shape_key((m, n, k))] = ck
            counts = wins.setdefault((rec_op, name), {})
            counts[ck] = counts.get(ck, 0) + 1
    for (op, name), counts in wins.items():
        # same deterministic tie-break as top_configs_by_candidate
        tables[op][name]["modal"] = min(
            counts, key=lambda ck: (-counts[ck], ck)
        )
    return tables


def measure_transpose_configs(
    rows: int,
    cols: int,
    dtype: str = "float32",
    reps: int = 3,
    warmup: int = 1,
    max_configs: int = 4,
    hardware: Optional[HardwareSpec] = None,
    seed: int = 0,
) -> Dict[str, float]:
    """Autotune the out-of-place transpose kernel's 2-D (b_rows, b_cols)
    tile space for one (rows, cols) operand: time the roofline-ranked
    shortlist (``kernels.tiling.transpose_config_space``) plus the
    kernel-default tiling, returning ``{config_key: seconds}``.  The
    transpose is the second stage of the TNN/TN candidates, so a tuned
    ``tblock`` feeds ``ops.matmul_tnn`` / ``ops.matmul_tn`` directly."""
    import warnings

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.tiling import (
        DEFAULT_CONFIG_KEY,
        config_key,
        transpose_config_space,
    )

    hw = hardware or device_spec()
    dt = jnp.dtype(dtype)

    def sweep_all() -> Dict[str, float]:
        times: Dict[str, float] = {}
        b = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols), dtype=dt)
        sweep = [(DEFAULT_CONFIG_KEY, None)] + [
            (config_key(cfg), cfg)
            for cfg in transpose_config_space(
                rows, cols, dt.itemsize, max_configs=max_configs, hardware=hw
            )
        ]
        for ck, cfg in sweep:
            try:
                times[ck] = bench_fn(
                    lambda x, _cfg=cfg: ops.transpose(x, block=_cfg),
                    b, reps=reps, warmup=warmup,
                )
            except (KeyboardInterrupt, SystemExit):
                raise  # user/runtime interrupts are never swallowed
            except Exception as e:
                warnings.warn(
                    f"measurement of transpose@{ck} at {rows}x{cols} {dtype} "
                    f"failed ({type(e).__name__}: {e}); not timed",
                    UserWarning,
                )
        return times

    return run_outside_trace(sweep_all)


def best_transpose_config(
    rows: int, cols: int, **kw
) -> Optional[Tuple[int, int]]:
    """The measured-fastest transpose tile for this operand, or None when
    the kernel default wins (or nothing could be measured)."""
    from repro.kernels.tiling import DEFAULT_CONFIG_KEY, parse_config_key

    times = measure_transpose_configs(rows, cols, **kw)
    if not times:
        return None
    ck = min(times, key=times.get)
    if ck == DEFAULT_CONFIG_KEY:
        return None
    return parse_config_key(ck, arity=2)
