"""Selection policies + context-scoped dispatch.

The paper's contribution is *which implementation of a dense layer's GEMMs
to run for a given shape*.  This module makes that decision a first-class,
pluggable policy instead of a module-global selector threaded through
every layer:

    with use_policy(FixedPolicy("XLA_TNN")):
        logits = lm.lm_forward(params, cfg, batch)   # every NT op -> XLA_TNN

The selection space is the full *(op x batch x shape x tile config)*
product: every policy's ``select`` takes an ``OpKey`` (``core/opkey.py``
— the forward NT, the backward NN/TN gradient GEMMs, and the batched
BNT/BNN attention contractions with their collapsed batch extent ``g``)
and returns a ``Decision(name, config)`` — the candidate to run and, for
tunable (Pallas) candidates, the ``(bm, bn, bk)`` VMEM tile to run it at
(``config=None`` means the kernel's built-in default tiling).

Policies implement the ``SelectionPolicy`` protocol (``select`` + ``stats``)
and are scoped with a ``contextvars.ContextVar``, so nested ``with`` blocks
restore the outer policy on exit and concurrent threads / asyncio tasks see
independent policies — the prerequisite for per-request policies in serving.
One ``use_policy(...)`` scope governs all three GEMMs of every dense layer:
``engine.dispatch`` is ``custom_vjp``-wrapped, and its backward rule
rebuilds NN/TN OpKeys and re-enters dispatch (wrap the whole
``value_and_grad`` call in the scope, not just the forward).

The policy zoo:

  ModelPolicy     the paper's learned selector (GBDT binary or k-way);
                  tile from the artifact's learned per-candidate config
  FixedPolicy     force one candidate (and optionally one tile) everywhere
  AnalyticPolicy  roofline/cost-model argmin over candidates, then over
                  tiles (``simulate.tile_time``) — no training data needed
  CascadePolicy   ordered preference list with OOM + distributed fallback
  AutotunePolicy  argmin of *on-device measurements* over the full
                  (candidate x config) space (core/measure.py);
                  measures-and-caches cold shapes, announced analytic
                  fallback when measurement is impossible (e.g.
                  multi-device pjit)

All selection runs at *trace* time under ``jit`` (JAX shapes are static),
so every policy's compiled-step overhead is exactly zero — the paper's
0.005 ms/call prediction cost disappears (benchmarks/policy_overhead.py
measures this).
"""

from __future__ import annotations

import contextlib
import contextvars
import warnings
from typing import (
    Dict,
    Iterator,
    NamedTuple,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    runtime_checkable,
)

from . import faults
from .candidates import (
    CANDIDATES,
    DEFAULT_BY_OP,
    Candidate,
    candidate_allowed,
    candidate_fits_memory,
    current_platform,
    get_candidate,
)
from .hardware import HardwareSpec, device_spec, target_spec
from .opkey import OPS, OpKey, check_op, coerce_key

__all__ = [
    "OpKey",
    "OPS",
    "Decision",
    "SelectionPolicy",
    "PolicyBase",
    "ModelPolicy",
    "FixedPolicy",
    "AnalyticPolicy",
    "CascadePolicy",
    "AutotunePolicy",
    "use_policy",
    "current_policy",
    "default_policy",
]


class Decision(NamedTuple):
    """One dispatch decision: the candidate to run and the tile config to
    run it at.  ``config=None`` means the candidate's default tiling (the
    only option for non-tunable candidates)."""

    name: str
    config: Optional[Tuple[int, int, int]] = None

    def label(self) -> str:
        """Report form: ``NAME`` or ``NAME@BMxBNxBK``."""
        if self.config is None:
            return self.name
        from repro.kernels.tiling import config_key

        return f"{self.name}@{config_key(self.config)}"


@runtime_checkable
class SelectionPolicy(Protocol):
    """Anything that can pick a (candidate, tile config) for an ``OpKey``.
    ``select`` takes an ``OpKey`` and returns a ``Decision`` (the legacy
    positional/bare-string conventions were removed after their
    deprecation release; the engine raises a clean error on them).

    ``stats`` must expose ``calls: int`` and ``by_candidate: Dict[str, int]``
    (see ``selector.SelectorStats``) so dispatch decisions stay observable.
    """

    stats: "object"

    def select(self, key: "OpKey") -> "Decision":
        ...


class PolicyBase:
    """Shared guards: the paper's OOM check + distributed-safety and
    op-support filters."""

    def __init__(
        self,
        hardware: Optional[HardwareSpec] = None,
        distributed: bool = False,
        mem_budget_frac: float = 0.9,
    ):
        from .selector import SelectorStats  # local: avoid import cycle

        self.hardware = hardware or target_spec()
        self.distributed = distributed
        self.mem_budget_frac = mem_budget_frac
        self.stats = SelectorStats()
        self._q_epoch = faults.quarantine_epoch()

    def _sync_quarantine(self, *memos: Dict) -> None:
        """Drop memoised decisions when the quarantine ledger changed
        since they were cached: a memo hit must never resurrect an arm
        that has since been quarantined (or keep avoiding one that was
        cleared).  One int compare when nothing changed."""
        epoch = faults.quarantine_epoch()
        if epoch != self._q_epoch:
            self._q_epoch = epoch
            for memo in memos:
                memo.clear()

    def _admissible(self, cand: Candidate, key: OpKey, config=None) -> bool:
        return candidate_fits_memory(
            cand, key.m, key.n, key.k, key.dsize,
            self.hardware.mem_gib, self.mem_budget_frac, config=config,
            op=key.op, g=key.g,
        ) and candidate_allowed(
            cand, self.distributed, config=config, op=key.op
        )

    def select(self, key: OpKey) -> Decision:
        raise NotImplementedError


class FixedPolicy(PolicyBase):
    """Always run one candidate per op — baselines and forced A/B arms.

    Single-name form: ``FixedPolicy("PALLAS_NT")`` forces that candidate
    for the op kinds it implements; other ops (e.g. the backward NN/TN
    GEMMs of a training step) degrade to the op's XLA reference
    (``DEFAULT_BY_OP``) so the forced arm can still train.  An optional
    ``config`` forces one tile too (tunable candidates only):
    ``FixedPolicy("PALLAS_NT", config=(256, 256, 512))`` is the forced arm
    of a tile A/B test.

    Op-qualified form: ``FixedPolicy(by_op={"NT": "XLA_NT", "NN":
    ("PALLAS_NN", (128, 128, 128))})`` forces a (candidate, tile) per op —
    the ``fixed:nt=...,nn=...`` spec grammar builds this.
    """

    def __init__(
        self,
        name: Optional[str] = None,
        config: Optional[Tuple[int, int, int]] = None,
        by_op: Optional[Dict[str, object]] = None,
        **kw,
    ):
        super().__init__(**kw)
        if name is None and not by_op:
            raise ValueError("FixedPolicy needs a candidate name or a by_op table")
        if name is None and config is not None:
            raise ValueError("FixedPolicy(config=...) needs a candidate name")
        self.by_op: Dict[str, Tuple[str, Optional[Tuple[int, int, int]]]] = {}
        for op, entry in (by_op or {}).items():
            check_op(op)
            cand_name, cfg = entry if isinstance(entry, tuple) else (entry, None)
            self.by_op[op] = (cand_name, self._validate(cand_name, cfg, op=op))
        self.name = name
        self.config = None
        if name is not None:
            self.config = self._validate(name, config)
            for op in get_candidate(name).ops:
                self.by_op.setdefault(op, (name, self.config))

    @staticmethod
    def _validate(name, config, op: Optional[str] = None):
        cand = get_candidate(name)  # fail fast on unknown names
        if op is not None and op not in cand.ops:
            raise ValueError(
                f"candidate {name!r} does not implement op {op!r} "
                f"(implements {cand.ops})"
            )
        if config is not None:
            from repro.kernels.tiling import validate_config

            config = validate_config(config, arity=cand.config_arity)
            if not cand.tunable:
                raise ValueError(
                    f"candidate {name!r} is not tunable; it cannot take a "
                    f"forced tile config {config}"
                )
        return config

    def select(self, key: OpKey) -> Decision:
        key = coerce_key(key)
        entry = self.by_op.get(key.op)
        if entry is None:
            # op not forced (e.g. a backward GEMM under a forced forward
            # arm): run the op's reference instead of mis-dispatching
            entry = (DEFAULT_BY_OP[key.op], None)
        decision = Decision(*entry)
        self.stats.record(decision.name, decision.config, op=key.op)
        return decision

    def __repr__(self):
        if self.name is not None and self.config is not None:
            return f"FixedPolicy({self.name!r}, config={self.config})"
        if self.name is not None:
            return f"FixedPolicy({self.name!r})"
        table = {
            op: Decision(*entry).label() for op, entry in self.by_op.items()
        }
        return f"FixedPolicy(by_op={table})"


class ModelPolicy:
    """The paper's learned selector as a policy.

    Thin adapter over ``MTNNSelector`` (which already implements the GBDT /
    k-way decision, shape cache, OOM guard and distributed filter); stats
    are the selector's own, so a report covers dispatches made through
    either API.  The tile config comes from the selector's learned
    per-candidate ``tile_configs`` (v2 artifacts trained from autotune
    caches carry one; otherwise the kernel default applies).
    """

    def __init__(self, selector=None):
        if selector is None:
            from .selector import default_selector

            selector = default_selector()
        self.selector = selector

    @classmethod
    def from_artifact(cls, path: str, **kw) -> "ModelPolicy":
        from .selector import MTNNSelector

        return cls(MTNNSelector.load(path, **kw))

    @property
    def stats(self):
        return self.selector.stats

    def select(self, key: OpKey) -> Decision:
        key = coerce_key(key)
        name = self.selector.select(key)
        # tile_config_for validates the learned tile for *this* dispatch
        # (tunability + VMEM at this dsize): an infeasible artifact entry
        # degrades to the kernel default, never to a VMEM bust.  Per-shape
        # table entries (nearest-shape fallback) win over the modal tile.
        return Decision(
            name,
            self.selector.tile_config_for(
                name, key.dsize, op=key.op, mnk=key.mnk()
            ),
        )

    def __repr__(self):
        return f"ModelPolicy(mode={self.selector.mode!r}, hw={self.selector.hardware.name!r})"


class AnalyticPolicy(PolicyBase):
    """Roofline argmin: pick the candidate whose analytic-cost-model arm
    (``core/simulate.py``) predicts the lowest time, then rank its tile
    configs with the roofline tile model (``simulate.tile_time``:
    arithmetic intensity of the padded problem vs VMEM residency of the
    blocks) and attach the winner.  Needs no training data — the zero-shot
    fallback for hardware with no measured dataset, and the reason the
    autotune fallback is not blind to tiling.
    """

    def __init__(
        self,
        hardware: Optional[HardwareSpec] = None,
        candidates: Optional[Sequence[str]] = None,
        sigma: float = 0.0,  # deterministic by default: no modelled noise
        **kw,
    ):
        super().__init__(hardware=hardware, **kw)
        self.candidates = tuple(candidates or CANDIDATES)
        for name in self.candidates:
            get_candidate(name)
        self.sigma = sigma
        # keyed by platform too: admissibility depends on jax.default_backend(),
        # so a decision cached under one backend must not replay on another
        self._cache: Dict[Tuple[str, OpKey], Decision] = {}

    def _best_config(self, cand: Candidate, key: OpKey):
        """Roofline-ranked tile for a tunable candidate (None otherwise).
        Fused-attention candidates (``config_arity == 2``) rank their
        (bq, bk) space with the attention tile model instead."""
        from repro.kernels.tiling import (
            enumerate_attn_configs,
            enumerate_tile_configs,
        )

        from .simulate import attn_tile_time, tile_time

        if not cand.tunable:
            return None
        best_cfg, best_t = None, None
        # the raw enumeration, not the shortlist: ranking happens right
        # here on self.hardware, so a pre-sorted list would be wasted work
        if cand.config_arity == 2:
            for cfg in enumerate_attn_configs(key.m, key.n, key.k, key.dsize):
                if not self._admissible(cand, key, config=cfg):
                    continue
                t = attn_tile_time(
                    self.hardware, key.m, key.n, key.k, key.dsize, block=cfg
                )
                if best_t is None or t < best_t:
                    best_t, best_cfg = t, cfg
            return best_cfg
        for cfg in enumerate_tile_configs(key.m, key.n, key.k, key.dsize):
            if not self._admissible(cand, key, config=cfg):
                continue
            t = tile_time(self.hardware, key.m, key.n, key.k, key.dsize, cfg)
            if best_t is None or t < best_t:
                best_t, best_cfg = t, cfg
        return best_cfg

    def select(self, key: OpKey) -> Decision:
        from .simulate import simulate_time

        key = coerce_key(key)
        self._sync_quarantine(self._cache)
        cache_key = (current_platform(), key)
        decision = self._cache.get(cache_key)
        if decision is None:
            best_t, name = None, None
            for cand_name in self.candidates:
                cand = get_candidate(cand_name)
                if not self._admissible(cand, key):
                    continue
                t = simulate_time(
                    self.hardware, cand.sim_algo, key.m, key.n, key.k,
                    key.dsize, sigma=self.sigma, g=key.g,
                )
                if best_t is None or t < best_t:
                    best_t, name = t, cand_name
            if name is None:  # nothing admissible: the op's reference fallback
                decision = Decision(DEFAULT_BY_OP[key.op], None)
            else:
                decision = Decision(
                    name, self._best_config(get_candidate(name), key)
                )
            self._cache[cache_key] = decision
        self.stats.record(decision.name, decision.config, op=key.op)
        return decision

    def __repr__(self):
        return f"AnalyticPolicy(hw={self.hardware.name!r}, candidates={self.candidates})"


class CascadePolicy(PolicyBase):
    """Ordered preference list: first admissible candidate wins.

    Admissibility honours the paper's OOM guard (extra-memory candidates
    must fit the budget) and the distributed-safety filter.  The *last*
    entry is the unconditional fallback — it is returned even when its own
    guards fail, so the cascade always produces a runnable candidate
    (mirror of the paper's "if B^T does not fit, use NT").
    """

    def __init__(self, names: Sequence[str], **kw):
        super().__init__(**kw)
        names = tuple(names)
        if not names:
            raise ValueError("CascadePolicy needs at least one candidate name")
        for name in names:
            get_candidate(name)
        self.names = names

    def select(self, key: OpKey) -> Decision:
        key = coerce_key(key)
        chosen = None
        for name in self.names:
            if self._admissible(get_candidate(name), key):
                chosen = name
                break
        if chosen is None:
            # unconditional fallback: the last entry when it can run this op
            # at all, else the op's reference (a cascade written for the
            # forward op must not mis-dispatch a backward GEMM)
            last = self.names[-1]
            chosen = (
                last
                if key.op in get_candidate(last).ops
                else DEFAULT_BY_OP[key.op]
            )
        self.stats.record(chosen, op=key.op)
        return Decision(chosen, None)

    def __repr__(self):
        return f"CascadePolicy({list(self.names)!r})"


class AutotunePolicy(PolicyBase):
    """Measurement-backed selection: argmin of *on-device* timings over the
    two-level (candidate x tile config) space.

    ``select`` answers from a persistent ``MeasurementCache`` (warm hit);
    on a cold shape it measures every admissible candidate — tunable ones
    across their roofline-pruned config shortlist (``max_tile_configs``
    wide) — right there at trace time (``core/measure.py`` times compiled
    executables on the device from a thread outside the trace), stores
    the result, and persists the cache.  When measurement is disabled or
    impossible — ``measure=False``, ``distributed=True`` (multi-device
    pjit traces run on placeholder devices), an unmeasurable dtype, a
    shape over ``max_measure_flops``, or no arm that ran — it falls back
    to ``AnalyticPolicy`` (which ranks tiles by the roofline model) so
    dispatch always proceeds, tiled; each such key is counted in
    ``n_fallbacks`` and announced once with a warning.

    Cache keys include the jax platform and the measuring device's
    descriptor (``hardware.device_spec``), so one file can hold
    measurements from several backends without cross-talk.
    """

    def __init__(
        self,
        cache=None,
        cache_path: Optional[str] = None,
        hardware: Optional[HardwareSpec] = None,
        candidates: Optional[Sequence[str]] = None,
        measure: bool = True,
        warmup: int = 1,
        reps: int = 3,
        max_measure_flops: float = 1e11,
        tune: bool = True,
        max_tile_configs: int = 4,
        **kw,
    ):
        from .measure import MeasurementCache

        super().__init__(hardware=hardware or device_spec(), **kw)
        if cache is None:
            # recover=True: a corrupt/truncated cache file is moved aside
            # and rebuilt empty — autotune re-measures instead of crashing
            cache = (
                MeasurementCache.load(cache_path, recover=True)
                if cache_path
                else MeasurementCache()
            )
        elif cache_path is not None:
            # a caller handing both means "use this cache, persist it here"
            cache.path = cache_path
        self.cache = cache
        self.candidates = tuple(candidates or CANDIDATES)
        for name in self.candidates:
            get_candidate(name)
        self.measure = measure
        self.warmup = warmup
        self.reps = reps
        self.max_measure_flops = max_measure_flops
        self.tune = tune
        self.max_tile_configs = max_tile_configs
        # the fallback honours the same candidate restriction, so a policy
        # scoped to a subset can never dispatch outside it via the fallback
        self.fallback = AnalyticPolicy(
            hardware=self.hardware,
            candidates=self.candidates,
            distributed=self.distributed,
            mem_budget_frac=self.mem_budget_frac,
        )
        # observability: cold shapes measured / warm hits / analytic fallbacks
        self.n_measured = 0
        self.n_cache_hits = 0
        self.n_fallbacks = 0
        # shapes where measurement produced nothing — don't retry them every
        # select (in-memory only: a later session/platform may succeed)
        self._unmeasurable: set = set()
        self._warned: set = set()  # keys whose fallback was announced
        # platform-keyed decision memo (same pattern as MTNNSelector /
        # AnalyticPolicy): repeat selects skip the re-filter + argmin scan
        self._decisions: Dict[Tuple[str, OpKey], Decision] = {}

    def _why_not_measured(self, dtype: Optional[str], flops: float) -> Optional[str]:
        """Why a cold key cannot be measured here (None: it can be)."""
        if not self.measure:
            return "measurement is disabled"
        if self.distributed:
            return "multi-device programs are not measured"
        if dtype is None:
            return "its element size has no measurable dtype"
        if flops > self.max_measure_flops:
            return f"{flops:.3g} FLOPs exceed max_measure_flops"
        return None

    def select(self, key: OpKey) -> Decision:
        from repro.kernels.tiling import parse_config_key

        from .measure import DTYPE_BY_DSIZE, measure_candidates

        key = coerce_key(key)
        self._sync_quarantine(self._decisions)
        platform = current_platform()
        memo_key = (platform, key)
        hit = self._decisions.get(memo_key)
        if hit is not None:
            self.n_cache_hits += 1
            self.stats.record(hit.name, hit.config, op=key.op)
            return hit
        dtype = DTYPE_BY_DSIZE.get(key.dsize)
        cache_key = (
            platform,
            self.hardware.name,
            dtype or f"{8 * key.dsize}-bit",
            key.op,
            key.g,
            key.m,
            key.n,
            key.k,
        )
        times = self.cache.get(cache_key)
        why = None
        if times is not None:
            self.n_cache_hits += 1
        elif cache_key in self._unmeasurable:
            why = "no arm could be measured"
        else:
            why = self._why_not_measured(
                dtype, 2.0 * key.g * key.m * key.n * key.k
            )
        if times is None and why is None:
            attempts: Dict[str, Dict[str, int]] = {}
            times = measure_candidates(
                key.m, key.n, key.k,
                dtype=dtype,
                op=key.op,
                g=key.g,
                candidates=self.candidates,
                hardware=self.hardware,
                distributed=self.distributed,
                mem_budget_frac=self.mem_budget_frac,
                warmup=self.warmup,
                reps=self.reps,
                tune=self.tune,
                max_tile_configs=self.max_tile_configs,
                attempts=attempts,
            )
            if times:
                self.cache.put(cache_key, times, attempts=attempts)
                self.n_measured += 1
                if self.cache.path:
                    self.cache.save()
            else:
                self._unmeasurable.add(cache_key)
                why = "no arm could be measured"
        decision = None
        if times:
            # re-filter at use time: cached entries may predate a registry /
            # distributed-mode / candidate-restriction change, and pairs the
            # policy would not measure itself must never dispatch — the
            # admissibility check is config-aware (VMEM budget included)
            # and op-aware (an NT entry can never answer an NN key)
            best = None
            for cand_name, cfgs in times.items():
                if cand_name not in self.candidates or cand_name not in CANDIDATES:
                    continue
                cand = get_candidate(cand_name)
                for cfg_key, t in cfgs.items():
                    try:
                        cfg = parse_config_key(cfg_key, arity=cand.config_arity)
                    except ValueError:
                        continue  # corrupt/foreign key: never dispatch it
                    if not self._admissible(cand, key, config=cfg):
                        continue
                    if best is None or t < best:
                        best, decision = t, Decision(cand_name, cfg)
        if decision is not None:
            self._decisions[memo_key] = decision
        else:
            # fallback decisions are not memoized: AnalyticPolicy has its
            # own platform-keyed memo, and a later measurement may succeed
            self.n_fallbacks += 1
            decision = self.fallback.select(key)
            if cache_key not in self._warned:
                self._warned.add(cache_key)
                warnings.warn(
                    f"AutotunePolicy: {key} has no usable measurement "
                    f"({why or 'no measured arm is admissible'}); the "
                    f"analytic model picks {decision.label()}",
                    UserWarning,
                    stacklevel=2,
                )
        self.stats.record(decision.name, decision.config, op=key.op)
        return decision

    def __repr__(self):
        return (
            f"AutotunePolicy(hw={self.hardware.name!r}, "
            f"cache={len(self.cache)} shapes, path={self.cache.path!r}, "
            f"measure={self.measure})"
        )


# -- context scoping ----------------------------------------------------------

_POLICY: contextvars.ContextVar[Optional[SelectionPolicy]] = contextvars.ContextVar(
    "repro_selection_policy", default=None
)

# Default-policy cache: one ModelPolicy per default MTNNSelector instance,
# so `set_default_selector` swaps are honoured without rebuilding stats.
_default_pair: Tuple[Optional[object], Optional[ModelPolicy]] = (None, None)


def default_policy() -> SelectionPolicy:
    """The ambient policy: the learned selector (artifact or freshly
    trained), distributed-safe — what dispatch uses outside any
    ``use_policy`` scope."""
    global _default_pair
    from .selector import default_selector

    sel = default_selector()
    cached_sel, cached_pol = _default_pair
    if cached_sel is not sel:
        cached_pol = ModelPolicy(sel)
        _default_pair = (sel, cached_pol)
    return cached_pol


def current_policy() -> SelectionPolicy:
    """The policy in scope: innermost ``use_policy`` or the default."""
    pol = _POLICY.get()
    return pol if pol is not None else default_policy()


@contextlib.contextmanager
def use_policy(policy) -> Iterator[SelectionPolicy]:
    """Scope ``policy`` over a ``with`` block.

    Accepts a ``SelectionPolicy`` or a bare candidate name (sugar for
    ``FixedPolicy``).  Nesting restores the outer policy on exit; threads
    and asyncio tasks each see their own stack (``contextvars``), so
    concurrent serve requests can run different policies simultaneously.
    """
    if isinstance(policy, str):
        policy = FixedPolicy(policy)
    token = _POLICY.set(policy)
    try:
        yield policy
    finally:
        _POLICY.reset(token)
