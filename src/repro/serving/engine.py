"""Continuous-batching serving engine with per-request-class policy scopes.

The paper's end-to-end claim is that algorithm selection pays off *inside
a real workload driver*, not on isolated GEMMs.  This engine is that
driver for inference: a request-queue server on top of the dispatch
machinery, replacing the fixed-batch prefill/decode demo.

Lifecycle of a request (``Request``/``RequestState``):

  QUEUED            submitted, waiting FCFS for a slot + admission budget
  ACTIVE            admitted: prefilled into a ``PagedKVCache`` slot, decoding,
                    or out of the batch with its last token still in flight
  FINISHED          every one of its ``max_new`` tokens (or as many as the
                    cache extent holds) has reached the host
  EVICTED           cancelled mid-stream (or its decode step crashed);
                    its slot is freed and reused
  DEADLINE_EXCEEDED its wall-clock deadline passed; evicted between
                    decode steps (queued or active alike)

Robustness (the fault-tolerance layer, ``core/faults.py``):

  * **Deadlines** — ``submit(..., deadline_s=...)`` bounds a request's
    wall-clock residency; ``step()`` expires overdue requests *before*
    spending a decode step on them.
  * **Backpressure** — the admission queue is bounded (``max_queue``);
    ``submit`` raises ``QueueFullError`` instead of growing without
    bound (callers shed load explicitly).
  * **Crash containment** — a decode/prefill step that raises, at its
    launch or when its tokens are fetched, evicts only the requests in
    that batch and counts a ``crashed_steps``; the serve loop keeps
    going.  Candidate-level failures never get this far: dispatch
    degrades down the fallback chain inside the trace
    (``core/engine.run_decision``), so a fault-injected Pallas kernel
    quarantines itself and the step completes on the XLA reference —
    chaos-tested in ``tests/test_faults.py``.

Each step **admits** queued requests (FCFS, gated by free slots and a
max-tokens admission budget) and recomposes the decode batch, so short
requests never hold the batch hostage for long ones (continuous
batching).  Ragged lengths coexist in one cache because every slot
carries its own write position (``attention_decode``'s per-sequence
``pos`` vector + validity mask).

**Overlap.**  A decode's input tokens stay on the device
(``PagedKVCache.tokens``), and nothing else about a row's next step
depends on a token's value: its length is known on the host, and it
stops after a count of tokens (``max_new`` or the cache extent), never
on a token.  So ``step()`` launches this step's decode for every live
row *before* it blocks on the previous step's tokens, and the chip has
the next program queued while the host fetches, retires, admits and
returns to its caller.  A request's decode tokens reach the host (and
``generated``) one step after their decode is launched; its prefill's
first token arrives in the step that admits it.  A row leaves the batch,
returning its slot and budget, once its last token has been *launched*;
the request turns ``FINISHED`` once that token has *arrived*.  A token
in flight for a request evicted or expired meanwhile is discarded and
counted (``discarded_rows``); ``run()`` drains the step in flight.
The host work of a step walks only live requests (queued, decoding or
awaiting tokens), never every request the engine has served.

Every request carries a *class* (e.g. ``interactive`` / ``bulk``) mapped
to its own ``SelectionPolicy``.  Each class's steps are traced inside
``use_policy(policy)`` — the contextvar scoping from the dispatch engine
— so different classes route the *same* GEMM shapes through different
policies concurrently, and ``class_reports()`` renders one
``dispatch_report`` per class.

Observability: each phase of ``step()`` runs inside a profiler span
(``jax.profiler.TraceAnnotation``, nearly free while no trace is taken),
in this order: ``engine.expire``; ``engine.admit`` holding one
``engine.prefill`` (pad, launch, KV insert) per admitted request; per
class ``engine.decode`` (build inputs, launch, drop the rows whose last
token is launched); ``engine.decode_wait`` (blocked until the previous
step's decode tokens are computed); ``engine.prefill_wait`` (blocked
until this step's first tokens are computed); ``engine.fetch`` (their
copy to the host, started at launch) and ``engine.retire`` (tokens to
their requests, finished requests out).  A wait span ends when the
program it waits for has ended (or at once, when it already had), so
the benchmark's trace reduction anchors the host clock on it.
``stats()`` returns cumulative counters of the same phases, with the
seconds blocked on the device kept apart from host seconds;
``launch/serve.py`` prints them per step.  Each request records its
admission time and the time each of its tokens reached the host (the
step's return), on the clock of ``submit_time``.

Decode shapes are bucketed (``buckets.BucketSpec``): the active batch
rounds up to a small bucket set (padding rows target the cache's null
slot) and prompt lengths round up to a length grid (right-padded,
prefilled with ``true_len``).  ``warmup()`` pre-traces every bucketed
shape under every class policy before traffic is admitted — selection
runs at trace time, so this drives every OpKey the serve loop can emit
through the policy (for ``AutotunePolicy``: through ``core/measure.py``)
up front.  ``cold_misses()`` reports any post-warmup measurement; a
drained bucketed run reports zero.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import threading
import time
import warnings
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import dispatch_report
from repro.core.policy import SelectionPolicy, use_policy
from repro.distributed.context import use_mesh
from repro.kernels.attention_decode import block_len, live_blocks
from repro.models import lm

from .buckets import BucketSpec, default_buckets
from .kv_cache import PagedKVCache, pool_extents, put_rows, take_rows

__all__ = ["Request", "RequestState", "ServeEngine", "QueueFullError"]


class RequestState(enum.Enum):
    QUEUED = "queued"
    ACTIVE = "active"
    FINISHED = "finished"
    EVICTED = "evicted"
    DEADLINE_EXCEEDED = "deadline_exceeded"


# states a request never leaves (slot released, out of queue)
TERMINAL_STATES = (
    RequestState.FINISHED,
    RequestState.EVICTED,
    RequestState.DEADLINE_EXCEEDED,
)


class QueueFullError(RuntimeError):
    """Admission queue at capacity — explicit backpressure; the caller
    sheds or retries instead of the queue growing without bound."""


@dataclasses.dataclass
class Request:
    """One generation request and its runtime bookkeeping."""

    rid: int
    tokens: np.ndarray  # (prompt_len,) int32 prompt
    max_new: int
    cls: str = "interactive"
    deadline_s: Optional[float] = None  # wall-clock budget from submit
    # runtime state (engine-owned)
    state: RequestState = RequestState.QUEUED
    slot: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    # tokens launched on the device; ``generated`` trails by those in flight
    launched: int = 0
    submit_step: int = -1
    admit_step: int = -1
    finish_step: int = -1
    submit_time: float = 0.0  # monotonic wall clock at submit
    admit_time: Optional[float] = None  # same clock, at admission
    # same clock: the return of the step that emitted each token
    token_times: List[float] = dataclasses.field(default_factory=list)

    def overdue(self, now: float) -> bool:
        return (
            self.deadline_s is not None
            and now - self.submit_time >= self.deadline_s
        )

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[-1])

    @property
    def reserve(self) -> int:
        """Tokens this request can occupy — the admission-budget unit."""
        return self.prompt_len + self.max_new


def _policy_scope(policy: Optional[SelectionPolicy]):
    return use_policy(policy) if policy is not None else contextlib.nullcontext()


# host phases of one engine step, and the device waits kept apart from them
HOST_PHASES = ("expire", "admit", "prefill", "decode", "fetch", "retire")
WAIT_PHASES = ("prefill_wait", "decode_wait")


@dataclasses.dataclass
class _InFlight:
    """Tokens launched on the device and not yet on the host: one class's
    decode batch (``tok`` has a row per request, then padding rows), or
    one admitted request's first token."""

    what: str  # names the step in a crash warning
    reqs: List[Request]
    tok: Any
    decode: bool = False
    kv_blocks: int = 0  # (row, kv block) pairs the decode's kernel reads


class _PhaseClock:
    """Cumulative seconds per phase of ``ServeEngine.step``, each phase
    also a profiler span ``engine.<phase>``.  Phases nest (``prefill``
    inside ``admit``); each phase's seconds exclude the phases nested in
    it, so the phases of one step sum to its wall time.  Used from the
    engine thread only."""

    def __init__(self):
        self.seconds: Dict[str, float] = dict.fromkeys(HOST_PHASES + WAIT_PHASES, 0.0)
        self._nested: List[float] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        self._nested.append(0.0)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.seconds[name] += dt - self._nested.pop()
            if self._nested:
                self._nested[-1] += dt


class ServeEngine:
    """Request-queue engine: continuous batching over a paged KV cache.

    ``policies`` maps request classes to ``SelectionPolicy`` instances
    (``None`` = the ambient default policy).  Each class gets its own
    jitted prefill/decode steps so tracing — and therefore dispatch
    selection — happens under that class's scope; jit caches are per
    function object, so two classes never share a trace.

    ``budget_tokens`` caps the sum of ``prompt_len + max_new`` over
    admitted requests (default: ``n_slots * max_seq``, i.e. cache-bound).
    Admission is strictly FCFS: the head of the queue blocks until it
    fits (no starvation by skip-ahead).  ``max_queue`` bounds the waiting
    queue (default ``8 * n_slots``); a full queue rejects ``submit`` with
    ``QueueFullError``.  Per-request ``deadline_s`` budgets are enforced
    between decode steps (``DEADLINE_EXCEEDED``); ``health()`` reports the
    degradation counters.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        n_slots: int = 8,
        max_seq: int = 128,
        policies: Optional[Dict[str, Optional[SelectionPolicy]]] = None,
        bucket_spec: Optional[BucketSpec] = None,
        budget_tokens: Optional[int] = None,
        max_queue: Optional[int] = None,
        cache_dtype=jnp.bfloat16,
        mesh=None,
    ):
        if cfg.input_mode != "tokens":
            raise ValueError(
                f"ServeEngine serves token LMs; arch {cfg.name!r} has "
                f"input_mode={cfg.input_mode!r}"
            )
        self.cfg = cfg
        self.params = params
        self.max_seq = int(max_seq)
        self.policies = dict(policies or {"interactive": None, "bulk": None})
        self.mesh = mesh
        self.cache_dtype = cache_dtype
        self.kv = PagedKVCache(cfg, n_slots, max_seq, dtype=cache_dtype)
        windows = [
            b.window
            for _, blocks in cfg.segments
            for b in blocks
            if b.window is not None
        ]
        self.buckets = bucket_spec or default_buckets(
            n_slots, max_seq, window=max(windows) if windows else 0
        )
        if self.buckets.batch_buckets[-1] > n_slots:
            raise ValueError(
                f"largest batch bucket {self.buckets.batch_buckets[-1]} "
                f"exceeds slot count {n_slots}"
            )
        # SSM state is cumulative over the padded tail, so padded prefill
        # is attention-only; SSM archs prefill at exact lengths (one
        # compile per distinct length — still correct, just not bucketed).
        self.exact_prefill = any(
            b.mixer == "mamba" for _, blocks in cfg.segments for b in blocks
        )
        self.budget_tokens = (
            int(budget_tokens) if budget_tokens else n_slots * self.max_seq
        )
        # bounded admission queue: default 8 waiting requests per slot —
        # deep enough to keep slots fed, shallow enough that rejected
        # traffic surfaces as backpressure instead of unbounded latency
        self.max_queue = int(max_queue) if max_queue else 8 * n_slots
        # graceful-degradation counters (health())
        self.crashed_steps = 0
        self.deadline_evictions = 0
        self.rejected_submits = 0
        # admission state is the submit/step contention surface: clients
        # submit from request threads while the engine loop admits
        self._lock = threading.Lock()
        self.queue: deque = deque()  # guarded-by: _lock
        # every request ever submitted, by id (evict, health); the step
        # walks only the live ones: queued, decoding or awaiting tokens
        self.requests: Dict[int, Request] = {}
        self._live: Dict[int, Request] = {}  # guarded-by: _lock
        self._batch: Dict[int, Request] = {}  # decoding rows, by slot
        self._inflight: List[_InFlight] = []  # decodes launched, unfetched
        self.clock = 0  # engine iterations (the virtual timeline)
        self._next_rid = 0
        self._reserved = 0  # guarded-by: _lock
        self._decode_steps: Dict[str, Any] = {}
        self._prefill_steps: Dict[str, Any] = {}
        for cls, policy in self.policies.items():
            self._decode_steps[cls] = jax.jit(
                self._make_decode_step(policy), donate_argnums=(1,)
            )
            self._prefill_steps[cls] = jax.jit(self._make_prefill_step(policy))
        self._measured_at_warmup: Dict[str, int] = {}
        self._warm = False
        # cumulative step counters (stats()), kept by the engine thread
        self._phases = _PhaseClock()
        self._counts: Dict[str, int] = dict.fromkeys(
            ("steps", "decode_steps", "decoded_rows", "decoded_rows_inplace",
             "kv_blocks_read", "prefills", "prompt_tokens", "prefill_tokens",
             "overlapped_steps", "discarded_rows"), 0)
        # (layers, positions per slot) of each attention block in the pool
        self._pool_extents = pool_extents(cfg, self.kv.data)
        self._step_s = 0.0

    # -- jitted steps (one trace per class x bucket shape) -----------------

    def _make_decode_step(self, policy: Optional[SelectionPolicy]):
        cfg, vocab = self.cfg, self.cfg.vocab

        def decode_step(params, segments, tokens, slot_ids, lengths):
            # the scope wraps the traced body: selection happens at trace
            # time, so this class's policy governs every GEMM in the step
            with _policy_scope(policy):
                logits, new = lm.lm_decode(
                    params, cfg,
                    {"segments": take_rows(cfg, segments, slot_ids),
                     "pos": lengths, "slots": slot_ids},
                    {"tokens": tokens[slot_ids][:, None]},
                )
                segments = put_rows(cfg, segments, new["segments"], slot_ids)
                next_tok = jnp.argmax(logits[:, -1, :vocab], axis=-1)
            return next_tok.astype(jnp.int32), segments

        return decode_step

    def _make_prefill_step(self, policy: Optional[SelectionPolicy]):
        cfg, vocab, max_seq = self.cfg, self.cfg.vocab, self.max_seq
        cache_dtype = self.cache_dtype

        def prefill_step(params, tokens, true_len):
            with _policy_scope(policy):
                logits, cache = lm.lm_prefill(
                    params, cfg, {"tokens": tokens}, max_seq=max_seq,
                    cache_dtype=cache_dtype, true_len=true_len,
                )
                tok = jnp.argmax(logits[:, -1, :vocab], axis=-1)
            return tok.astype(jnp.int32), cache

        return prefill_step

    def _mesh_scope(self):
        return use_mesh(self.mesh) if self.mesh is not None else contextlib.nullcontext()

    # -- request lifecycle -------------------------------------------------

    def submit(
        self,
        tokens,
        max_new: int,
        cls: str = "interactive",
        deadline_s: Optional[float] = None,
    ) -> Request:
        """Queue one request (FCFS).  Returns its ``Request`` handle.

        ``deadline_s`` bounds its wall-clock residency from this moment;
        an overdue request is evicted as ``DEADLINE_EXCEEDED`` between
        decode steps.  Raises ``QueueFullError`` when the admission queue
        is at ``max_queue`` — explicit backpressure."""
        with self._lock:
            if len(self.queue) >= self.max_queue:
                self.rejected_submits += 1
                raise QueueFullError(
                    f"admission queue is full ({self.max_queue} waiting); "
                    "shed load or retry after the queue drains"
                )
        if cls not in self.policies:
            raise KeyError(
                f"unknown request class {cls!r}; engine classes: "
                f"{sorted(self.policies)}"
            )
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size < 1:
            raise ValueError("request needs at least one prompt token")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if tokens.size + max_new > self.max_seq:
            raise ValueError(
                f"request needs {tokens.size} + {max_new} tokens; cache "
                f"slots hold max_seq={self.max_seq}"
            )
        if not self.exact_prefill:
            self.buckets.bucket_len(tokens.size)  # fail fast on oversize
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        req = Request(
            rid=self._next_rid, tokens=tokens, max_new=int(max_new), cls=cls,
            deadline_s=deadline_s, submit_step=self.clock,
            submit_time=time.monotonic(),
        )
        self._next_rid += 1
        self.requests[req.rid] = req
        with self._lock:
            self._live[req.rid] = req
            self.queue.append(req)
        return req

    def _leave_batch(self, req: Request) -> None:
        """Take a decoding row out of the batch, returning its slot and
        budget reservation.  A token already launched for it is in
        flight; the slot's next occupant is ordered after it on the
        device, since the pool and token vector thread every program."""
        del self._batch[req.slot]
        self.kv.free(req.slot)
        with self._lock:
            self._reserved -= req.reserve

    def _release(self, req: Request, state: RequestState) -> None:
        """Move a live request to a terminal state, returning its
        resources: a decoding request's slot + budget reservation, a
        QUEUED one's queue position."""
        with self._lock:
            if req.state is RequestState.QUEUED:
                self.queue.remove(req)
            del self._live[req.rid]
        if self._batch.get(req.slot) is req:
            self._leave_batch(req)
        req.state = state
        req.finish_step = self.clock

    def evict(self, rid: int) -> Request:
        """Cancel a request mid-stream.  An ACTIVE request's slot returns
        to the pool immediately (reused by the next admission) and a
        token in flight for it is discarded; a QUEUED one just leaves the
        queue."""
        req = self.requests[rid]
        if req.state in TERMINAL_STATES:
            return req
        self._release(req, RequestState.EVICTED)
        return req

    def _expire_deadlines(self) -> List[Request]:
        """Evict every live request past its wall-clock deadline — runs
        between decode steps, so an overdue request costs at most one
        step's latency past its budget, never a whole generation."""
        now = time.monotonic()
        with self._lock:
            overdue = [r for r in self._live.values()
                       if r.deadline_s is not None and r.overdue(now)]
        for req in overdue:
            self._release(req, RequestState.DEADLINE_EXCEEDED)
            self.deadline_evictions += 1
        return overdue

    def _crashed(self, what: str, reqs: List[Request], e: Exception) -> None:
        """Contain a step that raised: its live requests die, the engine
        lives (candidate failures degrade inside the trace and never
        reach here — this catches whole-step failures)."""
        self.crashed_steps += 1
        dead = [r for r in reqs if r.state is RequestState.ACTIVE]
        for req in dead:
            self._release(req, RequestState.EVICTED)
        warnings.warn(
            f"{what} crashed ({type(e).__name__}: {e}); {len(dead)} "
            "request(s) evicted, engine continues",
            UserWarning,
        )

    def _admit(self) -> List[_InFlight]:
        """FCFS admission: pop the queue head while a slot is free and the
        max-tokens budget holds, launch its prefill and land its cache and
        first token in the slot.  Returns the first tokens, in flight."""
        admitted = []
        while self.queue:
            with self._lock:
                if not self.queue:
                    break
                req = self.queue[0]
                if self._reserved + req.reserve > self.budget_tokens:
                    break  # head-of-line blocks: strict FCFS, no skip-ahead
                slot = self.kv.allocate(req.rid)
                if slot is None:
                    break
                self.queue.popleft()
                self._reserved += req.reserve
            req.slot = slot
            req.state = RequestState.ACTIVE
            req.admit_step = self.clock
            req.admit_time = time.monotonic()
            self._batch[slot] = req
            P = req.prompt_len
            Lb = P if self.exact_prefill else self.buckets.bucket_len(P)
            what = f"prefill for request {req.rid} (class {req.cls!r})"
            try:
                with self._phases.phase("prefill"), self._mesh_scope():
                    padded = np.zeros((1, Lb), np.int32)
                    padded[0, :P] = req.tokens
                    tok, cache = self._prefill_steps[req.cls](
                        self.params, padded, np.int32(P)
                    )
                    self.kv.insert(cache, slot, P, tok)
                    tok.copy_to_host_async()
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._crashed(what, [req], e)
                continue
            req.launched = 1
            if req.launched >= req.max_new:
                self._leave_batch(req)
            self._counts["prefills"] += 1
            self._counts["prompt_tokens"] += P
            self._counts["prefill_tokens"] += Lb
            admitted.append(_InFlight(what, [req], tok))
        return admitted

    def _decode_class(self, cls: str, reqs: List[Request]) -> _InFlight:
        """Launch one bucketed decode step for one class's decoding rows.
        Rows whose last token it launches leave the batch at once."""
        with self._phases.phase("decode"), self._mesh_scope():
            n = len(reqs)
            slot_ids = np.full(self.buckets.bucket_batch(n), self.kv.null_slot, np.int32)
            slot_ids[:n] = [r.slot for r in reqs]
            lengths = np.zeros_like(slot_ids)
            lengths[:n] = self.kv.lengths[slot_ids[:n]]
            slots_dev, lengths_dev = jax.device_put((slot_ids, lengths))
            tok, self.kv.data = self._decode_steps[cls](
                self.params, self.kv.data, self.kv.tokens, slots_dev, lengths_dev,
            )
            self.kv.put_tokens(slots_dev, tok)
            tok.copy_to_host_async()
            if self._inflight:
                self._counts["overlapped_steps"] += 1
            self.kv.advance(slot_ids[:n])
            for req in reqs:
                req.launched += 1
                # the next input would land at lengths[slot] — stop at
                # the cache extent as at ``max_new``
                if (req.launched >= req.max_new
                        or int(self.kv.lengths[req.slot]) + 1 >= self.max_seq):
                    self._leave_batch(req)
        blocks = sum(
            count * int(live_blocks(np.minimum(lengths + 1, T), block_len(T)).sum())
            for count, T in self._pool_extents)
        return _InFlight(f"decode step for class {cls!r}", reqs, tok, True, blocks)

    def _collect(self, launched: List[_InFlight]) -> Dict[int, Request]:
        """Block on tokens in flight (the previous step's decodes, then
        this step's prefills), copy them to the host and hand each to its
        request; a request whose last token arrived finishes.  A batch
        whose tokens fail to arrive is evicted as a crashed step.
        Returns the requests that received tokens."""
        ready = []
        for wait, decode in (("decode_wait", True), ("prefill_wait", False)):
            with self._phases.phase(wait):
                for f in launched:
                    if (f.decode is decode
                            and self._guard(f, jax.block_until_ready) is not None):
                        ready.append(f)
        with self._phases.phase("fetch"):
            fetched = [(f, toks) for f in ready
                       if (toks := self._guard(f, np.asarray)) is not None]
        got: Dict[int, Request] = {}
        with self._phases.phase("retire"):
            for f, toks in fetched:
                if f.decode:
                    n = len(f.reqs)
                    self._counts["decode_steps"] += 1
                    self._counts["decoded_rows"] += n
                    if self._pool_extents:
                        self._counts["decoded_rows_inplace"] += n
                        self._counts["kv_blocks_read"] += f.kv_blocks
                for req, t in zip(f.reqs, toks.tolist()):
                    if req.state is not RequestState.ACTIVE:
                        self._counts["discarded_rows"] += 1
                        continue
                    req.generated.append(t)
                    got[req.rid] = req
                    if (self._batch.get(req.slot) is not req
                            and len(req.generated) == req.launched):
                        self._release(req, RequestState.FINISHED)
        return got

    def _guard(self, f: _InFlight, fn):
        """``fn(f.tok)``, or None with ``f``'s batch evicted if it raises."""
        try:
            return fn(f.tok)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            self._crashed(f.what, f.reqs, e)
            return None

    # -- the serve loop ------------------------------------------------------

    def has_work(self) -> bool:
        """True while a request is queued, decoding or awaiting tokens, or
        a decode's tokens are still in flight."""
        return bool(self._live or self._inflight)

    def step(self) -> int:
        """One engine iteration: expire overdue deadlines, admit (launching
        the prefills), launch one decode step per class with decoding rows,
        then fetch the previous step's decode tokens and this step's first
        tokens.  Returns the number of tokens that reached the host.  A
        batch whose step raises, at launch or at the fetch, loses only its
        requests (evicted, ``crashed_steps`` counted); other classes and
        the loop itself keep serving."""
        t0 = time.perf_counter()
        with self._phases.phase("expire"):
            self._expire_deadlines()
        with self._phases.phase("admit"):
            launched = self._admit()
        by_cls: Dict[str, List[Request]] = {}
        for req in self._batch.values():
            by_cls.setdefault(req.cls, []).append(req)
        decodes = []
        for cls in sorted(by_cls):
            try:
                decodes.append(self._decode_class(cls, by_cls[cls]))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._crashed(f"decode step for class {cls!r}", by_cls[cls], e)
        launched += self._inflight
        self._inflight = decodes
        got = self._collect(launched)
        self.clock += 1
        now = time.monotonic()
        emitted = 0
        for req in got.values():
            new = len(req.generated) - len(req.token_times)
            req.token_times.extend([now] * new)
            emitted += new
        self._counts["steps"] += 1
        self._step_s += time.perf_counter() - t0
        return emitted

    def run(self, max_steps: int = 100_000) -> None:
        """Drain: step until no request is queued, decoding or awaiting
        its tokens, and no decode is in flight."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()
        raise RuntimeError(f"engine did not drain within {max_steps} steps")

    # -- warmup + observability ----------------------------------------------

    def warmup(self) -> Dict[str, int]:
        """Pre-trace every bucketed shape under every class policy.

        Selection runs at trace time, so this drives the full OpKey set of
        the serve loop — every (decode-batch bucket) x class and every
        (prefill-length bucket) x class — through the policies before any
        traffic: under ``AutotunePolicy`` each cold key is measured via
        ``core/measure.py`` here, and ``cold_misses()`` stays zero for the
        whole bucketed run."""
        n_shapes = 0
        with self._mesh_scope():
            for cls in sorted(self.policies):
                for Bb in self.buckets.decode_batches:
                    slot_ids = jnp.full(
                        (Bb,), self.kv.null_slot, jnp.int32
                    )
                    lengths = jnp.zeros((Bb,), jnp.int32)
                    tok, self.kv.data = self._decode_steps[cls](
                        self.params, self.kv.data, self.kv.tokens, slot_ids, lengths
                    )
                    self.kv.put_tokens(slot_ids, tok)
                    n_shapes += 1
                if not self.exact_prefill:
                    for Lb in self.buckets.prefill_lens:
                        self._prefill_steps[cls](
                            self.params, np.zeros((1, Lb), np.int32), np.int32(Lb)
                        )
                        n_shapes += 1
        self.kv.lengths[:] = 0  # warmup scribbled on the null row only
        for cls, policy in self.policies.items():
            self._measured_at_warmup[cls] = getattr(policy, "n_measured", 0)
        self._warm = True
        return {"shapes_traced": n_shapes}

    def cold_misses(self) -> Dict[str, int]:
        """Per-class autotune measurements made *after* warmup — the
        bucketed serve loop must keep these at zero."""
        out = {}
        for cls, policy in self.policies.items():
            n = getattr(policy, "n_measured", 0)
            out[cls] = n - self._measured_at_warmup.get(cls, 0)
        return out

    def stats(self) -> Dict[str, float]:
        """Cumulative counters of ``step()``: steps, decode steps (one per
        class per step), decoded rows, the rows decoded by the pool kernel
        in place (``decoded_rows_inplace``) and the live (row, kv block)
        pairs its grid fetched, summed over attention layers
        (``kv_blocks_read``), prefills, prompt tokens and the
        padded tokens prefilled for them, the steps' wall seconds
        (``step_s``), host seconds per phase (``<phase>_s`` for
        ``HOST_PHASES``) and seconds blocked on the device
        (``prefill_wait_s``, ``decode_wait_s``).  ``host_s`` is ``step_s``
        less the waits.  A decode step and its rows count when its tokens
        reach the host; ``overlapped_steps`` counts the decode launches
        made while the previous step's decode tokens were still in flight,
        ``discarded_rows`` the tokens that arrived for requests evicted or
        expired meanwhile.  Subtract two readings for a window."""
        out: Dict[str, float] = dict(self._counts)
        out.update({f"{k}_s": v for k, v in self._phases.seconds.items()})
        out["step_s"] = self._step_s
        out["host_s"] = self._step_s - sum(
            self._phases.seconds[k] for k in WAIT_PHASES)
        return out

    def health(self) -> Dict[str, int]:
        """Graceful-degradation counters + terminal-state tallies — the
        serve-side complement of ``core.engine.health_report()``."""
        by_state: Dict[str, int] = {s.value: 0 for s in RequestState}
        for req in self.requests.values():
            by_state[req.state.value] += 1
        return {
            "crashed_steps": self.crashed_steps,
            "deadline_evictions": self.deadline_evictions,
            "rejected_submits": self.rejected_submits,
            **by_state,
        }

    def class_reports(self) -> Dict[str, str]:
        """One rendered ``dispatch_report`` per request class."""
        return {
            cls: dispatch_report(policy) if policy is not None
            else "(ambient default policy)"
            for cls, policy in self.policies.items()
        }

    def class_dispatch_rows(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """Structured per-class decision counts: cls -> op -> label -> n."""
        out: Dict[str, Dict[str, Dict[str, int]]] = {}
        for cls, policy in self.policies.items():
            if policy is None:
                out[cls] = {}
                continue
            by_op = getattr(policy.stats, "by_op", None) or {}
            out[cls] = {
                op: dict(labels) for op, labels in by_op.items()
            }
        return out

    def __repr__(self):
        return (
            f"ServeEngine(arch={self.cfg.name!r}, slots={self.kv.n_slots}, "
            f"queued={len(self.queue)}, active={len(self._batch)}, "
            f"classes={sorted(self.policies)})"
        )
