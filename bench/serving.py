"""What the serving cells share: the client-side view of each request,
the engine loop with its accounting, and the check of served tokens."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import counts, harness, traffic


@dataclass
class Rec:
    """One request as its client sees it (host clock, seconds)."""

    req: object
    due: float
    submitted: float
    prompt_len: int
    n: int = 0  # tokens seen so far
    admitted: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    ended: Optional[float] = None

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


class Accounts:
    """Work and occupancy of the engine steps in one stretch of time."""

    def __init__(self):
        self.steps = 0
        self.decode_steps = 0
        self.rows = 0
        self.tokens = 0
        self.flops = 0.0
        self.decode_bytes = 0.0


class Loop:
    """Drives a ``ServeEngine``: submits, steps, and after each step reads
    every live request's new tokens at the step's return."""

    def __init__(self, engine, config: dict, slots: int):
        self.engine = engine
        self.c = config
        self.slots = slots
        self.live: Dict[int, Rec] = {}
        self.done: List[Rec] = []
        self.rejected: List[Rec] = []
        self.step_start: Dict[int, float] = {}
        self.accounts: List[Accounts] = []

    def submit(self, tokens, max_new: int, due: float) -> Rec:
        from repro.serving import QueueFullError

        now = time.perf_counter()
        with harness.span("engine.submit"):
            try:
                req = self.engine.submit(tokens, max_new)
            except QueueFullError:
                rec = Rec(None, due, now, len(tokens), ended=now)
                self.rejected.append(rec)
                return rec
        rec = Rec(req, due, now, len(tokens))
        self.live[req.rid] = rec
        return rec

    def has_work(self) -> bool:
        return bool(self.live)

    def step(self) -> float:
        from repro.serving.engine import TERMINAL_STATES

        eng = self.engine
        self.step_start[eng.clock] = time.perf_counter()
        with harness.span("engine.step"):
            eng.step()
        now = time.perf_counter()
        rows = prefills = 0
        flops = dbytes = 0.0
        cached = []
        tokens = 0
        for rid in list(self.live):
            rec = self.live[rid]
            req = rec.req
            n = len(req.generated)
            new = n - rec.n
            if new > 0:
                tokens += new
                if rec.n == 0:
                    rec.admitted = self.step_start[req.admit_step]
                    flops += counts.lm_prefill_flops(self.c, rec.prompt_len)
                    prefills += 1
                    decoded = range(2, n + 1)
                else:
                    decoded = range(rec.n + 1, n + 1)
                for k in decoded:  # producing the k-th token (1-based)
                    rows += 1
                    flops += counts.lm_decode_flops(self.c, rec.prompt_len + k - 1)
                    cached.append(rec.prompt_len + k - 2)
                rec.token_times.extend([now] * new)
                rec.n = n
            if req.state in TERMINAL_STATES:
                rec.ended = now
                del self.live[rid]
                self.done.append(rec)
        for acc in self.accounts:
            acc.steps += 1
            acc.tokens += tokens
            acc.flops += flops
            if rows:
                acc.decode_steps += 1
                acc.rows += rows
                acc.decode_bytes += counts.lm_decode_bytes(self.c, cached)
        return now


def finished(recs: List[Rec]) -> List[Rec]:
    from repro.serving import RequestState

    return [r for r in recs if r.req is not None and r.req.state is RequestState.FINISHED]


def check_served(run: harness.Run, prog, recs: List[Rec]) -> None:
    """Compare a seeded sample of finished requests, with the longest among
    them, against the plain reference: the widest gap by which a served
    token's logit lies below the reference's best."""
    fam = harness.family(run.config)
    done = finished(recs)
    if not done:
        run.check("logit_gap", float("inf"), run.workload["limits"]["logit_gap"])
        return
    k = run.workload["check_requests"]
    longest = max(range(len(done)), key=lambda i: done[i].prompt_len + len(done[i].req.generated))
    pick = traffic.rng(run.seed, "check").permutation(len(done))[:k].tolist()
    chosen = [done[i] for i in sorted(set(pick) | {longest})]
    sample = [(np.asarray(r.req.tokens, np.int32), list(r.req.generated)) for r in chosen]
    params = prog.reference_params()
    prog.free()
    gaps = fam.served_gaps(run.config, params, sample, prog.max_seq)
    run.counters.update(checked_tokens=int(gaps.size), served_sample=sample)
    run.check("logit_gap", float(np.max(gaps)), run.workload["limits"]["logit_gap"])
