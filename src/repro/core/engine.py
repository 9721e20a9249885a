"""The dispatch engine: every GEMM in the model layer lands here.

``dispatch(op, a, b)`` computes one of the three training GEMMs —
``"NT"`` (``a @ b^T``), ``"NN"`` (``a @ b``) or ``"TN"`` (``a^T @ b``) —
through whichever *(candidate, tile config)* the scoped policy picks for
the ``OpKey`` (``policy.current_policy()``); model code never threads a
selector argument.  Because JAX shapes are static under ``jit``, the
policy runs once per distinct key at trace time and contributes nothing
to the compiled step.

``dispatch_batched(op, a, b)`` is the batched entry point for the
attention contractions — ``"BNT"`` (``Q @ K^T`` logits) and ``"BNN"``
(``probs @ V``): the leading batch/head axes of both operands collapse to
one batch extent ``g`` and the policy selects over the batched candidate
sets, so one ``use_policy(...)`` scope governs dense *and* attention
GEMMs in train and serve.

``dispatch_attention(q, k, v, ...)`` raises the decision from one op to
a *plan* over the whole attention subgraph: the policy answers the
paired ``ATTN`` OpKey with either the fused flash kernel at a learned
``(bq, bk)`` tile (``kernels/attention_fused.py`` — the logits matrix
never touches HBM) or the existing unfused plan, whose ``BNT`` and
``BNN`` sub-GEMMs are then dispatched under their own per-op keys.  The
fallback chain terminates at the unfused plan, so a faulted or
quarantined fused kernel degrades to exactly the pair of batched
dispatches the model ran before fusion existed.

Both entry points are ``custom_vjp``-wrapped: the backward rules rebuild
gradient OpKeys and re-enter dispatch — the 2-D op space {NT, NN, TN} is
closed under differentiation, and the batched space {BNT, BNN} is closed
modulo one explicit operand transpose — so the scope must wrap the whole
``value_and_grad`` call (forward *and* backward trace), not just the
forward pass.

``dispatch_report()`` renders the per-(op, candidate, config) decision
counts of the scoped policy — surfaced at the end of train/serve runs so
dispatch stays observable in production.

The pre-op-space compatibility layer (``dispatch_nt``, positional
``select(m, n, k, dsize)`` adaptation, bare-string decisions) was removed
after its one-release deprecation cycle; those call patterns now raise
clean ``TypeError``/``AttributeError``s pointing at the op-space API.
"""

from __future__ import annotations

import functools
import os
import re
import warnings
from typing import Optional

import jax
import numpy as np

from . import faults
from .candidates import DEFAULT_BY_OP, fallback_chain, get_candidate
from .opkey import BATCHED_OPS, GROUPED_OPS, OPS, OpKey, check_op
from .policy import (
    AnalyticPolicy,
    AutotunePolicy,
    CascadePolicy,
    Decision,
    FixedPolicy,
    ModelPolicy,
    SelectionPolicy,
    current_policy,
    default_policy,
    use_policy,
)

__all__ = [
    "dispatch",
    "dispatch_attention",
    "dispatch_batched",
    "dispatch_report",
    "health_report",
    "run_decision",
    "DispatchError",
    "policy_select",
    "policy_from_spec",
    "add_policy_argument",
    "use_policy",
    "current_policy",
    "default_policy",
]


class DispatchError(RuntimeError):
    """Every arm of an OpKey's fallback chain failed — raised only when
    even the op's XLA reference cannot run (the chain's terminal arm is
    always attempted, quarantined or not)."""

POLICY_SPEC_HELP = (
    "dispatch policy: model[:artifact.json] | fixed:<NAME>[@BMxBNxBK] | "
    "fixed:nt=<NAME>[@cfg],nn=...,tn=...,bnt=...,bnn=...,"
    "attn=<fused|unfused>[@BQxBK] | analytic | "
    "cascade:<A,B,...> | autotune[:cache.json]"
)

# ``fixed:attn=...`` accepts the plan-member aliases alongside literal
# candidate names; the fused arm's tile configs are (bq, bk) pairs.
_ATTN_ALIASES = {"fused": "FUSED_ATTN", "unfused": "UNFUSED_ATTN"}

_WARNED: set = set()


def _warn_once(tag: str, msg: str) -> None:
    if tag not in _WARNED:
        _WARNED.add(tag)
        warnings.warn(msg, UserWarning, stacklevel=3)


def _spec_error(msg: str) -> ValueError:
    """Every malformed spec gets the same actionable hint."""
    return ValueError(f"{msg} ({POLICY_SPEC_HELP})")


def policy_select(policy: SelectionPolicy, key: OpKey) -> Decision:
    """Run ``policy.select`` on an ``OpKey`` and validate the decision.

    Policies must return a ``Decision(name, config)`` — a bare candidate
    name (the pre-op-space convention, removed after its deprecation
    release) raises a clean ``TypeError``.  A decision naming a candidate
    that does not implement ``key.op`` (a mis-op'd policy) degrades to the
    op's reference rather than executing a kernel on operands in the wrong
    storage layout (warns once per process — that is a policy bug, not a
    deprecation).
    """
    decision = policy.select(key)
    if isinstance(decision, str):
        raise TypeError(
            f"policy {policy!r} returned the bare candidate name "
            f"{decision!r}; policies must return a Decision(name, config) "
            "— the bare-string adapter was removed with the op-space "
            "deprecation cycle"
        )
    if key.op not in get_candidate(decision.name).ops:
        _warn_once(
            "op-mismatched-decision",
            f"policy {policy!r} returned candidate {decision.name!r} for an "
            f"op it does not implement; dispatching the op's reference "
            "instead",
        )
        decision = Decision(DEFAULT_BY_OP[key.op], None)
    return decision


def _decision_chain(op: str, decision: Decision) -> list:
    """The decisions dispatch will attempt, in order: the selected arm;
    the same candidate degraded to its default tiling (an explicit tile
    is the most fragile part of a decision — shed it before shedding the
    algorithm); then the registry's per-op fallback chain, terminating at
    the op's XLA reference."""
    chain = [decision]
    if decision.config is not None:
        chain.append(Decision(decision.name, None))
    for name in fallback_chain(op, decision.name):
        if name != decision.name:
            chain.append(Decision(name, None))
    return chain


# The scopes live in the ops' metadata, which JAX's persistent compile
# cache leaves out of its key unless asked: an executable cached from a
# program without scopes, or with other scope names, would otherwise be
# served for this one.  Source paths in the metadata are made relative to
# the checkout, so that the key does not depend on where the checkout lies.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
jax.config.update("jax_hlo_source_file_canonicalization_regex",
                  "^" + re.escape(_CHECKOUT + os.sep))


def scope_name(key: OpKey, decision: Decision) -> str:
    """The named scope an executed arm's ops carry in the compiled HLO's
    ``op_name`` metadata (and so in the device trace):
    ``dispatch.<op>.<m>x<n>x<k>[.g<g>].d<dsize>.<arm label>``, e.g.
    ``dispatch.NT.4096x26752x4096.d4.PALLAS_TNN_FUSED@128x128x128`` or
    ``dispatch.BNT.3072x2048x64.g24.d4.XLA_BNT``.  ``g`` is written for
    the batched ops and ATTN only.  A scope changes metadata alone: the
    compiled program is the same op for op."""
    g = f".g{key.g}" if key.op in GROUPED_OPS else ""
    return (f"dispatch.{key.op}.{key.m}x{key.n}x{key.k}{g}.d{key.dsize}."
            f"{decision.label()}")


def run_decision(key: OpKey, decision: Decision, a, b):
    """Execute a policy decision fault-tolerantly.

    Walks the decision's fallback chain: a candidate that raises is
    recorded in the quarantine ledger (``core/faults.py`` — every policy's
    admissible set excludes it from then on) and the next arm runs.
    Quarantined non-terminal arms are skipped without attempting them; the
    terminal arm — the op's always-runnable XLA reference — is attempted
    even when quarantined, because there is nothing beneath it.  Raises
    ``DispatchError`` only when the whole chain failed."""
    chain = _decision_chain(key.op, decision)
    last_err: Optional[BaseException] = None
    for i, dec in enumerate(chain):
        terminal = i == len(chain) - 1
        if not terminal and faults.is_quarantined(dec.name, key.op, dec.config):
            continue
        try:
            faults.check_candidate_fault(dec.name, key.op)
            with jax.named_scope(scope_name(key, dec)):
                out = get_candidate(dec.name).run(a, b, dec.config)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            faults.quarantine(dec.name, key.op, dec.config, e)
            _warn_once(
                f"quarantined:{dec.label()}:{key.op}",
                f"candidate {dec.label()!r} failed on op {key.op!r} "
                f"({type(e).__name__}: {e}); quarantined for this process, "
                "dispatch degrades down the fallback chain",
            )
            last_err = e
            continue
        if (dec.name, dec.config) != (decision.name, decision.config):
            faults.record_fallback(key.op, decision.label(), dec.label())
        return out
    raise DispatchError(
        f"every arm of the fallback chain for {key} failed: "
        f"{[d.label() for d in chain]}"
    ) from last_err


def _run(op: str, a, b):
    """Select and execute one 2-D GEMM (the custom_vjp core)."""
    import jax.numpy as jnp

    if op == "NT":  # a:(m,k) b:(n,k)
        m, k = a.shape
        n = b.shape[0]
    elif op == "NN":  # a:(m,k) b:(k,n)
        m, k = a.shape
        n = b.shape[1]
    else:  # TN: a:(k,m) b:(k,n)
        k, m = a.shape
        n = b.shape[1]
    key = OpKey(op, int(m), int(n), int(k), int(jnp.dtype(a.dtype).itemsize))
    decision = policy_select(current_policy(), key)
    return run_decision(key, decision, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch2(op: str, a, b):
    return _run(op, a, b)


def _dispatch2_fwd(op: str, a, b):
    return _run(op, a, b), (a, b)


def _dispatch2_bwd(op: str, res, g):
    """Backward rule: each gradient GEMM is itself a dispatch — the op
    space {NT, NN, TN} is closed under differentiation, so both gradients
    of every op land back on a policy-governed op.  (First-order reverse
    mode only: custom_vjp does not support forward-mode/higher-order.)"""
    a, b = res
    if op == "NT":  # C = A B^T: dA = G @ B (NN), dB = G^T @ A (TN)
        da = _dispatch2("NN", g, b)
        db = _dispatch2("TN", g, a)
    elif op == "NN":  # C = A B: dA = G @ B^T (NT), dB = A^T @ G (TN)
        da = _dispatch2("NT", g, b)
        db = _dispatch2("TN", a, g)
    else:  # TN, C = A^T B: dA = B @ G^T (NT), dB = A @ G (NN)
        da = _dispatch2("NT", b, g)
        db = _dispatch2("NN", a, g)
    return da.astype(a.dtype), db.astype(b.dtype)


_dispatch2.defvjp(_dispatch2_fwd, _dispatch2_bwd)


def _run3(op: str, a, b):
    """Select and execute one batched GEMM on (g, ., .) operands."""
    import jax.numpy as jnp

    g, m, k = a.shape
    n = b.shape[1] if op == "BNT" else b.shape[2]
    key = OpKey(
        op, int(m), int(n), int(k), int(jnp.dtype(a.dtype).itemsize), int(g)
    )
    decision = policy_select(current_policy(), key)
    return run_decision(key, decision, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch3(op: str, a, b):
    return _run3(op, a, b)


def _dispatch3_fwd(op: str, a, b):
    return _run3(op, a, b), (a, b)


def _dispatch3_bwd(op: str, res, g):
    """Batched backward rule: {BNT, BNN} is closed under differentiation
    modulo one explicit transpose of the cotangent/operand (a batched TN
    is a batched NN of the swapped operand) — every gradient of a batched
    dispatch is itself a policy-governed batched dispatch."""
    import jax.numpy as jnp

    a, b = res
    if op == "BNT":  # C_i = A_i B_i^T: dA_i = G_i @ B_i, dB_i = G_i^T @ A_i
        da = _dispatch3("BNN", g, b)
        db = _dispatch3("BNN", jnp.swapaxes(g, -1, -2), a)
    else:  # BNN, C_i = A_i B_i: dA_i = G_i @ B_i^T, dB_i = A_i^T @ G_i
        da = _dispatch3("BNT", g, b)
        db = _dispatch3("BNN", jnp.swapaxes(a, -1, -2), g)
    return da.astype(a.dtype), db.astype(b.dtype)


_dispatch3.defvjp(_dispatch3_fwd, _dispatch3_bwd)


# ---------------------------------------------------------------------------
# The fused-attention plan: one ATTN decision spanning the BNT+BNN pair.
# ---------------------------------------------------------------------------

# Finite masked-logit fill (mirrors kernels/attention_fused.NEG_INF):
# exp underflows to an exact 0.0 instead of producing inf - inf = nan.
_MASK_NEG = -1e30


def _attn_visibility(mask, lengths, m: int, n: int):
    """The (g, m, n) boolean visibility of ``MaskParams`` + the traced
    per-slice ``lengths`` — the jnp mirror of the in-kernel masking in
    ``kernels/attention_fused.py`` (same position arithmetic, so the
    fused and unfused plan arms agree bit-for-bit on *which* logits are
    masked)."""
    import jax.numpy as jnp

    rows = jnp.arange(m, dtype=jnp.int32)
    cols = jnp.arange(n, dtype=jnp.int32)
    q_seg = mask.q_seg if mask.q_seg else m
    q_pos = (mask.q_start + rows % q_seg)[None, :, None]  # (1, m, 1)
    k_pos = (mask.k_start + cols)[None, None, :]  # (1, 1, n)
    valid = cols[None, None, :] < lengths.reshape(-1, 1, 1)  # (g, 1, n)
    vis = valid
    if mask.causal:
        vis = vis & (k_pos <= q_pos)
    if mask.window:
        vis = vis & (k_pos > q_pos - mask.window)
    if mask.prefix_len:
        vis = vis | (valid & (k_pos < mask.prefix_len))
    return vis


def _attn_logits(q, k):
    """Raw f32 logits through the policy-dispatched batched GEMM — the
    unfused plan's first sub-op (a BNT OpKey at dsize 4, matching the
    model layer's pre-fusion upcast convention)."""
    import jax.numpy as jnp

    return _dispatch3(
        "BNT", q.astype(jnp.float32), k.astype(jnp.float32)
    ).astype(jnp.float32)


def _attn_probs(mask, s_raw, lengths):
    """f32 attention probabilities from raw logits: softcap, then the
    static+validity mask at a finite ``_MASK_NEG``, then softmax.  Fully
    masked columns come out exactly 0.0."""
    import jax.numpy as jnp

    m, n = s_raw.shape[-2:]
    s = s_raw
    if mask.softcap:
        cap = jnp.float32(mask.softcap)
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(_attn_visibility(mask, lengths, m, n), s, _MASK_NEG)
    return jax.nn.softmax(s, axis=-1)


def _zero_invalid_kv(x, lengths):
    """Zero key/value rows beyond each slice's valid length — same
    poison hygiene as the fused kernel: an all-masked row's probs are
    not 0, so junk rows must not be summable (0 * nan = nan)."""
    import jax.numpy as jnp

    n = x.shape[1]
    valid = jnp.arange(n, dtype=jnp.int32)[None, :, None] < lengths.reshape(
        -1, 1, 1
    )
    return jnp.where(valid, x, 0)


def _unfused_attn_plan(mask, q, k, v, lengths):
    """The unfused plan arm: dispatched BNT logits -> softcap/mask/f32
    softmax -> dispatched BNN mix.  Each sub-GEMM goes through its own
    per-op policy decision, so forcing ``attn=unfused`` reproduces the
    pre-fusion dispatch behaviour exactly — this is also the fallback
    chain's terminal arm."""
    probs = _attn_probs(mask, _attn_logits(q, k), lengths)
    vz = _zero_invalid_kv(v, lengths)
    out = _dispatch3("BNN", probs.astype(v.dtype), vz)
    return out.astype(q.dtype)


def _attn_key(q, k) -> OpKey:
    import jax.numpy as jnp

    g, m, dh = q.shape
    return OpKey(
        "ATTN", int(m), int(k.shape[1]), int(dh),
        int(jnp.dtype(q.dtype).itemsize), int(g),
    )


def _run_attn(mask, q, k, v, lengths):
    """Select and execute the attention plan (the custom_vjp core).

    Mirrors ``run_decision`` — quarantine-skipped non-terminal arms,
    fault checks, fallback recording — but executes *plans* rather than
    ``Candidate.run``: ``FUSED_ATTN`` runs the flash kernel with the
    mask folded inside; every other arm (``UNFUSED_ATTN`` included)
    runs the unfused sub-dispatch plan."""
    key = _attn_key(q, k)
    decision = policy_select(current_policy(), key)
    chain = _decision_chain("ATTN", decision)
    last_err: Optional[BaseException] = None
    for i, dec in enumerate(chain):
        terminal = i == len(chain) - 1
        if not terminal and faults.is_quarantined(dec.name, "ATTN", dec.config):
            continue
        try:
            faults.check_candidate_fault(dec.name, "ATTN")
            with jax.named_scope(scope_name(key, dec)):
                if dec.name == "FUSED_ATTN":
                    from repro.kernels.attention_fused import attention_fused

                    block = tuple(dec.config) if dec.config is not None else None
                    out = attention_fused(q, k, v, lengths, mask=mask, block=block)
                else:
                    out = _unfused_attn_plan(mask, q, k, v, lengths)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            faults.quarantine(dec.name, "ATTN", dec.config, e)
            _warn_once(
                f"quarantined:{dec.label()}:ATTN",
                f"candidate {dec.label()!r} failed on op 'ATTN' "
                f"({type(e).__name__}: {e}); quarantined for this process, "
                "dispatch degrades down the fallback chain",
            )
            last_err = e
            continue
        if (dec.name, dec.config) != (decision.name, decision.config):
            faults.record_fallback("ATTN", decision.label(), dec.label())
        return out
    raise DispatchError(
        f"every arm of the fallback chain for {key} failed: "
        f"{[d.label() for d in chain]}"
    ) from last_err


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_attn(mask, q, k, v, lengths):
    return _run_attn(mask, q, k, v, lengths)


def _dispatch_attn_fwd(mask, q, k, v, lengths):
    # Flash-style residuals: operands only, never the (m, n) probs
    # matrix — the backward rule recomputes the softmax.
    return _run_attn(mask, q, k, v, lengths), (q, k, v, lengths)


def _dispatch_attn_bwd(mask, res, dout):
    """Flash backward: recompute the masked softmax from the saved
    operands, then take every gradient contraction through the batched
    dispatch — dQ/dK/dV land on policy-governed BNT/BNN OpKeys, same
    closure property as ``_dispatch3_bwd``.  ``lengths`` is integral:
    its cotangent is float0."""
    q, k, v, lengths = res
    with jax.named_scope(scope_name(_attn_key(q, k), Decision("UNFUSED_ATTN", None))):
        return _attn_bwd(mask, q, k, v, lengths, dout)


def _attn_bwd(mask, q, k, v, lengths, dout):
    import jax.numpy as jnp

    s_raw = _attn_logits(q, k)
    probs = _attn_probs(mask, s_raw, lengths)  # (g, m, n) f32
    dout32 = dout.astype(jnp.float32)
    # dV = P^T dO; masked probs are exactly 0 so invalid rows get 0.
    dv = _dispatch3("BNN", jnp.swapaxes(probs, -1, -2), dout32)
    # dP = dO V^T (V zeroed beyond lengths, as in the forward mix).
    dp = _dispatch3("BNT", dout32, _zero_invalid_kv(v, lengths).astype(jnp.float32))
    # softmax vjp: dS = P * (dP - sum(dP * P)); masked entries stay 0.
    ds = probs * (dp - jnp.sum(dp * probs, axis=-1, keepdims=True))
    if mask.softcap:
        cap = jnp.float32(mask.softcap)
        ds = ds * (1.0 - jnp.tanh(s_raw / cap) ** 2)
    k32 = k.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    dq = _dispatch3("BNN", ds, k32)
    dk = _dispatch3("BNN", jnp.swapaxes(ds, -1, -2), q32)
    dlen = np.zeros(lengths.shape, dtype=jax.dtypes.float0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dlen


_dispatch_attn.defvjp(_dispatch_attn_fwd, _dispatch_attn_bwd)


def dispatch_attention(
    q,
    k,
    v,
    *,
    lengths=None,
    causal: bool = False,
    window: int = 0,
    q_start: int = 0,
    k_start: int = 0,
    prefix_len: int = 0,
    q_seg: int = 0,
    softcap: float = 0.0,
    policy: Optional[SelectionPolicy] = None,
):
    """Compute the whole ``softmax(mask(Q K^T)) V`` subgraph through one
    policy-selected attention *plan*.

      dispatch_attention(q, k, v)   q:(..., m, dh) k/v:(..., n, dh) -> (..., m, dh)

    The leading axes of all three operands must match (broadcast K/V
    across the GQA group first, or fold the group into the row extent
    and pass ``q_seg``) and collapse to one batch extent ``g``; the
    policy sees ``OpKey("ATTN", m, n, dh, dsize, g)`` and answers with
    either the fused flash kernel (``FUSED_ATTN``, optionally at a
    learned ``(bq, bk)`` tile) or the unfused plan whose BNT/BNN
    sub-GEMMs are dispatched under their own per-op keys.

    Masking is part of the plan, not the caller: ``causal``, sliding
    ``window``, ``prefix_len`` (prefix-LM bidirectional span),
    ``q_start``/``k_start`` position offsets, ``q_seg`` (per-group query
    count after a group fold — row ``r`` sits at ``q_start + r % q_seg``)
    and per-slice valid-key ``lengths`` (shape matching the leading axes,
    default: all ``n`` keys valid).  ``softcap`` applies the model
    layer's ``cap * tanh(x / cap)`` logit cap before masking.  Queries
    are expected pre-scaled by ``d_head**-0.5``, same as the unfused
    convention.

    Differentiating re-enters dispatch: the backward rule recomputes the
    softmax flash-style (residuals are the operands, never the (m, n)
    probs matrix) and lands every gradient contraction on batched
    gradient OpKeys — wrap the whole ``value_and_grad`` call in one
    ``use_policy`` scope.  Whatever arm ran forward, the backward runs
    the unfused recomputation, so its ops carry the ATTN key's scope with
    the ``UNFUSED_ATTN`` arm (``transpose(...)`` precedes it in
    ``op_name``).
    """
    import jax.numpy as jnp
    from repro.kernels.attention_fused import MaskParams

    if policy is not None:
        with use_policy(policy):
            return dispatch_attention(
                q, k, v, lengths=lengths, causal=causal, window=window,
                q_start=q_start, k_start=k_start, prefix_len=prefix_len,
                q_seg=q_seg, softcap=softcap,
            )
    if q.ndim < 3 or k.ndim != q.ndim or v.ndim != q.ndim:
        raise ValueError(
            "dispatch_attention needs >= 3-D operands with matching "
            f"leading batch axes; got {q.shape}, {k.shape}, {v.shape}"
        )
    lead = q.shape[:-2]
    if k.shape[:-2] != lead or v.shape[:-2] != lead:
        raise ValueError(
            "dispatch_attention leading batch axes differ: "
            f"{q.shape} vs {k.shape} vs {v.shape} — broadcast K/V across "
            "the GQA group before dispatching"
        )
    if k.shape != v.shape or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            "dispatch_attention operand extents mismatch: "
            f"{q.shape} vs {k.shape} vs {v.shape}"
        )
    q3 = q.reshape((-1,) + q.shape[-2:])
    k3 = k.reshape((-1,) + k.shape[-2:])
    v3 = v.reshape((-1,) + v.shape[-2:])
    g = q3.shape[0]
    n = k3.shape[1]
    if lengths is None:
        lengths3 = jnp.full((g, 1), n, jnp.int32)
    else:
        lengths3 = jnp.asarray(lengths, jnp.int32).reshape(g, 1)
    mask = MaskParams(
        causal=bool(causal),
        window=int(window or 0),
        q_start=int(q_start),
        k_start=int(k_start),
        prefix_len=int(prefix_len or 0),
        q_seg=int(q_seg or 0),
        softcap=float(softcap or 0.0),
    )
    out = _dispatch_attn(mask, q3, k3, v3, lengths3)
    return out.reshape(lead + out.shape[-2:])


def dispatch(op: str, a, b, policy: Optional[SelectionPolicy] = None):
    """Compute one dense-layer GEMM through the policy-selected
    (candidate, tile config).

      dispatch("NT", a, b)   a:(..., m, k) @ b:(n, k)^T -> (..., m, n)
      dispatch("NN", a, b)   a:(..., m, k) @ b:(k, n)   -> (..., m, n)
      dispatch("TN", a, b)   a:(k, m)^T    @ b:(k, n)   -> (m, n)

    ``a``/``b`` follow the op's storage layout (``core/opkey.py``): for NT,
    ``b`` is a weight in the paper's row-major (out, in) convention, so the
    forward pass of a dense layer is literally the paper's NT operation.
    Leading batch dims of ``a`` are flattened for NT/NN (TN contracts the
    leading dim, so it is strictly 2-D).  The batched BNT/BNN ops go
    through ``dispatch_batched``.

    Differentiating through ``dispatch`` re-enters it: the backward data
    and weight gradients are dispatched as NN/TN OpKeys under the policy
    in scope at *backward-trace* time — wrap the whole ``value_and_grad``
    call in ``use_policy(...)`` so one scope governs all three GEMMs.

    An explicit ``policy=`` scopes only this call's forward selection
    (prefer ``use_policy`` around the full computation).
    """
    check_op(op)
    if op == "ATTN":
        raise ValueError(
            "op 'ATTN' is the attention plan; call "
            "dispatch_attention(q, k, v, ...)"
        )
    if op in BATCHED_OPS:
        raise ValueError(
            f"op {op!r} is batched; call dispatch_batched({op!r}, a, b)"
        )
    if policy is not None:
        with use_policy(policy):
            return dispatch(op, a, b)
    if op == "TN":
        return _dispatch2("TN", a, b)
    lead = a.shape[:-1]
    a2 = a.reshape((-1, a.shape[-1]))
    out = _dispatch2(op, a2, b)
    n = b.shape[0] if op == "NT" else b.shape[1]
    return out.reshape(lead + (n,))


def dispatch_batched(op: str, a, b, policy: Optional[SelectionPolicy] = None):
    """Compute one batched GEMM — the attention contractions — through the
    policy-selected (candidate, tile config).

      dispatch_batched("BNT", a, b)  a:(..., m, k) @ b:(..., n, k)^T -> (..., m, n)
      dispatch_batched("BNN", a, b)  a:(..., m, k) @ b:(..., k, n)   -> (..., m, n)

    The leading axes of ``a`` and ``b`` must match (broadcast K/V across
    the GQA group *before* dispatching) and collapse to one batch extent
    ``g`` — the ``OpKey`` the policy sees is ``(op, m, n, k, dsize, g)``,
    with (m, n, k) the per-slice extents.  Differentiating re-enters
    dispatch with batched gradient OpKeys, same contract as ``dispatch``:
    wrap the whole ``value_and_grad`` call in one ``use_policy`` scope.
    """
    check_op(op)
    if op == "ATTN":
        raise ValueError(
            "op 'ATTN' is the attention plan; call "
            "dispatch_attention(q, k, v, ...)"
        )
    if op not in BATCHED_OPS:
        raise ValueError(
            f"op {op!r} is not batched; call dispatch({op!r}, a, b)"
        )
    if policy is not None:
        with use_policy(policy):
            return dispatch_batched(op, a, b)
    if a.ndim < 3 or b.ndim != a.ndim:
        raise ValueError(
            f"dispatch_batched({op!r}) needs >= 3-D operands with matching "
            f"leading batch axes; got {a.shape} and {b.shape}"
        )
    lead = a.shape[:-2]
    if b.shape[:-2] != lead:
        raise ValueError(
            f"dispatch_batched({op!r}) leading batch axes differ: "
            f"{a.shape} vs {b.shape} — broadcast the operands first"
        )
    a3 = a.reshape((-1,) + a.shape[-2:])
    b3 = b.reshape((-1,) + b.shape[-2:])
    out = _dispatch3(op, a3, b3)
    return out.reshape(lead + out.shape[-2:])


def dispatch_report(policy: Optional[SelectionPolicy] = None) -> str:
    """Pretty-print per-(op, candidate, tile-config) decision counts for
    ``policy`` (default: the scoped policy).  Rows are grouped by op kind
    and keyed ``NAME@BMxBNxBK`` for decisions that carried an explicit tile
    (``NAME`` for kernel-default ones), so backward-GEMM and attention
    routing is visible in production logs.  Returns the rendered table;
    callers print it."""
    pol = policy if policy is not None else current_policy()
    stats = pol.stats
    lines = [f"dispatch report — {pol!r}"]
    quarantined = faults.quarantine_entries()
    if quarantined:
        lines.append(
            f"  quarantined arms: {len(quarantined)} "
            f"({', '.join(e.label() for e in quarantined)}) — see "
            "health_report()"
        )
    if not stats.calls:
        lines.append("  (no dispatches recorded)")
        return "\n".join(lines)
    by_op = getattr(stats, "by_op", None)
    if by_op:
        rows = [
            (op, label, count)
            for op, labels in by_op.items()
            for label, count in labels.items()
        ]
    else:
        # stats objects predating the op split: one unlabelled group
        flat = getattr(stats, "by_decision", None) or stats.by_candidate
        rows = [("-", label, count) for label, count in flat.items()]
    width = max(len("candidate[@tile]"), max(len(label) for _, label, _ in rows))
    lines.append(
        f"  {'op':<4s} {'candidate[@tile]':<{width}s} {'calls':>8s} {'share':>7s}"
    )
    op_order = {op: i for i, op in enumerate(OPS)}
    rows.sort(key=lambda r: (op_order.get(r[0], 99), -r[2], r[1]))
    for op, label, count in rows:
        lines.append(
            f"  {op:<4s} {label:<{width}s} {count:8d} "
            f"{100.0 * count / stats.calls:6.1f}%"
        )
    lines.append(f"  {'':<4s} {'total':<{width}s} {stats.calls:8d}")
    return "\n".join(lines)


def health_report() -> str:
    """Render the process-wide dispatch health: armed fault-injection
    rules, the quarantine ledger (which arms failed, how, how often), and
    the fallbacks taken — the operator's view of graceful degradation.
    Returns the rendered text; callers print it."""
    lines = ["health report — dispatch fault tolerance"]
    rules = faults.active_faults()
    if rules:
        lines.append(f"  fault injection: {len(rules)} armed rule(s)")
        for rule in rules:
            lines.append(f"    {rule.describe()}")
    else:
        lines.append("  fault injection: (none armed)")
    entries = faults.quarantine_entries()
    if entries:
        lines.append(f"  quarantined arms: {len(entries)}")
        for e in entries:
            lines.append(
                f"    {e.op:<4s} {e.label():<24s} failures={e.count} "
                f"[{e.error}]"
            )
    else:
        lines.append("  quarantined arms: (none)")
    fallbacks = faults.fallback_counts()
    if fallbacks:
        total = sum(fallbacks.values())
        lines.append(f"  fallbacks taken: {total}")
        for (op, selected, executed), n in sorted(fallbacks.items()):
            lines.append(f"    {op:<4s} {selected} -> {executed} x{n}")
    else:
        lines.append("  fallbacks taken: (none)")
    return "\n".join(lines)


def _parse_fixed_arg(arg: str) -> FixedPolicy:
    """``fixed:`` spec bodies — either a single candidate or an
    op-qualified table (``nt=XLA_NT,bnt=PALLAS_BNT@128x128x128,``
    ``attn=fused@128x256``).  The ``attn=`` entry accepts the plan
    aliases ``fused``/``unfused`` alongside literal candidate names, and
    every config parses at its candidate's declared arity — ``BQxBK``
    for the fused attention kernel, ``BMxBNxBK`` for the matmul tiles."""
    from repro.kernels.tiling import parse_config_key

    def parse_entry(val: str, op: Optional[str] = None):
        name, _, cfg = val.partition("@")
        name = name.strip()
        if op == "ATTN":
            name = _ATTN_ALIASES.get(name.lower(), name)
        config = None
        if cfg.strip():
            try:
                arity = get_candidate(name).config_arity
            except KeyError:
                arity = 3
            try:
                config = parse_config_key(cfg.strip(), arity=arity)
            except ValueError as e:
                raise _spec_error(str(e))
        return name, config

    if "=" not in arg:
        name, config = parse_entry(arg)
        return FixedPolicy(name, config=config)
    by_op = {}
    for part in arg.split(","):
        part = part.strip()
        if not part:
            continue
        op_s, eq, val = part.partition("=")
        op = op_s.strip().upper()
        if not eq or op not in OPS or not val.strip():
            raise _spec_error(
                f"malformed op-qualified fixed entry {part!r}; expected "
                "nt=<NAME>[@BMxBNxBK] with op in nt/nn/tn/bnt/bnn/attn"
            )
        by_op[op] = parse_entry(val, op=op)
    if not by_op:
        raise _spec_error("fixed policy needs at least one op entry")
    return FixedPolicy(by_op=by_op)


def policy_from_spec(spec: str, distributed: bool = False) -> SelectionPolicy:
    """Build a policy from a CLI-friendly spec string.

      model[:path]              learned selector (default artifact or path)
      fixed:XLA_TNN             FixedPolicy (other ops — backward GEMMs,
                                attention contractions — run each op's
                                XLA reference)
      fixed:PALLAS_NT@256x256x512   FixedPolicy with a forced tile config
      fixed:nt=XLA_NT,nn=PALLAS_NN[@BMxBNxBK],tn=XLA_TN,bnt=PALLAS_BNT,bnn=XLA_BNN
                                op-qualified FixedPolicy: force a
                                (candidate, tile) per op kind
      fixed:attn=fused@128x256  attention-plan entry: ``fused``/``unfused``
                                alias the FUSED_ATTN/UNFUSED_ATTN pair;
                                fused tiles are (bq, bk)
      analytic                  AnalyticPolicy on the default hardware
      cascade:A,B,C             CascadePolicy over the named candidates
      autotune[:cache.json]     AutotunePolicy over the (op, candidate,
                                tile) measurement cache
                                (default: core.measure.default_cache_path())

    Whitespace around the kind and its argument is ignored, so quoted CLI
    values like ``--policy "fixed: XLA_NT"`` parse.  ``distributed=True``
    restricts guarded policies to pjit-safe candidates — launchers running
    on a >1-device mesh must pass it (FixedPolicy is exempt: forcing a
    candidate is an explicit user override) — and disables autotune
    measurement (cached timings are still used).
    """
    kind, _, arg = spec.strip().partition(":")
    kind = kind.strip()
    arg = arg.strip()
    if not kind:
        raise _spec_error("empty policy spec")
    if kind == "model":
        if not arg:
            return default_policy()  # builtin selector: distributed-safe
        # recover=True: the CLI is the production path — a corrupt artifact
        # is moved aside and a fallback selector trained, never a crash
        return ModelPolicy.from_artifact(
            arg, distributed=distributed, recover=True
        )
    if kind == "fixed":
        if not arg:
            raise _spec_error("fixed policy needs a candidate: fixed:<NAME>")
        return _parse_fixed_arg(arg)
    if kind == "analytic":
        return AnalyticPolicy(distributed=distributed)
    if kind == "autotune":
        from .measure import default_cache_path

        return AutotunePolicy(
            cache_path=arg or default_cache_path(), distributed=distributed
        )
    if kind == "cascade":
        names = [n.strip() for n in arg.split(",") if n.strip()]
        if not names:
            raise _spec_error("cascade policy needs names: cascade:<A,B,...>")
        return CascadePolicy(names, distributed=distributed)
    raise _spec_error(f"unknown policy spec {spec!r}")


def add_policy_argument(parser) -> None:
    """Attach the shared ``--policy`` option to an argparse parser."""
    parser.add_argument("--policy", default="model", help=POLICY_SPEC_HELP)
