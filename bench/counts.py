"""Operations and bytes the work requires, computed from shapes.

These count what the model needs, not what an implementation happens to
run: recomputation under remat, padding to buckets and the masked half of
a causal square are not counted.  A GEMM is ``(m, n, k)``: an ``m x k``
by ``k x n`` product, ``2*m*n*k`` operations.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List


@dataclass(frozen=True)
class Work:
    """Operations and the least bytes one kernel must move."""

    flops: float
    bytes: float


def gemm(m: int, n: int, k: int, elem_bytes: int) -> Work:
    return Work(2.0 * m * n * k, float(elem_bytes) * (m * k + k * n + m * n))


def roofline_s(gemms: Iterable[Work], peak_flops: float, peak_bw: float) -> float:
    """Least time the chip could take for these GEMMs, one after another:
    each bounded by the larger of its compute and its memory time."""
    return sum(max(g.flops / peak_flops, g.bytes / peak_bw) for g in gemms)


def total_flops(gemms: Iterable[Work]) -> float:
    return sum(g.flops for g in gemms)


# -- fully connected network (arXiv:1702.03192 §VI-C) -------------------------


def fcn_params(dims) -> int:
    return sum(i * o + o for i, o in zip(dims[:-1], dims[1:]))


def fcn_train_gemms(dims, batch: int, elem_bytes: int) -> List[Work]:
    """One training step: the forward NT product of every layer, the
    weight gradient of every layer and the input gradient of every layer
    but the first (the input needs none)."""
    out = []
    for li, (i, o) in enumerate(zip(dims[:-1], dims[1:])):
        out.append(gemm(batch, o, i, elem_bytes))  # y = x W^T
        out.append(gemm(o, i, batch, elem_bytes))  # dW = dy^T x
        if li > 0:
            out.append(gemm(batch, i, o, elem_bytes))  # dx = dy W
    return out


# -- decoder-only transformer (RMSNorm, RoPE, GQA, SwiGLU, tied head) ----------


def lm_layer_matmul_params(d: int, heads: int, kv: int, dh: int, dff: int) -> int:
    return d * (heads + 2 * kv) * dh + heads * dh * d + 3 * d * dff


def lm_matmul_params(c: dict) -> int:
    """Weights that enter a matrix product for every token: all layers'
    projections plus the output head (the tied embedding)."""
    per = lm_layer_matmul_params(
        c["d_model"], c["n_heads"], c["n_kv"], c["d_head"], c["d_ff"]
    )
    return c["n_layers"] * per + c["vocab"] * c["d_model"]


def lm_params(c: dict) -> int:
    """Every parameter: matmul weights plus the RMSNorm scales."""
    return lm_matmul_params(c) + (2 * c["n_layers"] + 1) * c["d_model"]


def kv_bytes_per_token(c: dict, elem_bytes: int = 2) -> int:
    return c["n_layers"] * 2 * c["n_kv"] * c["d_head"] * elem_bytes


def lm_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention
    (QK^T and PV over the kept half of the square, times three)."""
    attn = 6.0 * seq * c["n_heads"] * c["d_head"] * c["n_layers"]
    return 6.0 * lm_matmul_params(c) + attn


def causal_attention(c: dict, batch: int, seq: int, elem_bytes: int,
                     backward: bool) -> Work:
    """One layer's ``softmax(QK^T) V`` over the kept half of the causal
    square.  Forward: QK^T and PV, reading Q, K, V and writing O.
    Backward: dP, dV, dQ and dK (twice the forward), reading Q, K, V, O
    and dO and writing dQ, dK and dV.  The scores never need to leave the
    chip, so they are not counted as bytes."""
    h, kv, dh = c["n_heads"], c["n_kv"], c["d_head"]
    fwd = 2.0 * batch * h * seq * seq * dh
    q, k = batch * seq * h * dh, batch * seq * kv * dh
    if backward:
        return Work(2.0 * fwd, float(elem_bytes) * (3 * q + 2 * k + 2 * q + 2 * k))
    return Work(fwd, float(elem_bytes) * (2 * q + 2 * k))


def lm_train_gemms(c: dict, batch: int, seq: int, elem_bytes: int) -> List[Work]:
    """The GEMMs of one training step, forward and both gradients."""
    d, h, kv, dh, dff, L = (
        c["d_model"], c["n_heads"], c["n_kv"], c["d_head"], c["d_ff"], c["n_layers"]
    )
    T = batch * seq
    dense = [(h * dh, d), (kv * dh, d), (kv * dh, d), (d, h * dh),
             (dff, d), (dff, d), (d, dff)]
    out = []
    for _ in range(L):
        for o, i in dense:
            out += [gemm(T, o, i, elem_bytes), gemm(o, i, T, elem_bytes),
                    gemm(T, i, o, elem_bytes)]
        out.append(causal_attention(c, batch, seq, elem_bytes, backward=False))
        out.append(causal_attention(c, batch, seq, elem_bytes, backward=True))
    V = c["vocab"]
    out += [gemm(T, V, d, elem_bytes), gemm(V, d, T, elem_bytes),
            gemm(T, d, V, elem_bytes)]
    return out


def lm_decode_flops(c: dict, keys: int) -> float:
    """One new token attending ``keys`` cached positions (its own included)."""
    attn = 4.0 * keys * c["n_heads"] * c["d_head"] * c["n_layers"]
    return 2.0 * lm_matmul_params(c) + attn


def lm_prefill_flops(c: dict, prompt: int) -> float:
    """A prompt's forward pass: every layer at every position, causal
    attention over the kept half, the head at the last position only."""
    head = c["vocab"] * c["d_model"]
    attn = 2.0 * prompt * prompt * c["n_heads"] * c["d_head"] * c["n_layers"]
    return 2.0 * (lm_matmul_params(c) - head) * prompt + 2.0 * head + attn


def lm_decode_bytes(c: dict, cached: Iterable[int], param_bytes: int = 2) -> float:
    """Bytes one decode step must move: every weight once, the cached K/V
    of each row (``cached`` positions before the new token) and the new
    token's K/V written."""
    per_tok = kv_bytes_per_token(c)
    rows = list(cached)
    return float(lm_params(c) * param_bytes + per_tok * (sum(rows) + len(rows)))
