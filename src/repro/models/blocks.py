"""Residual blocks: the unit the layer-stack scans over.

A block = mixer (attention / Mamba-2 SSD / Zamba-style *shared* attention)
+ optional FFN (gated MLP / MoE), each pre-normed, with optional post-norms
(Gemma-2/3).  Block params are pytrees; stacked along a leading layer axis
by ``lm.init_lm`` for ``lax.scan``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import (
    AttnConfig,
    attention,
    attention_decode,
    init_attention,
    init_attn_cache,
)
from .layers import Param, gated_mlp, init_gated_mlp, init_rmsnorm, rmsnorm
from .moe import init_moe, moe_layer
from .ssm import init_ssm, init_ssm_cache, ssm_decode, ssm_layer

__all__ = ["ATTN_MIXERS", "BlockCfg", "init_block", "apply_block", "decode_block",
           "init_block_cache"]

# mixers whose decode cache is attention K/V (the serving pool's kernel layout)
ATTN_MIXERS = ("attn", "shared_attn")


@dataclass(frozen=True)
class BlockCfg:
    mixer: str  # 'attn' | 'mamba' | 'shared_attn'
    ffn: str = "mlp"  # 'mlp' | 'moe' | 'none'
    window: Optional[int] = None  # sliding window for attn mixers


def _attn_cfg(b: BlockCfg, mc) -> AttnConfig:
    return AttnConfig(
        d_model=mc.d_model,
        n_heads=mc.n_heads,
        n_kv=mc.n_kv,
        d_head=mc.d_head,
        window=b.window,
        softcap=mc.attn_softcap,
        rope_theta=mc.rope_theta,
        qk_norm=mc.qk_norm,
        chunk=mc.attn_chunk,
        sp_attention=getattr(mc, "sp_attention", False),
    )


def init_block(key: jax.Array, b: BlockCfg, mc, dtype=jnp.float32) -> Param:
    """mc: the ArchConfig (duck-typed: d_model, n_heads, ..., moe, ssm)."""
    k1, k2 = jax.random.split(key)
    p: Param = {"ln1": init_rmsnorm(mc.d_model, dtype)}
    if b.mixer == "attn":
        p["attn"] = init_attention(k1, _attn_cfg(b, mc), dtype)
    elif b.mixer == "mamba":
        p["ssm"] = init_ssm(k1, mc.ssm, dtype)
    elif b.mixer == "shared_attn":
        pass  # weights live in the model-level 'shared' slot
    else:
        raise ValueError(f"unknown mixer {b.mixer!r}")
    if mc.post_norm:
        p["ln1b"] = init_rmsnorm(mc.d_model, dtype)
    if b.ffn != "none":
        p["ln2"] = init_rmsnorm(mc.d_model, dtype)
        if b.ffn == "mlp":
            p["mlp"] = init_gated_mlp(k2, mc.d_model, mc.d_ff, dtype)
        elif b.ffn == "moe":
            p["moe"] = init_moe(k2, mc.moe, dtype)
        else:
            raise ValueError(f"unknown ffn {b.ffn!r}")
        if mc.post_norm:
            p["ln2b"] = init_rmsnorm(mc.d_model, dtype)
    return p


def _mix(h, p, b, mc, shared, positions, prefix_len):
    if b.mixer == "attn":
        return attention(p["attn"], h, _attn_cfg(b, mc), positions, prefix_len)
    if b.mixer == "shared_attn":
        return attention(shared["attn"], h, _attn_cfg(b, mc), positions, prefix_len)
    return ssm_layer(p["ssm"], h, mc.ssm)


def apply_block(
    p: Param,
    x: jax.Array,
    b: BlockCfg,
    mc,
    shared: Optional[Param] = None,
    positions=None,
    prefix_len: int = 0,
) -> jax.Array:
    h = _mix(rmsnorm(p["ln1"], x), p, b, mc, shared, positions, prefix_len)
    if mc.post_norm:
        h = rmsnorm(p["ln1b"], h)
    x = x + h
    if b.ffn != "none":
        h = rmsnorm(p["ln2"], x)
        if b.ffn == "mlp":
            h = gated_mlp(p["mlp"], h, mc.activation)
        else:
            h = moe_layer(p["moe"], h, mc.moe)
        if mc.post_norm:
            h = rmsnorm(p["ln2b"], h)
        x = x + h
    return x


def prefill_block(
    p: Param,
    x: jax.Array,
    b: BlockCfg,
    mc,
    max_seq: int,
    shared: Optional[Param] = None,
    positions=None,
    prefix_len: int = 0,
    cache_dtype=jnp.bfloat16,
    true_len=None,
):
    """apply_block + build this layer's decode cache.

    ``true_len`` marks a right-padded prefill (see ``attention``): the
    attention cache is built over the real positions only.  SSM state is
    cumulative over the whole padded sequence, so padded prefill is an
    attention-only feature — the serving engine prefills SSM archs at
    exact lengths."""
    h = rmsnorm(p["ln1"], x)
    if b.mixer in ATTN_MIXERS:
        ap = p["attn"] if b.mixer == "attn" else shared["attn"]
        h, cache = attention(
            ap, h, _attn_cfg(b, mc), positions, prefix_len,
            return_kv=True, max_seq=max_seq, cache_dtype=cache_dtype,
            true_len=true_len,
        )
    else:
        h, cache = ssm_layer(
            p["ssm"], h, mc.ssm, return_state=True, cache_dtype=cache_dtype
        )
    if mc.post_norm:
        h = rmsnorm(p["ln1b"], h)
    x = x + h
    if b.ffn != "none":
        h = rmsnorm(p["ln2"], x)
        h = (
            gated_mlp(p["mlp"], h, mc.activation)
            if b.ffn == "mlp"
            else moe_layer(p["moe"], h, mc.moe)
        )
        if mc.post_norm:
            h = rmsnorm(p["ln2b"], h)
        x = x + h
    return x, cache


# -- decode -------------------------------------------------------------------


def init_block_cache(b: BlockCfg, mc, batch: int, max_seq: int, dtype=jnp.bfloat16):
    if b.mixer in ATTN_MIXERS:
        return init_attn_cache(batch, _attn_cfg(b, mc), max_seq, dtype)
    return init_ssm_cache(batch, mc.ssm, dtype)


def decode_block(
    p: Param,
    x: jax.Array,  # (B, 1, d)
    b: BlockCfg,
    mc,
    cache,
    pos,
    shared: Optional[Param] = None,
):
    h = rmsnorm(p["ln1"], x)
    if b.mixer == "attn":
        h, cache = attention_decode(p["attn"], h, _attn_cfg(b, mc), cache, pos)
    elif b.mixer == "shared_attn":
        h, cache = attention_decode(shared["attn"], h, _attn_cfg(b, mc), cache, pos)
    else:
        h, cache = ssm_decode(p["ssm"], h, mc.ssm, cache)
    if mc.post_norm:
        h = rmsnorm(p["ln1b"], h)
    x = x + h
    if b.ffn != "none":
        h = rmsnorm(p["ln2"], x)
        if b.ffn == "mlp":
            h = gated_mlp(p["mlp"], h, mc.activation)
        else:
            h = moe_layer(p["moe"], h, mc.moe)
        if mc.post_norm:
            h = rmsnorm(p["ln2b"], h)
        x = x + h
    return x, cache
