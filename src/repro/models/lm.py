"""The decoder-only LM covering all ten assigned architectures.

Layer stack = a ``lax.scan`` per config segment over stacked block params
(compact HLO at any depth, remat-wrapped per unit).  Three entry points:

  lm_loss      training forward + next-token CE (train_4k)
  lm_prefill   forward that also emits the decode cache (prefill_32k)
  lm_decode    one-token step against a cache (decode_32k / long_500k)

Modalities: ``tokens`` (LMs), ``frames`` (musicgen — stub EnCodec frame
embeddings enter directly), ``vlm`` (paligemma — stub SigLIP patch
embeddings prepended as a bidirectional prefix).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .blocks import (
    ATTN_MIXERS,
    apply_block,
    decode_block,
    init_block,
    init_block_cache,
    prefill_block,
)
from .layers import (
    Param,
    cross_entropy_loss,
    embed,
    init_embedding,
    init_rmsnorm,
    rmsnorm,
    softcap,
    unembed,
)

__all__ = [
    "init_lm",
    "lm_forward",
    "lm_loss",
    "lm_prefill",
    "lm_decode",
    "init_lm_cache",
]


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def _has_shared(cfg) -> bool:
    return any(b.mixer == "shared_attn" for _, bl in cfg.segments for b in bl)


def init_lm(key: jax.Array, cfg) -> Param:
    dt = _dtype(cfg)
    keys = jax.random.split(key, len(cfg.segments) + 3)
    params: Param = {
        "embed": init_embedding(keys[0], cfg.vocab_padded, cfg.d_model, dt),
        "final_norm": init_rmsnorm(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(keys[1], cfg.vocab_padded, cfg.d_model, dt)
    if _has_shared(cfg):
        from .attention import init_attention
        from .blocks import _attn_cfg

        shared_b = next(
            b for _, bl in cfg.segments for b in bl if b.mixer == "shared_attn"
        )
        params["shared"] = {
            "attn": init_attention(keys[2], _attn_cfg(shared_b, cfg), dt)
        }
    segs = []
    for si, (count, blocks) in enumerate(cfg.segments):
        bkeys = jax.random.split(keys[3 + si], len(blocks))
        slot_params = []
        for bi, b in enumerate(blocks):
            stacked = jax.vmap(
                lambda k: init_block(k, b, cfg, dt)
            )(jax.random.split(bkeys[bi], count))
            slot_params.append(stacked)
        segs.append(tuple(slot_params))
    params["segments"] = segs
    return params


def _remat_wrap(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # 'full': save only unit boundaries


def _embed_input(params: Param, cfg, batch: Dict[str, jax.Array]):
    """Returns (x, positions, prefix_len, label_offset)."""
    if cfg.input_mode == "tokens":
        x = embed(params["embed"], batch["tokens"], cfg.emb_scale)
        return x, None, 0
    if cfg.input_mode == "frames":
        x = batch["frames"].astype(_dtype(cfg))
        return x, None, 0
    if cfg.input_mode == "vlm":
        patches = batch["patches"].astype(_dtype(cfg))
        text = embed(params["embed"], batch["tokens"], cfg.emb_scale)
        x = jnp.concatenate([patches, text], axis=1)
        return x, None, patches.shape[1]
    raise ValueError(f"unknown input_mode {cfg.input_mode!r}")


def _unit_slice(slot_params, i):
    return tuple(jax.tree.map(lambda leaf: leaf[i], sp) for sp in slot_params)


def _run_stack(params, cfg, x, positions, prefix_len):
    shared = params.get("shared")
    for (count, blocks), slot_params in zip(cfg.segments, params["segments"]):
        def unit(carry, unit_params, _blocks=blocks):
            h = carry
            for b, bp in zip(_blocks, unit_params):
                h = apply_block(bp, h, b, cfg, shared, positions, prefix_len)
            return h, None

        body = _remat_wrap(unit, cfg)
        if cfg.unroll_segments:  # accounting probes: no while loop
            for i in range(count):
                x, _ = body(x, _unit_slice(slot_params, i))
        else:
            x, _ = jax.lax.scan(body, x, tuple(slot_params))
    return x


def _logits(params, cfg, x):
    x = rmsnorm(params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x)
    return softcap(logits, cfg.final_softcap)


def lm_forward(params: Param, cfg, batch: Dict[str, jax.Array]):
    x, positions, prefix_len = _embed_input(params, cfg, batch)
    x = _run_stack(params, cfg, x, positions, prefix_len)
    return _logits(params, cfg, x)


def lm_loss(params: Param, cfg, batch: Dict[str, jax.Array]):
    logits = lm_forward(params, cfg, batch)
    if cfg.input_mode == "vlm":
        logits = logits[:, cfg.prefix_len :]  # loss on text positions only
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    loss = cross_entropy_loss(logits, labels, mask)
    return loss, {"loss": loss}


# -- prefill ------------------------------------------------------------------


def lm_prefill(
    params: Param,
    cfg,
    batch: Dict[str, jax.Array],
    max_seq: int,
    cache_dtype=jnp.bfloat16,
    true_len: Optional[jax.Array] = None,
):
    """Returns (last-position logits, cache).

    ``true_len`` (scalar or ``(B,)``, traced OK) marks a right-padded
    prefill: the logits are taken at each row's *real* last position
    (``true_len - 1``) and the cache's ``pos`` starts at ``true_len``, so
    the pad tail is never sampled from and decode overwrites/masks it.
    The serving engine uses this to bucket prompt lengths into a small
    compile set instead of one compile per distinct length."""
    x, positions, prefix_len = _embed_input(params, cfg, batch)
    shared = params.get("shared")
    caches = []
    for (count, blocks), slot_params in zip(cfg.segments, params["segments"]):
        def unit(carry, unit_params, _blocks=blocks):
            h = carry
            unit_cache = []
            for b, bp in zip(_blocks, unit_params):
                h, c = prefill_block(
                    bp, h, b, cfg, max_seq, shared, positions, prefix_len,
                    cache_dtype, true_len=true_len,
                )
                unit_cache.append(c)
            return h, tuple(unit_cache)

        body = _remat_wrap(unit, cfg)
        if cfg.unroll_segments:
            units = []
            for i in range(count):
                x, uc = body(x, _unit_slice(slot_params, i))
                units.append(uc)
            seg_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *units)
        else:
            x, seg_cache = jax.lax.scan(body, x, tuple(slot_params))
        caches.append(seg_cache)
    if true_len is None:
        logits = _logits(params, cfg, x[:, -1:])
        pos_next = jnp.asarray(x.shape[1], jnp.int32)
    else:
        pos_next = jnp.asarray(true_len, jnp.int32)
        idx = jnp.broadcast_to(
            jnp.atleast_1d(jnp.clip(pos_next - 1, 0, x.shape[1] - 1)),
            (x.shape[0],),
        )
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = _logits(params, cfg, x_last)
    return logits, {"segments": caches, "pos": pos_next}


# -- decode -------------------------------------------------------------------


def init_lm_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16,
                  per_seq_pos: bool = False):
    """Zero cache with the same pytree structure lm_prefill produces.

    ``per_seq_pos`` starts ``pos`` as a ``(batch,)`` vector instead of a
    scalar — the ragged form the serving engine decodes with, where every
    cache slot holds a sequence of its own length."""
    caches = []
    for count, blocks in cfg.segments:
        seg = tuple(
            jax.tree.map(
                lambda leaf: jnp.zeros((count,) + leaf.shape, leaf.dtype),
                init_block_cache(b, cfg, batch, max_seq, dtype),
            )
            for b in blocks
        )
        caches.append(seg)
    pos = jnp.zeros((batch,) if per_seq_pos else (), jnp.int32)
    return {"segments": caches, "pos": pos}


def _read_unit_cache(block_cache, i):
    """Dynamic per-unit slice of one block's stacked cache."""
    return jax.tree.map(
        lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, 0, False), block_cache
    )


def _write_unit_cache(block_cache, new_unit, i):
    """Write one unit's updated cache back into a block's stacked buffers.

    Chained dynamic-update-slices on a donated/carried buffer alias in
    place — the decode step holds ONE cache copy, not three (found via the
    dry-run memory proof; see EXPERIMENTS.md §Dry-run)."""
    return jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_index_in_dim(full, new, i, 0),
        block_cache,
        new_unit,
    )


def lm_decode(
    params: Param,
    cfg,
    cache,
    batch: Dict[str, jax.Array],
):
    """One-token step.  batch: {'tokens': (B,1)} or {'frames': (B,1,d)}.

    Returns (logits (B,1,V), new cache with pos+1).  ``cache['pos']`` may
    be a scalar (uniform batch) or a ``(B,)`` vector (ragged batch: each
    row decodes at its own position — the continuous-batching engine's
    form; see ``attention_decode``).  The stacked cache is carried whole
    through the layer scan and updated with dynamic slices, so XLA keeps
    it in place (while-loop carry aliasing).

    With ``cache['slots']`` (B,) the attention leaves are the serving
    engine's whole slot pool (``serving/kv_cache.py``): each attention
    layer gets the pool leaves with its layer index and the rows' slots,
    and reads and writes them in place (``attention._decode_in_pool``);
    the other leaves (SSM state) hold the rows' own gathered state.
    """
    pos = cache["pos"]
    slots = cache.get("slots")
    if cfg.input_mode == "frames":
        x = batch["frames"].astype(_dtype(cfg))
    else:
        x = embed(params["embed"], batch["tokens"], cfg.emb_scale)
    shared = params.get("shared")
    new_caches = []
    for (count, blocks), slot_params, seg_cache in zip(
        cfg.segments, params["segments"], cache["segments"]
    ):
        in_pool = tuple(
            slots is not None and b.mixer in ATTN_MIXERS for b in blocks
        )

        def unit(carry, xs, _blocks=blocks, _in_pool=in_pool):
            h, seg = carry
            i, unit_params = xs
            new_seg = []
            for b, bp, c, pooled in zip(_blocks, unit_params, seg, _in_pool):
                if pooled:
                    h, c = decode_block(bp, h, b, cfg, dict(c, slots=slots, layer=i),
                                        pos, shared)
                else:
                    h, cu = decode_block(bp, h, b, cfg, _read_unit_cache(c, i),
                                         pos, shared)
                    c = _write_unit_cache(c, cu, i)
                new_seg.append(c)
            return (h, tuple(new_seg)), None

        idx = jnp.arange(count, dtype=jnp.int32)
        if cfg.unroll_segments:
            carry = (x, seg_cache)
            for i in range(count):
                carry, _ = unit(carry, (idx[i], _unit_slice(slot_params, i)))
            x, new_seg = carry
        else:
            (x, new_seg), _ = jax.lax.scan(
                unit, (x, seg_cache), (idx, tuple(slot_params))
            )
        new_caches.append(new_seg)
    logits = _logits(params, cfg, x)
    return logits, {"segments": new_caches, "pos": pos + 1}
