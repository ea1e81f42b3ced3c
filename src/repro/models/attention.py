"""GQA attention layer (full-sequence and single-token-decode paths).

Cache layout per attention layer:
  ``k``/``v``: (B, S_cache, H_kv, head_dim).  For sliding-window archs the
  cache is a **ring buffer** of ``S_cache == window`` slots (the deployment-
  faithful layout: a warm h2o-danube replica at 500k context holds a 4k ring,
  not a 500k tensor); for full attention ``S_cache == max_seq``.
Keys are stored *post-RoPE* so decode never re-rotates the cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, ops
from repro.models import layers


def init_attention(rng, cfg, d_model: Optional[int] = None, *, cross: bool = False,
                   num_heads: Optional[int] = None, num_kv_heads: Optional[int] = None):
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.head_dim if d_model is None else d // h
    pdt = cfg.param_dtype
    r = jax.random.split(rng, 4)
    p = {
        "wq": layers.dense_init(r[0], d, h * hd, pdt),
        "wk": layers.dense_init(r[1], d, hkv * hd, pdt),
        "wv": layers.dense_init(r[2], d, hkv * hd, pdt),
        "wo": layers.dense_init(r[3], h * hd, d, pdt, scale=(h * hd) ** -0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), pdt)
        p["bk"] = jnp.zeros((hkv * hd,), pdt)
        p["bv"] = jnp.zeros((hkv * hd,), pdt)
    return p


def _proj_qkv(p, x, kv_x, h, hkv, hd):
    b = x.shape[0]
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, -1, h, hd)
    k = k.reshape(b, -1, hkv, hd)
    v = v.reshape(b, -1, hkv, hd)
    return q, k, v


def full_attention(p, x, cfg, *, q_pos, causal=True, window=None,
                   kv_x=None, use_rope=True, impl=None,
                   num_heads=None, num_kv_heads=None, return_kv=False,
                   train=False):
    """Full-sequence attention (train / prefill / encoder / cross).

    x: (B, Sq, d); kv_x: (B, Skv, d) for cross-attention (default: x).
    q_pos: (Sq,) absolute positions of the queries (= kv positions when self).
    ``train`` says a gradient will be taken (``ops.choose_flash_impl``).
    """
    h = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    hd = p["wq"].shape[1] // h
    self_attn = kv_x is None
    kv_in = x if self_attn else kv_x
    q, k, v = _proj_qkv(p, x, kv_in, h, hkv, hd)
    kv_pos = q_pos if self_attn else jnp.arange(kv_in.shape[1])
    if use_rope and self_attn:
        cos, sin = layers.rope_cos_sin(q_pos, hd, cfg.rope_theta)
        q = layers.apply_rope(q, cos[None], sin[None])
        k = layers.apply_rope(k, cos[None], sin[None])
    impl = ops.choose_flash_impl(impl or cfg.attention_impl, train=train,
                                 self_attn=self_attn, sq=q.shape[1],
                                 skv=k.shape[1])
    out = ops.flash_attention(
        q, k, v, causal=causal and self_attn, window=window,
        q_pos=q_pos, kv_pos=kv_pos, impl=impl)
    b, sq = x.shape[0], x.shape[1]
    y = out.reshape(b, sq, h * hd) @ p["wo"]
    if return_kv:
        return y, (k, v)
    return y


def prefill_attention(cfg, seq: int, window: Optional[int]) -> dict:
    """The kind of attention (``gqa``, or ``mla`` for latent attention,
    ``models/mla.py``), the path a prefill of ``seq`` tokens takes for
    causal self-attention, and the score blocks it computes of all it
    could, per head group: the kernel's block pairs, or the reference's
    chunk pairs (it computes all); the cache's bytes a token over every
    layer (latent attention keeps the latent and the rotated key).  Span
    attributes of ``engine.start.compile``."""
    impl = ops.choose_flash_impl(cfg.attention_impl, train=False,
                                 self_attn=True, sq=seq, skv=seq)
    if impl == "pallas":
        done, total = flash_attention.block_counts(
            seq, seq, cfg.num_heads, cfg.num_kv_heads, cfg.qk_head_dim,
            cfg.dtype, causal=True, window=window, dv=cfg.v_head_dim or None)
    else:
        done = total = (seq // ops.pick_chunk(seq, ops.REF_CHUNK)) ** 2
    per_pos = (cfg.kv_lora_rank + cfg.qk_rope_head_dim if cfg.mla
               else 2 * cfg.kv_dim)
    n_attn = cfg.layer_pattern.count("A")
    return {"attention_kind": "mla" if cfg.mla else "gqa",
            "prefill_attention": impl, "attention_blocks": f"{done}/{total}",
            "cache_bytes_per_token":
                per_pos * jnp.dtype(cfg.dtype).itemsize * n_attn}


def init_cache(cfg, batch: int, max_seq: int, *, window: Optional[int] = None,
               num_heads=None, num_kv_heads=None, dtype=None):
    hkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.head_dim
    s = min(window, max_seq) if window else max_seq
    dt = jnp.dtype(dtype or cfg.dtype)
    return {
        "k": jnp.zeros((batch, s, hkv, hd), dt),
        "v": jnp.zeros((batch, s, hkv, hd), dt),
    }


def decode_attention(p, x, cache, pos, cfg, *, window=None,
                     cross_kv=None, use_rope=True, impl=None,
                     num_heads=None, num_kv_heads=None):
    """One-token decode.  x: (B, d); pos: scalar int (current position).

    Returns (y (B, d), new_cache).  When ``cross_kv`` is given, attends the
    fixed encoder keys/values instead (cache unchanged).
    """
    h = num_heads or cfg.num_heads
    hkv = num_kv_heads or cfg.num_kv_heads
    hd = p["wq"].shape[1] // h
    b = x.shape[0]

    if cross_kv is not None:
        k, v = cross_kv
        q = (x @ p["wq"]).reshape(b, h, hd)
        valid = jnp.ones((b, k.shape[1]), bool)
        out = ops.decode_attention(q, k, v, valid, impl=impl or cfg.attention_impl)
        return out.reshape(b, h * hd) @ p["wo"], cache

    q, k, v = _proj_qkv(p, x[:, None, :], x[:, None, :], h, hkv, hd)
    if use_rope:
        cos, sin = layers.rope_cos_sin(jnp.asarray(pos)[None], hd, cfg.rope_theta)
        q = layers.apply_rope(q, cos[None], sin[None])
        k = layers.apply_rope(k, cos[None], sin[None])
    s_cache = cache["k"].shape[1]
    ring = window is not None and s_cache <= window
    slot = (pos % s_cache) if ring else pos
    # One-hot "where-scatter" write instead of dynamic_update_slice: purely
    # elementwise, so a cache sharded on the sequence dim (the decode_32k /
    # long-cache layout) partitions cleanly under GSPMD with no resharding.
    hot = (jnp.arange(s_cache) == slot)[None, :, None, None]
    k_cache = jnp.where(hot, k.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(hot, v.astype(cache["v"].dtype), cache["v"])
    idx = jnp.arange(s_cache)
    valid = idx <= pos                      # full cache AND ring (see module doc)
    if window is not None and not ring:
        # full-size cache but windowed attention (jamba @ 32k)
        valid &= idx > (pos - window)
    valid = jnp.broadcast_to(valid[None], (b, s_cache))
    out = ops.decode_attention(q.reshape(b, h, hd), k_cache, v_cache, valid,
                               impl=impl or cfg.attention_impl)
    y = out.reshape(b, h * hd) @ p["wo"]
    return y, {"k": k_cache, "v": v_cache}
