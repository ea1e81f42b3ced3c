"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Keys and values pass through a latent: per position ``c = norm(h W_kv_a)``
of ``kv_lora_rank`` values, beside one rotated key ``k_pe`` of
``qk_rope_head_dim`` values that every head shares.  Queries have no
latent (no q-LoRA): per head ``[q_nope; q_pe]``.  Rotation is YaRN's when
the config scales it, over the published interleaved pairs, and the
softmax scale is ``qk_head_dim ** -0.5`` times YaRN's ``mscale ** 2``.

Two paths, one for each phase:

  prefill  expanded: each head's key ``[c W_uk; k_pe]`` and value
           ``c W_uv`` are formed and go through ``ops.flash_attention``
           (qk head dim 192 and v head dim 128 for V2-Lite);
  decode   absorbed, over the latent cache: ``q_nope W_uk^T`` is scored
           against ``c`` and ``q_pe`` against ``k_pe``, and the weighted
           sum of ``c`` goes out through ``W_uv``.

The decode cache of a layer holds ``c`` (B, S, kv_lora_rank) and ``pe``
(B, S, qk_rope_head_dim), keys rotated: 576 values a position for
V2-Lite, where per-head keys and values would take 16 x 256.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers


def init_mla(rng, cfg):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    pdt = cfg.param_dtype
    k = jax.random.split(rng, 4)
    return {
        "wq": layers.dense_init(k[0], d, h * cfg.qk_head_dim, pdt),
        "wkv_a": layers.dense_init(k[1], d, r + cfg.qk_rope_head_dim, pdt),
        "kv_norm": layers.norm_init(r, "rmsnorm", pdt),
        "wkv_b": layers.dense_init(
            k[2], r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim), pdt),
        "wo": layers.dense_init(k[3], h * cfg.v_head_dim, d, pdt,
                                scale=(h * cfg.v_head_dim) ** -0.5),
    }


def softmax_scale(cfg) -> float:
    s = cfg.qk_head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None:
        s *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _rope(x, pos, cfg):
    """Rotate the last dim of x (..., S, H, rope) at positions pos (S,)."""
    dr = cfg.qk_rope_head_dim
    freqs = (None if cfg.rope_scaling is None else
             layers.yarn_freqs(dr, cfg.rope_theta, cfg.rope_scaling))
    cos, sin = layers.rope_cos_sin(pos, dr, cfg.rope_theta, freqs=freqs)
    return layers.apply_rope_interleaved(x, cos[None], sin[None])


def _project(p, x, pos, cfg):
    """x: (B, S, d) -> q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated,
    c (B,S,r) normed, k_pe (B,S,rope) rotated."""
    b, s, _ = x.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
    kv = x @ p["wkv_a"]
    c = layers.norm_apply(p["kv_norm"], kv[..., :r], "rmsnorm")
    k_pe = _rope(kv[..., None, r:], pos, cfg)[:, :, 0]
    return q[..., :dn], _rope(q[..., dn:], pos, cfg), c, k_pe


def _wkv_b(p, cfg):
    """W_kv_b as (r, H, nope + v): per head the key's and the value's."""
    return p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                              cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_full(p, x, cfg, *, q_pos, train=False):
    """Causal self-attention over x (B, S, d), expanded.  Returns
    (y (B, S, d), cache material {"c", "pe"})."""
    b, s, _ = x.shape
    h, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe, c, k_pe = _project(p, x, q_pos, cfg)
    kv = jnp.einsum("bsr,rhe->bshe", c, _wkv_b(p, cfg))
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (*kv.shape[:3],
                                                            k_pe.shape[-1]))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    o = ops.flash_attention(q, k, kv[..., dn:], causal=True, q_pos=q_pos,
                            kv_pos=q_pos, scale=softmax_scale(cfg),
                            train=train)
    return o.reshape(b, s, h * dv) @ p["wo"], {"c": c, "pe": k_pe}


def init_cache(cfg, batch: int, max_seq: int) -> Dict[str, jax.Array]:
    dt = jnp.dtype(cfg.dtype)
    return {"c": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dt),
            "pe": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dt)}


def mla_decode(p, x, cache, pos, cfg):
    """One token x (B, d) at position ``pos``, absorbed, against the latent
    cache.  Returns (y (B, d), new cache)."""
    b = x.shape[0]
    dn = cfg.qk_nope_head_dim
    q_nope, q_pe, c, k_pe = _project(p, x[:, None], jnp.asarray(pos)[None],
                                     cfg)
    c_all = jax.lax.dynamic_update_slice(
        cache["c"], c.astype(cache["c"].dtype), (0, pos, 0))
    pe_all = jax.lax.dynamic_update_slice(
        cache["pe"], k_pe.astype(cache["pe"].dtype), (0, pos, 0))
    w = _wkv_b(p, cfg)
    f32 = jnp.float32
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w[..., :dn],
                       preferred_element_type=f32)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(c_all.dtype), c_all,
                    preferred_element_type=f32)
         + jnp.einsum("bhe,bse->bhs", q_pe[:, 0], pe_all,
                      preferred_element_type=f32)) * softmax_scale(cfg)
    valid = jnp.arange(c_all.shape[1]) <= pos
    s = jnp.where(valid[None, None], s, ops.NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(c_all.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", a, c_all, preferred_element_type=f32)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(w.dtype), w[..., dn:],
                   preferred_element_type=f32).astype(x.dtype)
    return o.reshape(b, -1) @ p["wo"], {"c": c_all, "pe": pe_all}

