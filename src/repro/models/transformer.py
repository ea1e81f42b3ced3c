"""Decoder-only LM over heterogeneous block patterns, scan-stacked.

Layers are grouped into *periods* (the repeating unit of ``block_pattern`` ×
MoE cadence — e.g. Jamba's 8-layer block, xLSTM's [mLSTM, sLSTM] pair, or a
single layer for homogeneous stacks).  Parameters are stacked over
``num_layers / period`` repeats and the stack runs under ``lax.scan`` — HLO
size and XLA compile time are *independent of depth*.  Compile time is the
dominant cold-start phase in serverless ML serving (EXPERIMENTS.md §Claims),
so this is a cold-start optimization as much as a compile-memory one.

Modes:
  full   — train / prefill over (B, S); returns per-layer cache material
  decode — one token against per-layer caches/states
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import attention, layers, mamba, moe, xlstm


# --------------------------------------------------------------------------- #
# pattern / period logic
# --------------------------------------------------------------------------- #


def period_len(cfg) -> int:
    p = len(cfg.block_pattern)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every_n_layers)
    if cfg.num_layers % p:
        raise ValueError(
            f"{cfg.name}: num_layers={cfg.num_layers} not a multiple of "
            f"pattern period {p}")
    return p


def _block_meta(cfg) -> List[Dict[str, Any]]:
    """Per-position-in-period: mixer kind + ffn kind."""
    per = period_len(cfg)
    moe_mask = cfg.moe_layer_mask()
    pat = cfg.layer_pattern
    out = []
    for i in range(per):
        ffn = "moe" if moe_mask[i] else ("dense" if cfg.d_ff else "none")
        out.append({"kind": pat[i], "ffn": ffn})
    return out


# --------------------------------------------------------------------------- #
# block init
# --------------------------------------------------------------------------- #


def _init_block(rng, cfg, meta) -> Dict[str, Any]:
    r = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype)}
    kind = meta["kind"]
    if kind == "A":
        p["attn"] = attention.init_attention(r[0], cfg)
    elif kind == "M":
        p["ssm"] = mamba.init_mamba(r[0], cfg)
    elif kind == "L":
        p["xl"] = xlstm.init_mlstm(r[0], cfg)
    elif kind == "S":
        p["xl"] = xlstm.init_slstm(r[0], cfg)
    else:
        raise ValueError(kind)
    if meta["ffn"] == "dense":
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype)
        p["ffn"] = layers.mlp_init(r[1], cfg.d_model, cfg.d_ff, cfg.act, cfg.param_dtype)
    elif meta["ffn"] == "moe":
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype)
        p["moe"] = moe.init_moe(r[1], cfg)
    return p


def init_stack(rng, cfg) -> List[Any]:
    """Returns a list (one entry per period position) of param trees whose
    leaves are stacked over the ``n_rep = L / period`` repeats."""
    per = period_len(cfg)
    metas = _block_meta(cfg)
    n_rep = cfg.num_layers // per
    stacked = []
    for pos in range(per):
        keys = jax.random.split(jax.random.fold_in(rng, pos), n_rep)
        stacked.append(jax.vmap(lambda k, m=metas[pos]: _init_block(k, cfg, m))(keys))
    return stacked


# --------------------------------------------------------------------------- #
# block apply
# --------------------------------------------------------------------------- #


def _apply_ffn(p, x, cfg):
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        with jax.named_scope("norm"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
        h = sharding.logical(h, ("batch", "seq", "embed"))
        with jax.named_scope("mlp"):
            x = x + layers.mlp_apply(p["ffn"], h, cfg.act)
    elif "moe" in p:
        with jax.named_scope("norm"):
            h = layers.norm_apply(p["norm2"], x, cfg.norm)
        with jax.named_scope("moe"):
            y, aux = moe.moe_ffn(p["moe"], h, cfg)
        x = x + y
    return x, aux


def _mixer_scope(kind: str) -> str:
    return {"A": "attention", "M": "ssm"}.get(kind, "xlstm")


def _block_full(p, x, cfg, meta, q_pos, window, states, train):
    """Full-sequence block.  states: prior recurrent state or None.
    Returns (x, aux, cache_material)."""
    with jax.named_scope("norm"):
        h = layers.norm_apply(p["norm1"], x, cfg.norm)
    kind = meta["kind"]
    with jax.named_scope(_mixer_scope(kind)):
        y, cache = _mixer_full(p, h, cfg, kind, q_pos, window, states, train)
    x = x + y
    x, aux = _apply_ffn(p, x, cfg)
    x = sharding.logical(x, ("batch", "seq", "embed"))
    return x, aux, cache


def _mixer_full(p, h, cfg, kind, q_pos, window, states, train):
    if kind == "A":
        # context-parallel fallback (§Perf iter. 3): tokens sharded over the
        # model axis through the attention block when heads don't divide it
        h = sharding.logical(h, ("batch", "attn_seq", None))
        y, kv = attention.full_attention(
            p["attn"], h, cfg, q_pos=q_pos, window=window,
            use_rope=cfg.encoder is None, return_kv=True, train=train)
        y = sharding.logical(y, ("batch", "attn_seq", None))
        cache = {"k": kv[0], "v": kv[1]}
    elif kind == "M":
        y, cache = mamba.mamba_forward(p["ssm"], h, cfg,
                                       h0=None if states is None else states["h"])
    elif kind == "L":
        y, cache = xlstm.mlstm_forward(p["xl"], h, cfg, state=states)
    else:
        y, cache = xlstm.slstm_forward(p["xl"], h, cfg, state=states)
    return y, cache


def _block_decode(p, x, cfg, meta, pos, window, cache):
    """One-token block.  x: (B, d).  Returns (x, new_cache)."""
    with jax.named_scope("norm"):
        h = layers.norm_apply(p["norm1"], x, cfg.norm)
    kind = meta["kind"]
    with jax.named_scope(_mixer_scope(kind)):
        y, cache = _mixer_decode(p, h, cfg, kind, pos, window, cache)
    x = x + y
    x3 = x[:, None, :]
    x3, _ = _apply_ffn(p, x3, cfg)
    return x3[:, 0, :], cache


def _mixer_decode(p, h, cfg, kind, pos, window, cache):
    if kind == "A":
        y, cache = attention.decode_attention(
            p["attn"], h, cache, pos, cfg, window=window,
            use_rope=cfg.encoder is None)
    elif kind == "M":
        y, cache = mamba.mamba_step(p["ssm"], h, cache, cfg)
    elif kind == "L":
        y, cache = xlstm.mlstm_step(p["xl"], h, cache, cfg)
    else:
        y, cache = xlstm.slstm_step(p["xl"], h, cache, cfg)
    return y, cache


# --------------------------------------------------------------------------- #
# stack apply (scan over periods)
# --------------------------------------------------------------------------- #


def stack_full(stack_params, x, cfg, *, q_pos, window=None, train=False):
    """x: (B, S, d) -> (x, aux_loss, caches).

    caches: list per period position; each leaf stacked over n_rep.
    """
    metas = _block_meta(cfg)

    def period_fn(carry, period_params):
        x, aux = carry
        caches = []
        for pos, meta in enumerate(metas):
            x, a, c = _block_full(period_params[pos], x, cfg, meta, q_pos,
                                  window, None, train)
            aux = aux + a
            caches.append(c)
        return (x, aux), tuple(caches)

    fn = (jax.checkpoint(period_fn, prevent_cse=False)
          if (train and cfg.remat) else period_fn)
    if cfg.unroll_layers:
        # roofline mode: python loop so XLA cost_analysis sees every layer
        carry = (x, jnp.zeros((), jnp.float32))
        all_caches = []
        n_rep = cfg.num_layers // len(metas)
        for i in range(n_rep):
            pp = jax.tree.map(lambda a: a[i], tuple(stack_params))
            carry, caches_i = fn(carry, pp)
            all_caches.append(caches_i)
        (x, aux) = carry
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *all_caches)
        return x, aux, list(caches)
    (x, aux), caches = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), tuple(stack_params))
    return x, aux, list(caches)


def stack_decode(stack_params, x, cfg, *, pos, window=None, caches=None):
    """x: (B, d) one token -> (x, new_caches)."""
    metas = _block_meta(cfg)

    def period_fn(x, xs):
        period_params, period_caches = xs
        new = []
        for i, meta in enumerate(metas):
            x, c = _block_decode(period_params[i], x, cfg, meta, pos, window,
                                 period_caches[i])
            new.append(c)
        return x, tuple(new)

    if cfg.unroll_layers:
        n_rep = cfg.num_layers // len(metas)
        outs = []
        for i in range(n_rep):
            xs_i = jax.tree.map(lambda a: a[i],
                                (tuple(stack_params), tuple(caches)))
            x, new_i = period_fn(x, xs_i)
            outs.append(new_i)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return x, list(new_caches)
    x, new_caches = jax.lax.scan(period_fn, x, (tuple(stack_params), tuple(caches)))
    return x, list(new_caches)


def init_decode_caches(cfg, batch: int, max_seq: int, *, window=None):
    """Allocate per-period-position caches, stacked over n_rep."""
    per = period_len(cfg)
    metas = _block_meta(cfg)
    n_rep = cfg.num_layers // per
    out = []
    for meta in metas:
        if meta["kind"] == "A":
            one = attention.init_cache(cfg, batch, max_seq, window=window)
        elif meta["kind"] == "M":
            one = mamba.init_mamba_state(cfg, batch)
        elif meta["kind"] == "L":
            one = xlstm.init_mlstm_state(cfg, batch)
        else:
            one = xlstm.init_slstm_state(cfg, batch)
        out.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (n_rep, *a.shape)), one))
    return out
