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
from repro.models import attention, layers, mamba, mla, moe, xlstm


# --------------------------------------------------------------------------- #
# pattern / period logic
# --------------------------------------------------------------------------- #


def lead_len(cfg) -> int:
    """Leading dense layers (``MoEConfig.first_dense``), stacked apart
    from the periods that follow them."""
    return cfg.moe.first_dense if cfg.moe is not None else 0


def period_len(cfg) -> int:
    p = len(cfg.block_pattern)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every_n_layers)
    if (cfg.num_layers - lead_len(cfg)) % p:
        raise ValueError(
            f"{cfg.name}: num_layers={cfg.num_layers} less {lead_len(cfg)} "
            f"leading layers not a multiple of pattern period {p}")
    return p


def _block_meta(cfg) -> List[Dict[str, Any]]:
    """Per-position-in-period: mixer kind + ffn kind."""
    per, lead = period_len(cfg), lead_len(cfg)
    moe_mask = cfg.moe_layer_mask()
    pat = cfg.layer_pattern
    out = []
    for i in range(lead, lead + per):
        ffn = "moe" if moe_mask[i] else ("dense" if cfg.d_ff else "none")
        out.append({"kind": pat[i], "ffn": ffn})
    return out


def _lead_meta(cfg) -> List[Dict[str, Any]]:
    """The leading dense layers' one stacked position, if any."""
    lead = lead_len(cfg)
    kinds = set(cfg.layer_pattern[:lead])
    if len(kinds) > 1:
        raise ValueError(f"{cfg.name}: leading layers of kinds {kinds}")
    return [{"kind": k, "ffn": "dense"} for k in kinds]


def cache_metas(cfg) -> List[Dict[str, Any]]:
    """Metas of the decode caches' list: leading layers, then periods."""
    return _lead_meta(cfg) + _block_meta(cfg)


# --------------------------------------------------------------------------- #
# block init
# --------------------------------------------------------------------------- #


def _init_block(rng, cfg, meta) -> Dict[str, Any]:
    r = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype)}
    kind = meta["kind"]
    if kind == "A":
        p["attn"] = (mla.init_mla(r[0], cfg) if cfg.mla
                     else attention.init_attention(r[0], cfg))
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


def _init_stacked(rng, cfg, metas, n_rep) -> List[Any]:
    stacked = []
    for pos, meta in enumerate(metas):
        keys = jax.random.split(jax.random.fold_in(rng, pos), n_rep)
        stacked.append(jax.vmap(lambda k, m=meta: _init_block(k, cfg, m))(keys))
    return stacked


def init_stack(rng, cfg) -> List[Any]:
    """Returns a list (one entry per period position) of param trees whose
    leaves are stacked over the ``n_rep = (L - lead) / period`` repeats."""
    n_rep = (cfg.num_layers - lead_len(cfg)) // period_len(cfg)
    return _init_stacked(rng, cfg, _block_meta(cfg), n_rep)


def init_lead(rng, cfg) -> List[Any]:
    """The leading dense layers' params, stacked over them ([] if none)."""
    return _init_stacked(rng, cfg, _lead_meta(cfg), lead_len(cfg))


# --------------------------------------------------------------------------- #
# block apply
# --------------------------------------------------------------------------- #


def _apply_ffn(p, x, cfg):
    aux, counts = jnp.zeros((), jnp.float32), moe.zero_counts()
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
            y, aux, counts = moe.moe_ffn(p["moe"], h, cfg)
        x = x + y
    return x, aux, counts


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
    x, aux, counts = _apply_ffn(p, x, cfg)
    x = sharding.logical(x, ("batch", "seq", "embed"))
    return x, aux, cache, counts


def _mixer_full(p, h, cfg, kind, q_pos, window, states, train):
    if kind == "A" and cfg.mla:
        y, cache = mla.mla_full(p["attn"], h, cfg, q_pos=q_pos, train=train)
    elif kind == "A":
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
    """One-token block.  x: (B, d).  Returns (x, new_cache, counts)."""
    with jax.named_scope("norm"):
        h = layers.norm_apply(p["norm1"], x, cfg.norm)
    kind = meta["kind"]
    with jax.named_scope(_mixer_scope(kind)):
        y, cache = _mixer_decode(p, h, cfg, kind, pos, window, cache)
    x = x + y
    x3 = x[:, None, :]
    x3, _, counts = _apply_ffn(p, x3, cfg)
    return x3[:, 0, :], cache, counts


def _mixer_decode(p, h, cfg, kind, pos, window, cache):
    if kind == "A" and cfg.mla:
        y, cache = mla.mla_decode(p["attn"], h, cache, pos, cfg)
    elif kind == "A":
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


def _scan_full(stack_params, metas, n_rep, x, cfg, q_pos, window, train):
    """One stacked group of layers over the full sequence.  Returns
    (x, aux, caches, counts)."""
    def period_fn(carry, period_params):
        x, aux, counts = carry
        caches = []
        for pos, meta in enumerate(metas):
            x, a, c, n = _block_full(period_params[pos], x, cfg, meta, q_pos,
                                     window, None, train)
            aux = aux + a
            counts = jax.tree.map(jnp.add, counts, n)
            caches.append(c)
        return (x, aux, counts), tuple(caches)

    fn = (jax.checkpoint(period_fn, prevent_cse=False)
          if (train and cfg.remat) else period_fn)
    carry = (x, jnp.zeros((), jnp.float32), moe.zero_counts())
    if cfg.unroll_layers:
        # roofline mode: python loop so XLA cost_analysis sees every layer
        all_caches = []
        for i in range(n_rep):
            pp = jax.tree.map(lambda a: a[i], tuple(stack_params))
            carry, caches_i = fn(carry, pp)
            all_caches.append(caches_i)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *all_caches)
    else:
        carry, caches = jax.lax.scan(fn, carry, tuple(stack_params))
    x, aux, counts = carry
    return x, aux, list(caches), counts


def stack_full(stack_params, x, cfg, *, q_pos, window=None, train=False,
               lead=()):
    """x: (B, S, d) -> (x, aux_loss, caches, routing counts).

    ``lead`` holds the leading dense layers' params (``init_lead``), run
    before the periods.  caches: a list by ``cache_metas`` position, each
    leaf stacked over that position's layers; counts: ``moe_ffn``'s,
    summed over the layers."""
    n_rep = (cfg.num_layers - lead_len(cfg)) // period_len(cfg)
    aux, caches, counts = 0.0, [], moe.zero_counts()
    if lead:
        x, aux, caches, counts = _scan_full(lead, _lead_meta(cfg),
                                            lead_len(cfg), x, cfg, q_pos,
                                            window, train)
    x, a, c, n = _scan_full(stack_params, _block_meta(cfg), n_rep, x, cfg,
                            q_pos, window, train)
    return x, aux + a, caches + c, jax.tree.map(jnp.add, counts, n)


def _scan_decode(stack_params, metas, n_rep, x, cfg, pos, window, caches):
    """One stacked group of layers for one token.  Returns (x, new caches,
    counts).  Held experts that the decode kernel reads in place stay out
    of the scanned params (``moe.split_stacks``): the scan passes them
    whole, with the layer's index."""
    split = [moe.split_stacks(p, meta, cfg, x.shape[0])
             for p, meta in zip(stack_params, metas)]
    scanned = tuple(p for p, _ in split)
    whole = [w for _, w in split]

    def period_fn(carry, xs):
        x, counts = carry
        period_params, period_caches, layer = xs
        new = []
        for i, meta in enumerate(metas):
            p = moe.join_stacks(period_params[i], whole[i], layer)
            x, c, n = _block_decode(p, x, cfg, meta, pos, window,
                                    period_caches[i])
            counts = jax.tree.map(jnp.add, counts, n)
            new.append(c)
        return (x, counts), tuple(new)

    carry = (x, moe.zero_counts())
    if cfg.unroll_layers:
        outs = []
        for i in range(n_rep):
            xs_i = jax.tree.map(lambda a: a[i], (scanned, tuple(caches)))
            carry, new_i = period_fn(carry, (*xs_i, i))
            outs.append(new_i)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    else:
        layers_ = jnp.arange(n_rep) if any(w is not None for w in whole) \
            else None
        carry, new_caches = jax.lax.scan(period_fn, carry,
                                         (scanned, tuple(caches), layers_))
    x, counts = carry
    return x, list(new_caches), counts


def stack_decode(stack_params, x, cfg, *, pos, window=None, caches=None,
                 lead=()):
    """x: (B, d) one token -> (x, new_caches, routing counts); ``lead``
    and the counts as in ``stack_full``."""
    n_rep = (cfg.num_layers - lead_len(cfg)) // period_len(cfg)
    new, counts = [], moe.zero_counts()
    if lead:
        x, new, counts = _scan_decode(lead, _lead_meta(cfg), lead_len(cfg),
                                      x, cfg, pos, window,
                                      caches[:len(lead)])
        caches = caches[len(lead):]
    x, rest, n = _scan_decode(stack_params, _block_meta(cfg), n_rep, x, cfg,
                              pos, window, caches)
    return x, new + rest, jax.tree.map(jnp.add, counts, n)


def init_decode_caches(cfg, batch: int, max_seq: int, *, window=None):
    """Allocate caches by ``cache_metas`` position, each stacked over that
    position's layers."""
    n_rep = (cfg.num_layers - lead_len(cfg)) // period_len(cfg)
    reps = [lead_len(cfg)] * len(_lead_meta(cfg)) + [n_rep] * len(_block_meta(cfg))
    out = []
    for meta, n in zip(cache_metas(cfg), reps):
        if meta["kind"] == "A" and cfg.mla:
            one = mla.init_cache(cfg, batch, max_seq)
        elif meta["kind"] == "A":
            one = attention.init_cache(cfg, batch, max_seq, window=window)
        elif meta["kind"] == "M":
            one = mamba.init_mamba_state(cfg, batch)
        elif meta["kind"] == "L":
            one = xlstm.init_mlstm_state(cfg, batch)
        else:
            one = xlstm.init_slstm_state(cfg, batch)
        out.append(jax.tree.map(
            lambda a, n=n: jnp.broadcast_to(a, (n, *a.shape)), one))
    return out
