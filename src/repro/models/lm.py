"""Decoder-only language model (dense / MoE / hybrid / SSM / VLM backbones)."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import layers, moe, transformer


def init_lm(rng, cfg, *, max_seq: int):
    r = jax.random.split(rng, 4)
    p: Dict[str, Any] = {
        "embed": layers.embed_init(r[0], cfg.vocab_size, cfg.d_model, cfg.param_dtype),
        "blocks": transformer.init_stack(r[1], cfg),
        "norm_f": layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
    }
    if transformer.lead_len(cfg):
        p["lead"] = transformer.init_lead(jax.random.fold_in(r[1], 1), cfg)
    if not cfg.tie_embeddings:
        p["unembed"] = layers.dense_init(r[2], cfg.d_model, cfg.vocab_size,
                                         cfg.param_dtype)
    if cfg.vision is not None:
        # projector stub: patch embeddings arrive at LM width already; a single
        # linear keeps the interface of a real MLP projector.
        p["proj"] = layers.dense_init(r[3], cfg.vision.d_embed, cfg.d_model,
                                      cfg.param_dtype)
    return p


def _embed_tokens(p, cfg, tokens):
    x = jnp.take(p["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.norm == "rmsnorm":
        pass
    return x


def _inputs_to_x(p, cfg, batch):
    """tokens (+ optional image embeds prepended) -> (B, S, d)."""
    with jax.named_scope("embed"):
        x = _embed_tokens(p, cfg, batch["tokens"])
        if cfg.vision is not None and "image_embeds" in batch:
            img = batch["image_embeds"].astype(x.dtype) @ p["proj"]
            x = jnp.concatenate([img, x[:, : x.shape[1] - img.shape[1], :]],
                                axis=1)
    return sharding.logical(x, ("batch", "seq", "embed"))


def _unembed(p, cfg, x):
    w = p["embed"].T if cfg.tie_embeddings else p["unembed"]
    with jax.named_scope("unembed"):
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return sharding.logical(logits, ("batch", None, "vocab"))


def _final_norm(p, cfg, x):
    with jax.named_scope("norm"):
        return layers.norm_apply(p["norm_f"], x, cfg.norm)


def _forward(p, cfg, batch, *, window, train):
    """Full-sequence forward: (final hidden states, aux, caches, routing
    counts)."""
    x = _inputs_to_x(p, cfg, batch)
    s = x.shape[1]
    q_pos = jnp.arange(s)
    x, aux, caches, counts = transformer.stack_full(
        p["blocks"], x, cfg, q_pos=q_pos, window=window, train=train,
        lead=p.get("lead", ()))
    return _final_norm(p, cfg, x), aux, caches, counts


def lm_forward(p, cfg, batch, *, window=None, train=False):
    """Full-sequence forward: returns (logits, aux, caches)."""
    x, aux, caches, _ = _forward(p, cfg, batch, window=window, train=train)
    return _unembed(p, cfg, x), aux, caches


def lm_loss(p, cfg, batch, *, window=None):
    """Causal LM loss.  labels == -1 are masked out."""
    logits, aux, _ = lm_forward(p, cfg, batch, window=window, train=True)
    labels = batch["labels"]
    if cfg.vision is not None and "image_embeds" in batch:
        # image positions carry no LM loss
        n_img = batch["image_embeds"].shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], n_img), -1, labels.dtype),
             labels[:, : labels.shape[1] - n_img]], axis=1)
    mask = labels >= 0
    labels_c = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1)
    loss = jnp.where(mask, nll, 0.0).sum() / denom
    # z-loss for logit drift (MaxText default)
    zl = 1e-4 * jnp.where(mask, jax.nn.logsumexp(logits, -1) ** 2, 0.0).sum() / denom
    total = loss + zl + aux
    return total, {"loss": loss, "aux": aux, "zloss": zl,
                   "tokens": denom.astype(jnp.float32)}


def lm_prefill(p, cfg, batch, *, max_seq: int, window=None):
    """Prefill: returns (last-token logits, decode caches, next position,
    routing counts).  ``max_seq`` is the decode cache's length: the
    prompt and the tokens decoded after it (``ModelBundle.max_seq``)."""
    x, _, raw, counts = _forward(p, cfg, batch, window=window, train=False)
    seq_len = x.shape[1]
    caches = _format_caches(cfg, raw, seq_len=seq_len, max_seq=max_seq,
                            window=window)
    # the next token needs the last position's logits only
    return (_unembed(p, cfg, x[:, -1:])[:, 0], caches, seq_len,
            counts if cfg.moe is not None else {})


def _format_caches(cfg, raw_caches, *, seq_len: int, max_seq: int, window):
    """Pack stack_full cache material into fixed decode cache layout:
    attention caches (leaves (n_rep, B, S, ...)) padded to ``max_seq``
    slots, or under a window kept as a ring of ``min(window, max_seq)``."""
    out = []
    for meta, c in zip(transformer.cache_metas(cfg), raw_caches):
        if meta["kind"] != "A":
            out.append(c)  # recurrent states are already decode-ready
            continue
        s_cache = min(window, max_seq) if window else max_seq

        def pad(a, n):
            return jnp.pad(a, [(0, 0), (0, 0), (0, n)] + [(0, 0)] * (a.ndim - 3))

        if window and seq_len >= s_cache:
            # ring layout: absolute position p lives in slot p % w; the
            # kept suffix starts at `start`, so roll right by start % w.
            w = s_cache
            start = seq_len - w
            out.append(jax.tree.map(
                lambda a: jnp.roll(a[:, :, -w:], start % w, axis=2), c))
        else:
            out.append(jax.tree.map(lambda a: pad(a, s_cache - seq_len), c))
    return out


def lm_decode_step(p, cfg, caches, token, pos, *, window=None):
    """token: (B,) int32; pos: scalar int32.  Returns (logits (B, V),
    caches); a model with experts reports the step's routing counts to
    an open ``moe.counting`` block."""
    with jax.named_scope("embed"):
        x = jnp.take(p["embed"], token, axis=0).astype(jnp.dtype(cfg.dtype))
    x, caches, counts = transformer.stack_decode(
        p["blocks"], x, cfg, pos=pos, lead=p.get("lead", ()), window=window,
        caches=caches)
    if cfg.moe is not None:
        moe.report(counts)
    x = _final_norm(p, cfg, x)
    logits = _unembed(p, cfg, x[:, None, :])[:, 0, :]
    return logits, caches
