"""Plain float32 reference of a dense decoder with grouped-query attention.

The Llama / Mistral block as published: pre-RMSNorm, rotary positions
(half-split rotation), causal attention, optionally within a sliding
window that ends at the query (key ``j`` is seen by query ``i`` when
``i - window < j <= i``), and a SwiGLU feed-forward.  Every matrix product
is float32 at ``Precision.HIGHEST``, over the whole sequence, with no cache
and no kernel: one jitted layer applied ``num_layers`` times, so it fits
beside the weights on one chip.

It imports nothing of the program.  It reads the benchmark's weights
(``weights.make``) by the leaf names of the served layout:

  embed (V, d); unembed (d, V) unless tied, else embed transposed;
  norm_f/scale; blocks[0] stacked over layers: norm1/scale,
  attn/{wq, wk, wv, wo[, bq, bk, bv]}, norm2/scale, ffn/{wi, wg, wo}
  with ffn = wo(silu(x wi) * (x wg)).

With ``fp8=True`` it is the control: both operands of every projection
and of the head are rounded to float8 (e4m3, one scale per row of the
activations and per column of the weights) before a float32 product, the
next precision below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale along ``axis``; back to f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _proj(a, w, fp8):
    """Activations (S, k) times weights (k, n)."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return _mm(a, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, blocks, layer, *, m, fp8):
    def w(group, name):
        return blocks[group][name][layer].astype(jnp.float32)

    def proj(a, group, name):
        return _proj(a, w(group, name), fp8)

    s = x.shape[0]
    h_, k_, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, window = m["rms_norm_eps"], m["sliding_window"]
    pos = jnp.arange(s)

    h = _rms(x, w("norm1", "scale"), eps)
    q, k, v = (proj(h, "attn", n) for n in ("wq", "wk", "wv"))
    if m["qkv_bias"]:
        q, k, v = (a + w("attn", b) for a, b in
                   ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(s, h_, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(s, k_, hd), pos, m["rope_theta"])
    v = v.reshape(s, k_, hd)
    k = jnp.repeat(k, h_ // k_, axis=1)
    v = jnp.repeat(v, h_ // k_, axis=1)

    def block(args):
        qi, pi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / hd ** 0.5
        seen = pos[None, :] <= pi[:, None]
        if window:
            seen &= pos[None, :] > pi[:, None] - window
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    nb = s // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, h_, hd),
                            pos.reshape(nb, Q_BLOCK)))
    x = x + proj(o.reshape(s, h_ * hd), "attn", "wo")
    h = _rms(x, w("norm2", "scale"), eps)
    f = jax.nn.silu(proj(h, "ffn", "wi")) * proj(h, "ffn", "wg")
    return x + proj(f, "ffn", "wo")


@partial(jax.jit, static_argnames=("m", "fp8"))
def _head(x, rows, weights, *, m, fp8):
    x = _rms(x[rows], weights["norm_f"]["scale"].astype(jnp.float32),
             m["rms_norm_eps"])
    if m["tie_embeddings"]:
        u = weights["embed"].astype(jnp.float32).T
    else:
        u = weights["unembed"].astype(jnp.float32)
    return _proj(x, u, fp8)


def logits_at(weights, model: dict, tokens: np.ndarray,
              rows: Sequence[int], *, fp8: bool = False) -> np.ndarray:
    """Next-token logits (len(rows), V) at the given positions of one
    sequence ``tokens`` (S,), float32."""
    m = _static(model)
    s = len(tokens)
    pad = -s % Q_BLOCK
    ids = jnp.asarray(np.concatenate([tokens, np.zeros(pad, tokens.dtype)]))
    x = weights["embed"][ids].astype(jnp.float32)
    (blocks,) = weights["blocks"]
    for layer in range(model["num_layers"]):
        x = _layer(x, blocks, layer, m=m, fp8=fp8)
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                {k: weights[k] for k in ("embed", "norm_f", "unembed")
                 if k in weights}, m=m, fp8=fp8)
    return np.asarray(out)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _static(model: dict) -> _Frozen:
    keys = ("num_heads", "num_kv_heads", "head_dim", "rms_norm_eps",
            "sliding_window", "rope_theta", "qkv_bias", "tie_embeddings")
    return _Frozen({k: model[k] for k in keys})
