"""Plain float32 reference of DeepSeek-V2's decoder: latent attention and
routed plus shared experts (arXiv:2405.04434 §2.1-2.2, the published
``modeling_deepseek.py``), at one chip's expert share.

Per layer, pre-RMSNorm:

  attention  q = h Wq per head [q_nope; q_pe] (no q-LoRA); the latent
             [c; k_pe] = h W_kv_a, c = RMSNorm(c); per head [k_nope; v] =
             c W_kv_b, key [k_nope; k_pe] with k_pe shared by every head;
             q_pe and k_pe rotated over interleaved pairs (x0, x1), (x2,
             x3), ... with YaRN's frequencies; causal softmax scaled by
             qk_dim ** -0.5 * (0.1 mscale_all_dim ln(factor) + 1) ** 2;
             out through Wo.
  FFN        the first ``moe.first_dense`` layers a dense SwiGLU; the
             rest route each token by a softmax over every expert of the
             layer, take its top ``moe.top_k`` (renormalised if
             ``moe.norm_topk``), and add the SwiGLU experts held here,
             0 .. ``moe.experts_held`` - 1 (chip 0's), each weighted;
             plus the shared experts, one SwiGLU.

Every matrix product is float32 at ``Precision.HIGHEST`` over the whole
sequence (keys in the expanded form, no cache, no kernel).  Attention runs
in query blocks, each against every key under the causal mask; each held expert
runs on the tokens routed to it, gathered on the host from the routing in
chunks of ``ROWS``.
The layer is applied ``num_layers`` times, so it fits beside the
weights on one chip.

It imports nothing of the program.  It reads the benchmark's weights
(``weights.make``) by the leaf names of the served layout:

  embed (V, d); unembed (d, V); norm_f/scale; lead[0] (the dense layers)
  and blocks[0] (the expert layers), stacked over their layers:
  norm1/scale, attn/{wq, wkv_a, kv_norm/scale, wkv_b, wo}, norm2/scale,
  then ffn/{wi, wg, wo} or moe/{router, wi, wg, wo, shared/{wi, wg, wo}}
  with a SwiGLU wo(silu(x wi) * (x wg)).

With ``fp8=True`` it is the control: both operands of every projection,
expert and the head are rounded to float8 (e4m3, one scale per row of the
activations and per column of the weights) before a float32 product, the
next precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ROWS = 256               # positions of one held expert computed together
CHUNKS = 16              # chunk counts are padded to a power of two, >= this


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale along ``axis``; back to f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _proj(a, w, fp8):
    """Activations (..., k) times weights (k, n)."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, w, fp8):
    return _proj(jax.nn.silu(_proj(x, w["wi"], fp8)) * _proj(x, w["wg"], fp8),
                 w["wo"], fp8)


def _yarn(m):
    """(inverse frequencies of the rotated dims, softmax scale)."""
    dim, base, y = m["qk_rope_head_dim"], m["rope_theta"], m["rope_scaling"]

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    freqs = extra / y["factor"] * ramp + extra * (1 - ramp)

    def mscale(ms):
        return 0.1 * ms * math.log(y["factor"]) + 1.0

    # the published cos/sin factor mscale(mscale) / mscale(mscale_all_dim)
    assert mscale(y["mscale"]) == mscale(y["mscale_all_dim"])
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return freqs.astype(np.float32), qk ** -0.5 * mscale(y["mscale_all_dim"]) ** 2


def _rope(x, pos, freqs):
    """x: (S, ..., dim) interleaved pairs -> rotated, as halves."""
    ang = pos.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freqs.shape[0],)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _attention(x, w, *, m, fp8):
    """x + attention(norm1(x)) over the whole (padded) sequence."""
    s = x.shape[0]
    h_, dn, dr = m["num_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, dv, eps = m["kv_lora_rank"], m["v_head_dim"], m["rms_norm_eps"]
    freqs, scale = _yarn(m)
    pos = jnp.arange(s)

    h = _rms(x, w["norm1"]["scale"], eps)
    q = _proj(h, w["attn"]["wq"], fp8).reshape(s, h_, dn + dr)
    kv = _proj(h, w["attn"]["wkv_a"], fp8)
    c = _rms(kv[:, :r], w["attn"]["kv_norm"]["scale"], eps)
    k_pe = _rope(kv[:, r:], pos, freqs)                       # (S, dr)
    kvb = _proj(c, w["attn"]["wkv_b"], fp8).reshape(s, h_, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, freqs)], -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe[:, None], (s, h_, dr))], -1)
    v = kvb[..., dn:]

    def block(args):
        qi, pi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        sc = jnp.where((pos[None, :] <= pi[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    nb = s // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, h_, dn + dr),
                            pos.reshape(nb, Q_BLOCK)))
    return x + _proj(o.reshape(s, h_ * dv), w["attn"]["wo"], fp8)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _dense_ffn(x, w, *, m, fp8):
    return x + _swiglu(_rms(x, w["norm2"]["scale"], m["rms_norm_eps"]),
                       w["ffn"], fp8)


@partial(jax.jit, static_argnames=("m",))
def _route(x, w, *, m):
    """(norm2(x), top-k weights, top-k experts) of every position."""
    h = _rms(x, w["norm2"]["scale"], m["rms_norm_eps"])
    probs = jax.nn.softmax(jnp.matmul(h, w["moe"]["router"], precision=HI), -1)
    top_w, top_i = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return h, top_w, top_i


@partial(jax.jit, static_argnames=("fp8",))
def _moe_ffn(x, h, chunk_e, chunk_rows, chunk_wt, w, *, fp8):
    """x + shared(h) + the held experts' weighted outputs.  Chunk ``c``
    holds ``ROWS`` positions routed to held expert ``chunk_e[c]`` and their
    weights, padded with the zero row at len(x) and weight 0."""
    hz = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])

    def chunk(args):
        e, rows, wt = args
        ew = {k: w["moe"][k][e] for k in ("wi", "wg", "wo")}
        return _swiglu(hz[rows], ew, fp8) * wt[:, None]

    y = jax.lax.map(chunk, (chunk_e, chunk_rows, chunk_wt))
    out = jnp.zeros_like(hz).at[chunk_rows.reshape(-1)].add(
        y.reshape(-1, h.shape[1]))
    return x + out[:-1] + _swiglu(h, w["moe"]["shared"], fp8)


def _held_chunks(top_i: np.ndarray, top_w: np.ndarray, n: int, held: int):
    """The positions routed to each held expert, in chunks of ``ROWS``:
    each chunk's expert, positions and weights, the number of chunks
    padded to a power of two (so few shapes compile)."""
    es, rows, wts = [], [], []
    for e in range(held):
        t, k = np.nonzero(top_i == e)
        for j in range(0, len(t), ROWS):
            pad = ROWS - len(t[j:j + ROWS])
            es.append(e)
            rows.append(np.pad(t[j:j + ROWS], (0, pad), constant_values=n))
            wts.append(np.pad(top_w[t[j:j + ROWS], k[j:j + ROWS]], (0, pad)))
    for _ in range(max(CHUNKS, 1 << (len(es) - 1).bit_length()) - len(es)):
        es.append(0)
        rows.append(np.full(ROWS, n))
        wts.append(np.zeros(ROWS))
    return (jnp.asarray(np.array(es, np.int32)),
            jnp.asarray(np.array(rows, np.int32)),
            jnp.asarray(np.array(wts, np.float32)))


def _layer(ws, i):
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), ws)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, rows, scale, unembed, *, eps, fp8):
    return _proj(_rms(x[rows], scale.astype(jnp.float32), eps),
                 unembed.astype(jnp.float32), fp8)


def logits_at(weights, model: dict, tokens: np.ndarray,
              rows: Sequence[int], *, fp8: bool = False) -> np.ndarray:
    """Next-token logits (len(rows), V) at the given positions of one
    sequence ``tokens`` (S,), float32."""
    m = _static(model)
    s = len(tokens)
    pad = -s % Q_BLOCK
    ids = jnp.asarray(np.concatenate([tokens, np.zeros(pad, tokens.dtype)]))
    x = weights["embed"][ids].astype(jnp.float32)
    (lead,), (blocks,) = weights["lead"], weights["blocks"]
    n_lead = model["moe"]["first_dense"]
    for layer in range(model["num_layers"]):
        w = _layer(lead, layer) if layer < n_lead else \
            _layer(blocks, layer - n_lead)
        x = _attention(x, w, m=m, fp8=fp8)
        if layer < n_lead:
            x = _dense_ffn(x, w, m=m, fp8=fp8)
            continue
        h, top_w, top_i = _route(x[:s], w, m=m)
        chunks = _held_chunks(np.asarray(top_i), np.asarray(top_w), s,
                              w["moe"]["wi"].shape[0])
        x = x.at[:s].set(_moe_ffn(x[:s], h, *chunks, w, fp8=fp8))
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                weights["norm_f"]["scale"], weights["unembed"],
                eps=model["rms_norm_eps"], fp8=fp8)
    return np.asarray(out)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _static(model: dict) -> _Frozen:
    keys = ("num_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "kv_lora_rank", "v_head_dim", "rms_norm_eps", "rope_theta")
    out = _Frozen({k: model[k] for k in keys})
    out.update({k: model["moe"][k] for k in ("top_k", "norm_topk")})
    out["rope_scaling"] = _Frozen(model["rope_scaling"])
    return out
