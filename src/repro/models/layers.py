"""Shared building blocks: inits, norms, MLPs, RoPE, embeddings.

All models are pure functions over pytree parameter dicts.  Weights are stored
``(in_dim, out_dim)``; compute runs in ``cfg.dtype`` with fp32 accumulation
where it matters (norms, softmax, router).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def dt(name: str):
    return jnp.dtype(name)


# --------------------------------------------------------------------------- #
# initialisers
# --------------------------------------------------------------------------- #


def dense_init(rng, in_dim: int, out_dim: int, dtype="float32", scale: Optional[float] = None):
    """Truncated-normal fan-in init (the MaxText/T5 default)."""
    std = scale if scale is not None else in_dim ** -0.5
    w = jax.random.truncated_normal(rng, -2.0, 2.0, (in_dim, out_dim), jnp.float32)
    return (w * std).astype(dtype)


def embed_init(rng, vocab: int, d_model: int, dtype="float32"):
    w = jax.random.truncated_normal(rng, -2.0, 2.0, (vocab, d_model), jnp.float32)
    return (w * d_model ** -0.5).astype(dtype)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #


def norm_init(d_model: int, kind: str, dtype="float32"):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d_model,), dtype)}
    return {"scale": jnp.ones((d_model,), dtype), "bias": jnp.zeros((d_model,), dtype)}


_NORM_EPS = 1e-6


def _mean_last_f32(a, b):
    """mean over last dim of a*b with f32 accumulation, result in a.dtype."""
    d = a.shape[-1]
    s = jnp.einsum("...d,...d->...", a, b, preferred_element_type=jnp.float32)
    return (s / d)[..., None]


# Custom-VJP norms: forward accumulates reductions in fp32 (MXU-style bf16
# multiply / f32 accumulate), and — critically — the BACKWARD is pure
# x.dtype pointwise math.  If the backward's first consumer of the saved
# per-layer residual is `convert(x, f32)` (as with autodiff through an
# upcast norm), XLA hoists the convert out of the remat backward loop and
# persists an f32 copy of EVERY layer's input: +20 GB/device measured on
# granite train_4k (EXPERIMENTS.md §Perf iteration 0).


@jax.custom_vjp
def _rmsnorm(x, scale):
    inv = jax.lax.rsqrt(_mean_last_f32(x, x) + _NORM_EPS).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def _rmsnorm_fwd(x, scale):
    inv = jax.lax.rsqrt(_mean_last_f32(x, x) + _NORM_EPS).astype(x.dtype)
    return x * inv * scale.astype(x.dtype), (x, inv, scale)


def _rmsnorm_bwd(res, g):
    x, inv, scale = res
    xn = x * inv
    g2 = g * scale.astype(g.dtype)
    dot = _mean_last_f32(g2, xn).astype(g.dtype)
    dx = (inv * (g2 - xn * dot)).astype(x.dtype)
    dscale = jnp.sum((g * xn).astype(jnp.float32),
                     axis=tuple(range(g.ndim - 1))).astype(scale.dtype)
    return dx, dscale


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@jax.custom_vjp
def _layernorm(x, scale, bias):
    return _layernorm_fwd(x, scale, bias)[0]


def _layernorm_fwd(x, scale, bias):
    d = x.shape[-1]
    mean = (jnp.sum(x, axis=-1, keepdims=True, dtype=jnp.float32) / d)
    sq = _mean_last_f32(x, x).astype(jnp.float32)
    var = jnp.maximum(sq - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + _NORM_EPS).astype(x.dtype)
    xc = x - mean.astype(x.dtype)
    y = xc * inv * scale.astype(x.dtype) + bias.astype(x.dtype)
    return y, (x, inv, mean.astype(x.dtype), scale)


def _layernorm_bwd(res, g):
    x, inv, mean, scale = res
    xn = (x - mean) * inv
    g2 = g * scale.astype(g.dtype)
    m1 = _mean_last_f32(g2, jnp.ones_like(g2)).astype(g.dtype)
    m2 = _mean_last_f32(g2, xn).astype(g.dtype)
    dx = (inv * (g2 - m1 - xn * m2)).astype(x.dtype)
    red = tuple(range(g.ndim - 1))
    dscale = jnp.sum((g * xn).astype(jnp.float32), axis=red).astype(scale.dtype)
    dbias = jnp.sum(g.astype(jnp.float32), axis=red).astype(scale.dtype)
    return dx, dscale, dbias


_layernorm.defvjp(lambda x, s, b: (_layernorm_fwd(x, s, b)[0],
                                   _layernorm_fwd(x, s, b)[1]),
                  _layernorm_bwd)


def norm_apply(params, x, kind: str, eps: float = 1e-6):
    del eps  # fixed at _NORM_EPS (custom_vjp closures)
    if kind == "rmsnorm":
        return _rmsnorm(x, params["scale"])
    return _layernorm(x, params["scale"], params["bias"])


# --------------------------------------------------------------------------- #
# MLP (SwiGLU / GELU)
# --------------------------------------------------------------------------- #


def mlp_init(rng, d_model: int, d_ff: int, act: str, dtype="float32"):
    r1, r2, r3 = jax.random.split(rng, 3)
    p = {"wi": dense_init(r1, d_model, d_ff, dtype),
         "wo": dense_init(r2, d_ff, d_model, dtype)}
    if act == "swiglu":
        p["wg"] = dense_init(r3, d_model, d_ff, dtype)
    return p


def mlp_apply(params, x, act: str):
    h = x @ params["wi"]
    if act == "swiglu":
        h = jax.nn.silu(h) * (x @ params["wg"])
    else:
        h = jax.nn.gelu(h)
    return h @ params["wo"]


# --------------------------------------------------------------------------- #
# rotary position embedding
# --------------------------------------------------------------------------- #


def rope_cos_sin(positions, head_dim: int, theta: float, dtype=jnp.float32,
                 *, freqs=None):
    """positions: int array (...,) -> cos/sin of shape (..., head_dim//2).
    ``freqs`` replaces the plain inverse frequencies (``yarn_freqs``)."""
    half = head_dim // 2
    if freqs is None:
        freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def _yarn_dim(rotations: float, dim: int, theta: float, base_len: int) -> float:
    """The dimension whose wavelength fits ``rotations`` turns in ``base_len``."""
    return (dim * math.log(base_len / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_freqs(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies of ``dim`` rotated dims (DeepSeek-V2's
    rule): dims below the correction range keep the extrapolated
    frequency, dims above it take it divided by ``factor``, with a linear
    ramp between.  The rule also scales cos and sin by the ratio of the
    ``mscale`` and ``mscale_all_dim`` factors; only configs where that is
    1 are built."""
    lo = max(math.floor(_yarn_dim(yarn.beta_fast, dim, theta,
                                  yarn.original_max_position_embeddings)), 0)
    hi = min(math.ceil(_yarn_dim(yarn.beta_slow, dim, theta,
                                 yarn.original_max_position_embeddings)), dim - 1)
    if yarn_mscale(yarn.factor, yarn.mscale) != yarn_mscale(
            yarn.factor, yarn.mscale_all_dim):
        raise ValueError("YaRN with mscale != mscale_all_dim is not built")
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, cos, sin):
    """x: (..., S, H, head_dim); cos/sin: (..., S, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def apply_rope_interleaved(x, cos, sin):
    """Rotary positions over interleaved pairs (x0, x1), (x2, x3), ...
    (DeepSeek-V2): the pairs are gathered into halves, then rotated as
    ``apply_rope`` does; the result is in the halves layout."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*lead, d)
    return apply_rope(x, cos, sin)


# --------------------------------------------------------------------------- #
# learned absolute positions (whisper-style decoders)
# --------------------------------------------------------------------------- #


def posembed_init(rng, max_len: int, d_model: int, dtype="float32"):
    return jax.random.normal(rng, (max_len, d_model), jnp.float32).astype(dtype) * 0.02


def sinusoid_embed(length: int, d_model: int, dtype=jnp.float32):
    """Whisper encoder sinusoids (used inside the audio-frontend stub)."""
    pos = np.arange(length)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    inv = np.exp(-np.log(10000.0) * dim / max(1, d_model // 2 - 1))
    ang = pos * inv
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, dtype)
