"""Mamba-1 selective-SSM mixer block (Jamba's SSM half).

Full-sequence path uses ``ops.ssm_scan`` (chunked two-level scan);
decode is a single recurrence step.  Decode state per layer:
``conv`` (B, d_conv-1, d_in) trailing inputs + ``h`` (B, d_in, N) fp32 SSM
state — O(1) in sequence length, which is why hybrid/SSM archs run the
long_500k shape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import sharding
from repro.kernels import ops
from repro.models import layers


def _dt_rank(cfg) -> int:
    return cfg.ssm.dt_rank or -(-cfg.d_model // 16)


def init_mamba(rng, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    n = s.d_state
    dtr = _dt_rank(cfg)
    pdt = cfg.param_dtype
    r = jax.random.split(rng, 6)
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (d_in, 1))
    return {
        "in_proj": layers.dense_init(r[0], d, 2 * d_in, pdt),
        "conv_w": (jax.random.normal(r[1], (s.d_conv, d_in), jnp.float32)
                   * (s.d_conv ** -0.5)).astype(pdt),
        "conv_b": jnp.zeros((d_in,), pdt),
        "x_proj": layers.dense_init(r[2], d_in, dtr + 2 * n, pdt),
        "dt_w": layers.dense_init(r[3], dtr, d_in, "float32"),
        "dt_b": jnp.full((d_in,), math.log(math.expm1(0.01)), jnp.float32),
        "A_log": jnp.log(a),                      # fp32; A = -exp(A_log)
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": layers.dense_init(r[4], d_in, d, pdt, scale=d_in ** -0.5),
    }


def _split_xproj(p, xs, cfg):
    s = cfg.ssm
    dtr = _dt_rank(cfg)
    proj = xs @ p["x_proj"]
    dt_low, b, c = jnp.split(proj, [dtr, dtr + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt_low.astype(jnp.float32) @ p["dt_w"] + p["dt_b"])
    return dt, b, c


def mamba_forward(p, x, cfg, *, h0=None):
    """x: (B, T, d) -> (y (B, T, d), final_state dict)."""
    s = cfg.ssm
    b, t, d = x.shape
    d_in = s.expand * d
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)                      # (B, T, d_in) x2
    xs = sharding.logical(xs, ("batch", "seq", "ssm_inner"))
    # causal depthwise conv over time
    pad = s.d_conv - 1
    xp = jnp.pad(xs, ((0, 0), (pad, 0), (0, 0)))
    conv = sum(xp[:, i: i + t, :] * p["conv_w"][i][None, None]
               for i in range(s.d_conv))
    xs = jax.nn.silu(conv + p["conv_b"][None, None])
    conv_state = xp[:, t:, :] if pad == 0 else xp[:, -pad:, :]

    dt, bm, cm = _split_xproj(p, xs, cfg)
    A = -jnp.exp(p["A_log"])
    h0 = h0 if h0 is not None else jnp.zeros((b, d_in, s.d_state), jnp.float32)
    y, hT = ops.ssm_scan(xs, dt, A, bm, cm, p["D"], h0)
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out, {"conv": conv_state, "h": hT}


def init_mamba_state(cfg, batch: int):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, d_in), jnp.dtype(cfg.dtype)),
        "h": jnp.zeros((batch, d_in, s.d_state), jnp.float32),
    }


def mamba_step(p, x, state, cfg):
    """One decode step. x: (B, d) -> (y (B, d), new_state)."""
    s = cfg.ssm
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)                      # (B, d_in)
    window = jnp.concatenate([state["conv"], xs[:, None, :]], axis=1)  # (B, d_conv, d_in)
    conv = jnp.einsum("bcd,cd->bd", window, p["conv_w"].astype(window.dtype))
    xs1 = jax.nn.silu(conv + p["conv_b"][None])
    dt, bm, cm = _split_xproj(p, xs1, cfg)
    A = -jnp.exp(p["A_log"])
    y, h = ops.ssm_step(xs1, dt, A, bm, cm, p["D"], state["h"])
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out, {"conv": window[:, 1:, :], "h": h}
