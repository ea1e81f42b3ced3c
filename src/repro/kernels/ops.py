"""Jit-ready kernel entry points, and the one rule that picks each path.

* ``flash_attention`` — the Pallas kernel (``flash_attention.py``) for
  forward-only self-attention on a TPU whose lengths are multiples of its
  smallest block (``choose_flash_impl``), and everywhere else the chunked
  online-softmax reference in pure jnp: memory-bounded (it never
  materialises an O(S^2) score tensor) and GSPMD-shardable, so it is also
  the path the multi-pod dry-run lowers and the one training takes.
* ``decode_attention`` and ``ssm_scan`` — memory-light jnp on every
  platform.

The expert pass picks its own path the same way (``models/moe.expert_path``);
no configuration or caller chooses a kernel.  The naive oracles in
``ref.py`` are what ``tests/test_kernels.py`` compares every path against.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
REF_CHUNK = 1024        # the reference's q and kv chunk (``_flash_reference``)


# --------------------------------------------------------------------------- #
# flash attention (training / prefill hot spot)
# --------------------------------------------------------------------------- #


def pick_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (halving would degrade to
    chunk=4 for whisper's 1500-frame encoder: 375x375 blocks)."""
    c = min(target, s)
    while c > 1 and s % c:
        c -= 1
    return max(c, 1)


def choose_flash_impl(*, train: bool, self_attn: bool, sq: int,
                      skv: int) -> str:
    """The flash-attention path for one call: ``"pallas"`` for
    forward-only (the kernel has no VJP) self-attention on a TPU whose
    lengths are multiples of its smallest block, and ``"reference"``
    everywhere else: the CPU, training, cross-attention, whisper's
    1500-frame encoder."""
    from repro.kernels.flash_attention import MIN_BLOCK
    if (jax.default_backend() == "tpu" and not train and self_attn
            and sq % MIN_BLOCK == 0 and skv % MIN_BLOCK == 0):
        return "pallas"
    return "reference"


def _flash_reference(q, k, v, *, causal, window, q_pos, kv_pos, scale=None,
                     q_chunk=REF_CHUNK, kv_chunk=REF_CHUNK):
    """Chunked online-softmax attention in pure jnp (fp32 accumulators)."""
    b, sq, hq, d = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    g = hq // hkv
    qc = pick_chunk(sq, q_chunk)
    kc = pick_chunk(skv, kv_chunk)
    scale = scale or 1.0 / (d ** 0.5)

    # (B, Skv, Hkv, D) -> (nk, B, kc, Hkv, D)
    kb = jnp.moveaxis(k.reshape(b, skv // kc, kc, hkv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, skv // kc, kc, hkv, dv), 1, 0)
    kpb = kv_pos.reshape(skv // kc, kc)

    def q_block(args):
        qi, qp = args                          # (B, qc, Hkv, G, D), (qc,)
        qi = qi.astype(jnp.float32) * scale

        def kv_step(carry, xs):
            acc, m, l = carry
            kj, vj, kp = xs                    # (B, kc, Hkv, D) x2, (kc,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj.astype(jnp.float32))
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= kp[None, :] <= qp[:, None]
            if window is not None:
                mask &= kp[None, :] > (qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, hkv, g, qc, dv), jnp.float32)
        m0 = jnp.full((b, hkv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (kb, vb, kpb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, Hkv, G, qc, D) -> (B, qc, Hkv, G, D)
        return jnp.moveaxis(out, 3, 1)

    qg = q.reshape(b, sq // qc, qc, hkv, g, d)
    qg = jnp.moveaxis(qg, 1, 0)                      # (nq, B, qc, Hkv, G, D)
    qpb = q_pos.reshape(sq // qc, qc)
    out = jax.lax.map(q_block, (qg, qpb))            # (nq, B, qc, Hkv, G, D)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_pos=None, kv_pos=None, scale: Optional[float] = None,
                    train: bool = False, self_attn: bool = True):
    """Blocked attention. q: (B,Sq,Hq,D), k: (B,Skv,Hkv,D), v:
    (B,Skv,Hkv,Dv) -> (B,Sq,Hq,Dv); scores scaled by ``scale`` (default
    D**-0.5).  ``train`` says a gradient will be taken and ``self_attn``
    that q and k are one sequence; with the shapes they pick the path
    (``choose_flash_impl``)."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if q_pos is None:
        q_pos = jnp.arange(sq) + (skv - sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    if choose_flash_impl(train=train, self_attn=self_attn, sq=sq,
                         skv=skv) == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_pos=q_pos, kv_pos=kv_pos, scale=scale)
    return _flash_reference(q, k, v, causal=causal, window=window,
                            q_pos=q_pos, kv_pos=kv_pos, scale=scale)


# --------------------------------------------------------------------------- #
# decode attention (single new token vs long KV cache)
# --------------------------------------------------------------------------- #


def decode_attention(q, k_cache, v_cache, valid_mask):
    """q: (B,Hq,D); caches (B,S,Hkv,D); valid_mask (B,S) -> (B,Hq,D)."""
    # memory-light jnp: scores are only (B, Hq, S)
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32) / (d ** 0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k_cache.astype(jnp.float32))
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# --------------------------------------------------------------------------- #
# selective-scan (Mamba) — chunked two-level scan
# --------------------------------------------------------------------------- #


def ssm_scan(u, delta, A, B, C, D, h0, *, chunk: int = 256):
    """Mamba-1 selective scan.  See ``ref.ssm_scan_ref`` for semantics."""
    bsz, t, din = u.shape
    n = A.shape[1]
    c = pick_chunk(t, chunk)

    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def inner_step(h, xs):
        u_t, d_t, b_t, c_t = xs
        decay = jnp.exp(d_t[..., None] * Af[None])
        h = decay * h + (d_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    def chunk_step(h, xs):
        uc, dc, bc, cc = xs                     # (c, Bt, ...) time-major
        h, ys = jax.lax.scan(inner_step, h, (uc, dc, bc, cc))
        return h, ys

    def tm(x):                                   # (Bt, T, ...) -> (nc, c, Bt, ...)
        x = jnp.moveaxis(x, 1, 0)                # (T, Bt, ...)
        return x.reshape(t // c, c, *x.shape[1:])

    hT, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32),
                          (tm(uf), tm(df), tm(Bf), tm(Cf)))
    ys = ys.reshape(t, bsz, din)
    y = jnp.moveaxis(ys, 0, 1) + uf * D.astype(jnp.float32)[None, None]
    return y.astype(u.dtype), hT


def ssm_step(u, delta, A, B, C, D, h):
    """Single decode step of the selective scan (B, Din) inputs."""
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    decay = jnp.exp(df[..., None] * A.astype(jnp.float32)[None])
    h = decay * h + (df * uf)[..., None] * B.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, C.astype(jnp.float32))
    y = y + uf * D.astype(jnp.float32)[None]
    return y.astype(u.dtype), h
