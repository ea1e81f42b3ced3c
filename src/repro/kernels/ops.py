"""Jit-ready kernel entry points.

Each op has three execution paths:

* ``impl="reference"`` — memory-bounded pure-jnp implementation (chunked
  online-softmax flash attention, two-level SSM scan).  This is the path the
  multi-pod dry-run lowers (it is GSPMD-shardable and never materialises an
  O(S^2) score tensor), and what runs in CPU tests/benchmarks.
* ``impl="pallas"`` — the TPU Pallas kernels (``flash_attention.py``,
  ``decode_attention.py``, ``ssm_scan.py``) with explicit BlockSpec VMEM
  tiling.  They compile natively on an accelerator and run through the
  Pallas interpreter only on the CPU backend (tests).
* ``impl="oracle"`` — the naive oracles in ``ref.py`` (tests only).

Models ask for ``"auto"`` (the config default): ``choose_flash_impl`` picks
the path of each full-sequence attention call from what it can see, and
every other op reads ``"auto"`` as ``"reference"``.

All paths agree to numerical tolerance; see ``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

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


def choose_flash_impl(requested: str, *, train: bool, self_attn: bool,
                      sq: int, skv: int) -> str:
    """The flash-attention path for one call.  ``"auto"`` is the Pallas
    kernel for forward-only (it has no VJP) self-attention on a TPU whose
    lengths are multiples of its smallest block, and the reference
    everywhere else: the CPU, training, cross-attention, whisper's
    1500-frame encoder.  Any other ``requested`` path is kept."""
    if requested != "auto":
        return requested
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
                    impl: str = "reference"):
    """Blocked attention. q: (B,Sq,Hq,D), k: (B,Skv,Hkv,D), v:
    (B,Skv,Hkv,Dv) -> (B,Sq,Hq,Dv); scores scaled by ``scale`` (default
    D**-0.5)."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if q_pos is None:
        q_pos = jnp.arange(sq) + (skv - sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    if impl == "oracle":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                        q_pos=q_pos, kv_pos=kv_pos,
                                        scale=scale)
    if impl == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_pos=q_pos, kv_pos=kv_pos, scale=scale)
    return _flash_reference(q, k, v, causal=causal, window=window,
                            q_pos=q_pos, kv_pos=kv_pos, scale=scale)


# --------------------------------------------------------------------------- #
# decode attention (single new token vs long KV cache)
# --------------------------------------------------------------------------- #


def decode_attention(q, k_cache, v_cache, valid_mask, *, impl: str = "reference"):
    """q: (B,Hq,D); caches (B,S,Hkv,D); valid_mask (B,S) -> (B,Hq,D)."""
    if impl == "pallas":
        from repro.kernels.decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, valid_mask)
    if impl == "oracle":
        return _ref.decode_attention_ref(q, k_cache, v_cache, valid_mask)
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


def ssm_scan(u, delta, A, B, C, D, h0, *, chunk: int = 256,
             impl: str = "reference"):
    """Mamba-1 selective scan.  See ``ref.ssm_scan_ref`` for semantics."""
    if impl == "oracle":
        return _ref.ssm_scan_ref(u, delta, A, B, C, D, h0)
    if impl == "pallas":
        from repro.kernels.ssm_scan import ssm_scan_pallas
        return ssm_scan_pallas(u, delta, A, B, C, D, h0)
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
