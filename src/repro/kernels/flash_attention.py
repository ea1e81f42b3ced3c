"""Blocked flash-attention Pallas kernel (TPU target, prefill/train hot spot).

TPU adaptation notes (vs the canonical CUDA flash kernel):
  * tiles live in VMEM via explicit ``BlockSpec``s — (block_q, head_dim) and
    (block_k, head_dim) tiles of a heads-major (B, H, S, D) copy, so each
    block's last two dims are the TPU tile's (sublane, lane) pair — sized
    so q/k/v/acc fit the ~16 MiB VMEM budget with MXU-aligned
    (multiple-of-128) matmul dims;
  * the KV loop is the innermost *grid* dimension (TPU grids execute
    sequentially per core), with the online-softmax state (m, l, acc) carried
    in VMEM scratch across grid steps — no warp shuffles / shared-memory
    reductions, the MXU consumes (block_q × d) × (d × block_k) tiles directly;
  * GQA is expressed in the index_map: the kv-head index is ``h // group``,
    so kv tiles are fetched once per q-head group rather than materialising
    repeated heads in HBM.

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, dtypes, causal/window settings).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_on_this_platform

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _attn_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, causal: bool,
                 window: Optional[int], num_kv_blocks: int, scale: float):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale             # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),  # q @ k.T on MXU
                            preferred_element_type=jnp.float32)

    qp = qpos_ref[0]                                         # (bq, 1)
    kp = kpos_ref[0]                                         # (1, bk)
    mask = jnp.ones(s.shape, dtype=jnp.bool_)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > (qp - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                      # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           q_pos=None, kv_pos=None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    ``interpret=None`` runs natively on an accelerator and through the
    Pallas interpreter on the CPU."""
    if interpret is None:
        interpret = interpret_on_this_platform()
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "pad sequences to block multiples"
    if q_pos is None:
        q_pos = jnp.arange(sq) + (skv - sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    nq, nk = sq // bq, skv // bk
    # positions split per block into a column and a row, so each block's
    # last two dims, (bq, 1) and (1, bk), are the whole of the array's
    q_pos = q_pos.astype(jnp.int32).reshape(nq, bq, 1)
    kv_pos = kv_pos.astype(jnp.int32).reshape(nk, 1, bk)
    grid = (b, hq, nq, nk)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, num_kv_blocks=nk,
        scale=1.0 / (d ** 0.5))

    # heads-major layout: every tile is a (block, head_dim) slab
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda bi, h, qi, ki: (qi, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda bi, h, qi, ki: (ki, 0, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),     # l (running denom)
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(q_pos, kv_pos, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
