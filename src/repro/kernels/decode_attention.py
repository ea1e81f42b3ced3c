"""Flash-decode Pallas kernel: one new token vs a long KV cache (serving
hot spot — the ``decode_32k`` / ``long_500k`` shapes).

TPU adaptation: the cache, transposed heads-major to (B, Hkv, S, D), is
streamed HBM→VMEM in (block_s, head_dim) tiles
along the innermost (sequential) grid dimension, with the online-softmax
state for the whole q-head *group* carried in VMEM scratch.  One grid step
processes all ``G = Hq/Hkv`` query heads of a kv head against one KV tile, so
each cache byte is read exactly once per group — the TPU analogue of
flash-decode's split-K, without the CUDA-style cross-SM reduction (the
sequential grid *is* the reduction).
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
DEFAULT_BLOCK_S = 512


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, num_blocks: int, scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = mask_ref[0] > 0                                  # (1, bs)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),  # (G, bs)
                            preferred_element_type=jnp.float32)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]                  # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == num_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, valid_mask, *,
                            block_s: int = DEFAULT_BLOCK_S,
                            interpret: Optional[bool] = None):
    """q: (B, Hq, D); caches (B, S, Hkv, D); valid_mask (B, S) -> (B, Hq, D).

    ``interpret=None`` runs natively on an accelerator and through the
    Pallas interpreter on the CPU."""
    if interpret is None:
        interpret = interpret_on_this_platform()
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    bs = min(block_s, s)
    assert s % bs == 0, "cache length must be a multiple of block_s"
    nb = s // bs
    qg = q.reshape(b, hkv, g, d)
    # heads-major caches and a (B, 1, S) int mask: every block's last two
    # dims are a (sublane, lane) tile or the whole axis
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    mask = valid_mask.astype(jnp.int32).reshape(b, 1, s)

    kernel = functools.partial(_decode_kernel, num_blocks=nb,
                               scale=1.0 / (d ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, h, si: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, h, si: (bi, h, si, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda bi, h, si: (bi, h, si, 0)),
            pl.BlockSpec((1, 1, bs), lambda bi, h, si: (bi, 0, si)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, h, si: (bi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt, mask)
    return out.reshape(b, hq, d)
