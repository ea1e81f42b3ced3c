"""Fused flash-attention Pallas kernel (TPU target, prefill hot spot).

Forward only: it has no VJP, so ``ops.choose_flash_impl`` gives it the
forward-only self-attention of prefill on a TPU and nothing else.

TPU adaptation notes (vs the canonical CUDA flash kernel):
  * one grid step computes one kv-head's (block_k, head_dim) K/V tile
    against the q tiles of all ``G = Hq/Hkv`` q-heads that share it,
    stacked into one (G*block_q, head_dim) operand: each K/V tile is
    fetched once per group, and the MXU's M dimension is G times larger;
  * tiles of a heads-major (B, H, S, D) copy live in VMEM via explicit
    ``BlockSpec``s, ``head_dim`` the full last dimension of every block;
    the score tile and the online-softmax state (m, l, acc) never leave
    VMEM;
  * MXU operands stay in the input dtype (q is scaled in float32 and cast
    back, p is cast to v's dtype) with float32 accumulation; m, l and acc
    are float32;
  * the KV loop is the innermost *grid* dimension (sequential per core),
    the softmax state carried across it in VMEM scratch;
  * blocks that the causal or window mask hides whole are skipped: a
    per-block table, prefetched into SMEM, tells each step whether its
    block is dead, partly masked or wholly visible.  Dead steps compute
    nothing, and their K/V ``index_map`` clamps to the q block's last (or
    first) live block, so the pipeline sees an unchanged block index and
    issues no DMA.  Only partly masked blocks build the element mask.

Block sizes come from the shape: ``block_q`` and ``block_k`` are the
largest of 512/256/128 that divide the sequence and whose working set
(``_vmem_bytes``: double-buffered q/k/v/out tiles, the positions, m/l/acc
and the float32 score and probability tiles) fits ``VMEM_BUDGET`` of the
``VMEM_LIMIT`` of scoped VMEM the kernel asks for.  At danube's prefill
(4096 tokens, G=4, head_dim 80, bf16) that is 512x512, about 17 MiB, and
36 of 64 block pairs per head group computed.

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, dtypes, causal/window settings).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_on_this_platform

NEG_INF = -1e30
BLOCK_SIZES = (512, 256, 128)
MIN_BLOCK = BLOCK_SIZES[-1]
VMEM_LIMIT = 64 * 2 ** 20       # scoped VMEM asked for (a v5e core has 128 MiB)
VMEM_BUDGET = 48 * 2 ** 20      # what the estimate may use; the rest is slack
DEAD, PARTIAL, FULL = 0, 1, 2   # block kinds in the prefetched table


def _vmem_bytes(g: int, bq: int, bk: int, d: int, itemsize: int,
                dv: int) -> int:
    """Estimated VMEM working set of one grid step (lanes padded to 128)."""
    lanes, vlanes = (-(-n // 128) * 128 for n in (d, dv))
    rows = g * bq
    tiles = 2 * itemsize * ((rows + bk) * lanes + (rows + bk) * vlanes)
    pos = 2 * 4 * 128 * (bq + 8 * bk // 128)             # (bq,1), (1,bk) x2
    state = 4 * rows * (2 * 128 + vlanes)                 # m, l, acc
    scores = rows * bk * (4 + 4 + itemsize)               # s, p, p cast
    return tiles + pos + state + scores


def choose_blocks(sq: int, skv: int, g: int, d: int, itemsize: int,
                  dv: Optional[int] = None) -> Tuple[int, int]:
    """(block_q, block_k): the largest sizes that divide the sequences and
    fit ``VMEM_BUDGET``; a sequence no size divides is one block.  ``dv``
    is the value head dim where it is not ``d``."""
    qs = [b for b in BLOCK_SIZES if sq % b == 0] or [sq]
    ks = [b for b in BLOCK_SIZES if skv % b == 0] or [skv]
    for bq in qs:
        for bk in ks:
            if _vmem_bytes(g, bq, bk, d, itemsize, dv or d) <= VMEM_BUDGET:
                return bq, bk
    return qs[-1], ks[-1]


def block_table(q_pos, kv_pos, bq: int, bk: int, *, causal: bool,
                window: Optional[int], xp=jnp):
    """Per q block the first and last live kv block, and per block pair
    its kind (``DEAD``, ``PARTIAL``, ``FULL``), from the positions' extent
    in each block.  ``xp`` is ``jnp`` in the kernel's wrapper and ``np``
    for a count on the host."""
    qb, kb = q_pos.reshape(-1, bq), kv_pos.reshape(-1, bk)
    q_lo, q_hi = qb.min(axis=1)[:, None], qb.max(axis=1)[:, None]
    k_lo, k_hi = kb.min(axis=1)[None, :], kb.max(axis=1)[None, :]
    live = xp.ones((qb.shape[0], kb.shape[0]), bool)
    full = live
    if causal:
        live = live & (k_lo <= q_hi)
        full = full & (k_hi <= q_lo)
    if window is not None:
        live = live & (k_hi > q_lo - window)
        full = full & (k_lo > q_hi - window)
    kind = xp.where(live, xp.where(full, FULL, PARTIAL), DEAD)
    idx = xp.arange(kb.shape[0])[None, :]
    last = xp.maximum(xp.where(live, idx, -1).max(axis=1), 0)
    first = xp.minimum(xp.where(live, idx, kb.shape[0]).min(axis=1), last)
    return first, last, kind


def block_counts(sq: int, skv: int, hq: int, hkv: int, d: int, dtype, *,
                 causal: bool, window: Optional[int],
                 dv: Optional[int] = None) -> Tuple[int, int]:
    """(computed, total) block pairs per head group of the kernel at
    these shapes, with the default (suffix-aligned) positions."""
    bq, bk = choose_blocks(sq, skv, hq // hkv, d, jnp.dtype(dtype).itemsize,
                           dv)
    _, _, kind = block_table(np.arange(sq) + (skv - sq), np.arange(skv),
                             bq, bk, causal=causal, window=window, xp=np)
    return int((kind != DEAD).sum()), int(kind.size)


def _attn_kernel(first_ref, last_ref, kind_ref, qpos_ref, kpos_ref,
                 q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 causal: bool, window: Optional[int], scale: float):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    kind = kind_ref[qi * nk + ki]
    _, g, bq, d = q_ref.shape

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        q = q_ref[0].reshape(g * bq, d)
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        k, v = k_ref[0, 0], v_ref[0, 0]                       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            qp, kp = qpos_ref[0], kpos_ref[0]                 # (bq,1), (1,bk)
            mask = jnp.ones((bq, kp.shape[1]), jnp.bool_)
            if causal:
                mask &= kp <= qp
            if window is not None:
                mask &= kp > qp - window
            s = jnp.where(mask[None], s.reshape(g, bq, -1), NEG_INF)
            s = s.reshape(g * bq, -1)
        m_prev = m_ref[...]                                   # (G*bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when(kind == FULL)(lambda: step(False))
    pl.when(kind == PARTIAL)(lambda: step(True))

    @pl.when(ki == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.reshape(g, bq, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           q_pos=None, kv_pos=None,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv) ->
    (B, Sq, Hq, Dv).  Scores are scaled by ``scale`` (default D**-0.5).

    Blocks come from ``choose_blocks`` unless given (tests sweep them).
    ``interpret=None`` runs natively on an accelerator and through the
    Pallas interpreter on the CPU."""
    if interpret is None:
        interpret = interpret_on_this_platform()
    b, sq, hq, d = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    g = hq // hkv
    cq, ck = choose_blocks(sq, skv, g, d, q.dtype.itemsize, dv)
    bq, bk = block_q or cq, block_k or ck
    if sq % bq or skv % bk:
        raise ValueError(f"blocks {bq}x{bk} do not divide {sq}x{skv}")
    if q_pos is None:
        q_pos = jnp.arange(sq) + (skv - sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    q_pos, kv_pos = q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32)
    nq, nk = sq // bq, skv // bk
    first, last, kind = block_table(q_pos, kv_pos, bq, bk, causal=causal,
                                    window=window)
    tables = (first.astype(jnp.int32), last.astype(jnp.int32),
              kind.reshape(-1).astype(jnp.int32))

    # a dead step's kv block is clamped to the q block's live ones: it maps
    # to the block its neighbouring step holds, so no DMA is issued for it
    def kv_map(bi, j, qi, ki, first_ref, last_ref, _):
        return bi, j, jnp.clip(ki, first_ref[qi], last_ref[qi]), 0

    def kpos_map(bi, j, qi, ki, first_ref, last_ref, _):
        return jnp.clip(ki, first_ref[qi], last_ref[qi]), 0, 0

    def q_map(bi, j, qi, ki, *_):
        return bi, j, qi, 0

    kernel = functools.partial(_attn_kernel, causal=causal, window=window,
                               scale=scale or 1.0 / (d ** 0.5))
    # heads-major layout: every tile is a (block, head_dim) slab, and the
    # G q-heads of kv-head j are rows j*G .. j*G+G-1
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, nq, nk),
            in_specs=[
                # positions split per block into a column and a row, so
                # each block's last two dims are the whole of the array's
                pl.BlockSpec((1, bq, 1), lambda bi, j, qi, ki, *_: (qi, 0, 0)),
                pl.BlockSpec((1, 1, bk), kpos_map),
                pl.BlockSpec((1, g, bq, d), q_map),
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, dv), kv_map),
            ],
            out_specs=pl.BlockSpec((1, g, bq, dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((g * bq, 1), jnp.float32),   # m (running max)
                pltpu.VMEM((g * bq, 1), jnp.float32),   # l (running denom)
                pltpu.VMEM((g * bq, dv), jnp.float32),  # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="prefill_flash_attention",
    )(*tables, q_pos.reshape(nq, bq, 1), kv_pos.reshape(nk, 1, bk),
      qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
