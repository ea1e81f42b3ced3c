"""Chunked selective-scan (Mamba-1) Pallas kernel — the SSM/hybrid hot spot.

TPU adaptation: the recurrence h_t = exp(Δt·A)·h_{t-1} + Δt·B_t·u_t is
sequential in t but *independent per channel*, so the kernel tiles the
channel dimension (``block_d``) across a parallel grid axis and streams time
in ``chunk``-sized VMEM tiles along the innermost sequential grid axis; the
fp32 state h (N, block_d) persists in VMEM scratch across chunk steps.
Inside a chunk the timestep loop is a ``fori_loop`` over VPU elementwise ops
on (N, block_d) tiles — the TPU replacement for the CUDA kernel's
warp-parallel scan (there is no cross-lane shuffle; the lane dimension IS
the channel tile).

Layout: channel-minor (..., chunk, block_d) tiles keep the 128-wide lane
dimension on channels, which is the natural VREG mapping; time is the
sublane axis, so step t reads its rows from the refs at ``pl.ds(t, 1)``
(Mosaic has no dynamic slice of a loaded value).  B_t and C_t turn from
(1, N) rows into (N, 1) columns by a masked lane sum.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_on_this_platform

DEFAULT_CHUNK = 256
DEFAULT_BLOCK_D = 256


def _ssm_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, dsk_ref, h0_ref,
                y_ref, hT_ref, h_scr, ys_scr, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)          # (N, bd)

    a = a_ref[...].astype(jnp.float32)                      # (N, bd)
    n = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):
        """(1, N) row -> (N, 1) column, exactly (a masked lane sum)."""
        return jnp.where(eye, row, 0.0).sum(axis=1, keepdims=True)

    def step(t, h):
        # time is the sublane axis of every tile: ref loads at pl.ds(t, 1)
        u_t = u_ref[0, pl.ds(t, 1), :].astype(jnp.float32)     # (1, bd)
        dt_t = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1, bd)
        b_t = column(b_ref[0, pl.ds(t, 1), :].astype(jnp.float32))  # (N, 1)
        c_t = column(c_ref[0, pl.ds(t, 1), :].astype(jnp.float32))
        h = jnp.exp(dt_t * a) * h + (dt_t * u_t) * b_t          # (N, bd)
        ys_scr[pl.ds(t, 1), :] = (h * c_t).sum(axis=0, keepdims=True)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    y_ref[0] = (ys_scr[...] + u_ref[0].astype(jnp.float32)
                * dsk_ref[...].astype(jnp.float32)).astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _finish():
        hT_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan_pallas(u, delta, A, B, C, D, h0, *, chunk: int = DEFAULT_CHUNK,
                    block_d: int = DEFAULT_BLOCK_D,
                    interpret: Optional[bool] = None):
    """See ``ref.ssm_scan_ref``.  u/delta: (Bt, T, Din); B/C: (Bt, T, N).

    ``interpret=None`` runs natively on an accelerator and through the
    Pallas interpreter on the CPU."""
    if interpret is None:
        interpret = interpret_on_this_platform()
    bt, t, din = u.shape
    n = A.shape[1]
    ck = min(chunk, t)
    bd = min(block_d, din)
    assert t % ck == 0 and din % bd == 0
    nc, nd = t // ck, din // bd

    kernel = functools.partial(_ssm_kernel, chunk=ck, num_chunks=nc)
    # state-major (N, Din) layouts keep channels on the lanes
    y, hT = pl.pallas_call(
        kernel,
        grid=(bt, nd, nc),
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda bi, di, ci: (bi, ci, di)),  # u
            pl.BlockSpec((1, ck, bd), lambda bi, di, ci: (bi, ci, di)),  # dt
            pl.BlockSpec((n, bd), lambda bi, di, ci: (0, di)),           # A^T
            pl.BlockSpec((1, ck, n), lambda bi, di, ci: (bi, ci, 0)),    # B
            pl.BlockSpec((1, ck, n), lambda bi, di, ci: (bi, ci, 0)),    # C
            pl.BlockSpec((1, bd), lambda bi, di, ci: (0, di)),           # D skip
            pl.BlockSpec((1, n, bd), lambda bi, di, ci: (bi, 0, di)),    # h0^T
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, n, bd), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, t, din), u.dtype),
            jax.ShapeDtypeStruct((bt, n, din), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32),
                        pltpu.VMEM((ck, bd), jnp.float32)],
        interpret=interpret,
    )(u, delta, A.T, B, C, D.reshape(1, din), h0.transpose(0, 2, 1))
    return y, hT.transpose(0, 2, 1)
