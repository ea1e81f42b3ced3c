"""Decode's expert pass as one Pallas kernel: only the chosen experts' weights
are read.

A decode step routes its ``t`` tokens to ``t * k`` (token, expert) pairs,
no more than the layer's held experts, and they reach few of them: a
DeepSeek-V2-Lite token reaches 1.5 of chip 0's 16 experts on average.
Passing every token through every held expert, each gated by 0 where it
was not chosen, streams all of their weights each step.  This kernel
streams only the experts the pairs chose:

  * the pairs come sorted by expert, as the grouped matmuls take them:
    slot ``s`` is a pair, its expert ``ids[s]`` and its token ``tok[s]``
    prefetched into SMEM with ``n``, the number of held pairs, which come
    first.  Slots past ``n`` repeat the last held expert;
  * the grid is (ff tile, slot), slots innermost.  Consecutive slots of
    one expert, and every slot past ``n``, map to the block the previous
    grid step holds, so the pipeline issues no DMA for them: each chosen
    expert's tile is read once, and a call never reads more experts than
    ``min(t * k, held)``.  Past ``n`` ``pl.when`` skips the compute;
  * a slot passes its token through its expert and writes the result to
    its own output row; the caller weights each row by the pair's gate
    and adds it to its token, as the grouped matmuls' rows are;
  * the weights come whole, as the decode scan holds them: stacks of
    ``[n_rep, held, d, ff]`` (``wo``: ``[n_rep, held, ff, d]``) with the
    layer's index prefetched beside the ids.  A slice of a scanned stack
    cannot be fused into the kernel's custom call, so XLA would copy the
    layer's experts out of it every step;
  * operands stay in the weights' dtype with float32 accumulation: the
    hidden activations are rounded to that dtype, as the dense pass's
    products are, and the output rows are float32.

``ff`` is tiled by the largest multiple of 128 that divides it and whose
double-buffered weight tiles fit ``VMEM_BUDGET`` (whole at DeepSeek's
1408: 3 x 2048 x 1408 bf16, twice, 34.6 MB); a width that is no multiple
of 128 is one tile.

Validated in interpret mode on CPU against the dense gated pass
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_on_this_platform

VMEM_LIMIT = 64 * 2 ** 20       # scoped VMEM asked for (a v5e core has 128 MiB)
VMEM_BUDGET = 48 * 2 ** 20      # what the weight tiles may use


def choose_ff_tile(d: int, ff: int, mats: int, itemsize: int) -> int:
    """The ff tile: the largest multiple of 128 dividing ``ff`` whose
    ``mats`` double-buffered (d, tile) weight tiles fit ``VMEM_BUDGET``;
    ``ff`` itself where it is no multiple of 128."""
    if ff % 128:
        return ff
    fits = [f for f in range(128, ff + 1, 128)
            if ff % f == 0 and 2 * mats * d * f * itemsize <= VMEM_BUDGET]
    return max(fits, default=128)


def _kernel(layer_ref, ids_ref, tok_ref, n_ref, x_ref, wi_ref, *rest,
            gated: bool):
    wg_ref, wo_ref, o_ref = rest if gated else (None, *rest)
    j, s = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (s == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_ref[0])
    def _step():
        x = x_ref[tok_ref[s]]                                 # (1, d)
        dt = wi_ref.dtype
        h = jnp.dot(x, wi_ref[0, 0], preferred_element_type=jnp.float32)
        h = h.astype(dt).astype(jnp.float32)
        if gated:
            g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
            h = jax.nn.silu(h) * g.astype(dt).astype(jnp.float32)
        else:
            h = jax.nn.gelu(h)
        o_ref[s] += jnp.dot(h.astype(dt), wo_ref[0, 0],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_ff", "interpret"))
def held_experts_pallas(x, tok, ids, n, layer, wi, wo, wg=None, *,
                        block_ff: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """x: (t, d); tok, ids: (slots,) int32, each pair's token and held
    expert, sorted by expert, the ``n`` held pairs first and the last held
    expert repeated after them; n, layer: int32 scalars; wi, wg: (n_rep,
    held, d, ff); wo: (n_rep, held, ff, d) -> (slots, d) float32: slot
    ``s``'s token through its expert of layer ``layer`` (SwiGLU with
    ``wg``, else GELU), zero past ``n``.

    ``block_ff`` comes from ``choose_ff_tile`` unless given (tests sweep
    it).  ``interpret=None`` runs natively on an accelerator and through
    the Pallas interpreter on the CPU."""
    if interpret is None:
        interpret = interpret_on_this_platform()
    t, d = x.shape
    ff = wi.shape[-1]
    slots = ids.shape[0]
    gated = wg is not None
    tf = block_ff or choose_ff_tile(d, ff, 3 if gated else 2,
                                    wi.dtype.itemsize)
    if ff % tf:
        raise ValueError(f"ff tile {tf} does not divide {ff}")

    def w_in_map(j, s, layer_ref, ids_ref, *_):
        return layer_ref[0], ids_ref[s], 0, j

    def w_out_map(j, s, layer_ref, ids_ref, *_):
        return layer_ref[0], ids_ref[s], j, 0

    def whole(j, s, *_):
        return 0, 0, 0

    w_in = pl.BlockSpec((1, 1, d, tf), w_in_map)
    in_specs = [pl.BlockSpec((t, 1, d), whole), w_in]
    weights = [wi]
    if gated:
        in_specs.append(w_in)
        weights.append(wg)
    in_specs.append(pl.BlockSpec((1, 1, tf, d), w_out_map))
    weights.append(wo)
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32),
               ids.astype(jnp.int32), tok.astype(jnp.int32),
               jnp.reshape(n, (1,)).astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_kernel, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ff // tf, slots),
            in_specs=in_specs,
            # every slot's row stays in VMEM across the ff tiles
            out_specs=pl.BlockSpec((slots, 1, d), whole)),
        out_shape=jax.ShapeDtypeStruct((slots, 1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_held_experts",
    )(*scalars, x.astype(wi.dtype).reshape(t, 1, d), *weights)
    return out.reshape(slots, d)
