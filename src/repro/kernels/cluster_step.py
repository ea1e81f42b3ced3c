"""Pallas kernel for the batch cold-start simulator's per-step hot loop.

One grid program advances ONE scenario cell through a ``chunk`` of fixed-dt
timesteps: the cohort state (``nw`` container counts, ``fs`` per-function
scalars, ``free`` worker capacity) lives in VMEM scratch across the
sequential chunk axis, so a whole simulation streams only the per-chunk
arrival tile from HBM.  The cell axis is parallel — a 64-cell ``Sweep``
grid is 64 independent programs.

The step itself — TTL-expiry walk down the demotion schedule, warm-hit
serving with tier promotes, first-fit spawn placement, per-tier idle
billing — is implemented here in kernel style (iota one-hots, per-worker
prefix-sum placement) and tested for parity against the pure-jnp oracle
``repro.kernels.ref.cluster_step_ref`` under ``interpret=True``
(tests/test_batchsim.py).  Layout constants (FS_*/FP_*/SC_*/AG_* columns)
are shared from ``kernels/ref.py``.

TPU layout: inside the kernel every value is 2-D.  Per-function
quantities are (1, F) rows with functions on the lanes, so a step's
arrivals are one row of the (chunk, F) tile read at ``pl.ds(t, 1)``; the
per-cell tables are transposed to match (``nw`` (W, F), ``fs``
(FS_N, F), ``fparam`` (FP_N, F), ...), columns of a table are static
row slices, scalars are (1, 1), and the gathers, scatters, cumsum and
stacks that Mosaic does not lower are iota masks.  Shapes are
cold-start sized (F functions x W workers, both small), far from the
fp32 (8, 128) tile, so the compiled kernel runs padded;
``repro.core.batchsim`` uses the jitted oracle by default.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_on_this_platform
from repro.kernels.ref import (AG_COLD, AG_DEMOTIONS, AG_EXEC_GB_S,
                               AG_IDLE_PAUSED, AG_IDLE_SNAP, AG_IDLE_WARM,
                               AG_LAT_SUM, AG_LAUNCHED, AG_N, AG_PROMOTIONS,
                               AG_QWAIT_SUM, AG_REQUESTS, AG_WARM, BIG_TIME,
                               FP_EXEC_GB, FP_EXEC_S, FP_MEM_GB, FP_MEM_MB,
                               FP_N, FP_SVC, FS_DEADLINE, FS_EDGE,
                               FS_HAS_SNAP, FS_IMG, FS_N, FS_QUEUED, FS_TIER,
                               N_TIERS, SC_DT, SC_HORIZON, SC_IMG_CACHE,
                               SC_N, SC_SANITIZE_S, SC_SNAPSHOT, T_DEAD,
                               T_IMG, T_PAUSED, T_SNAP, T_WARM)

DEFAULT_CHUNK = 128


def _rows_iota(n: int, width: int):
    return jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)


def _pick(table, idx):
    """Column-wise gather ``table[idx[f], f]``: (K, F) table, (1, F)
    float indices -> (1, F)."""
    onehot = _rows_iota(table.shape[0], table.shape[1]).astype(
        jnp.float32) == idx
    return jnp.where(onehot, table, 0.0).sum(axis=0, keepdims=True)


def _frac_at(frac, tiers):
    """Footprint fraction of each function's tier: (N_TIERS, 1) column,
    (1, F) tiers -> (1, F)."""
    onehot = _rows_iota(N_TIERS, tiers.shape[1]).astype(jnp.float32) == tiers
    return jnp.where(onehot, frac, 0.0).sum(axis=0, keepdims=True)


def _total(x):
    """Sum of a 2-D tile as a (1, 1) value."""
    return x.sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)


def _stack_rows(rows: Dict[int, jax.Array], n: int):
    """(n, X) tile whose row j is ``rows[j]`` ((1, X) each); absent rows
    are zero."""
    width = next(iter(rows.values())).shape[1]
    it = _rows_iota(n, width)
    out = jnp.zeros((n, width), jnp.float32)
    for j, r in rows.items():
        out = jnp.where(it == j, r, out)
    return out


def _kernel_step(nw, fs, free, arrivals, conc, now, fparam, promote, dwell,
                 ntier, frac, scal, n_edges):
    """One fixed-dt cohort step (kernel-style implementation; semantics
    documented on ``ref.cluster_step_ref`` and in docs/batchsim.md).

    Transposed layout: nw (W, F); fs (FS_N, F); free (W, 1); arrivals,
    conc (1, F); now (1, 1); fparam (FP_N, F); promote (N_TIERS, F);
    dwell, ntier (K, F); frac (N_TIERS, 1); scal (SC_N, 1).  Returns
    ``(nw, fs, free, agg)`` with agg (AG_N, 1)."""
    f32 = jnp.float32
    w = nw.shape[0]
    dt = scal[SC_DT:SC_DT + 1]
    dt_eff = jnp.clip(scal[SC_HORIZON:SC_HORIZON + 1] - now, 0.0, dt)
    active = (dt_eff > 0.0).astype(f32)

    tier, edge = fs[FS_TIER:FS_TIER + 1], fs[FS_EDGE:FS_EDGE + 1]
    deadline = fs[FS_DEADLINE:FS_DEADLINE + 1]
    queued = fs[FS_QUEUED:FS_QUEUED + 1]
    has_snap, img = fs[FS_HAS_SNAP:FS_HAS_SNAP + 1], fs[FS_IMG:FS_IMG + 1]
    mem = fparam[FP_MEM_MB:FP_MEM_MB + 1]
    exec_s = fparam[FP_EXEC_S:FP_EXEC_S + 1]
    exec_gb = fparam[FP_EXEC_GB:FP_EXEC_GB + 1]
    svc = fparam[FP_SVC:FP_SVC + 1]
    mem_gb = fparam[FP_MEM_GB:FP_MEM_GB + 1]
    dwell0 = dwell[0:1]
    demotions = jnp.zeros((1, 1), f32)

    # 1. expiry walk — up to n_edges schedule edges can fire per step
    for _ in range(n_edges):
        n = nw.sum(axis=0, keepdims=True)
        tgt = _pick(ntier, jnp.clip(edge, 0, n_edges - 1))
        fire = ((n > 0) & (deadline <= now)).astype(f32) * active
        died = fire * (tgt == T_DEAD)
        demoted = fire - died
        new_res = mem * _frac_at(frac, tgt) * (1.0 - died)
        delta_mb = (new_res - mem * _frac_at(frac, tier)) * fire
        free = free - (nw * delta_mb).sum(axis=1, keepdims=True)
        demotions = demotions + _total(demoted * n)
        nw = nw * (1.0 - died)
        nxt = _pick(dwell, jnp.clip(edge + 1, 0, n_edges - 1))
        deadline = jnp.where(demoted > 0, now + nxt,
                             jnp.where(died > 0, BIG_TIME, deadline))
        tier = jnp.where(demoted > 0, tgt, tier)
        has_snap = jnp.maximum(has_snap, demoted * (tgt == T_SNAP))
        edge = edge + fire

    # 2. spawn to cover within-step concurrency: the host-precomputed
    # peak overlap ``conc`` (exact from event timestamps) or the
    # Little's-law floor demand*exec_s/dt, whichever is larger
    demand = queued + arrivals
    n = nw.sum(axis=0, keepdims=True)
    required = jnp.maximum(
        jnp.ceil(demand * exec_s / jnp.maximum(dt_eff, 1e-9)), conc)
    spawn_want = jnp.clip(required - n, 0.0, demand)
    img_cache = scal[SC_IMG_CACHE:SC_IMG_CACHE + 1]
    spawn_tier = jnp.where(
        has_snap > 0, T_SNAP,
        jnp.where((img_cache > 0) & (img > 0), T_IMG, T_DEAD))
    spawn_cost = _pick(promote, spawn_tier)

    # vectorized first-fit (see ref.cluster_step_ref): parallel packing
    # against the current free vector, proportional scale-back on any
    # over-committed worker; the exclusive prefix over workers is an
    # unrolled sum of earlier rows
    need = spawn_want * active                               # (1, F)
    cap_w = jnp.maximum(jnp.floor(free / jnp.maximum(mem, 1.0)), 0.0)
    rows = _rows_iota(w, cap_w.shape[1])
    before = jnp.zeros_like(cap_w)
    for j in range(w - 1):
        before = before + jnp.where(rows > j, cap_w[j:j + 1], 0.0)
    take = jnp.clip(need - before, 0.0, cap_w)
    used_w = (take * mem).sum(axis=1, keepdims=True)        # (W, 1)
    scale = jnp.where(used_w > free,
                      free / jnp.maximum(used_w, 1e-9), 1.0)
    take = take * scale
    nw_pre = nw
    free = free - (take * mem).sum(axis=1, keepdims=True)
    nw = nw + take
    granted = take.sum(axis=0, keepdims=True)
    snapshot = scal[SC_SNAPSHOT:SC_SNAPSHOT + 1]
    has_snap = jnp.maximum(has_snap, (granted > 0) * snapshot)
    img = jnp.maximum(img, (granted > 0).astype(f32))

    # 3. serve queued + fresh demand
    capacity = jnp.floor((n + granted) * svc
                         * jnp.where(dt > 0, dt_eff / dt, 0.0))
    served = jnp.minimum(demand, capacity)
    cohort_demoted = (tier < T_WARM) & (n > 0)
    # promote only the concurrency the step needs; surplus demoted
    # containers retire instead of re-arming (see ref.cluster_step_ref)
    used = jnp.clip(
        jnp.maximum(jnp.ceil(served * exec_s / jnp.maximum(dt_eff, 1e-9)),
                    conc), 1.0, jnp.maximum(n, 1.0))
    promoted_req = jnp.where(cohort_demoted, jnp.minimum(served, used), 0.0)
    cold_spawn = jnp.minimum(granted, served - promoted_req)
    warm_served = served - promoted_req - cold_spawn
    prom_cost = _pick(promote, tier)
    restore = cohort_demoted & (served > 0)
    res_now = mem * _frac_at(frac, tier)
    # warm-cohort surplus retires exponentially at dt/warm_dwell — the
    # per-container TTL clocks the shared deadline can't express (see
    # ref.cluster_step_ref)
    decaying = (~cohort_demoted) & (served > 0) & (n > 0)
    surplus = jnp.clip(n - used, 0.0, None)
    decay = surplus * jnp.minimum(dt_eff / jnp.maximum(dwell0, 1e-9), 1.0)
    keep = jnp.where(
        restore & (n > 0), used / jnp.maximum(n, 1.0),
        jnp.where(decaying, 1.0 - decay / jnp.maximum(n, 1.0), 1.0))
    delta = jnp.where(restore, keep * (mem - res_now), 0.0) \
        - (1.0 - keep) * res_now
    free = free - (nw_pre * delta).sum(axis=1, keepdims=True)
    nw = nw - nw_pre * (1.0 - keep)
    tier = jnp.where(restore, T_WARM, tier)

    leftover = demand - served
    sanitize = scal[SC_SANITIZE_S:SC_SANITIZE_S + 1]
    busy_warm = warm_served * (exec_s + sanitize)
    busy_cold = promoted_req * (exec_s + prom_cost) \
        + cold_spawn * (exec_s + spawn_cost)

    hit = (served + granted) > 0
    edge = jnp.where(hit, 0.0, edge)
    deadline = jnp.where(hit, now + exec_s + dwell0, deadline)
    tier = jnp.where(hit, T_WARM, tier)

    # 4. idle GB-s at the cohort tier's footprint
    idle_cs = jnp.clip(nw.sum(axis=0, keepdims=True) * dt_eff - busy_warm
                       - busy_cold, 0.0, None)
    idle_gb = idle_cs * mem_gb * _frac_at(frac, tier)

    agg = _stack_rows({
        AG_DEMOTIONS: demotions,
        AG_PROMOTIONS: _total(promoted_req),
        AG_REQUESTS: _total(served),
        AG_COLD: _total(promoted_req + cold_spawn),
        AG_WARM: _total(warm_served),
        AG_LAUNCHED: _total(granted),
        AG_LAT_SUM: _total(busy_warm + busy_cold)
        + _total(leftover) * dt_eff,
        AG_QWAIT_SUM: _total(leftover) * dt_eff,
        AG_EXEC_GB_S: _total(
            (busy_warm + (promoted_req + cold_spawn) * exec_s) * exec_gb),
        AG_IDLE_WARM: _total(idle_gb * (tier == T_WARM)),
        AG_IDLE_PAUSED: _total(idle_gb * (tier == T_PAUSED)),
        AG_IDLE_SNAP: _total(idle_gb * (tier == T_SNAP)),
    }, AG_N)
    fs = _stack_rows({FS_TIER: tier, FS_EDGE: edge, FS_DEADLINE: deadline,
                      FS_QUEUED: leftover, FS_HAS_SNAP: has_snap,
                      FS_IMG: img}, FS_N)
    return nw, fs, free, agg


def _cluster_kernel(nw_ref, fs_ref, free_ref, arr_ref, conc_ref, fparam_ref,
                    promote_ref, dwell_ref, ntier_ref, frac_ref, scal_ref,
                    nw_out, fs_out, free_out, agg_out,
                    nw_s, fs_s, free_s, agg_s, *,
                    chunk: int, num_chunks: int, n_edges: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        nw_s[...] = nw_ref[0]
        fs_s[...] = fs_ref[0]
        free_s[...] = free_ref[0]
        agg_s[...] = jnp.zeros_like(agg_s)

    scal = scal_ref[0]                               # (SC_N, 1)
    dt = scal[SC_DT:SC_DT + 1]
    tables = (fparam_ref[0], promote_ref[0], dwell_ref[0], ntier_ref[0],
              frac_ref[0], scal)

    def body(t, carry):
        nw, fs, free, agg = carry
        now = (ci * chunk + t).astype(jnp.float32) * dt
        arrivals = arr_ref[0, pl.ds(t, 1), :]         # (1, F)
        conc = conc_ref[0, pl.ds(t, 1), :]
        nw, fs, free, d = _kernel_step(nw, fs, free, arrivals, conc, now,
                                       *tables, n_edges)
        return nw, fs, free, agg + d

    nw, fs, free, agg = jax.lax.fori_loop(
        0, chunk, body, (nw_s[...], fs_s[...], free_s[...], agg_s[...]))
    nw_s[...] = nw
    fs_s[...] = fs
    free_s[...] = free
    agg_s[...] = agg

    @pl.when(ci == num_chunks - 1)
    def _finish():
        nw_out[0] = nw_s[...]
        fs_out[0] = fs_s[...]
        free_out[0] = free_s[...]
        agg_out[0] = agg_s[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def cluster_sim_pallas(nw, fs, free, arrivals, conc, fparam, promote, dwell,
                       ntier, frac, scal, *, chunk: int = DEFAULT_CHUNK,
                       interpret: Optional[bool] = None):
    """Advance every cell through all T steps in one kernel launch.

    nw: (C, F, W); fs: (C, F, FS_N); free: (C, W); arrivals and conc
    (per-step peak concurrency): (C, T, F); fparam/promote: (C, F, 5);
    dwell/ntier: (C, F, K); frac: (C, 5); scal: (C, SC_N).  T must be a
    multiple of ``chunk`` (the driver pads arrivals with empty steps —
    post-horizon steps are no-ops).  ``interpret=None`` runs natively on
    an accelerator and through the Pallas interpreter on the CPU.

    Returns ``(nw_final, fs_final, free_final, agg)`` with agg (C, AG_N).
    """
    if interpret is None:
        interpret = interpret_on_this_platform()
    c, t, f = arrivals.shape
    w = nw.shape[2]
    k = dwell.shape[2]
    ck = min(chunk, t)
    assert t % ck == 0, f"T={t} not a multiple of chunk={ck}"
    nc = t // ck

    kernel = functools.partial(_cluster_kernel, chunk=ck, num_chunks=nc,
                               n_edges=k)
    # every table transposed to the kernel's (X, F) / (X, 1) layout, so
    # each block's last two dims equal the array's
    tr = lambda x: jnp.swapaxes(x, 1, 2)
    col = lambda x: x[:, :, None]
    cell = lambda c_, ci: (c_, 0, 0)         # per-cell block, chunk-invariant
    full = lambda *shape: pl.BlockSpec((1, *shape), cell)
    nw_f, fs_f, free_f, agg_f = pl.pallas_call(
        kernel,
        grid=(c, nc),
        in_specs=[
            full(w, f),                                           # nw^T
            full(FS_N, f),                                        # fs^T
            full(w, 1),                                           # free
            pl.BlockSpec((1, ck, f), lambda c_, ci: (c_, ci, 0)),  # arrivals
            pl.BlockSpec((1, ck, f), lambda c_, ci: (c_, ci, 0)),  # conc
            full(FP_N, f),                                        # fparam^T
            full(N_TIERS, f),                                     # promote^T
            full(k, f),                                           # dwell^T
            full(k, f),                                           # ntier^T
            full(N_TIERS, 1),                                     # frac
            full(SC_N, 1),                                        # scal
        ],
        out_specs=[full(w, f), full(FS_N, f), full(w, 1), full(AG_N, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((c, w, f), jnp.float32),
            jax.ShapeDtypeStruct((c, FS_N, f), jnp.float32),
            jax.ShapeDtypeStruct((c, w, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, AG_N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((w, f), jnp.float32),
            pltpu.VMEM((FS_N, f), jnp.float32),
            pltpu.VMEM((w, 1), jnp.float32),
            pltpu.VMEM((AG_N, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tr(nw), tr(fs), col(free), arrivals, conc, tr(fparam), tr(promote),
      tr(dwell), tr(ntier), col(frac), col(scal))
    return tr(nw_f), tr(fs_f), free_f[:, :, 0], agg_f[:, :, 0]
