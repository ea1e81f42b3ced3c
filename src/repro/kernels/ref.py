"""Pure-jnp oracles for every Pallas kernel.

These are deliberately naive (O(S^2) score materialisation, step-by-step
scans): they are the *correctness* reference that both the memory-bounded
jnp implementations in ``ops.py`` and the Pallas TPU kernels are tested
against (``tests/test_kernels.py`` sweeps shapes/dtypes with
``assert_allclose``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(k, num_q_heads):
    """(B, S, Hkv, D) -> (B, S, Hq, D) by repeating kv heads."""
    b, s, hkv, d = k.shape
    rep = num_q_heads // hkv
    return jnp.repeat(k, rep, axis=2)


def attention_mask(q_pos, kv_pos, *, causal: bool, window: Optional[int]):
    """(Sq, Skv) boolean mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= kv_pos[None, :] > (q_pos[:, None] - window)
    return m


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_pos=None, kv_pos=None,
                        scale: Optional[float] = None):
    """Naive attention oracle.

    q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv) with
    Hq % Hkv == 0.  Scores scaled by ``scale`` (default D**-0.5).
    Returns (B, Sq, Hq, Dv) in q.dtype; softmax in fp32.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if q_pos is None:
        q_pos = jnp.arange(sq) + (skv - sq)  # suffix alignment (prefill default)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    scale = scale or 1.0 / float(d) ** 0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = attention_mask(q_pos, kv_pos, causal=causal, window=window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, valid_mask):
    """Single-token decode oracle.

    q: (B, Hq, D); caches: (B, S, Hkv, D); valid_mask: (B, S) bool.
    Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    k = _gqa_expand(k_cache, hq)
    v = _gqa_expand(v_cache, hq)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.where(valid_mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# batched cold-start cluster step (the batch simulator's physics)
# --------------------------------------------------------------------------- #
# Array-form mirror of one ``ClusterState`` cell for the fixed-timestep
# batch driver (``repro.core.batchsim``).  Containers of one function are
# collapsed into a *cohort*: one count per (function, worker), one warmth
# tier / schedule edge / demotion deadline per function.  All layout
# constants live here so the Pallas kernel (``kernels/cluster_step.py``),
# the table builder, and the tests agree on column meanings.
#
# state (per cell, float32 throughout — tiers/edges are small exact ints):
#   nw   [F, W]   resident containers of function f on worker w
#   fs   [F, 6]   per-function cohort scalars (FS_* columns)
#   free [W]      free memory per worker, MB
# static tables (per cell):
#   fparam  [F, 5]   FP_* columns (mem MB, exec s, GB billed per
#                    execution-second, requests servable per container
#                    per dt, mem GB)
#   promote [F, 5]   seconds to bring a container to serving from tier t
#   dwell   [F, K]   demotion-schedule dwell seconds (inf-padded)
#   ntier   [F, K]   demotion-schedule target tier (DEAD-padded)
#   frac    [5]      resident-footprint fraction per tier
#   scal    [SC_N]   cell scalars (SC_* columns)
# aggregates (one [AG_N] vector per cell, summed over steps):
#   counts + QoS sums that reconstruct into a ledger summary

FS_TIER, FS_EDGE, FS_DEADLINE, FS_QUEUED, FS_HAS_SNAP, FS_IMG = range(6)
FS_N = 6
FP_MEM_MB, FP_EXEC_S, FP_EXEC_GB, FP_SVC, FP_MEM_GB = range(5)
FP_N = 5
SC_DT, SC_HORIZON, SC_IMG_CACHE, SC_SNAPSHOT, SC_SANITIZE_S = range(5)
SC_N = 5
(AG_REQUESTS, AG_COLD, AG_WARM, AG_LAUNCHED, AG_PROMOTIONS, AG_DEMOTIONS,
 AG_LAT_SUM, AG_QWAIT_SUM, AG_EXEC_GB_S, AG_IDLE_WARM, AG_IDLE_PAUSED,
 AG_IDLE_SNAP) = range(12)
AG_N = 12

# WarmthTier ordinals as floats (DEAD < IMG_CACHED < SNAPSHOT_READY <
# PAUSED < WARM_IDLE, matching repro.core.lifecycle.WarmthTier)
T_DEAD, T_IMG, T_SNAP, T_PAUSED, T_WARM = 0.0, 1.0, 2.0, 3.0, 4.0
N_TIERS = 5
BIG_TIME = 1e30               # "never" deadline (inf-like, finite for f32)


def _tier_select(table, tier):
    """``table[f, tier[f]]`` via one-hot over the small tier axis."""
    cols = table.shape[1]
    out = jnp.zeros(table.shape[0], jnp.float32)
    for t in range(cols):
        out = out + table[:, t] * (tier == t)
    return out


def cluster_step_full(nw, fs, free, arrivals, conc, now, fparam, promote,
                      dwell, ntier, frac, scal):
    """One fixed-dt step of the batched cluster cohort model (one cell).

    Semantics per step, in order (mirroring the scalar simulator's
    dispatch; see docs/batchsim.md for the divergences):

      1. expiry walk — cohorts whose demotion deadline passed slide down
         their schedule (up to K edges per step), freeing/charging the
         per-tier footprint; DEAD edges destroy the cohort.
      2. spawn — a container serves one request at a time, so the cohort
         grows to cover this step's peak concurrency: ``conc`` (the
         host-precomputed max number of arrivals inside one exec window,
         exact from event timestamps) or the Little's-law floor
         ``demand * exec_s / dt``, whichever is larger.  New containers
         place first-fit across workers.
      3. serve — queued + new arrivals consume cohort capacity
         (``n * svc`` requests per step); demoted cohorts promote back to
         WARM_IDLE, their requests billed the promote latency and counted
         cold (matching the scalar ledger, where resumes are cold=True);
         leftovers stay queued and accrue wait.
      4. idle accounting — container-seconds not spent serving are billed
         GB-s at the cohort tier's footprint fraction.

    Returns ``(nw, fs, free, agg_delta[AG_N], extras)`` where ``extras``
    is a ``(cold[F], idle_gb[F])`` pair of *per-function* step deltas —
    the reward channels the RL gym (``repro.learn.gym``) consumes before
    they are summed into the cell aggregate.
    """
    f32 = jnp.float32
    F, W = nw.shape
    K = dwell.shape[1]
    dt = scal[SC_DT]
    dt_eff = jnp.clip(scal[SC_HORIZON] - now, 0.0, dt)
    active = dt_eff > 0.0

    tier = fs[:, FS_TIER]
    edge = fs[:, FS_EDGE]
    deadline = fs[:, FS_DEADLINE]
    queued = fs[:, FS_QUEUED]
    has_snap = fs[:, FS_HAS_SNAP]
    img = fs[:, FS_IMG]
    mem = fparam[:, FP_MEM_MB]
    exec_s = fparam[:, FP_EXEC_S]
    exec_gb = fparam[:, FP_EXEC_GB]
    svc = fparam[:, FP_SVC]
    mem_gb = fparam[:, FP_MEM_GB]
    agg = jnp.zeros((AG_N,), f32)

    # ---- 1. expiry walk (K unrolled edges; a zero dwell can cascade) ---- #
    for _ in range(K):
        n = nw.sum(axis=1)
        edge_c = jnp.clip(edge, 0, K - 1)
        tgt = _tier_select(ntier, edge_c)
        fire = (n > 0) & (deadline <= now) & active
        died = fire & (tgt == T_DEAD)
        demoted = fire & ~died
        old_res = mem * _tier_select(jnp.tile(frac[None, :], (F, 1)), tier)
        new_res = jnp.where(died, 0.0,
                            mem * _tier_select(jnp.tile(frac[None, :],
                                                        (F, 1)), tgt))
        delta_mb = jnp.where(fire, new_res - old_res, 0.0)
        free = free - (nw * delta_mb[:, None]).sum(axis=0)
        agg = agg.at[AG_DEMOTIONS].add((demoted * n).sum())
        nw = jnp.where(died[:, None], 0.0, nw)
        next_edge = jnp.clip(edge + 1, 0, K - 1)
        nxt_dwell = _tier_select(dwell, next_edge)
        deadline = jnp.where(demoted, now + nxt_dwell,
                             jnp.where(died, BIG_TIME, deadline))
        tier = jnp.where(demoted, tgt, tier)
        has_snap = jnp.maximum(has_snap, (demoted & (tgt == T_SNAP)))
        edge = jnp.where(fire, edge + 1.0, edge)

    # ---- 2. spawn to cover within-step concurrency ---- #
    # a container serves requests sequentially, so ``demand`` requests of
    # ``exec_s`` each need ~demand*exec_s/dt concurrent containers
    # (Little's law over the step) — the scalar sim spawns one container
    # per overlapping request; this is its fixed-dt analogue
    demand = queued + arrivals
    n = nw.sum(axis=1)
    required = jnp.maximum(
        jnp.ceil(demand * exec_s / jnp.maximum(dt_eff, 1e-9)), conc)
    spawn_want = jnp.clip(required - n, 0.0, demand)
    spawn_tier = jnp.where(
        has_snap > 0, T_SNAP,
        jnp.where((scal[SC_IMG_CACHE] > 0) & (img > 0), T_IMG, T_DEAD))
    spawn_cost = _tier_select(promote, spawn_tier)

    # vectorized first-fit: every function packs against the CURRENT free
    # vector in parallel (exact whenever one function spawns per step —
    # the dominant case); if simultaneous spawners over-commit a worker,
    # their takes scale back proportionally so free never goes negative
    need = (spawn_want * active.astype(f32))[:, None]            # (F, 1)
    cap_w = jnp.maximum(jnp.floor(free[None, :]
                                  / jnp.maximum(mem, 1.0)[:, None]), 0.0)
    prior = jnp.cumsum(cap_w, axis=1) - cap_w
    take = jnp.clip(need - prior, 0.0, cap_w)                    # (F, W)
    used_w = (take * mem[:, None]).sum(axis=0)
    scale = jnp.where(used_w > free,
                      free / jnp.maximum(used_w, 1e-9), 1.0)
    take = take * scale[None, :]
    nw_pre = nw                       # resident counts before this spawn
    free = free - (take * mem[:, None]).sum(axis=0)
    nw = nw + take
    granted = take.sum(axis=1)
    has_snap = jnp.maximum(has_snap, (granted > 0) * scal[SC_SNAPSHOT])
    img = jnp.maximum(img, (granted > 0).astype(f32))

    # ---- 3. serve queued + fresh demand ---- #
    capacity = jnp.floor((n + granted) * svc
                         * jnp.where(dt > 0, dt_eff / dt, 0.0))
    served = jnp.minimum(demand, capacity)
    cohort_demoted = (tier < T_WARM) & (n > 0)
    # only as many containers promote as the step's concurrency needs;
    # the scalar leaves the rest at the demoted tier on their stale
    # deadlines (SPES-style short dwells then kill them before the next
    # burst), so the surplus retires here rather than re-arming
    used = jnp.clip(
        jnp.maximum(jnp.ceil(served * exec_s / jnp.maximum(dt_eff, 1e-9)),
                    conc), 1.0, jnp.maximum(n, 1.0))
    promoted_req = jnp.where(cohort_demoted, jnp.minimum(served, used), 0.0)
    cold_spawn = jnp.minimum(granted, served - promoted_req)
    warm_served = served - promoted_req - cold_spawn
    prom_cost = _tier_select(promote, tier)
    restore = cohort_demoted & (served > 0)
    res_now = mem * _tier_select(jnp.tile(frac[None, :], (F, 1)), tier)
    # serving re-arms the shared cohort deadline, which the per-container
    # scalar sim does only for the container that served: its surplus
    # siblings keep their own TTL clocks and die ~one warm dwell after
    # their last personal use.  Mimic that with an exponential retirement
    # of the surplus (n - used) at rate dt/warm_dwell whenever a warm
    # cohort serves
    d0 = dwell[:, 0]
    decaying = (~cohort_demoted) & (served > 0) & (n > 0)
    surplus = jnp.clip(n - used, 0.0, None)
    decay = surplus * jnp.minimum(dt_eff / jnp.maximum(d0, 1e-9), 1.0)
    keep = jnp.where(
        restore & (n > 0), used / jnp.maximum(n, 1.0),
        jnp.where(decaying, 1.0 - decay / jnp.maximum(n, 1.0), 1.0))
    # promoted part re-inflates to full memory, surplus frees its
    # demoted footprint (spawns were already charged at placement)
    delta = jnp.where(restore, keep * (mem - res_now), 0.0) \
        - (1.0 - keep) * res_now
    free = free - (nw_pre * delta[:, None]).sum(axis=0)
    nw = nw - nw_pre * (1.0 - keep)[:, None]
    tier = jnp.where(restore, T_WARM, tier)
    agg = agg.at[AG_PROMOTIONS].add(promoted_req.sum())

    leftover = demand - served
    cold = promoted_req + cold_spawn
    sanitize = scal[SC_SANITIZE_S]
    agg = agg.at[AG_REQUESTS].add(served.sum())
    agg = agg.at[AG_COLD].add(cold.sum())
    agg = agg.at[AG_WARM].add(warm_served.sum())
    agg = agg.at[AG_LAUNCHED].add(granted.sum())
    agg = agg.at[AG_LAT_SUM].add(
        (warm_served * (exec_s + sanitize)
         + promoted_req * (prom_cost + exec_s)
         + cold_spawn * (spawn_cost + exec_s)).sum())
    agg = agg.at[AG_QWAIT_SUM].add(leftover.sum() * dt_eff)
    agg = agg.at[AG_LAT_SUM].add(leftover.sum() * dt_eff)
    agg = agg.at[AG_EXEC_GB_S].add(
        ((warm_served * (exec_s + sanitize)
          + (promoted_req + cold_spawn) * exec_s) * exec_gb).sum())

    # any activity re-arms the cohort at the top of its schedule
    active_f = (served + granted) > 0
    edge = jnp.where(active_f, 0.0, edge)
    deadline = jnp.where(active_f, now + exec_s + d0, deadline)
    tier = jnp.where(active_f, T_WARM, tier)
    queued = leftover

    # ---- 4. idle GB-s at the cohort's tier footprint ---- #
    n = nw.sum(axis=1)
    nonidle_s = (warm_served * (exec_s + sanitize)
                 + promoted_req * (exec_s + prom_cost)
                 + cold_spawn * (exec_s + spawn_cost))
    idle_cs = jnp.clip(n * dt_eff - nonidle_s, 0.0, None)
    fr = _tier_select(jnp.tile(frac[None, :], (F, 1)), tier)
    idle_gb = idle_cs * mem_gb * fr
    agg = agg.at[AG_IDLE_WARM].add((idle_gb * (tier == T_WARM)).sum())
    agg = agg.at[AG_IDLE_PAUSED].add((idle_gb * (tier == T_PAUSED)).sum())
    agg = agg.at[AG_IDLE_SNAP].add((idle_gb * (tier == T_SNAP)).sum())

    fs = jnp.stack([tier, edge, deadline, queued, has_snap,
                    img.astype(f32)], axis=1)
    return nw, fs, free, agg, (cold, idle_gb)


def cluster_step_ref(nw, fs, free, arrivals, conc, now, fparam, promote,
                     dwell, ntier, frac, scal):
    """Aggregate-only view of :func:`cluster_step_full` — the signature the
    batch driver and the Pallas twin are parity-tested against."""
    nw, fs, free, agg, _ = cluster_step_full(
        nw, fs, free, arrivals, conc, now, fparam, promote, dwell, ntier,
        frac, scal)
    return nw, fs, free, agg


def ssm_scan_ref(u, delta, A, B, C, D, h0):
    """Mamba-1 selective-scan oracle (sequential over time, fp32 state).

    u, delta: (Batch, T, Din); A: (Din, N); B, C: (Batch, T, N); D: (Din,);
    h0: (Batch, Din, N).  Returns (y (Batch, T, Din), hT).
    Discretisation: h_t = exp(delta_t * A) * h_{t-1} + delta_t * B_t * u_t.
    """
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs           # (Bt, Din), (Bt, Din), (Bt, N), (Bt, N)
        decay = jnp.exp(d_t[..., None] * Af[None])          # (Bt, Din, N)
        h = decay * h + (d_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = (jnp.moveaxis(uf, 1, 0), jnp.moveaxis(df, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1) + uf * D.astype(jnp.float32)[None, None]
    return y.astype(u.dtype), hT
