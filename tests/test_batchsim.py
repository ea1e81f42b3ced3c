"""Batch-driver tests: Pallas-vs-ref kernel parity (randomized fixtures;
interpreted on the CPU, native on a TPU), batch-vs-scalar tolerance
spot-checks, unsupported-policy rejection, the runner's batch plumbing
(summary schema, events= rejection, ``max_cells`` guard, progress
callbacks), and the trace-cache LRU regression."""
import numpy as np
import pytest

from repro.core import batchsim
from repro.core.batchsim import (BatchUnsupportedPolicy, build_tables,
                                 ledgers_from_agg, run_tables, simulate_batch,
                                 spot_check)
from repro.experiments import runner
from repro.experiments.spec import ClusterSpec, Scenario, WorkloadSpec
from repro.experiments.sweep import Sweep
from repro.kernels import ref as R
from repro.kernels.cluster_step import cluster_sim_pallas


def _cell(name="t/batch", *, rate=8.0, horizon=60.0, fns=4, seed=3,
          policy="provider_short", ttl=None, workers=2):
    return Scenario(
        name=name,
        workload=WorkloadSpec("poisson",
                              {"rate": rate, "horizon": horizon,
                               "num_functions": fns}, seed=seed),
        policy=policy, keepalive_ttl=ttl,
        cluster=ClusterSpec(num_workers=workers,
                            worker_memory_mb=8192.0))


# --------------------------------------------------------------------------- #
# Pallas kernel vs pure-jnp reference driver
# --------------------------------------------------------------------------- #
def _random_tables(rng, *, C=3, F=4, W=2, K=4, T=16):
    """Randomized array-state in the kernel's own layout: arrivals with
    bursts, mixed tiers/edges/deadlines, partially-used workers."""
    f32 = np.float32
    nw = (rng.integers(0, 3, (C, F, W))).astype(f32)
    fs = np.zeros((C, F, R.FS_N), f32)
    fs[:, :, R.FS_TIER] = rng.integers(1, 5, (C, F))
    fs[:, :, R.FS_EDGE] = rng.integers(0, K - 1, (C, F))
    fs[:, :, R.FS_DEADLINE] = rng.uniform(0.0, 6.0, (C, F))
    fs[:, :, R.FS_QUEUED] = rng.integers(0, 2, (C, F))
    arrivals = rng.poisson(0.7, (C, T, F)).astype(f32)
    conc = np.maximum(arrivals, rng.integers(0, 3, (C, T, F))).astype(f32)
    fparam = np.zeros((C, F, R.FP_N), f32)
    fparam[:, :, R.FP_MEM_MB] = rng.choice([256.0, 512.0, 1024.0], (C, F))
    fparam[:, :, R.FP_EXEC_S] = rng.uniform(0.05, 0.4, (C, F))
    fparam[:, :, R.FP_SVC] = np.maximum(
        np.floor(0.5 / fparam[:, :, R.FP_EXEC_S]), 1.0)
    fparam[:, :, R.FP_MEM_GB] = fparam[:, :, R.FP_MEM_MB] / 1024.0
    fparam[:, :, R.FP_EXEC_GB] = fparam[:, :, R.FP_MEM_GB]
    promote = np.sort(rng.uniform(0.01, 2.0, (C, F, 5)))[:, :, ::-1].copy()
    dwell = np.full((C, F, K), R.BIG_TIME, f32)
    dwell[:, :, :2] = rng.uniform(2.0, 20.0, (C, F, 2))
    ntier = np.zeros((C, F, K), f32)
    ntier[:, :, 0] = rng.choice([R.T_PAUSED, R.T_DEAD], (C, F))
    frac = np.tile(np.array([0.0, 0.02, 0.1, 0.3, 1.0], f32), (C, 1))
    scal = np.zeros((C, R.SC_N), f32)
    scal[:, R.SC_DT] = 0.5
    scal[:, R.SC_HORIZON] = T * 0.5 - rng.uniform(0.0, 2.0, C)
    free = np.full((C, W), 8192.0, f32)
    free -= (nw * fparam[:, :, R.FP_MEM_MB][:, :, None]).sum(axis=1)
    return (nw, fs, free.astype(f32), arrivals, conc,
            promote.astype(f32), dwell, ntier, frac, scal, fparam)


def _ref_drive(nw, fs, free, arrivals, conc, fparam, promote, dwell,
               ntier, frac, scal):
    import jax
    import jax.numpy as jnp

    step = jax.vmap(R.cluster_step_ref,
                    in_axes=(0, 0, 0, 0, 0, None, 0, 0, 0, 0, 0, 0))
    C, T, F = arrivals.shape
    agg = jnp.zeros((C, R.AG_N), jnp.float32)
    nw, fs, free = jnp.asarray(nw), jnp.asarray(fs), jnp.asarray(free)
    for t in range(T):
        nw, fs, free, d = step(nw, fs, free, arrivals[:, t], conc[:, t],
                               jnp.float32(t * 0.5), fparam, promote,
                               dwell, ntier, frac, scal)
        agg = agg + d
    return map(np.asarray, (nw, fs, free, agg))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_matches_ref_on_random_state(seed):
    rng = np.random.default_rng(seed)
    (nw, fs, free, arrivals, conc, promote, dwell, ntier, frac, scal,
     fparam) = _random_tables(rng)
    ref = list(_ref_drive(nw, fs, free, arrivals, conc, fparam, promote,
                          dwell, ntier, frac, scal))
    pal = cluster_sim_pallas(nw, fs, free, arrivals, conc, fparam, promote,
                             dwell, ntier, frac, scal, chunk=8)
    for name, a, b in zip(("nw", "fs", "free", "agg"), ref, pal):
        np.testing.assert_allclose(np.asarray(b), a, rtol=1e-4, atol=1e-2,
                                   err_msg=f"pallas/{name} diverged")


def test_pallas_matches_ref_on_built_tables():
    cells = [_cell(seed=s, ttl=ttl)
             for s, ttl in ((1, 20.0), (2, None), (3, 90.0))]
    tables = build_tables(cells)
    ref = run_tables(tables, kernel="ref")
    pal = run_tables(tables, kernel="pallas")
    for name, a, b in zip(("nw", "fs", "agg"), ref, pal):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-2,
                                   err_msg=f"pallas/{name} diverged")


def test_unknown_kernel_rejected():
    tables = build_tables([_cell()])
    with pytest.raises(ValueError, match="unknown batch kernel"):
        run_tables(tables, kernel="tpu")


# --------------------------------------------------------------------------- #
# batch vs scalar: the tolerance contract
# --------------------------------------------------------------------------- #
def test_spot_check_within_tolerance_small_cells():
    # horizon >> TTL, several arrivals/s per function: the regime the
    # tolerance contract is documented for (docs/batchsim.md)
    cells = [_cell(seed=1, ttl=30.0, rate=16.0, horizon=180.0, fns=8,
                   workers=4),
             _cell(seed=2, policy="tiered_fixed", rate=16.0, horizon=180.0,
                   fns=8, workers=4)]
    rows = spot_check(cells)
    assert len(rows) == 2
    for r in rows:
        assert r.ok, (f"{r.name}: cold {r.cold_rate_sim}/{r.cold_rate_batch}"
                      f" idle {r.idle_gb_s_sim}/{r.idle_gb_s_batch}")


def test_batch_ledger_matches_qos_summary_schema():
    from repro.core.simulator import simulate

    sc = _cell(seed=5, ttl=45.0)
    batch = runner.run(sc, "batch")
    sim = simulate(sc.trace(), sc.suite(), cost_model=sc.cost_model(),
                   cfg=sc.sim_config())
    bs, ss = batch.summary(), sim.summary()
    assert set(bs) == set(ss)
    # count/GB-s fields are real numbers; percentile fields are NaN
    assert np.isfinite(bs["cold_start_frequency"])
    assert np.isfinite(bs["idle_gb_s"])
    assert np.isnan(bs["latency_p95_s"])


def test_prewarm_policy_is_rejected():
    with pytest.raises(BatchUnsupportedPolicy, match="prewarm"):
        simulate_batch([_cell(policy="prewarm_ewma")])


def test_batch_driver_rejects_event_capture():
    from repro.core.events import EventLog

    with pytest.raises(ValueError, match="per-invocation events"):
        runner.run(_cell(), "batch", events=EventLog())


# --------------------------------------------------------------------------- #
# run_sweep plumbing: batch grids, progress, max_cells guard
# --------------------------------------------------------------------------- #
def _sweep(n_ttl=3):
    return Sweep(name="t/grid", base=_cell(),
                 axes={"keepalive_ttl":
                       tuple(15.0 * (i + 1) for i in range(n_ttl))},
                 driver="batch")


def test_run_sweep_batch_yields_every_cell_with_progress():
    calls = []
    rows = list(runner.run_sweep(
        _sweep(), "batch",
        progress=lambda i, n, sc, s: calls.append((i, n))))
    assert len(rows) == 3
    assert calls == [(1, 3), (2, 3), (3, 3)]
    for sc, s in rows:
        assert 0.0 <= s["cold_start_frequency"] <= 1.0


def test_run_sweep_max_cells_guard():
    with pytest.raises(ValueError, match="max_cells"):
        list(runner.run_sweep(_sweep(), "batch", max_cells=2))
    # at the limit it runs
    assert len(list(runner.run_sweep(_sweep(), "batch", max_cells=3))) == 3


def test_batch_and_sim_sweeps_agree_on_grid_order():
    sw = _sweep()
    batch_names = [sc.name for sc, _ in runner.run_sweep(sw, "batch")]
    sim_names = [sc.name for sc, _ in runner.run_sweep(sw, "sim")]
    assert batch_names == sim_names


# --------------------------------------------------------------------------- #
# trace-cache LRU regression
# --------------------------------------------------------------------------- #
def _wl_cell(seed):
    return Scenario(name=f"t/lru{seed}",
                    workload=WorkloadSpec("poisson",
                                          {"rate": 1.0, "horizon": 2.0},
                                          seed=seed),
                    policy="provider_short")


def test_trace_cache_is_true_lru(monkeypatch):
    monkeypatch.setattr(runner, "_TRACE_CACHE", type(
        runner._TRACE_CACHE)())
    monkeypatch.setattr(runner, "_TRACE_CACHE_MAX", 3)
    t0 = runner.build_trace(_wl_cell(0))
    for s in (1, 2):
        runner.build_trace(_wl_cell(s))
    # hit refreshes recency: 0 becomes most-recent, 1 is now oldest
    assert runner.build_trace(_wl_cell(0)) is t0
    runner.build_trace(_wl_cell(3))            # evicts 1, not 0
    assert runner.build_trace(_wl_cell(0)) is t0
    keys = list(runner._TRACE_CACHE)
    assert len(keys) == 3
    assert not any('"seed": 1' in k for k in keys)


def test_trace_cache_hit_returns_same_object():
    a = runner.build_trace(_wl_cell(11))
    b = runner.build_trace(_wl_cell(11))
    assert a is b
