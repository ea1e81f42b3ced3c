"""End-to-end behaviour tests: the paper's headline claims, exercised
through the whole stack (train -> checkpoint -> serve -> mitigate)."""
import numpy as np
import pytest

from repro.core.metrics import QoSLedger
from repro.core.policies import suite
from repro.core.simulator import simulate
from repro.core.workload import azure_like, flash_crowd, poisson


def test_rq1_cold_starts_degrade_every_qos_parameter():
    """RQ1: with cold starts (vs eliminated), latency/SLA all worse."""
    tr = poisson(rate=0.02, horizon=4000.0, num_functions=2, seed=0)
    cold = simulate(tr, suite("cold_always")).summary(sla_latency_s=0.5)
    warm = simulate(tr, suite("periodic_ping")).summary(sla_latency_s=0.5)
    assert cold["latency_p50_s"] > 5 * warm["latency_p50_s"]
    assert cold["sla_violation_rate"] > warm["sla_violation_rate"]
    # cost trade-off is two-sided: cold saves idle GB-s but pays exec time
    assert warm["idle_gb_s"] > cold["idle_gb_s"]


def test_rq2_concurrency_flash_crowd_causes_cold_burst():
    tr = flash_crowd(base_rate=0.5, spike_rate=40.0, horizon=120.0,
                     spike_len=5.0, seed=1)
    led = simulate(tr, suite("provider_default"))
    recs = led.records
    t0 = 0.5 * 120.0
    spike_colds = [r for r in recs if r.cold and t0 <= r.arrival < t0 + 5.0]
    pre_colds = [r for r in recs if r.cold and 20.0 <= r.arrival < t0]
    assert len(spike_colds) > 5 * max(len(pre_colds), 1)
    # and contention makes those cold starts slower than a lone one
    lone = min(r.startup.total for r in recs if r.cold)
    worst = max(r.startup.total for r in spike_colds)
    assert worst > lone


def test_taxonomy_orderings_hold_on_azure_mix():
    """The qualitative Table-4/5 orderings on a realistic mix."""
    tr = azure_like(1200.0, num_functions=30, seed=4)
    res = {n: simulate(tr, suite(n)).summary() for n in
           ["cold_always", "provider_default", "snapshot_restore",
            "faascache", "prewarm_histogram", "beyond_combo"]}
    # CSL: snapshot restore cuts the cold-start latency under same τ.
    # (Azure-mix functions are mostly rare: ~half the colds are FIRST-EVER
    # starts with no snapshot yet, so the aggregate improvement is bounded;
    # the matched per-start >=3x claim is validated in test_policies.py.)
    assert (res["snapshot_restore"]["cold_p50_s"]
            < 0.9 * res["provider_default"]["cold_p50_s"])
    # CSF: faascache never does worse on cost than fixed TTL
    assert res["faascache"]["cost_usd"] <= res["provider_default"]["cost_usd"]
    # beyond-paper combo: at-least-as-good p99, strictly cheaper
    assert (res["beyond_combo"]["latency_p99_s"]
            <= res["provider_default"]["latency_p99_s"])
    assert res["beyond_combo"]["cost_usd"] < res["provider_default"]["cost_usd"]
    # everything beats always-cold on latency
    for n, s in res.items():
        if n != "cold_always":
            assert s["latency_p50_s"] < res["cold_always"]["latency_p50_s"]


def test_train_checkpoint_serve_loop(tmp_path):
    """The full lifecycle: train a model, checkpoint it, and serve with the
    checkpoint as the cold-start snapshot image."""
    import jax
    from repro.config import InputShape, get_config, reduced
    from repro.data import pipeline
    from repro.models import registry
    from repro.serving.engine import InferenceEngine, SnapshotStore
    from repro.training import checkpoint
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_loop import train

    cfg = reduced(get_config("granite-3-2b"), d_model=128)
    bundle = registry.build(cfg, max_seq=32)
    it = pipeline.batches(cfg, InputShape("t", 32, 2, "train"))
    res = train(bundle, it, steps=8, log_every=0, log_fn=lambda s: None,
                opt_cfg=OptimizerConfig(lr=5e-3, warmup_steps=2, total_steps=8))
    ck = str(tmp_path / "model.npz")
    checkpoint.save(ck, res.final_params)

    store = SnapshotStore(str(tmp_path / "snaps"))
    e = InferenceEngine("granite-3-2b", smoke=True, max_seq=32, batch=1,
                        store=store)
    e.cold_start()
    # checkpoint doubles as the snapshot image format
    trained, _ = checkpoint.restore(ck)
    store.save_params("trained_model", trained)
    loaded = checkpoint.place(store.read_params("trained_model"))
    assert checkpoint.tree_equal(trained, loaded)
    out, stats = e.serve(np.ones((1, 32), np.int32), decode_steps=4)
    assert out.shape == (1, 4)
    assert stats.decode_s > 0
