"""Counts of latent attention with routed and shared experts against hand
counts of DeepSeek-V2-Lite's one-chip share, its readers without a trace,
and the program against the plain reference at the smoke size."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, counts, counts_mla_moe, harness, weights
from chipbench.references import mla_moe
from chipbench.tests.test_chipbench_cells import smoke_model

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
CELL = "deepseek-v2-lite-ep4.doc-qa-8k"
READERS = ("mla_moe_serve_mfu", "mla_moe_prefill_roofline",
           "mla_moe_decode_roofline", "expert_load_imbalance")

ATTN = 2048 * 16 * 192 + 2048 * 576 + 2048 * 2048       # wq, kv_a, wo
KV_B = 512 * 16 * 256
EXPERT = 3 * 2048 * 1408
# per token, out of attention's scores: layer 0's dense FFN; each of the 26
# MoE layers' shared experts, router and 6 x 16/64 = 1.5 routed experts
FFN = 3 * 2048 * 10944 + 26 * (3 * 2048 * 2816 + 2048 * 64 + 1.5 * EXPERT)


def _model():
    return json.loads((CONFIGS / "deepseek-v2-lite-ep4.json").read_text())


def test_param_counts_held_and_whole():
    m = _model()
    assert counts_mla_moe.param_count(m) == 4_910_345_728
    assert counts_mla_moe.param_count(m, whole=True) == 15_706_484_224
    # bf16, the 26 routers' 2048 x 64 in float32
    assert counts_mla_moe.param_bytes(m) == m["param_bytes"] == \
        2 * 4_910_345_728 + 2 * 26 * 2048 * 64


def test_prefill_hand_count():
    m = _model()
    keys = 8192 * 8193 // 2                       # causal: query i, i+1 keys
    want = (2 * 8192 * (27 * (ATTN + KV_B) + FFN)
            + 27 * 2 * 16 * (192 + 128) * keys + 2 * 2048 * 102400)
    ops, moved = counts_mla_moe.prefill(m, 8192)
    assert ops == int(want)
    # every held weight but the embedding table, 8192 embedding rows, and
    # 576 latent values a position written in each layer
    assert moved == (m["param_bytes"] - 2 * 102400 * 2048 + 2 * 8192 * 2048
                     + 27 * 8192 * 576 * 2)
    _, bound = counts.seconds_at_peak(ops, moved, "TPU v5 lite")
    assert bound == "compute"


def test_decode_hand_count():
    m = _model()
    pos = 8200
    absorbed = 2 * 16 * 512 * (128 + 128) + 2 * 16 * (1024 + 64) * (pos + 1)
    want = 2 * (27 * ATTN + FFN) + 27 * absorbed + 2 * 2048 * 102400
    ops, moved = counts_mla_moe.decode(m, pos)
    assert ops == int(want)
    # held weights less the table and the 14.5 unrouted experts a layer,
    # one embedding row, the latent cache read and one slot written
    unread = 2 * (102400 * 2048 + 26 * 14.5 * EXPERT)
    assert moved == int(m["param_bytes"] - unread + 2 * 2048
                        + 27 * (pos + 1) * 576 * 2)
    t, bound = counts.seconds_at_peak(ops, moved, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(moved / 819e9)


@pytest.mark.parametrize("name", READERS)
def test_readers_need_a_trace(name):
    cell = harness.load_cell(CELL)
    assert name in {metric.name for metric in cell.metrics}
    run = harness.Run(cell, 1, 51.0, "TPU v5 lite", [], 10.0, trace=None)
    assert harness.read_metric(name, run) is None


def test_latent_decode_matches_the_reference():
    """Prefill, then absorbed decode through the latent cache, at the
    program's smoke size: each step's logits are the plain reference's
    full forward pass's at that position."""
    from repro.models import registry

    model = smoke_model(_model())
    cfg = harness.program_config(model)
    prompt, steps = 24, 6
    bundle = registry.build(cfg, max_seq=prompt + steps)
    params = weights.make(jax.eval_shape(bundle.init, weights.key(0)), 5)
    tokens = np.random.default_rng(6).integers(
        0, cfg.vocab_size, prompt + steps).astype(np.int32)
    logits, caches, pos, routed = jax.jit(bundle.prefill)(
        params, {"tokens": jnp.asarray(tokens[None, :prompt])})
    got = [logits[0]]
    decode = jax.jit(bundle.decode_step)
    for i in range(steps - 1):
        logits, caches = decode(params, caches, jnp.asarray(tokens[None, pos + i]),
                                jnp.asarray(pos + i, jnp.int32))
        got.append(logits[0])
    want = mla_moe.logits_at(params, model, tokens,
                             np.arange(prompt - 1, prompt + steps - 1))
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4, rtol=1e-4)
    # one MoE layer of the smoke size: 24 tokens x top-2 over 4 experts,
    # of which 1 is held
    assert 0 < int(routed["moe_pairs_held"]) <= 48


@pytest.mark.parametrize("fault", (None,) + control.FAULTS)
def test_control_and_faults_are_not_correct(fault, monkeypatch):
    """At the smoke size, the program reads inside the cell's limits, and
    the float8 control and each planted fault read above one of them."""
    from chipbench.tests.test_chipbench_cells import SEED, smoke_cell

    monkeypatch.setattr(harness, "CHECKED_REQUESTS", 3)
    cell = smoke_cell(CELL)
    if fault is None:
        served = harness.serve(cell, SEED, 1.5, False, t_process=0.0)
        assert all(c.ok for c in harness.compare(cell, SEED, served))
        checks = harness.compare(cell, SEED, served, fp8=True)
    else:
        with control.planted(fault):
            served = harness.serve(cell, SEED, 1.5, False, t_process=0.0)
        checks = harness.compare(cell, SEED, served)
    assert not all(c.ok for c in checks)
    (err,) = [c for c in checks if c.name == "logit_err"]
    assert err.value > err.limit
