"""Real-execution serving tests: measured cold starts, snapshot restore,
scale-to-zero, fusion (one compile for a chain), router QoS accounting."""
import numpy as np
import pytest

from repro.core.lifecycle import Phase
from repro.serving.engine import (InferenceEngine, SnapshotStore, StartPath,
                                  fuse_chain)
from repro.serving.router import FunctionDef, ServerlessRouter


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    return SnapshotStore(str(tmp_path_factory.mktemp("snaps")))


def test_cold_start_breakdown_measured(store):
    e = InferenceEngine("granite-3-2b", smoke=True, max_seq=16, batch=1,
                        store=store)
    bd = e.cold_start()
    assert bd.seconds[Phase.CODE_INIT] > 0.01          # real XLA compile
    assert bd.seconds[Phase.DEPS_LOAD] > 0.0
    out, stats = e.serve(np.ones((1, 16), np.int32), decode_steps=2)
    assert out.shape == (1, 2)
    assert stats.prefill_s > 0


def test_snapshot_restore_much_faster(store):
    # max_seq differs from the other granite tests so this engine's cache
    # key is unique: the "full" cold start must pay a real compile, not hit
    # the executable cached by a previous test through the shared store
    e = InferenceEngine("granite-3-2b", smoke=True, max_seq=24, batch=1,
                        store=store)
    full = e.cold_start()
    e.shutdown()
    restored = e.cold_start(from_snapshot=True)
    # executable cache + param snapshot: restore must be >=3x faster
    assert full.total / restored.total >= 3.0
    out, _ = e.serve(np.ones((1, 24), np.int32), decode_steps=2)
    assert np.all(out >= 0)


def test_snapshot_params_roundtrip(store):
    import jax
    e = InferenceEngine("xlstm-125m", smoke=True, max_seq=16, batch=1,
                        store=store)
    e.cold_start()
    before = jax.tree.leaves(e.params)[0].copy()
    e.shutdown()
    e.cold_start(from_snapshot=True)
    after = jax.tree.leaves(e.params)[0]
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


def test_snapshot_store_roundtrips_bf16_params(tmp_path):
    """Published configs keep bfloat16 params; the snapshot must bring
    them back bit-exact with their dtype."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import granite3_2b
    from repro.models import registry
    from repro.training import checkpoint
    from repro.training.checkpoint import tree_equal

    cfg = dataclasses.replace(granite3_2b.SMOKE, param_dtype="bfloat16",
                              dtype="bfloat16")
    params = registry.build(cfg, max_seq=16).init(jax.random.key(0))
    st = SnapshotStore(str(tmp_path))
    st.save_params("bf16", params)
    back = checkpoint.place(st.read_params("bf16"))
    assert tree_equal(params, back)
    assert {x.dtype for x in jax.tree.leaves(back)} == {jnp.dtype(jnp.bfloat16)}


def test_fusion_single_compile(store):
    engines = []
    for arch in ("granite-3-2b", "h2o-danube-3-4b"):
        e = InferenceEngine(arch, smoke=True, max_seq=16, batch=1, store=store)
        e.cold_start()
        engines.append(e)
    fused, compile_s = fuse_chain(engines, decode_steps=2)
    assert compile_s > 0
    import jax.numpy as jnp
    out = fused({"tokens": jnp.ones((1, 16), jnp.int32)})
    assert out.shape == (1, 16)


def test_router_scale_to_zero_and_qos(store):
    r = ServerlessRouter(ttl_s=0.0, use_snapshots=True, store=store)
    r.register(FunctionDef("granite", "granite-3-2b", max_seq=16,
                           decode_steps=2))
    _, rec1 = r.invoke("granite")
    assert rec1.cold
    # ttl=0 -> scaled to zero immediately -> next call cold again (restore)
    _, rec2 = r.invoke("granite")
    assert rec2.cold
    # the second start restored the param snapshot and found the compiled
    # programs in the store (the module store may hold them already, so
    # the first start's path is not asserted)
    (replica,) = r.pool.replicas.values()
    assert replica.engine.last_start == StartPath(from_snapshot=True,
                                                  executable_hit=True)
    s = r.summary()
    assert s["cold_starts"] == 2
    assert s["requests"] == 2


def test_router_warm_reuse(store):
    r = ServerlessRouter(ttl_s=300.0, use_snapshots=True, store=store)
    r.register(FunctionDef("g", "granite-3-2b", max_seq=16, decode_steps=2))
    _, rec1 = r.invoke("g")
    _, rec2 = r.invoke("g")
    assert rec1.cold and not rec2.cold
    assert rec2.latency < rec1.latency


def test_engine_decodes_past_its_prompt_without_a_window():
    """A model without a window keeps every decoded token's keys: the
    engine's cache has room for the prompt and its decode steps, so its
    tokens and last logits are the full forward pass's."""
    import jax.numpy as jnp

    from repro.models import lm

    e = InferenceEngine("granite-3-2b", smoke=True, max_seq=16, batch=1,
                        decode_steps=6)
    e.cold_start()
    assert e.bundle.window is None and e.cache_len == 22
    prompt = np.random.default_rng(5).integers(
        0, e.bundle.cfg.vocab_size, (1, 16)).astype(np.int32)
    out, stats = e.serve(prompt, decode_steps=6)
    seq = jnp.asarray(np.concatenate([prompt, out], axis=1))
    want = np.asarray(lm.lm_forward(e.params, e.bundle.cfg,
                                    {"tokens": seq})[0][0])
    np.testing.assert_array_equal(out[0], want[15:21].argmax(-1))
    np.testing.assert_allclose(stats.logits[0], want[21], atol=2e-4,
                               rtol=2e-4)


def test_danube_cache_shapes_unchanged_at_a_full_window():
    """h2o-danube-1.8b at a 4096-token prompt, the width of its window,
    keeps its 4096-slot ring after prefill and in decode, with room for
    16 decoded tokens asked for."""
    import jax

    from repro.config import ModelConfig
    from repro.models import registry

    cfg = ModelConfig(name="danube", family="dense", source="-",
                      num_layers=24, d_model=2560, num_heads=32,
                      num_kv_heads=8, head_dim=80, d_ff=6912,
                      vocab_size=32000, sliding_window=4096)
    bundle = registry.build(cfg, max_seq=4096 + 16)
    params = bundle.params_spec()
    tokens = jax.ShapeDtypeStruct((1, 4096), np.int32)
    caches = jax.eval_shape(lambda p, t: bundle.prefill(p, {"tokens": t})[1],
                            params, tokens)
    ring = (24, 1, 4096, 8, 80)
    assert [a.shape for a in jax.tree.leaves(caches)] == [ring, ring]
    assert [a.shape for a in jax.tree.leaves(
        bundle.decode_caches_spec(1))] == [ring, ring]


def test_latent_attention_expert_engine_reports_its_layout():
    """DeepSeek-V2-Lite at the smoke size: ``engine.start.compile`` names
    the latent cache and the experts held, and each prefill its routing."""
    from repro import spans

    e = InferenceEngine("deepseek-v2-lite", smoke=True, max_seq=16, batch=1,
                        decode_steps=4)
    spans.reset()
    e.cold_start()
    e.serve(np.ones((1, 16), np.int32), decode_steps=4)
    (compile_,) = [s for s in spans.records()
                   if s.name == "engine.start.compile"]
    (prefill,) = [s for s in spans.records()
                  if s.name == "engine.prefill_run"]
    (decode,) = [s for s in spans.records() if s.name == "engine.decode"]
    cfg = e.bundle.cfg                # 2 layers, latent 64 + rotated 32, f32
    assert compile_.attrs["attention_kind"] == "mla"
    assert compile_.attrs["cache_bytes_per_token"] == 2 * (64 + 32) * 4
    assert compile_.attrs["experts_held"] == "1/4"
    # a decode step's 2 pairs are more than the 1 held expert
    assert compile_.attrs["moe_dispatch"] == \
        "sorted ragged_dot; sorted ragged_dot at decode"
    assert prefill.attrs["moe_dropped"] == 0
    # one expert layer: 16 tokens, top-2 of 4 experts, 1 held
    assert 0 <= prefill.attrs["moe_max_expert_tokens"] \
        == prefill.attrs["moe_pairs_held"] <= 16
    assert prefill.attrs["moe_layers"] == 1
    assert cfg.moe.top_k == 2 and cfg.moe.first_dense == 1
    # the 4 steps' routing, fetched once after the last
    assert decode.attrs["moe_decode_layers"] == 4
    assert decode.attrs["moe_decode_dropped"] == 0
    assert 0 <= decode.attrs["moe_decode_experts_read"] \
        <= decode.attrs["moe_decode_pairs_held"] <= 4
