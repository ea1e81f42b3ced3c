"""Model-level correctness: prefill/decode cache consistency (the serving
engine's core invariant), ring-buffer SWA caches, MoE dispatch vs dense
reference, parameter counts vs model names."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ARCH_IDS, canonical_arch_id, get_config
from repro.models import registry

PREFIX, TOTAL = 8, 16
B = 2

CONSISTENCY_ARCHS = ["granite3_2b", "h2o_danube3_4b", "jamba_v01_52b",
                     "xlstm_125m", "qwen3_moe_30b_a3b", "whisper_large_v3",
                     "internvl2_1b", "deepseek_v2_lite"]


def _smoke(arch):
    return importlib.import_module(f"repro.configs.{canonical_arch_id(arch)}").SMOKE


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode logits == full-sequence forward logits.

    This is the cache-correctness invariant: a warm container serving
    token-by-token must produce exactly what a fresh full forward would.
    """
    cfg = _smoke(arch)
    bundle = registry.build(cfg, max_seq=TOTAL)
    params = bundle.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, TOTAL)), jnp.int32)

    full_batch = {"tokens": tokens}
    pre_batch = {"tokens": tokens[:, :PREFIX]}
    if cfg.encoder is not None:
        frames = jnp.asarray(rng.standard_normal(
            (B, cfg.encoder.num_frames, cfg.encoder.d_model)), jnp.float32)
        full_batch["frames"] = frames
        pre_batch["frames"] = frames
    if cfg.vision is not None:
        img = jnp.asarray(rng.standard_normal(
            (B, cfg.vision.num_image_tokens, cfg.vision.d_embed)), jnp.float32)
        full_batch["image_embeds"] = img
        pre_batch["image_embeds"] = img

    # ground truth: full forward over all TOTAL tokens
    if cfg.encoder is not None:
        from repro.models import encdec
        enc_out = encdec.encode(params, cfg, frames)
        want_logits, _, _ = encdec._dec_full(params, cfg, tokens, enc_out)
    else:
        from repro.models import lm
        want_logits, _, _ = lm.lm_forward(params, cfg, full_batch,
                                          window=bundle.window)

    logits, caches, pos, _ = jax.jit(bundle.prefill)(params, pre_batch)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(want_logits[:, pos - 1], np.float32),
                               atol=2e-3, rtol=2e-3)

    dstep = jax.jit(bundle.decode_step)
    # VLM prefill consumed image tokens: teacher-force from the text stream
    for i in range(PREFIX, TOTAL):
        tok = tokens[:, i - (cfg.vision.num_image_tokens if cfg.vision else 0)] \
            if cfg.vision else tokens[:, i]
        logits, caches = dstep(params, caches, tok, jnp.asarray(pos, jnp.int32))
        pos += 1
        want = want_logits[:, pos - 1]
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"{arch} step {i}")


def test_swa_ring_cache_equals_full_cache():
    """A ring cache of width W must give the same decode logits as a full
    cache under a width-W sliding window."""
    import dataclasses
    cfg = _smoke("h2o_danube3_4b")           # window=64 in smoke
    assert cfg.sliding_window == 64
    total = 80                                # > window: ring wraps
    bundle = registry.build(cfg, max_seq=total)
    params = bundle.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, total)), jnp.int32)

    from repro.models import lm
    want_logits, _, _ = lm.lm_forward(params, cfg, {"tokens": tokens},
                                      window=64)

    logits, caches, pos, _ = bundle.prefill(params, {"tokens": tokens[:, :72]})
    # ring cache must be window-sized, not total-sized
    k0 = jax.tree.leaves(caches)[0]
    assert 64 in k0.shape, f"expected ring cache of width 64, got {k0.shape}"
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits[:, 71]),
                               atol=2e-3, rtol=2e-3)
    for i in range(72, total):
        logits, caches = bundle.decode_step(params, caches, tokens[:, i],
                                            jnp.asarray(i, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits[:, i]),
                                   atol=2e-3, rtol=2e-3, err_msg=f"pos {i}")


def _moe_cfg(**kw):
    from repro.config import MoEConfig, ModelConfig
    moe = dict(num_experts=8, top_k=3, expert_ff=64, shared_ff=48)
    moe.update(kw)
    return ModelConfig(name="t", family="moe", source="t", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=0,
                       vocab_size=64, dtype="float32", param_dtype="float32",
                       moe=MoEConfig(**moe))


def _moe_dense(p, x, cfg):
    """Every token through every expert, weighted by its top-k weights
    (renormalised or not), plus the shared FFN."""
    from repro.models import layers
    m = cfg.moe
    t = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(t @ p["router"], -1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", t, p["wi"])
    h = jax.nn.silu(h) * jnp.einsum("td,edf->tef", t, p["wg"])
    out_all = jnp.einsum("tef,efd->ted", h, p["wo"])
    want = layers.mlp_apply(p["shared"], t, "swiglu")
    for k in range(m.top_k):
        sel = jnp.take_along_axis(out_all, top_i[:, k][:, None, None], 1)[:, 0]
        want = want + sel * top_w[:, k][:, None]
    return want.reshape(x.shape)


@pytest.mark.parametrize("tokens", [16, 2])      # sorted; dense over held
@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_dispatch_matches_dense_reference(skewed, norm_topk, tokens):
    """Sorted dropless dispatch == explicit per-token expert mixing, also
    when the router sends every token to one expert (a capacity would drop
    most of them there): every pair routed is computed."""
    from repro.models import moe as moe_mod

    cfg = _moe_cfg(norm_topk=norm_topk)
    p = moe_mod.init_moe(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, tokens // 2, 32)), jnp.float32)
    if skewed:
        x = jnp.abs(x)
        p["router"] = p["router"].at[:, 5].set(4.0)
    got, aux, counts = moe_mod.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_moe_dense(p, x, cfg)),
                               atol=1e-5, rtol=1e-5)
    assert float(aux) > 0
    assert int(counts["moe_pairs_held"]) == tokens * 3   # all held: every pair
    assert int(counts["moe_dropped"]) == 0
    if skewed:
        assert int(counts["moe_max_expert_tokens"]) == tokens


def test_moe_expert_shares_add_up_to_the_layer():
    """The four shares of an expert-parallel layer (experts 0-1, ..., 6-7
    of 8), each computing its own experts' part for the tokens routed to
    them (as a rank of ``_moe_ffn_ep`` does, from its offset), add up to
    the uncut layer, the shared FFN counted once."""
    from repro.models import moe as moe_mod

    cfg = _moe_cfg(norm_topk=False)
    p = moe_mod.init_moe(jax.random.key(3), cfg)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 32)),
                    jnp.float32)
    whole, _, counts = moe_mod.moe_ffn(p, x, cfg)
    total = moe_mod.layers.mlp_apply(p["shared"], x, "swiglu")[0]
    pairs = 0
    for first in (0, 2, 4, 6):
        ps = {k: (v[first:first + 2] if k in ("wi", "wg", "wo") else v)
              for k, v in p.items()}
        y, _, c = moe_mod._experts(x[0], ps, cfg, first=first, held=2)
        assert int(c["moe_dropped"]) == 0
        total, pairs = total + y, pairs + int(c["moe_pairs_held"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole[0]),
                               atol=1e-5, rtol=1e-5)
    assert pairs == int(counts["moe_pairs_held"]) == 24 * 3


@pytest.mark.parametrize("arch,lo,hi", [
    ("starcoder2_15b", 14e9, 17e9),
    ("jamba_v01_52b", 49e9, 54e9),
    ("qwen25_14b", 13e9, 16e9),
    ("whisper_large_v3", 1.4e9, 1.8e9),
    ("h2o_danube3_4b", 3.5e9, 4.5e9),
    ("internvl2_1b", 0.4e9, 0.9e9),
    ("qwen3_moe_30b_a3b", 29e9, 32e9),
    ("xlstm_125m", 0.12e9, 0.18e9),
    ("arctic_480b", 450e9, 500e9),
    ("granite3_2b", 2.2e9, 2.9e9),
    ("deepseek_v2_lite", 4.9e9, 4.92e9),     # 16 of its 64 experts held
])
def test_param_counts_match_model_names(arch, lo, hi):
    n = get_config(arch).param_count()
    assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B outside [{lo / 1e9}, {hi / 1e9}]"


def test_active_params_match_moe_names():
    assert 2.9e9 <= get_config("qwen3_moe_30b_a3b").param_count(True) <= 3.8e9
    assert 13e9 <= get_config("arctic_480b").param_count(True) <= 19e9


def test_yarn_frequencies_and_softmax_scale_match_hand_values():
    """DeepSeek-V2-Lite's 64 rotated dims: the correction range is dims
    10..23 (floor 10.47, ceil 22.51), the extrapolated frequency below it,
    the frequency divided by 40 above it, a linear ramp between;
    the softmax scale is 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2."""
    from repro.models import layers, mla

    cfg = get_config("deepseek_v2_lite")
    f = layers.yarn_freqs(64, 10000.0, cfg.rope_scaling)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(f[16], 0.01 * (1 - ramp) + 0.01 / 40 * ramp,
                               rtol=1e-6)
    assert mla.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
