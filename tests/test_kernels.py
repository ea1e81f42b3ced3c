"""Kernel validation: Pallas (interpreted on the CPU, native on a TPU)
and the memory-bounded jnp paths vs the naive oracles in
``kernels/ref.py`` — shape/dtype sweeps with assert_allclose (assignment
requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import registry
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import block_counts, flash_attention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=3e-5)


ATTN_CASES = [
    # (b, sq, skv, hq, hkv, d, causal, window)
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 256, 256, 8, 8, 32, True, None),
    (2, 128, 128, 4, 1, 64, True, 64),      # SWA
    (1, 128, 384, 2, 2, 128, True, None),   # suffix-aligned prefill
    (1, 128, 128, 4, 4, 64, False, None),   # encoder (non-causal)
    (3, 256, 256, 6, 2, 48, True, 128),
    (1, 256, 256, 8, 2, 80, True, None),    # danube's G=4 and head_dim 80
    (1, 512, 512, 8, 2, 80, True, 256),     # the same, windowed
    # (..., v head dim, softmax scale): latent attention's expanded prefill
    (1, 256, 256, 4, 4, 192, True, None, 128, 0.114721),   # DeepSeek-V2
    (2, 128, 128, 4, 4, 96, True, None, 64, 0.25),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    b, sq, skv, hq, hkv, d, causal, window, *rest = case
    dv, scale = rest or (d, None)
    q = jnp.asarray(RNG.normal(size=(b, sq, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, dv)), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)
    got_pallas = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                        scale=scale)
    got_ref = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, impl="reference")
    np.testing.assert_allclose(np.asarray(got_pallas, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_ref, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


DECODE_CASES = [
    (2, 512, 8, 2, 64),
    (1, 1024, 4, 4, 128),
    (3, 512, 8, 1, 32),
    (1, 2048, 16, 4, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(case, dtype):
    b, s, hq, hkv, d = case
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), dtype)
    mask = jnp.asarray(RNG.random((b, s)) > 0.25)
    want = ref.decode_attention_ref(q, k, v, mask)
    got_p = decode_attention_pallas(q, k, v, mask)
    got_r = ops.decode_attention(q, k, v, mask, impl="reference")
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_r, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


SSM_CASES = [
    (2, 256, 256, 8),
    (1, 512, 512, 16),
    (2, 128, 1024, 4),
]


@pytest.mark.parametrize("case", SSM_CASES)
def test_ssm_scan_matches_oracle(case):
    bt, t, din, n = case
    u = jnp.asarray(RNG.normal(size=(bt, t, din)), jnp.float32)
    dt = jnp.asarray(RNG.random((bt, t, din)) * 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random((din, n)) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(bt, t, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(bt, t, n)), jnp.float32)
    Dm = jnp.asarray(RNG.normal(size=(din,)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(bt, din, n)), jnp.float32)
    want_y, want_h = ref.ssm_scan_ref(u, dt, A, Bm, Cm, Dm, h0)
    got_y, got_h = ssm_scan_pallas(u, dt, A, Bm, Cm, Dm, h0)
    np.testing.assert_allclose(got_y, want_y, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(got_h, want_h, atol=5e-5, rtol=5e-5)
    ref_y, ref_h = ops.ssm_scan(u, dt, A, Bm, Cm, Dm, h0, impl="reference")
    np.testing.assert_allclose(ref_y, want_y, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(ref_h, want_h, atol=5e-5, rtol=5e-5)


def test_ssm_step_matches_scan():
    """Decode recurrence == one step of the full scan."""
    bt, din, n = 2, 64, 8
    u = jnp.asarray(RNG.normal(size=(bt, 4, din)), jnp.float32)
    dt = jnp.asarray(RNG.random((bt, 4, din)) * 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random((din, n)) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(bt, 4, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(bt, 4, n)), jnp.float32)
    Dm = jnp.asarray(RNG.normal(size=(din,)), jnp.float32)
    h = jnp.zeros((bt, din, n), jnp.float32)
    ys = []
    for t in range(4):
        y, h = ops.ssm_step(u[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], Dm, h)
        ys.append(y)
    got = jnp.stack(ys, 1)
    want, want_h = ref.ssm_scan_ref(u, dt, A, Bm, Cm, Dm,
                                    jnp.zeros((bt, din, n), jnp.float32))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5, rtol=2e-5)


def test_flash_attention_pallas_vs_reference_chunked_grid():
    """Block-size sweep: different grid tilings agree."""
    q = jnp.asarray(RNG.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=None)
    for bq, bk in [(64, 64), (128, 32), (32, 128), (256, 256)]:
        got = flash_attention_pallas(q, k, v, causal=True, window=None,
                                     block_q=bq, block_k=bk)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5,
                                   err_msg=f"blocks {bq}x{bk}")


SKIP_CASES = [
    # (seq, window, poisoned keys, rows that never see them, computed/total)
    (1024, None, (512, 1024), (0, 512), (3, 4)),       # causal: 512x512
    (1536, 128, (0, 512), (1024, 1536), (5, 9)),       # and the window
]


@pytest.mark.parametrize("case", SKIP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_skips_masked_blocks(case, dtype):
    """Blocks that the mask hides whole are neither computed nor read:
    NaN values in them leave the rows that cannot see them exact (a
    computed, masked block would give 0 * NaN), at danube's head ratio."""
    s, window, (k0, k1), (r0, r1), counts = case
    assert block_counts(s, s, 8, 2, 80, dtype, causal=True,
                        window=window) == counts
    q = jnp.asarray(RNG.normal(size=(1, s, 8, 80)), dtype)
    k = jnp.asarray(RNG.normal(size=(1, s, 2, 80)), dtype)
    v = jnp.asarray(RNG.normal(size=(1, s, 2, 80)), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    got = flash_attention_pallas(q, k, v.at[:, k0:k1].set(jnp.nan),
                                 causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got[:, r0:r1], np.float32),
                               np.asarray(want[:, r0:r1], np.float32),
                               **_tol(dtype))


CHOICES = [
    # (backend, train, self_attn, seq, impl)
    ("tpu", False, True, 4096, "pallas"),
    ("cpu", False, True, 4096, "reference"),
    ("tpu", True, True, 4096, "reference"),       # no VJP
    ("tpu", False, False, 4096, "reference"),      # cross-attention
    ("tpu", False, True, 1500, "reference"),       # whisper's encoder
]


@pytest.mark.parametrize("case", CHOICES)
def test_choose_flash_impl(monkeypatch, case):
    backend, train, self_attn, seq, want = case
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.choose_flash_impl("auto", train=train, self_attn=self_attn,
                                 sq=seq, skv=seq) == want
    for explicit in ("reference", "pallas", "oracle"):
        assert ops.choose_flash_impl(explicit, train=train,
                                     self_attn=self_attn, sq=seq,
                                     skv=seq) == explicit


def test_prefill_attention_span_attributes(monkeypatch):
    from repro.config import ModelConfig
    from repro.models import attention

    # h2o-danube-1.8b's attention (chipbench/configs/h2o-danube-1.8b.json)
    cfg = ModelConfig(name="danube", family="dense", source="-",
                      num_layers=24, num_heads=32, num_kv_heads=8,
                      head_dim=80)
    cache = {"attention_kind": "gqa", "cache_bytes_per_token": 61440}
    assert attention.prefill_attention(cfg, 4096, 4096) == {
        "prefill_attention": "reference", "attention_blocks": "16/16",
        **cache}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.prefill_attention(cfg, 4096, 4096) == {
        "prefill_attention": "pallas", "attention_blocks": "36/64", **cache}


def test_latent_prefill_attention_span_attributes(monkeypatch):
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.models import attention

    # DeepSeek-V2-Lite at 8192 tokens: 27 latent caches of 512 + 64 bf16
    cache = {"attention_kind": "mla", "cache_bytes_per_token": 31104}
    assert attention.prefill_attention(CONFIG, 8192, None) == {
        "prefill_attention": "reference", "attention_blocks": "64/64",
        **cache}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.prefill_attention(CONFIG, 8192, None) == {
        "prefill_attention": "pallas", "attention_blocks": "136/256",
        **cache}


def _pallas_calls(fn, *args):
    # a fresh closure, so that no earlier trace of ``fn`` is reused
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args)).count("pallas_call")


def test_auto_attention_path_by_platform_and_gradient(monkeypatch):
    """``"auto"``: the CPU keeps the reference everywhere; on a TPU only
    prefill takes the kernel, one call per layer scan, and the loss's
    gradient still runs (through the reference)."""
    from repro.models import lm

    bundle = registry.build_arch("granite-3-2b", smoke=True, max_seq=128)
    cfg = bundle.cfg
    assert cfg.attention_impl == "auto"
    params = bundle.init(jax.random.key(0))
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 128)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}

    def loss(p):
        return lm.lm_loss(p, cfg, batch)[0]

    assert _pallas_calls(bundle.prefill, params, batch) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas_calls(bundle.prefill, params, batch) == 1
    assert _pallas_calls(jax.grad(loss), params) == 0
    grads = jax.grad(loss)(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


# --------------------------------------------------------------------------- #
# the decode expert kernel (kernels/moe_decode.py) against the dense pass
# --------------------------------------------------------------------------- #


def _moe_layer_cfg(experts, held, top_k, d, ff, act="swiglu",
                   dtype="float32"):
    from repro.config import MoEConfig, ModelConfig

    return ModelConfig(name="t", family="moe", source="t", num_layers=1,
                       d_model=d, num_heads=1, vocab_size=64, act=act,
                       dtype=dtype, param_dtype=dtype,
                       moe=MoEConfig(num_experts=experts, top_k=top_k,
                                     expert_ff=ff, norm_topk=False,
                                     experts_held=held))


def _on_the_kernel(monkeypatch):
    """``moe.expert_path`` as on a TPU: a call with no more pairs than
    held experts takes the kernel (interpreted here)."""
    from repro.models import moe

    path = moe.expert_path
    monkeypatch.setattr(moe, "expert_path",
                        lambda pairs, held: "pallas"
                        if path(pairs, held) == "dense" else path(pairs, held))


MOE_DECODE_CASES = {
    # name: (experts, held, top_k, d, ff, act, dtype, chosen): ``chosen``
    # is each token's experts by rank, None a random router
    "none_held": (8, 4, 2, 64, 128, "swiglu", "float32", [[6, 5]]),
    "one_held": (8, 4, 2, 64, 128, "swiglu", "float32", [[2, 6]]),
    "all_held": (8, 4, 2, 64, 128, "swiglu", "float32", [[3, 1]]),
    "same_expert": (8, 6, 2, 64, 128, "swiglu", "float32", [[1, 3], [1, 2]]),
    "pairs_equal_held": (8, 4, 2, 64, 128, "swiglu", "float32",
                         [[0, 1], [3, 2]]),
    "gelu": (8, 4, 2, 64, 256, "gelu", "float32", [[1, 7]]),
    # DeepSeek-V2-Lite's ratios at smoke widths: top-6 of 64, 16 held,
    # expert ff / d_model = 1408 / 2048
    "deepseek_ratio": (64, 16, 6, 256, 176, "swiglu", "bfloat16", None),
}


@pytest.mark.parametrize("name", MOE_DECODE_CASES)
def test_moe_decode_kernel_matches_dense_pass(name, monkeypatch):
    """The kernel's path of ``moe._experts`` (chosen held experts only)
    gives the dense gated pass's output and counts, save the experts
    read: those the pairs chose, not every held one."""
    from repro.models import moe

    experts, held, k, d, ff, act, dtype, chosen = MOE_DECODE_CASES[name]
    cfg = _moe_layer_cfg(experts, held, k, d, ff, act, dtype)
    p = moe.init_moe(jax.random.key(7), cfg)
    t = len(chosen) if chosen else 1
    x = RNG.normal(size=(t, d)).astype(np.float32)
    if chosen:
        # router weights under which each token ranks its chosen experts
        # first, in order, and the rest far below
        scores = np.full((t, experts), -8.0, np.float32)
        for i, row in enumerate(chosen):
            scores[i, row] = 4.0 - np.arange(len(row))
        p["router"] = jnp.asarray(np.linalg.pinv(x) @ scores)
    x = jnp.asarray(x, dtype)
    want, _, dense = moe._experts(x, p, cfg, first=0, held=held)
    _on_the_kernel(monkeypatch)
    got, _, kernel = moe._experts(x, p, cfg, first=0, held=held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(jnp.dtype(dtype)))
    routed = jax.lax.top_k(jnp.asarray(x, jnp.float32) @ p["router"], k)[1]
    read = len(set(np.asarray(routed).ravel().tolist()) & set(range(held)))
    if chosen:
        assert np.asarray(routed).tolist() == chosen
    assert int(dense["moe_experts_read"]) == held
    assert int(kernel["moe_experts_read"]) == read
    for key in ("moe_pairs_held", "moe_max_expert_tokens", "moe_dropped"):
        assert int(kernel[key]) == int(dense[key])
    assert int(kernel["moe_dropped"]) == 0


@pytest.mark.parametrize("block_ff", [None, 128])
def test_moe_decode_kernel_reads_each_slots_expert(block_ff):
    """The kernel alone, whole ff or tiled: slot s holds its token through
    layer ``layer``'s expert ``ids[s]``, two tokens of one expert read it
    in consecutive slots, and slots past ``n`` are zero."""
    from repro.kernels.moe_decode import held_experts_pallas

    reps, held, d, ff, t = 3, 5, 64, 256, 2
    wi, wg, wo = (jnp.asarray(RNG.normal(size=s) * s[-2] ** -0.5, jnp.float32)
                  for s in ((reps, held, d, ff), (reps, held, d, ff),
                            (reps, held, ff, d)))
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.float32)
    tok = jnp.asarray([0, 1, 0, 0], jnp.int32)
    ids = jnp.asarray([0, 3, 3, 3], jnp.int32)
    got = held_experts_pallas(x, tok, ids, 3, 1, wi, wo, wg,
                              block_ff=block_ff)
    for s in range(3):
        xs, e = x[tok[s]], ids[s]
        h = jax.nn.silu(xs @ wi[1, e]) * (xs @ wg[1, e])
        np.testing.assert_allclose(np.asarray(got[s]), np.asarray(h @ wo[1, e]),
                                   atol=1e-4, rtol=1e-4)
    assert not np.asarray(got[3]).any()


def _small_deepseek(unroll: bool):
    """DeepSeek-V2-Lite's smoke shape (latent attention, a leading dense
    layer, shared experts), in bfloat16, with 3 layers and an expert share
    in which a decode step's pairs are no more than the held experts:
    top-3 of 16, 8 held."""
    import dataclasses

    from repro.configs.deepseek_v2_lite import SMOKE

    return dataclasses.replace(
        SMOKE, num_layers=3, dtype="bfloat16", param_dtype="bfloat16",
        unroll_layers=unroll,
        moe=dataclasses.replace(SMOKE.moe, num_experts=16, top_k=3,
                                experts_held=8))


@pytest.mark.parametrize("unroll", [False, True], ids=["scan", "unrolled"])
def test_moe_decode_kernel_in_a_deepseek_decode_step(unroll, monkeypatch):
    """A decode step of a small DeepSeek-shaped model through its layer
    scan (or the unrolled loop): the kernel's logits are the dense pass's
    to bfloat16 tolerance, and no held pair is dropped."""
    from repro.models import moe

    cfg = _small_deepseek(unroll)
    assert cfg.moe.first_dense == 1 and cfg.moe.shared_ff
    bundle = registry.build(cfg, max_seq=24)
    params = bundle.init(jax.random.key(3))
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab_size, (1, 17)), jnp.int32)
    _, caches, pos, _ = bundle.prefill(params, {"tokens": tokens[:, :16]})
    step = moe.counted_decode(bundle.decode_step)
    args = (params, caches, tokens[:, 16], jnp.asarray(pos, jnp.int32),
            moe.zero_counts())
    want, _, dense = jax.jit(step)(*args)
    assert _pallas_calls(step, *args) == 0
    _on_the_kernel(monkeypatch)
    got, _, kernel = jax.jit(moe.counted_decode(bundle.decode_step))(*args)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 * scale, rtol=0)
    layers = cfg.num_layers - cfg.moe.first_dense
    assert int(kernel["moe_layers"]) == int(dense["moe_layers"]) == layers
    assert int(kernel["moe_dropped"]) == int(dense["moe_dropped"]) == 0
    assert int(dense["moe_experts_read"]) == 8 * layers
    assert int(kernel["moe_pairs_held"]) == int(dense["moe_pairs_held"]) > 0
    assert 0 < int(kernel["moe_experts_read"]) <= int(
        kernel["moe_pairs_held"])
    assert _pallas_calls(moe.counted_decode(bundle.decode_step), *args) > 0


def test_expert_path_by_platform_and_pairs(monkeypatch):
    """The kernel on a single TPU for no more pairs than held experts;
    the dense pass on the CPU or under a sharding context; the grouped
    matmuls for more pairs.  ``moe_dispatch`` names the decode path."""
    from repro import sharding
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.models import moe

    assert [moe.expert_path(p, 16) for p in (6, 16, 17)] == [
        "dense", "dense", "sorted"]
    assert moe.start_attrs(CONFIG, 1)["moe_dispatch"] == \
        "sorted ragged_dot; dense over held at decode"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [moe.expert_path(p, 16) for p in (6, 16, 17)] == [
        "pallas", "pallas", "sorted"]
    assert moe.start_attrs(CONFIG, 1) == {
        "experts_held": "16/64",
        "moe_dispatch": "sorted ragged_dot; pallas over chosen held at decode"}
    assert moe.start_attrs(CONFIG, 3)["moe_dispatch"] == \
        "sorted ragged_dot; sorted ragged_dot at decode"
    monkeypatch.setattr(sharding, "current_rules_and_mesh",
                        lambda: ({"expert": "model"}, None))
    assert moe.expert_path(6, 16) == "dense"
