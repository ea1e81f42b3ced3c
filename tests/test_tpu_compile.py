"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e and refuses what the chip would refuse (misaligned
Pallas blocks, primitives Mosaic cannot lower, programs that do not fit
HBM).  These tests compile the serving path at granite-3-2b's and
DeepSeek-V2-Lite's published widths, the batch simulator's device program
at ``batch_dense64`` shapes, and both Pallas kernels (prefill flash
attention, the decode expert pass) at real widths natively
(``interpret=False``).

Nothing runs, so these say nothing about results or times.  The topology
is described inside a fixture, never at import: only one process may load
the TPU runtime at a time, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9          # 16 GB of HBM per v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache; keep it off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


def test_granite_full_width_serving_fits_one_chip(one_chip):
    """The engine's prefill and decode_step at published widths, as
    InferenceEngine compiles them for max_seq=128, batch 1."""
    from repro.models import registry

    bundle = registry.build_arch("granite-3-2b", smoke=False, max_seq=128)
    assert (bundle.cfg.num_layers, bundle.cfg.d_model) == (40, 2048)
    params = _on(one_chip, bundle.params_spec())
    batch = {"tokens": _spec(one_chip, (1, 128), jnp.int32)}
    prefill = jax.jit(bundle.prefill).lower(params, batch).compile()
    caches = _on(one_chip, jax.eval_shape(
        lambda p, b: bundle.prefill(p, b)[1], params, batch))
    decode = jax.jit(bundle.decode_step).lower(
        params, caches, _spec(one_chip, (1,), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    for compiled in (prefill, decode):
        args = compiled.memory_analysis().argument_size_in_bytes
        assert args > 5.0e9                  # ~5.07 GB of bf16 params
        assert _device_bytes(compiled) < V5E_HBM_BYTES


def _batch_dense64_shapes():
    """The batch tables of ``batch_dense64``: every cell shares one
    scenario shape, so one built cell gives the grid's shapes."""
    from repro.core import batchsim
    from repro.experiments import registry, runner

    cells = registry.get_sweep("batch_dense64").scenarios()
    t = batchsim.build_tables(cells[:1], trace_fn=runner.build_trace)
    names = ("nw", "fs", "free", "arrivals", "conc", "fparam", "promote",
             "dwell", "ntier", "frac", "scal")
    return len(cells), {k: getattr(t, k).shape[1:] for k in names}


def test_deepseek_v2_lite_serving_fits_one_chip(one_chip, monkeypatch):
    """The engine's prefill and decode programs of DeepSeek-V2-Lite's
    one-chip expert share (16 of 64 experts) at an 8192-token prompt and 32
    decoded tokens: latent attention, the dropless grouped expert matmuls,
    and decode's expert kernel, which reads the layer scan's expert stacks
    in place: no program op makes a copy of one layer's 16 experts."""
    from repro.models import moe, registry

    bundle = registry.build_arch("deepseek-v2-lite", max_seq=8192 + 32)
    params = _on(one_chip, bundle.params_spec())
    batch = {"tokens": _spec(one_chip, (1, 8192), jnp.int32)}
    prefill = jax.jit(bundle.prefill).lower(params, batch).compile()
    caches = _on(one_chip, jax.eval_shape(
        lambda p, b: bundle.prefill(p, b)[1], params, batch))
    assert jax.tree.leaves(caches)[0].shape[2] == 8192 + 32
    # the path a TPU takes (``moe.expert_path``), compiled natively
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode = jax.jit(moe.counted_decode(bundle.decode_step)).lower(
        params, caches, _spec(one_chip, (1,), jnp.int32),
        _spec(one_chip, (), jnp.int32),
        _on(one_chip, jax.eval_shape(moe.zero_counts))).compile()
    for compiled in (prefill, decode):
        args = compiled.memory_analysis().argument_size_in_bytes
        assert args > 9.8e9                  # 9.82 GB of bf16 params
        assert _device_bytes(compiled) < V5E_HBM_BYTES
    text = decode.as_text()
    assert "moe_held_experts" in text
    assert not re.search(r"= bf16\[16,(2048,1408|1408,2048)\]", text)


def test_batchsim_ref_scan_compiles_at_batch_dense64(one_chip):
    from repro.core import batchsim

    c, shapes = _batch_dense64_shapes()
    f32 = jnp.float32
    args = [_spec(one_chip, (c, *shapes[k]), f32)
            for k in ("nw", "fs", "free", "arrivals", "conc")]
    now_t = _spec(one_chip, (shapes["arrivals"][0],), f32)
    tables = [_spec(one_chip, (c, *shapes[k]), f32)
              for k in ("fparam", "promote", "dwell", "ntier", "frac",
                        "scal")]
    compiled = batchsim._scan_driver().lower(*args, now_t, *tables).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _flash():
    from repro.kernels.flash_attention import flash_attention_pallas

    bf = jnp.bfloat16                # granite-3-2b: 32 q heads, 8 kv, d 64
    return (lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
            [((1, 512, 32, 64), bf), ((1, 512, 8, 64), bf),
             ((1, 512, 8, 64), bf)])


def _flash_danube():
    from repro.kernels.flash_attention import flash_attention_pallas

    bf = jnp.bfloat16     # h2o-danube-1.8b's prefill: 32 q heads, 8 kv, d 80
    return (lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                                   window=4096,
                                                   interpret=False),
            [((1, 4096, 32, 80), bf), ((1, 4096, 8, 80), bf),
             ((1, 4096, 8, 80), bf)])


def _flash_mla():
    from repro.kernels.flash_attention import flash_attention_pallas

    bf = jnp.bfloat16     # DeepSeek-V2-Lite's expanded prefill: 16 heads,
    return (lambda q, k, v: flash_attention_pallas(   # qk 192, v 128
        q, k, v, causal=True, scale=0.114721, interpret=False),
            [((1, 8192, 16, 192), bf), ((1, 8192, 16, 192), bf),
             ((1, 8192, 16, 128), bf)])


def _moe_decode():
    from repro.kernels.moe_decode import held_experts_pallas

    bf = jnp.bfloat16     # DeepSeek-V2-Lite's decode: 26 MoE layers, 16
    i32 = jnp.int32       # held experts of 2048 x 1408, top-6, one token
    return (lambda x, tok, ids, n, layer, wi, wo, wg: held_experts_pallas(
        x, tok, ids, n, layer, wi, wo, wg, interpret=False),
            [((1, 2048), bf), ((6,), i32), ((6,), i32), ((), i32), ((), i32),
             ((26, 16, 2048, 1408), bf), ((26, 16, 1408, 2048), bf),
             ((26, 16, 2048, 1408), bf)])


@pytest.mark.parametrize("kernel", [_flash, _flash_danube, _flash_mla,
                                    _moe_decode],
                         ids=["flash_attention", "flash_attention_danube",
                              "flash_attention_mla", "moe_decode"])
def test_pallas_kernel_compiles_natively(one_chip, kernel):
    fn, arg_shapes = kernel()
    args = [_spec(one_chip, shape, dtype) for shape, dtype in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
