"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family (2 layers, d_model <= 512, <= 4 experts) runs one forward /
train step and one prefill+decode step on CPU; output shapes + no NaNs."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ARCH_IDS, canonical_arch_id
from repro.models import registry

B, S = 2, 32


def _smoke_cfg(arch):
    return importlib.import_module(f"repro.configs.{canonical_arch_id(arch)}").SMOKE


def _batch(cfg, with_labels=True, seed=0):
    rng = np.random.default_rng(seed)
    d = {"tokens": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)}
    if with_labels:
        d["labels"] = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    if cfg.encoder is not None:
        d["frames"] = rng.standard_normal(
            (B, cfg.encoder.num_frames, cfg.encoder.d_model)).astype(np.float32)
    if cfg.vision is not None:
        d["image_embeds"] = rng.standard_normal(
            (B, cfg.vision.num_image_tokens, cfg.vision.d_embed)).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in d.items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_config_limits(arch):
    cfg = _smoke_cfg(arch)
    assert cfg.num_layers == 2
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = _smoke_cfg(arch)
    bundle = registry.build(cfg, max_seq=S)
    params = bundle.init(jax.random.key(0))
    from repro.training.optimizer import OptimizerConfig, init_opt_state
    from repro.training.train_loop import make_train_step
    step = jax.jit(make_train_step(bundle, OptimizerConfig(total_steps=10)))
    params, opt, metrics = step(params, init_opt_state(params), _batch(cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch} loss {loss}"
    assert loss < 2 * np.log(cfg.vocab_size) + 1
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(params)
               ), f"{arch}: non-finite params after step"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes_no_nans(arch):
    cfg = _smoke_cfg(arch)
    bundle = registry.build(cfg, max_seq=S + 8)
    params = bundle.init(jax.random.key(0))
    batch = _batch(cfg, with_labels=False)
    logits, caches, pos, _ = jax.jit(bundle.prefill)(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    dstep = jax.jit(bundle.decode_step)
    for i in range(3):
        logits, caches = dstep(params, caches, tok,
                               jnp.asarray(pos + i, jnp.int32))
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits))), f"{arch} step {i}"
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
