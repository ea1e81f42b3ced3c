"""The transformer next-invocation-gap quantile forecaster.

A small ``models/transformer.py`` stack (2 layers, d_model 32, float32)
behind the repo's own training loop: feature windows project into the
stack, the last (most recent) position reads out through a 3-unit head,
and monotone softplus offsets turn it into ordered ``(q05, q50, q95)``
quantiles of ``log1p(next gap)``.  Training minimises the pinball
(quantile) loss at those levels — the calibrated (p05, p95) window is
exactly what ``PredictivePrewarm``/``PredictiveLadder`` consume from the
histogram predictor today, so the checkpoint drops into the same
policies unchanged.

Checkpoints ride ``training/checkpoint.py`` with the model dims and the
:class:`~repro.learn.features.FeatureConfig` persisted in ``extra``;
``resolve_checkpoint`` implements the discovery order (explicit path >
``REPRO_FORECASTER_CKPT`` > ``checkpoints/forecaster.npz``) used by the
serving-side predictor and the policy catalog.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.learn.features import FeatureConfig
from repro.models import layers, transformer
from repro.models.registry import ModelBundle
from repro.training import checkpoint
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import TrainResult, train

CHECKPOINT_ENV = "REPRO_FORECASTER_CKPT"
DEFAULT_CHECKPOINT = os.path.join("checkpoints", "forecaster.npz")
CHECKPOINT_VERSION = 1


def resolve_checkpoint(path: Optional[str] = None) -> Optional[str]:
    """Explicit path > env var > repo-default; None when nothing exists."""
    for cand in (path, os.environ.get(CHECKPOINT_ENV), DEFAULT_CHECKPOINT):
        if cand and os.path.exists(cand):
            return cand
    return None


def model_config(*, num_layers: int = 2, d_model: int = 32,
                 num_heads: int = 4, d_ff: int = 64) -> ModelConfig:
    return ModelConfig(
        name="gap-forecaster", family="dense",
        source="repro.learn in-repo forecaster (arXiv 2504.11338 lineage)",
        num_layers=num_layers, d_model=d_model, num_heads=num_heads,
        d_ff=d_ff, dtype="float32", param_dtype="float32", remat=False)


def init_forecaster(rng, cfg: ModelConfig, feat: FeatureConfig):
    r = jax.random.split(rng, 3)
    return {
        "inp": {"w": layers.dense_init(r[0], feat.n_features, cfg.d_model,
                                       cfg.param_dtype),
                "b": jnp.zeros((cfg.d_model,), cfg.param_dtype)},
        "stack": transformer.init_stack(r[1], cfg),
        "norm": layers.norm_init(cfg.d_model, cfg.norm, cfg.param_dtype),
        "head": {"w": layers.dense_init(r[2], cfg.d_model, 3,
                                        cfg.param_dtype),
                 "b": jnp.zeros((3,), cfg.param_dtype)},
    }


def apply_forecaster(params, x, cfg: ModelConfig, *, train: bool = False):
    """x: (B, W, n_features) -> ordered (B, 3) log-gap quantiles."""
    h = x @ params["inp"]["w"] + params["inp"]["b"]
    q_pos = jnp.arange(x.shape[1])
    h, _, _, _ = transformer.stack_full(params["stack"], h, cfg,
                                        q_pos=q_pos, train=train)
    h = layers.norm_apply(params["norm"], h[:, -1, :], cfg.norm)
    raw = h @ params["head"]["w"] + params["head"]["b"]
    q50 = raw[:, 0]
    q05 = q50 - jax.nn.softplus(raw[:, 1])
    q95 = q50 + jax.nn.softplus(raw[:, 2])
    return jnp.stack([q05, q50, q95], axis=1)


def pinball_loss(q, y, quantiles) -> jax.Array:
    """Mean quantile (pinball) loss: q (B, Q), y (B,)."""
    taus = jnp.asarray(quantiles, jnp.float32)[None, :]
    err = y[:, None] - q
    return jnp.mean(jnp.maximum(taus * err, (taus - 1.0) * err))


def make_bundle(cfg: ModelConfig, feat: FeatureConfig) -> ModelBundle:
    def loss_fn(params, batch):
        q = apply_forecaster(params, batch["x"], cfg, train=True)
        loss = pinball_loss(q, batch["y"], feat.quantiles)
        tokens = jnp.asarray(batch["y"].shape[0] * feat.window, jnp.float32)
        return loss, {"loss": loss, "tokens": tokens}

    def unsupported(*_a, **_k):
        raise NotImplementedError("the forecaster has no decode path")

    return ModelBundle(cfg=cfg, shape=None, max_seq=feat.window, window=None,
                       init=lambda rng: init_forecaster(rng, cfg, feat),
                       loss=loss_fn, prefill=unsupported,
                       decode_step=unsupported)


def train_forecaster(data_iter: Iterator[Dict[str, Any]], *, steps: int,
                     cfg: Optional[ModelConfig] = None,
                     feat: Optional[FeatureConfig] = None,
                     lr: float = 3e-3, log_every: int = 50,
                     log_fn=print) -> Tuple[Any, TrainResult, ModelConfig,
                                            FeatureConfig]:
    cfg = cfg or model_config()
    feat = feat or FeatureConfig()
    bundle = make_bundle(cfg, feat)
    opt = OptimizerConfig(lr=lr, warmup_steps=min(100, steps // 10 + 1),
                          total_steps=steps, weight_decay=0.01)
    result = train(bundle, data_iter, steps=steps, opt_cfg=opt,
                   log_every=log_every,
                   log_fn=log_fn or (lambda *_a, **_k: None))
    return result.final_params, result, cfg, feat


def save_forecaster(path: str, params, cfg: ModelConfig,
                    feat: FeatureConfig, *,
                    metrics: Optional[dict] = None) -> int:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    extra = {
        "version": CHECKPOINT_VERSION,
        "model": {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
                  "num_heads": cfg.num_heads, "d_ff": cfg.d_ff},
        "features": feat.to_dict(),
        "metrics": metrics or {},
    }
    return checkpoint.save(path, params, extra=extra)


def load_forecaster(path: str) -> Tuple[Any, ModelConfig, FeatureConfig,
                                        dict]:
    params, extra = checkpoint.restore(path)
    if extra.get("version") != CHECKPOINT_VERSION:
        raise ValueError(f"{path}: forecaster checkpoint version "
                         f"{extra.get('version')!r} != {CHECKPOINT_VERSION}")
    cfg = model_config(**extra["model"])
    feat = FeatureConfig.from_dict(extra["features"])
    return params, cfg, feat, extra
