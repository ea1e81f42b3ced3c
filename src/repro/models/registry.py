"""Model registry: config -> ModelBundle (init / loss / prefill / decode).

The bundle is the single entry surface used by the serving engine, the
trainer, the smoke tests, and the multi-pod dry-run.  ``input_specs`` returns
``jax.ShapeDtypeStruct`` stand-ins (weak-type-correct, shardable, zero
allocation) for every model input of a given assigned input shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import InputShape, ModelConfig, get_config
from repro.models import encdec, lm, transformer


def resolve_window(cfg: ModelConfig, shape: Optional[InputShape]) -> Optional[int]:
    """Sliding-window width for this (arch, shape).

    Jamba's attention layers switch to a 4096 window at the long_500k shape
    (standard Jamba long-context serving); SWA archs use their config window
    everywhere.
    """
    if cfg.sliding_window is not None:
        return cfg.sliding_window
    if cfg.family == "hybrid" and shape is not None and shape.seq_len > 262_144:
        return 4096
    return None


@dataclasses.dataclass
class ModelBundle:
    cfg: ModelConfig
    shape: Optional[InputShape]
    max_seq: int
    window: Optional[int]
    init: Callable[[jax.Array], Any]
    loss: Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Dict]]
    # (last logits, decode caches, next position, routing counts)
    prefill: Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Any, int, Dict]]
    decode_step: Callable[[Any, Any, jax.Array, jax.Array], Tuple[jax.Array, Any]]

    # ----------------------------------------------------------------- #
    def params_spec(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def decode_caches_spec(self, batch: int):
        return jax.eval_shape(
            lambda: _init_caches(self.cfg, batch, self.max_seq, self.window))

    def input_specs(self) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the shape's entry point."""
        assert self.shape is not None
        return input_specs(self.cfg, self.shape)


def _init_caches(cfg, batch, max_seq, window):
    if cfg.encoder is not None:
        per = transformer.period_len(cfg)
        n_rep = cfg.num_layers  # encdec stacks all decoder layers
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        f = cfg.encoder.num_frames
        return {
            "self": {"k": jnp.zeros((n_rep, batch, max_seq, hkv, hd), dt),
                     "v": jnp.zeros((n_rep, batch, max_seq, hkv, hd), dt)},
            "cross": {"k": jnp.zeros((n_rep, batch, f, hkv, hd), dt),
                      "v": jnp.zeros((n_rep, batch, f, hkv, hd), dt)},
        }
    return transformer.init_decode_caches(cfg, batch, max_seq, window=window)


def build(cfg: ModelConfig, shape: Optional[InputShape] = None,
          *, max_seq: Optional[int] = None) -> ModelBundle:
    window = resolve_window(cfg, shape)
    mseq = max_seq or (shape.seq_len if shape else 2048)

    # named functions, so their jitted programs read ``jit_init``,
    # ``jit_prefill``, ``jit_decode_step`` in a profiler trace
    if cfg.encoder is not None:
        def init(rng):
            return encdec.init_encdec(rng, cfg, max_seq=mseq)

        def loss(p, b):
            return encdec.encdec_loss(p, cfg, b)

        def prefill(p, b):
            return encdec.encdec_prefill(p, cfg, b, max_seq=mseq)

        def decode_step(p, c, t, pos):
            return encdec.encdec_decode_step(p, cfg, c, t, pos)
    else:
        def init(rng):
            return lm.init_lm(rng, cfg, max_seq=mseq)

        def loss(p, b):
            return lm.lm_loss(p, cfg, b, window=window)

        def prefill(p, b):
            return lm.lm_prefill(p, cfg, b, max_seq=mseq, window=window)

        def decode_step(p, c, t, pos):
            return lm.lm_decode_step(p, cfg, c, t, pos, window=window)

    return ModelBundle(cfg=cfg, shape=shape, max_seq=mseq, window=window,
                       init=init, loss=loss, prefill=prefill,
                       decode_step=decode_step)


def build_arch(arch: str, shape: Optional[InputShape] = None, *, smoke: bool = False,
               max_seq: Optional[int] = None) -> ModelBundle:
    import importlib
    from repro.config import canonical_arch_id
    mod = importlib.import_module(f"repro.configs.{canonical_arch_id(arch)}")
    cfg = mod.SMOKE if smoke else mod.CONFIG
    return build(cfg, shape, max_seq=max_seq)


# --------------------------------------------------------------------------- #
# input specs (dry-run stand-ins)
# --------------------------------------------------------------------------- #


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStructs for the given entry point — no device allocation."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    act = jnp.dtype(cfg.dtype)
    window = resolve_window(cfg, shape)

    def batch_specs(with_labels: bool) -> Dict[str, Any]:
        d: Dict[str, Any] = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if with_labels:
            d["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        if cfg.encoder is not None:
            d["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.num_frames, cfg.encoder.d_model), act)
        if cfg.vision is not None:
            d["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.vision.num_image_tokens, cfg.vision.d_embed), act)
        return d

    if shape.kind == "train":
        return {"batch": batch_specs(True)}
    if shape.kind == "prefill":
        return {"batch": batch_specs(False)}
    # decode: one new token against a seq_len cache
    caches = jax.eval_shape(lambda: _init_caches(cfg, b, s, window))
    return {
        "caches": caches,
        "token": jax.ShapeDtypeStruct((b,), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }
