"""Logical-axis sharding rules (divisibility-aware), GSPMD constraints.

Models annotate activations with *logical* axis names via :func:`logical`;
outside a mesh context this is a no-op (CPU smoke tests see one device), and
inside ``use_rules(...)`` each logical name maps to mesh axes and becomes a
``with_sharding_constraint``.

Rule construction (:func:`make_rules`) checks divisibility per architecture:
an axis is only sharded if the dimension is divisible by the mesh-axis size —
e.g. heads shard over ``model`` only when ``H % 16 == 0`` (qwen2.5's 40 heads
and whisper's 20 do not), vocab only when divisible (granite's 49155 is not).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

_state = threading.local()


def _current():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_rules(rules: Dict[str, Axis], mesh: Mesh):
    prev = _current()
    _state.ctx = (dict(rules), mesh)
    try:
        yield
    finally:
        _state.ctx = prev


def current_rules_and_mesh():
    """(rules, mesh) if a rules context is active, else None — used by the
    explicit shard_map paths (expert-parallel MoE)."""
    return _current()


def logical(x, names: Sequence[Optional[str]]):
    """Constrain array ``x`` whose dims carry logical names (None = any)."""
    ctx = _current()
    if ctx is None:
        return x
    rules, mesh = ctx
    spec = P(*(rules.get(n) if n else None for n in names))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def spec_for(names: Sequence[Optional[str]]) -> P:
    ctx = _current()
    if ctx is None:
        return P()
    rules, _ = ctx
    return P(*(rules.get(n) if n else None for n in names))


# --------------------------------------------------------------------------- #
# rule construction per (arch config, input shape, mesh)
# --------------------------------------------------------------------------- #


def _axsize(mesh: Mesh, ax: Axis) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        return mesh.shape[ax]
    n = 1
    for a in ax:
        n *= mesh.shape[a]
    return n


def make_rules(cfg, shape, mesh: Mesh, *, seq_shard: Optional[bool] = None) -> Dict[str, Axis]:
    """Build logical->mesh rules for one (arch, shape, mesh) combination.

    Logical axes used across the codebase:
      batch       activation batch / MoE group dim
      seq         sequence dim of activations & KV caches
      embed       d_model dim of activations (sharded only as fallback TP)
      heads/kv_heads  attention head dims (params & activations & caches)
      ff          FFN hidden dim
      qkv         fused q/k/v output dim of attention params
      vocab       embedding/unembedding vocab dim
      expert      MoE expert dim
      layers      stacked-layer leading dim (never sharded)
      fsdp        weight-shard dim for non-TP dims of params
    """
    data_axes: Axis = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    model: Axis = "model" if "model" in mesh.shape else None
    dsize = _axsize(mesh, data_axes)
    msize = _axsize(mesh, model)

    def fits(dim: int, ax: Axis) -> Axis:
        return ax if (ax is not None and dim % _axsize(mesh, ax) == 0 and dim >= _axsize(mesh, ax)) else None

    rules: Dict[str, Axis] = {}
    rules["layers"] = None
    # batch: decode long_500k has batch 1 -> unshardable; shard seq instead.
    rules["batch"] = fits(shape.global_batch, data_axes)
    shard_seq = seq_shard if seq_shard is not None else (rules["batch"] is None)
    rules["seq"] = fits(shape.seq_len, data_axes) if shard_seq else None
    # tensor-parallel dims
    rules["heads"] = fits(cfg.num_heads, model)
    rules["kv_heads"] = fits(cfg.num_kv_heads, model)
    rules["ff"] = fits(max(cfg.d_ff, cfg.moe.expert_ff if cfg.moe else 0), model)
    rules["qkv"] = fits(cfg.q_dim, model) if rules["heads"] is not None else None
    # vocab: GSPMD pads uneven shardings, and the vocab dim only appears in
    # matmul outputs / gathers (no reshapes), so divisibility is not required
    # — sharding 49155 16-ways (pad to 49168) beats a 13 GB/device logits
    # buffer.  (Reshape-involved dims — heads, experts — stay divisible.)
    rules["vocab"] = model if (model and cfg.vocab_size >= msize) else None
    # ... but jit *arguments* (the embed/unembed params) need even shards:
    rules["vocab_param"] = fits(cfg.vocab_size, model)
    rules["expert"] = fits(cfg.moe.num_experts, model) if cfg.moe else None
    # embed: shard activations on d_model over model axis only when heads are
    # NOT sharded (fallback TP for 40/20/14-head archs); params' d_model dim
    # is the fsdp dim.
    rules["embed"] = None
    rules["fsdp"] = fits(cfg.d_model, data_axes) if data_axes else None
    # §Perf iteration 1 (EXPERIMENTS.md): decode re-gathers FSDP-sharded
    # weights EVERY token (collective term 0.079s/token on starcoder2).
    # Inference wants weights TP-stationary: replicate over data axes when
    # the TP-sharded params fit HBM (collective_s -> 0.0008s, 95x better).
    # Iteration 1b (measured): EXCLUDE MoE archs — the dispatch einsum
    # touches every local expert's weights each step, so replication turns
    # into 16x more per-step HBM weight reads (jamba decode bound
    # 0.035s -> 0.058s, qwen3 0.027s -> 0.063s).
    if shape.kind == "decode" and msize and cfg.moe is None:
        itemsize = 2 if cfg.param_dtype == "bfloat16" else 4
        n_params = cfg.param_count()
        per_chip_gb = n_params * itemsize / msize / 2**30
        if per_chip_gb <= 8.0:
            rules["fsdp"] = None
    # inner SSM dims
    if cfg.ssm is not None:
        d_in = cfg.ssm.expand * cfg.d_model
        rules["ssm_inner"] = fits(d_in, model)
    if cfg.xlstm is not None:
        d_in = int(cfg.xlstm.proj_factor * cfg.d_model)
        rules["xlstm_inner"] = fits(d_in, model)
    # §Perf iteration 3: context-parallel attention fallback.  When heads
    # are not divisible by the model axis (qwen2.5's 40, whisper's 20,
    # internvl2's 14), GSPMD replicates the whole attention block across
    # `model` (measured: useful-FLOPs 0.31 on qwen25 train_4k).  Instead,
    # shard the attention block's tokens over `model` on the sequence dim —
    # per-layer cost: two (B,S,d) reshards + a small GQA KV all-gather.
    # Measured: big win for train (qwen25: 49.7s -> 13.6s bound, useful
    # 0.31 -> 0.95) but a REGRESSION for prefill (4.2s -> 6.0s: forward-only
    # replication waste is smaller than the reshard cost) -> train only.
    rules["attn_seq"] = (fits(shape.seq_len, model)
                         if (rules["heads"] is None and shape.kind == "train")
                         else None)
    # §Perf iteration 6: sequence-parallel residual stream for training
    # (Megatron-SP shape): the remat-saved per-layer residual stack is the
    # train-memory bound (starcoder2: 30 GB/device bf16); sharding the
    # residual seq dim over `model` cuts it 16x (peak 93.8 -> 20.6 GiB on
    # the emulated backend) for +2.9s of gather collectives.  Pure-attention
    # archs only: EP-MoE assumes model-replicated tokens, and recurrent
    # time-scans cannot consume a seq-sharded xs.
    if (shape.kind == "train" and model is not None
            and cfg.moe is None and cfg.ssm is None and cfg.xlstm is None
            and shape.seq_len % msize == 0):
        rules["seq"] = model
    # decode KV caches: batch over data; the (long) sequence dim over model —
    # the only way a 32k×128 cache fits per-chip HBM (DESIGN.md §4).
    if shape.kind == "decode":
        rules["cache_batch"] = fits(shape.global_batch, data_axes)
        rules["cache_seq"] = fits(shape.seq_len, model)
    return rules


def named_sharding(mesh: Mesh, *axes: Axis) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))
