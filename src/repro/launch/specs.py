"""PartitionSpec assignment for parameters, optimizer state, batches, and
decode caches — driven by the divisibility-checked logical rules of
``repro.sharding.make_rules``.

Layout summary (DESIGN.md §4):
  params     TP dims (q_dim when heads divide, d_ff, experts, vocab-when-
             divisible, SSM/xLSTM inner dims) over ``model``; the d_model dim
             over ``data`` (+``pod``) as the FSDP shard; stacked-layer leading
             dims unsharded.
  batch      (B, S) over (pod, data) on B.
  caches     B over data axes, long KV sequence dim over ``model``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import keystr


def _p(rules, *names):
    return P(*(rules.get(n) if n else None for n in names))


def param_pspec(path: str, ndim: int, rules: Dict[str, Any]) -> P:
    """PartitionSpec for one parameter leaf addressed by its tree path."""
    stacked = path.startswith(("blocks", "enc_blocks", "dec_blocks"))
    lead = (None,) if stacked else ()

    def mk(*names):
        spec = lead + tuple(rules.get(n) if n else None for n in names)
        assert len(spec) == ndim, (path, ndim, spec)
        return P(*spec)

    last = path.rsplit("/", 1)[-1]
    # top-level tables
    if path == "embed":
        return P(rules.get("vocab_param"), rules.get("fsdp"))
    if path == "unembed":
        return P(rules.get("fsdp"), rules.get("vocab_param"))
    if path == "pos":
        return P(None, rules.get("fsdp"))
    if path == "proj":
        return P(None, None)
    if "norm" in path or last in ("scale", "bias"):
        return P(*([None] * ndim))

    if "/moe/" in path and "/shared/" not in path:
        if last == "router":
            return mk("fsdp", None)
        if last in ("wi", "wg"):
            return mk("expert", "fsdp", None)
        if last == "wo":
            return mk("expert", None, "fsdp")
    if "/ffn/" in path or "/shared/" in path:
        if last in ("wi", "wg"):
            return mk("fsdp", "ff")
        if last == "wo":
            return mk("ff", "fsdp")
    if "/attn/" in path or "/self/" in path or "/cross/" in path:
        if last == "wq":
            return mk("fsdp", "qkv")
        if last in ("wk", "wv"):
            return mk("fsdp", None)
        if last == "wo":
            return mk("qkv", "fsdp")
        if last == "bq":
            return mk("qkv")
        if last in ("bk", "bv"):
            return mk(None)
    if "/ssm/" in path:
        table = {
            "in_proj": ("fsdp", "ssm_inner"),
            "conv_w": (None, "ssm_inner"),
            "conv_b": ("ssm_inner",),
            "x_proj": ("ssm_inner", None),
            "dt_w": (None, "ssm_inner"),
            "dt_b": ("ssm_inner",),
            "A_log": ("ssm_inner", None),
            "D": ("ssm_inner",),
            "out_proj": ("ssm_inner", "fsdp"),
        }
        if last in table:
            return mk(*table[last])
    if "/xl/" in path:
        table = {
            "up": ("fsdp", "xlstm_inner"),
            "wq": (None, "xlstm_inner"),
            "wk": (None, "xlstm_inner"),
            "wv": (None, "xlstm_inner"),
            "down": ("xlstm_inner", "fsdp"),
            "skip": ("xlstm_inner",),
            "wx": (None, "xlstm_inner"),
            "wh": (None, None, None),
            "b": ("xlstm_inner",),
            "w_i": (None, None), "w_f": (None, None),
            "b_i": (None,), "b_o": (None,), "b_f": (None,),
            "wo": ("xlstm_inner",), "bo": (None,),
        }
        if last in table:
            return mk(*table[last])
    # default: replicated
    return P(*([None] * ndim))


def _pathstr(path) -> str:
    s = keystr(path)
    # "['blocks'][0]['attn']['wq']" -> "blocks/0/attn/wq"
    return (s.replace("']['", "/").replace("[", "/").replace("]", "")
            .replace("'", "").lstrip("/"))


def params_shardings(params_spec, rules, mesh: Mesh):
    def leaf(path, x):
        return NamedSharding(mesh, param_pspec(_pathstr(path), x.ndim, rules))
    return jax.tree_util.tree_map_with_path(leaf, params_spec)


def opt_state_shardings(opt_spec, params_shardings_tree, mesh: Mesh):
    """m/v mirror the params; step is replicated."""
    from repro.training.optimizer import OptState
    rep = NamedSharding(mesh, P())
    return OptState(rep, params_shardings_tree, params_shardings_tree)


def batch_shardings(batch_spec, rules, mesh: Mesh):
    b = rules.get("batch")

    def leaf(path, x):
        spec = (b,) + (None,) * (x.ndim - 1)
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(leaf, batch_spec)


def cache_pspec(path: str, ndim: int, rules: Dict[str, Any]) -> P:
    cb = rules.get("cache_batch")
    cs = rules.get("cache_seq")
    last = path.rsplit("/", 1)[-1]
    if last in ("k", "v"):
        if "cross" in path:
            cs = None      # encoder frames (1500) — not the seq_len dim
        if ndim == 5:      # (layers, B, S, Hkv, hd)
            return P(None, cb, cs, None, None)
        return P(cb, cs, None, None)
    if last == "conv":     # (layers, B, d_conv-1, d_in)
        return P(*([None, cb] + [None] * (ndim - 2)))
    if last == "h" and ndim >= 4:  # mamba h (layers, B, d_in, N)
        return P(None, cb, rules.get("ssm_inner"), None)
    # xLSTM states and anything else: batch on dim 1 (after layer stack)
    if ndim >= 2:
        return P(*([None, cb] + [None] * (ndim - 2)))
    return P(*([None] * ndim))


def caches_shardings(caches_spec, rules, mesh: Mesh):
    def leaf(path, x):
        return NamedSharding(mesh, cache_pspec(_pathstr(path), x.ndim, rules))
    return jax.tree_util.tree_map_with_path(leaf, caches_spec)
