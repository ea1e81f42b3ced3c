"""Operations and bytes that prefill and decode need, from a config's shapes.

These are the algorithm's counts, not what the program happens to do: a
matrix product of ``(m, k) x (k, n)`` is ``2 m k n`` operations, causal
attention counts only the keys a query sees (its window included), prefill
needs the logits of its last position only, and every weight is read once
per call.  A program that does more work, or moves more bytes, than this
reads below 100% of its roofline; one that reads above it is counted
wrongly.  Peaks come from ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Tuple

PEAKS = pathlib.Path(__file__).with_name("peaks.json")
_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def _sizes(m: dict) -> Dict[str, int]:
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    if m["qkv_bias"]:
        attn += q + 2 * kv
    ffn = 3 * d * m["d_ff"]
    return {"layer_matmul": d * q + 2 * d * kv + q * d + ffn,
            "layer": attn + ffn + 2 * d,
            "table": m["vocab_size"] * d,
            "kv_row": 2 * kv}


def param_count(m: dict) -> int:
    s = _sizes(m)
    heads = 1 if m["tie_embeddings"] else 2
    return m["num_layers"] * s["layer"] + heads * s["table"] + m["d_model"]


def param_bytes(m: dict) -> int:
    return param_count(m) * _DTYPE_BYTES[m["param_dtype"]]


def _seen(i: int, window) -> int:
    """Keys that the query at position ``i`` attends to."""
    return min(i + 1, window) if window else i + 1


def _attn_ops(m: dict, keys: int) -> int:
    """Scores and weighted values for one query against ``keys`` keys."""
    return 4 * m["num_heads"] * m["head_dim"] * keys


def prefill(m: dict, prompt: int) -> Tuple[int, int]:
    """(operations, bytes) of one prefill call of batch 1."""
    s, L, w = _sizes(m), m["num_layers"], m["sliding_window"]
    keys = sum(_seen(i, w) for i in range(prompt))
    ops = (L * (2 * s["layer_matmul"] * prompt + _attn_ops(m, keys))
           + 2 * m["d_model"] * m["vocab_size"])
    el = _DTYPE_BYTES[m["param_dtype"]]
    act = _DTYPE_BYTES[m["dtype"]]
    weights = L * s["layer"] + s["table"] + m["d_model"]      # layers + head
    kept = min(prompt, w) if w else prompt
    moved = (el * weights + el * prompt * m["d_model"]        # embedding rows
             + act * L * kept * s["kv_row"])                  # cache written
    return ops, moved


def decode(m: dict, pos: int) -> Tuple[int, int]:
    """(operations, bytes) of one decode step of batch 1 at position ``pos``
    (the new token's position; the cache holds the ``pos`` before it)."""
    s, L = _sizes(m), m["num_layers"]
    keys = _seen(pos, m["sliding_window"])
    ops = (L * (2 * s["layer_matmul"] + _attn_ops(m, keys))
           + 2 * m["d_model"] * m["vocab_size"])
    el = _DTYPE_BYTES[m["param_dtype"]]
    act = _DTYPE_BYTES[m["dtype"]]
    weights = L * s["layer"] + s["table"] + m["d_model"]
    moved = (el * (weights + m["d_model"])                    # + one embed row
             + act * L * (keys - 1) * s["kv_row"]             # cache read
             + act * L * s["kv_row"])                         # new slot written
    return ops, moved


def seconds_at_peak(ops: int, moved: int, device_kind: str) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    p = peaks(device_kind)
    t_ops, t_bytes = ops / p["bf16_flops_per_s"], moved / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
