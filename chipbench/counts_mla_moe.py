"""Operations and bytes of latent attention with routed and shared experts
(DeepSeek-V2), from a config's shapes, at the chip's expert share.

The algorithm's counts, as ``counts`` gives them for a dense decoder: a
matrix product ``(m, k) x (k, n)`` is ``2 m k n`` operations, causal
attention counts only the keys a query sees, the head only the last
position's logits, and each weight is read once per call.  Particular to
this family:

  - prefill attention is counted in the expanded form it runs in: each
    head's key ``[k_nope; k_pe]`` of ``qk_nope + qk_rope`` dims and value
    of ``v_head_dim``, ``kv_b`` applied at every position;
  - decode attention in the absorbed form, over the latent cache:
    ``q_nope W_uk`` and ``o_lat W_uv`` per head, scores against ``c`` and
    ``k_pe``, the weighted sum of ``c``; ``kv_b``'s weights are read, not
    applied to the cache;
  - routed experts count ``top_k * held / num_experts`` experts a token in
    each MoE layer (the pairs a uniform router sends to the experts held
    here), and a decode step reads only those experts' weights; prefill
    reads every held expert once;
  - the router's weights are float32, as the program keeps them.

Keys are the program's (``ModelConfig``'s), so a configuration cut to the
smoke size is counted as it runs.
"""
from __future__ import annotations

from typing import Dict, Tuple

from chipbench.counts import _DTYPE_BYTES

ROUTER_BYTES = 4


def _sizes(m: dict) -> Dict[str, float]:
    d, h, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    e = m["moe"]
    held = e["experts_held"] or e["num_experts"]
    expert = 3 * d * e["expert_ff"]
    return {
        "wq": d * h * (dn + dr), "wkv_a": d * (r + dr), "wkv_b": r * h * (dn + dv),
        "wo": h * dv * d,
        "norms": 2 * d + r,                       # norm1, norm2, kv_norm
        "dense": 3 * d * m["d_ff"], "shared": 3 * d * e["shared_ff"],
        "router": d * e["num_experts"], "expert": expert, "held": held,
        "routed": e["top_k"] * held / e["num_experts"],   # experts a token
        "table": m["vocab_size"] * d,
        "lead": e["first_dense"], "moe_layers": m["num_layers"] - e["first_dense"],
    }


def param_count(m: dict, *, whole: bool = False) -> int:
    """Parameters held on the chip, or with ``whole`` every expert of the
    published layer."""
    s = _sizes(m)
    n_exp = m["moe"]["num_experts"] if whole else s["held"]
    attn = s["wq"] + s["wkv_a"] + s["wkv_b"] + s["wo"] + s["norms"]
    lead = attn + s["dense"]
    layer = attn + s["shared"] + s["router"] + n_exp * s["expert"]
    heads = 1 if m["tie_embeddings"] else 2
    return (s["lead"] * lead + s["moe_layers"] * layer + heads * s["table"]
            + m["d_model"])


def param_bytes(m: dict) -> int:
    """Bytes of the held parameters, the router's in float32."""
    el = _DTYPE_BYTES[m["param_dtype"]]
    routers = _sizes(m)["moe_layers"] * _sizes(m)["router"]
    return param_count(m) * el + routers * (ROUTER_BYTES - el)


def _token_ops(m: dict, s: dict, *, absorbed: bool) -> float:
    """Operations a token takes outside attention's scores and values."""
    attn = s["wq"] + s["wkv_a"] + s["wo"] + (0 if absorbed else s["wkv_b"])
    moe = s["shared"] + s["router"] + s["routed"] * s["expert"]
    return 2 * (m["num_layers"] * attn + s["lead"] * s["dense"]
                + s["moe_layers"] * moe)


def _cache_row(m: dict) -> int:
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * _DTYPE_BYTES[m["dtype"]]


def prefill(m: dict, prompt: int) -> Tuple[int, int]:
    """(operations, bytes) of one prefill call of batch 1."""
    s = _sizes(m)
    h, L = m["num_heads"], m["num_layers"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    keys = prompt * (prompt + 1) // 2                 # causal, summed
    ops = (prompt * _token_ops(m, s, absorbed=False)
           + L * 2 * h * (qk + m["v_head_dim"]) * keys
           + 2 * m["d_model"] * m["vocab_size"])
    el = _DTYPE_BYTES[m["param_dtype"]]
    weights = param_bytes(m) - el * s["table"]        # all but the embedding
    moved = (weights + el * prompt * m["d_model"]     # + the prompt's rows
             + L * prompt * _cache_row(m))            # latent cache written
    return int(ops), int(moved)


def decode(m: dict, pos: int) -> Tuple[int, int]:
    """(operations, bytes) of one decode step of batch 1 at position
    ``pos`` (the cache holds the ``pos`` before it)."""
    s = _sizes(m)
    h, L, r = m["num_heads"], m["num_layers"], m["kv_lora_rank"]
    keys = pos + 1
    absorb = 2 * h * r * (m["qk_nope_head_dim"] + m["v_head_dim"])
    scores = 2 * h * (2 * r + m["qk_rope_head_dim"]) * keys
    ops = (_token_ops(m, s, absorbed=True) + L * (absorb + scores)
           + 2 * m["d_model"] * m["vocab_size"])
    el = _DTYPE_BYTES[m["param_dtype"]]
    unread = (s["held"] - s["routed"]) * s["expert"] * s["moe_layers"]
    weights = param_bytes(m) - el * (s["table"] + unread)
    moved = (weights + el * m["d_model"]               # + one embedding row
             + L * pos * _cache_row(m)                 # latent cache read
             + L * _cache_row(m))                      # new slot written
    return int(ops), int(moved)
