"""Mixture-of-Experts FFN: top-k routing, dropless sorted dispatch.

The router scores every token against all ``num_experts`` experts; each
token's ``top_k`` (token, expert) pairs are sorted by expert, and the held
experts' rows go through one grouped matmul per projection
(``jax.lax.ragged_dot``, group sizes counted from the routing).  The sorted
buffer has a row for every pair, so no pair is ever dropped, however skewed
the routing: there is no capacity.  Pairs routed to experts this device
does not hold sort last, past every group, and add nothing.  A call with no
more pairs than held experts (a decode step) reads only the held experts
its pairs chose instead, and passes each token through each of them,
weighted by its gates (0 where it did not choose the expert): on a TPU the
Pallas kernel ``kernels/moe_decode.py`` does, reading the decode scan's
expert stacks in place; elsewhere every held expert is read, gated, which
is the kernel's reference (``expert_path``).

A layer holds experts 0 .. ``MoEConfig.experts_held`` - 1 (all of them by
default): the share chip 0 of an expert-parallel deployment computes.  On
one device there is no exchange; under a mesh whose rules map experts to
an axis, ``_moe_ffn_ep`` gives each rank its slice of the held experts and
combines the partial results with a psum.  The router scores in float32
at full precision, as the published gate does: a TPU's default for a
float32 product is one bfloat16 pass, which flips near-tie top-k choices.

A dense FFN that every token passes beside the experts (``shared_ff``:
Arctic's dense residual, DeepSeek's shared experts) is added once.  The
layer returns the load-balance auxiliary loss and counts of its routing:
pairs routed to held experts, the heaviest held expert's pairs, the held
pairs the dispatch left uncomputed (dropped: 0 unless it is at fault),
the held experts whose weights it read, and 1 for the layer itself, so
that sums over layers and steps can be divided by it.

``lm_decode_step`` returns logits and caches alone; a decode step's counts
reach a caller that traces it under ``counting`` (``counted_decode``).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models import layers

# the expert paths (``expert_path``), as ``moe_dispatch`` names them
DISPATCH = {"sorted": "sorted ragged_dot", "dense": "dense over held",
            "pallas": "pallas over chosen held"}
EXPERT_WEIGHTS = ("wi", "wg", "wo")


def init_moe(rng, cfg):
    m = cfg.moe
    d = cfg.d_model
    pdt = cfg.param_dtype
    r = jax.random.split(rng, 5)
    ff = m.expert_ff

    def expert_stack(key, a, b):
        w = jax.random.truncated_normal(key, -2.0, 2.0, (m.held, a, b),
                                        jnp.float32)
        return (w * (a ** -0.5)).astype(pdt)

    p = {
        "router": layers.dense_init(r[0], d, m.num_experts, "float32"),
        "wi": expert_stack(r[1], d, ff),
        "wo": expert_stack(r[2], ff, d),
    }
    if cfg.act == "swiglu":
        p["wg"] = expert_stack(r[3], d, ff)
    if m.shared_ff:
        p["shared"] = layers.mlp_init(r[4], d, m.shared_ff, cfg.act, pdt)
    return p


def expert_path(pairs: int, held: int) -> str:
    """The expert pass of a call with ``pairs`` (token, expert) pairs over
    ``held`` experts: ``"sorted"``, the grouped matmuls, when it has more
    pairs than held experts; for no more (a decode step) ``"pallas"``, the
    kernel that reads only the chosen held experts, on a TPU outside a
    sharding context (one device), and ``"dense"``, every held expert
    gated, elsewhere."""
    if pairs > held:
        return "sorted"
    if (jax.default_backend() == "tpu"
            and sharding.current_rules_and_mesh() is None):
        return "pallas"
    return "dense"


def start_attrs(cfg, batch: int) -> Dict[str, str]:
    """Span attributes of ``engine.start.compile`` for an MoE model whose
    decode steps take ``batch`` tokens."""
    m = cfg.moe
    decode = expert_path(batch * m.top_k, m.held)
    return {"experts_held": f"{m.held}/{m.num_experts}",
            "moe_dispatch": f"{DISPATCH['sorted']}; {DISPATCH[decode]} "
                            f"at decode"}


def zero_counts() -> Dict[str, jax.Array]:
    return {k: jnp.zeros((), jnp.int32)
            for k in ("moe_pairs_held", "moe_max_expert_tokens",
                      "moe_dropped", "moe_experts_read", "moe_layers")}


# the innermost open ``counting`` block's list, per thread and context
_counting: contextvars.ContextVar[Optional[list]] = contextvars.ContextVar(
    "moe_counting", default=None)


@contextlib.contextmanager
def counting():
    """Collects, in the list it yields, the routing counts that decode
    steps traced inside the block ``report``."""
    got: list = []
    token = _counting.set(got)
    try:
        yield got
    finally:
        _counting.reset(token)


def report(counts: Dict[str, jax.Array]) -> None:
    """A decode step's counts, summed over its layers, for the innermost
    open ``counting`` block (none open: dropped)."""
    got = _counting.get()
    if got is not None:
        got.append(counts)


def counted_decode(decode_step):
    """``decode_step`` (p, caches, token, pos) -> (logits, caches) as one
    program that also adds the step's routing counts to a running sum:
    (p, caches, token, pos, routed) -> (logits, caches, routed).  For a
    model without experts ``routed`` is ``{}`` and stays so."""
    def decode_step_counted(p, caches, token, pos, routed):
        with counting() as steps:
            logits, caches = decode_step(p, caches, token, pos)
        for n in steps:
            routed = jax.tree.map(jnp.add, routed, n)
        return logits, caches, routed

    decode_step_counted.__name__ = decode_step.__name__
    return decode_step_counted


def split_stacks(p, meta, cfg, tokens: int):
    """A decode scan position's params without the held experts' weights,
    and those weights whole (``None`` where the position keeps them), for
    an MoE layer whose ``tokens``-token steps take the kernel: it reads
    the stacked ``[n_rep, held, ...]`` weights in place, given the layer's
    index, so that no layer's slice of them is copied each step."""
    m = cfg.moe
    if meta["ffn"] != "moe" or expert_path(tokens * m.top_k, m.held) \
            != "pallas":
        return p, None
    inner = {k: v for k, v in p["moe"].items() if k not in EXPERT_WEIGHTS}
    whole = {k: v for k, v in p["moe"].items() if k in EXPERT_WEIGHTS}
    return {**p, "moe": inner}, whole


def join_stacks(p, whole, layer):
    """``p`` with the weights ``split_stacks`` took whole, and the index
    of ``p``'s layer in them."""
    if whole is None:
        return p
    return {**p, "moe": {**p["moe"], **whole, "layer": layer}}


def _route(x, router, m):
    """x: (t, d) -> top-k weights (t, k) f32, experts (t, k), probs (t, E)."""
    scores = jnp.dot(x.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(scores, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_i, probs


def _ffn(h_in, p, mm):
    """The experts' hidden activations (SwiGLU or GELU), products by ``mm``."""
    h = mm(h_in, p["wi"])
    h = jax.nn.silu(h) * mm(h_in, p["wg"]) if "wg" in p else jax.nn.gelu(h)
    return h


def _experts(x, p, cfg, *, first, held):
    """The part of experts ``first .. first + held - 1`` for one token set.

    x: (t, d) -> (y (t, d) f32, aux loss, counts).  The pairs are sorted
    by expert and each computed once, through the grouped matmuls or, with
    no more pairs than held experts (a decode step) on a TPU, the kernel
    that reads only the chosen held experts (``expert_path``); there ``p``
    may hold the weights as whole stacks with the layer's index
    (``join_stacks``).  Elsewhere such a call takes every held expert for
    each token, weighted by its gates (0 where not routed)."""
    m = cfg.moe
    t, d = x.shape
    k, e = m.top_k, m.num_experts
    top_w, top_i, probs = _route(x, p["router"], m)

    local = top_i.reshape(t * k) - first
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)          # not held: after all groups
    sizes = jnp.bincount(group, length=held + 1)[:held]
    w = jnp.where(mine, top_w.reshape(t * k), 0.0)
    tok = jnp.arange(t * k) // k
    path = expert_path(t * k, held)
    if path == "dense":
        gate = jnp.zeros((t, held + 1), jnp.float32).at[tok, group].add(w)
        gated = jnp.zeros((t, held + 1), jnp.int32).at[tok, group].add(1)
        read = held
        computed = gated[:, :held].sum()
        h = _ffn(x, p, lambda a, b: jnp.einsum("td,edf->tef", a, b))
        out = jnp.einsum("tef,efd->ted", h, p["wo"],
                         preferred_element_type=jnp.float32)
        y = jnp.einsum("ted,te->td", out, gate[:, :held])
    else:
        order = jnp.argsort(group, stable=True)
        n = sizes.sum()                   # held pairs: the first n rows
        if path == "pallas":
            from repro.kernels.moe_decode import held_experts_pallas

            # past the held pairs the last held expert again: no DMA
            srt = group[order]
            ids = jnp.minimum(srt, jnp.minimum(srt[jnp.maximum(n - 1, 0)],
                                               held - 1))
            stacks = {name: p[name] if "layer" in p else p[name][None]
                      for name in EXPERT_WEIGHTS if name in p}
            out = held_experts_pallas(x, tok[order], ids, n,
                                      p.get("layer", 0), stacks["wi"],
                                      stacks["wo"], stacks.get("wg"))
        else:
            def mm(a, b):
                return jax.lax.ragged_dot(a, b, sizes)

            rows = x[tok[order]]                  # (t*k, d), by expert
            out = mm(_ffn(rows, p, mm), p["wo"])
        read = (sizes > 0).sum()
        live = jnp.arange(t * k) < n              # rows past the groups: none
        computed = jnp.sum(live & mine[order])
        out = jnp.where(live[:, None], out.astype(jnp.float32), 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[tok[order]].add(
            out * w[order][:, None])

    # load-balance aux loss (Switch): E * sum_e f_e * p_e, over all experts
    f = jnp.bincount(top_i.reshape(-1), length=e).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(f * probs.mean(axis=0))
    counts = {"moe_pairs_held": sizes.sum().astype(jnp.int32),
              "moe_max_expert_tokens": sizes.max().astype(jnp.int32),
              "moe_dropped": (mine.sum() - computed).astype(jnp.int32),
              "moe_experts_read": jnp.asarray(read, jnp.int32),
              "moe_layers": jnp.ones((), jnp.int32)}
    return y, aux, counts


def _moe_ffn_ep(p, x, cfg, rules, mesh):
    """Explicit expert-parallel MoE via shard_map (EXPERIMENTS.md §Perf
    iteration 2): GSPMD replicates the dispatch (≈26 GB/layer of
    collectives on qwen3-moe prefill); manual EP needs only the combine
    all-reduce of the token activations (≈0.27 GB/layer)."""
    from jax.sharding import PartitionSpec as P

    def _axsize(ax):
        if ax is None:
            return 1
        names = (ax,) if isinstance(ax, str) else ax
        n = 1
        for a in names:
            n *= mesh.shape[a]
        return n

    model_ax = rules["expert"]
    # shard_map needs even division: drop token axes that don't divide
    # (decode steps have seq==1; long_500k has batch==1)
    batch_ax = rules.get("batch")
    seq_ax = rules.get("seq")
    if x.shape[0] % _axsize(batch_ax):
        batch_ax = None
    if x.shape[1] % _axsize(seq_ax):
        seq_ax = None
    msize = mesh.shape[model_ax] if isinstance(model_ax, str) else 1
    m = cfg.moe
    e_local = m.held // msize

    moe_parts = {k: p[k] for k in ("router", "wi", "wg", "wo") if k in p}
    spec_parts = {k: (P(None, None) if k == "router" else P(model_ax, None, None))
                  for k in moe_parts}
    shared = p.get("shared")
    shared_spec = None
    if shared is not None:
        ff_ax = rules.get("ff")
        shared_spec = {k: (P(None, ff_ax) if k in ("wi", "wg") else P(ff_ax, None))
                       for k in shared}

    def local_fn(parts, shared_local, xl):
        rank = jax.lax.axis_index(model_ax) if msize > 1 else 0
        b, s, d = xl.shape
        y, aux, counts = _experts(xl.reshape(b * s, d), parts, cfg,
                                  first=rank * e_local,
                                  held=e_local)
        y = y.reshape(b, s, d)
        if shared_local is not None:
            # shared branch: ff dim sharded on the same axis; its partial
            # sums ride the same combine all-reduce
            y = y + layers.mlp_apply(shared_local, xl, cfg.act).astype(
                jnp.float32)
        y = jax.lax.psum(y, model_ax)
        counts = {k: (jax.lax.pmax if k in ("moe_max_expert_tokens",
                                            "moe_layers")
                      else jax.lax.psum)(v, model_ax)
                  for k, v in counts.items()}
        return y.astype(xl.dtype), aux, counts

    in_specs = (spec_parts, shared_spec, P(batch_ax, seq_ax, None))
    out_specs = (P(batch_ax, seq_ax, None), P(), P())
    try:
        sm = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    except TypeError:
        from jax.experimental.shard_map import shard_map as _shard_map
        sm = _shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_rep=False)
    y, aux, counts = sm(moe_parts, shared, x)
    return y, cfg.moe.router_aux_weight * aux, counts


def moe_ffn(p, x, cfg) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar, routing counts).

    Uses the explicit shard_map expert-parallel path whenever an active
    sharding context maps experts to a mesh axis; otherwise the single-
    device path (CPU smoke tests, serving engines).
    """
    ctx = sharding.current_rules_and_mesh()
    if ctx is not None:
        rules, mesh = ctx
        # EP shard_map wins for big token counts (prefill/train: 10x on
        # qwen3 prefill) but REGRESSES for decode-sized batches (arctic
        # decode bound 0.07s -> 0.6s: the replicated local dispatch out-
        # weighs GSPMD's resharding at ~128 tokens) — measured, §Perf iter 2b.
        if rules.get("expert") and x.shape[0] * x.shape[1] >= 2048:
            return _moe_ffn_ep(p, x, cfg, rules, mesh)
    b, s, d = x.shape
    m = cfg.moe
    y, aux, counts = _experts(x.reshape(b * s, d), p, cfg, first=0,
                              held=m.held)
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + layers.mlp_apply(p["shared"], x, cfg.act).astype(jnp.float32)
    return y.astype(x.dtype), m.router_aux_weight * aux, counts
