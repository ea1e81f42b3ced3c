"""Checkpoints: one pickle-free .npz format for params pytrees.

The checkpoint doubles as the serving snapshot format (SnapshotStore calls
:func:`save` and :func:`restore`) — a trained model's checkpoint IS its
pre-baked cold-start image, closing the loop between the training and
serving halves.

Layout: every leaf is an .npz member named by its flattened pytree path
(``blocks/0/attn/wq``).  A JSON manifest (``__manifest__``) lists each
leaf's path, as dict keys (str) and list indices (int), and its dtype
name, so dtypes numpy cannot name (bfloat16 would come back as ``|V2``)
round-trip through their raw bits.  ``extra`` is stored in the manifest
as JSON.  Nothing is pickled, so a file outlives the JAX version that
wrote it.  Trees are nested dicts (str keys) and lists of arrays.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import DictKey, SequenceKey

FORMAT = 1
_MANIFEST = "__manifest__"

PathEntry = Union[str, int]


def _path_entries(path) -> List[PathEntry]:
    out: List[PathEntry] = []
    for k in path:
        if isinstance(k, DictKey) and isinstance(k.key, str):
            out.append(k.key)
        elif isinstance(k, SequenceKey):
            out.append(k.idx)
        else:
            raise TypeError(f"checkpoint trees hold str-keyed dicts and "
                            f"lists only; got path entry {k!r}")
    return out


def _storable(a: np.ndarray) -> np.ndarray:
    """``a`` with a dtype numpy can write: ml_dtypes (bfloat16, fp8) are
    stored as their raw bits."""
    if a.dtype.kind == "V":
        return a.view(np.dtype(f"u{a.dtype.itemsize}"))
    return a


def save(path: str, params: Any, *, extra: Optional[dict] = None) -> int:
    """Write ``params`` (and JSON-able ``extra``) to ``path``; returns the
    file size in bytes."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    arrs: Dict[str, np.ndarray] = {}
    manifest = {"format": FORMAT, "extra": extra or {}, "leaves": []}
    for p, x in leaves:
        entries = _path_entries(p)
        a = np.asarray(x)
        arrs["/".join(map(str, entries))] = _storable(a)
        manifest["leaves"].append({"path": entries, "dtype": a.dtype.name})
    blob = np.frombuffer(json.dumps(manifest).encode(), np.uint8)
    with open(path, "wb") as f:
        np.savez(f, **{_MANIFEST: blob}, **arrs)
    return os.path.getsize(path)


def _insert(tree: Any, entries: List[PathEntry], leaf) -> Any:
    if not entries:
        return leaf
    head, rest = entries[0], entries[1:]
    if tree is None:
        tree = [] if isinstance(head, int) else {}
    if isinstance(head, int):
        while len(tree) <= head:
            tree.append(None)
    else:
        tree.setdefault(head, None)
    tree[head] = _insert(tree[head], rest, leaf)
    return tree


class HostTree(NamedTuple):
    """A checkpoint's leaves in host memory, with their pytree paths."""

    paths: List[List[PathEntry]]
    leaves: List[np.ndarray]


def read(path: str) -> Tuple[HostTree, dict]:
    """Read a checkpoint written by :func:`save` into host memory; returns
    ``(HostTree, extra)``."""
    with np.load(path, allow_pickle=False) as z:
        manifest = json.loads(z[_MANIFEST].tobytes().decode())
        if manifest.get("format") != FORMAT:
            raise ValueError(f"{path}: checkpoint format "
                             f"{manifest.get('format')!r} != {FORMAT}")
        paths, leaves = [], []
        for spec in manifest["leaves"]:
            a = z["/".join(map(str, spec["path"]))]
            want = jnp.dtype(spec["dtype"])
            paths.append(spec["path"])
            leaves.append(a if a.dtype == want else a.view(want))
    return HostTree(paths, leaves), manifest["extra"]


def place(host: HostTree) -> Any:
    """The params pytree, its leaves put on the default device in one
    transfer."""
    tree = None
    for path, leaf in zip(host.paths, jax.device_put(host.leaves)):
        tree = _insert(tree, path, leaf)
    return tree


def restore(path: str) -> Tuple[Any, dict]:
    """Read a checkpoint written by :func:`save`; returns
    ``(params, extra)`` with the leaves put on the default device in one
    transfer."""
    host, extra = read(path)
    return place(host), extra


def tree_equal(a: Any, b: Any) -> bool:
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb or len(la) != len(lb):
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
