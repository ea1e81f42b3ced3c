"""Real JAX inference engine with *measured* cold starts.

This is the ground-truth side of the framework: a "serverless function" is a
model endpoint, and its cold start is genuinely paid here —

  runtime_init   building the model bundle (python, imports, closures)
  deps_load      parameter materialisation / checkpoint load + device_put
                 (bytes = the paper's "deployment package size")
  code_init      XLA compilation of prefill + decode_step (AOT
                 ``.lower().compile()`` — the dominant phase)
  execute        the compiled calls

Mitigation paths implemented for real:
  * snapshot/restore (vHive/Catalyzer): params serialized to an .npz
    snapshot (the ``training/checkpoint.py`` format) + compiled executables
    kept in a process-level cache keyed by (arch, shapes) — a restore pays
    deserialization + device_put only;
  * keep-warm / scale-to-zero: ``shutdown()`` drops device state; the
    frontend (router.py) applies TTL policies over engines;
  * fusion: ``fuse_chain`` compiles a chained two-stage pipeline as ONE
    program (one compile) vs two.

All timings are wall-clock measured (perf_counter + block_until_ready).
"""
from __future__ import annotations

import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lifecycle import Breakdown, Phase
from repro.models import registry
from repro.training import checkpoint


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


class _Timer:
    def __init__(self):
        self.seconds: Dict[Phase, float] = {}

    def phase(self, p: Phase):
        timer = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *a):
                timer.seconds[p] = timer.seconds.get(p, 0.0) + (
                    time.perf_counter() - self.t0)

        return _Ctx()

    def breakdown(self) -> Breakdown:
        return Breakdown(dict(self.seconds))


# --------------------------------------------------------------------------- #
# snapshot store (vHive/Catalyzer analogue)
# --------------------------------------------------------------------------- #


class SnapshotStore:
    """Param snapshots on disk + compiled-executable cache in process.

    The executable cache models a node-local XLA compilation cache (on a
    real deployment: ``jax.config.jax_compilation_cache_dir``); the .npz is
    the pre-baked memory image.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = root or os.path.join(tempfile.gettempdir(),
                                         "coldjax_snapshots")
        os.makedirs(self.root, exist_ok=True)
        self.executables: Dict[str, Any] = {}

    # params ------------------------------------------------------------- #
    def _path(self, key: str) -> str:
        return os.path.join(self.root, key.replace("/", "_") + ".npz")

    def has_params(self, key: str) -> bool:
        return os.path.exists(self._path(key))

    def save_params(self, key: str, params) -> int:
        return checkpoint.save(self._path(key), params)

    def load_params(self, key: str):
        return checkpoint.restore(self._path(key))[0]

    # executables ---------------------------------------------------------- #
    def get_executable(self, key: str):
        return self.executables.get(key)

    def put_executable(self, key: str, compiled):
        self.executables[key] = compiled


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens: int = 0
    logits: Optional[np.ndarray] = None   # last decode step's (B, V) logits


@dataclass(frozen=True)
class StartPath:
    """Which mechanisms a cold start used."""

    from_snapshot: bool      # params restored from the SnapshotStore
    executable_hit: bool     # compiled programs found in the store


class InferenceEngine:
    """One 'serverless function' instance (container analogue)."""

    def __init__(self, arch: str, *, smoke: bool = True, max_seq: int = 128,
                 batch: int = 1, store: Optional[SnapshotStore] = None,
                 runtime: str = "python-jit", seed: int = 0):
        self.arch = arch
        self.smoke = smoke
        self.max_seq = max_seq
        self.batch = batch
        self.store = store
        self.runtime = runtime
        self.seed = seed
        self.params = None
        self.bundle = None
        self._prefill_c = None
        self._decode_c = None
        self.warm = False
        self.last_breakdown: Optional[Breakdown] = None
        self.last_start: Optional[StartPath] = None
        self.last_used = 0.0

    # ------------------------------------------------------------------ #
    @property
    def key(self) -> str:
        return f"{self.arch}_s{self.max_seq}_b{self.batch}_{self.smoke}"

    def package_bytes(self) -> int:
        return _tree_bytes(self.params) if self.params is not None else 0

    def _prefill_batch_spec(self):
        cfg = self.bundle.cfg
        spec = {"tokens": jax.ShapeDtypeStruct((self.batch, self.max_seq), jnp.int32)}
        if cfg.encoder is not None:
            spec["frames"] = jax.ShapeDtypeStruct(
                (self.batch, cfg.encoder.num_frames, cfg.encoder.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.vision is not None:
            spec["image_embeds"] = jax.ShapeDtypeStruct(
                (self.batch, cfg.vision.num_image_tokens, cfg.vision.d_embed),
                jnp.dtype(cfg.dtype))
        return spec

    # ------------------------------------------------------------------ #
    def cold_start(self, *, from_snapshot: bool = False) -> Breakdown:
        """Full measured startup.  Returns the per-phase breakdown."""
        t = _Timer()
        with t.phase(Phase.PROVISION):
            pass  # process/slice allocation has no CPU-container analogue here
        with t.phase(Phase.RUNTIME_INIT):
            self.bundle = registry.build_arch(self.arch, smoke=self.smoke,
                                              max_seq=self.max_seq)
        use_snap = (from_snapshot and self.store is not None
                    and self.store.has_params(self.key))
        with t.phase(Phase.DEPS_LOAD):
            if use_snap:
                self.params = self.store.load_params(self.key)
            else:
                # one jitted program on the device, so no float32
                # temporaries per leaf; an RngBitGenerator key because a
                # threefry init of full-width granite takes ~22 s to
                # compile for a v5e, rbg ~6 s
                self.params = jax.jit(self.bundle.init)(
                    jax.random.key(self.seed, impl="rbg"))
            jax.block_until_ready(self.params)
        with t.phase(Phase.CODE_INIT):
            exe = None if self.store is None else \
                self.store.get_executable(self.key)
            self.last_start = StartPath(from_snapshot=use_snap,
                                        executable_hit=exe is not None)
            if exe is not None:
                self._prefill_c, self._decode_c = exe
            else:
                params_spec = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params)
                bspec = self._prefill_batch_spec()
                self._prefill_c = jax.jit(self.bundle.prefill).lower(
                    params_spec, bspec).compile()
                caches_spec = jax.eval_shape(
                    lambda p, b: self.bundle.prefill(p, b)[1], params_spec, bspec)
                self._decode_c = jax.jit(self.bundle.decode_step).lower(
                    params_spec, caches_spec,
                    jax.ShapeDtypeStruct((self.batch,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32)).compile()
                if self.store is not None:
                    self.store.put_executable(
                        self.key, (self._prefill_c, self._decode_c))
        if self.store is not None and not self.store.has_params(self.key):
            self.store.save_params(self.key, self.params)
        self.warm = True
        self.last_breakdown = t.breakdown()
        return self.last_breakdown

    def shutdown(self):
        """Scale to zero: drop device state (keep nothing warm)."""
        self.params = None
        self._prefill_c = None
        self._decode_c = None
        self.bundle = None
        self.warm = False

    # ------------------------------------------------------------------ #
    def serve(self, tokens: np.ndarray, *, decode_steps: int = 8,
              extras: Optional[Dict[str, np.ndarray]] = None) -> Tuple[np.ndarray, ServeStats]:
        """Greedy generation; measures prefill + decode wall time."""
        assert self.warm, "cold engine — call cold_start() first"
        stats = ServeStats()
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        t0 = time.perf_counter()
        logits, caches, pos = self._prefill_c(self.params, batch)
        jax.block_until_ready(logits)
        stats.prefill_s = time.perf_counter() - t0
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.perf_counter()
        p = jnp.asarray(tokens.shape[1], jnp.int32)
        for i in range(decode_steps):
            out.append(np.asarray(tok))
            logits, caches = self._decode_c(self.params, caches, tok, p + i)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        stats.decode_s = time.perf_counter() - t0
        stats.tokens = decode_steps
        stats.logits = np.asarray(logits)
        self.last_used = time.monotonic()
        return np.stack(out, axis=1), stats


# --------------------------------------------------------------------------- #
# function fusion (real): chain two LM stages into ONE compiled program
# --------------------------------------------------------------------------- #


def fuse_chain(engines: List[InferenceEngine], *, decode_steps: int = 4):
    """Compile a chained pipeline (stage i's sampled tokens feed stage i+1)
    as a single jitted program.  Returns (compiled_fn, compile_seconds) —
    exactly one XLA compile for the whole chain, vs one per stage unfused.
    """
    bundles = [e.bundle for e in engines]
    params = [e.params for e in engines]
    batch0_spec = engines[0]._prefill_batch_spec()

    def chained(params_list, batch):
        tokens = batch["tokens"]
        for bundle, p in zip(bundles, params_list):
            tokens = tokens % bundle.cfg.vocab_size
            logits, caches, pos = bundle.prefill(p, {"tokens": tokens})
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs = []
            pp = jnp.asarray(tokens.shape[1], jnp.int32)

            def step(carry, i):
                tok, caches = carry
                lg, caches = bundle.decode_step(p, caches, tok, pp + i)
                nt = jnp.argmax(lg, -1).astype(jnp.int32)
                return (nt, caches), tok

            (tok, caches), outs = jax.lax.scan(
                step, (tok, caches), jnp.arange(decode_steps))
            gen = jnp.moveaxis(outs, 0, 1)                       # (B, steps)
            # generated tokens feed the next stage (same prompt length)
            tokens = jnp.concatenate([tokens, gen], axis=1)[:, -tokens.shape[1]:]
        return tokens

    t0 = time.perf_counter()
    params_specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    compiled = jax.jit(chained).lower(params_specs, batch0_spec).compile()
    compile_s = time.perf_counter() - t0
    return lambda batch: compiled(params, batch), compile_s
