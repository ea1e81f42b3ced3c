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

All timings are wall-clock measured: the durations of ``repro.spans`` spans
(perf_counter_ns, ended by block_until_ready where a device result is
waited for).
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

from repro import compile_cache, spans
from repro.core.lifecycle import Breakdown, Phase
from repro.models import attention, moe, registry
from repro.training import checkpoint


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


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

    def read_params(self, key: str) -> checkpoint.HostTree:
        """The snapshot's leaves in host memory (``checkpoint.place`` puts
        them on the device)."""
        return checkpoint.read(self._path(key))[0]

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
    prefill_s: float = 0.0    # engine.prefill_run
    decode_s: float = 0.0     # engine.decode
    run_s: float = 0.0        # engine.run: the whole call
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
                 batch: int = 1, decode_steps: int = 8,
                 store: Optional[SnapshotStore] = None,
                 runtime: str = "python-jit", seed: int = 0):
        self.arch = arch
        self.smoke = smoke
        self.max_seq = max_seq              # the prompt's length
        self.decode_steps = decode_steps    # tokens the cache has room for
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
    def cache_len(self) -> int:
        """Positions the decode cache covers: the prompt and every decoded
        token (a window layer keeps ``min(cache_len, window)`` slots)."""
        return self.max_seq + self.decode_steps

    @property
    def key(self) -> str:
        return (f"{self.arch}_s{self.max_seq}_c{self.cache_len}"
                f"_b{self.batch}_{self.smoke}")

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
        """Full measured startup.  Returns the per-phase breakdown: the
        durations of the ``engine.start`` phase spans (``repro.spans``).
        Process or slice allocation has no analogue here, so ``provision``
        is 0."""
        use_snap = (from_snapshot and self.store is not None
                    and self.store.has_params(self.key))
        with spans.span("engine.start", arch=self.arch,
                        from_snapshot=use_snap):
            with spans.span("engine.start.build") as build:
                self.bundle = registry.build_arch(self.arch, smoke=self.smoke,
                                                  max_seq=self.cache_len)
            with spans.span("engine.start.weights") as weights:
                if use_snap:
                    with spans.span("engine.start.weights.read"):
                        host = self.store.read_params(self.key)
                    with spans.span("engine.start.weights.put"):
                        self.params = checkpoint.place(host)
                        jax.block_until_ready(self.params)
                else:
                    # one jitted program on the device, so no float32
                    # temporaries per leaf; an RngBitGenerator key because a
                    # threefry init of full-width granite takes ~22 s to
                    # compile for a v5e, rbg ~6 s
                    with spans.span("engine.start.weights.compile"):
                        key = jax.random.key(self.seed, impl="rbg")
                        init = jax.jit(self.bundle.init).lower(key).compile()
                    with spans.span("engine.start.weights.run"):
                        self.params = init(key)
                        jax.block_until_ready(self.params)
            exe = None if self.store is None else \
                self.store.get_executable(self.key)
            self.last_start = StartPath(from_snapshot=use_snap,
                                        executable_hit=exe is not None)
            cfg = self.bundle.cfg
            attrs = {}
            if "A" in cfg.layer_pattern:
                attrs.update(attention.prefill_attention(
                    cfg, self.max_seq, self.bundle.window))
            if cfg.moe is not None:
                attrs.update(moe.start_attrs(cfg, self.batch))
            with spans.span("engine.start.compile",
                            executable_hit=exe is not None, **attrs) as code:
                with compile_cache.counting() as cache:
                    if exe is not None:
                        self._prefill_c, self._decode_c = exe
                    else:
                        self._compile()
                code.attrs.update(cache)
            if self.store is not None and not self.store.has_params(self.key):
                with spans.span("engine.start.save"):
                    self.store.save_params(self.key, self.params)
        self.warm = True
        self.last_breakdown = Breakdown({
            Phase.PROVISION: 0.0, Phase.RUNTIME_INIT: build.seconds,
            Phase.DEPS_LOAD: weights.seconds, Phase.CODE_INIT: code.seconds})
        return self.last_breakdown

    def _compile(self) -> None:
        params_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params)
        bspec = self._prefill_batch_spec()
        self._prefill_c = jax.jit(self.bundle.prefill).lower(
            params_spec, bspec).compile()
        caches_spec = jax.eval_shape(
            lambda p, b: self.bundle.prefill(p, b)[1], params_spec, bspec)
        self._decode_c = jax.jit(moe.counted_decode(
            self.bundle.decode_step)).lower(
            params_spec, caches_spec,
            jax.ShapeDtypeStruct((self.batch,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.eval_shape(self._zero_routed)).compile()
        if self.store is not None:
            self.store.put_executable(
                self.key, (self._prefill_c, self._decode_c))

    def _zero_routed(self):
        """The decode program's running routing counts, before its first
        step (``moe.counted_decode``): none for a model without experts."""
        return {} if self.bundle.cfg.moe is None else moe.zero_counts()

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
        """Greedy generation, one ``engine.run`` span (``repro.spans``):
        prefill, then per step the previous token fetched to the host and
        the next step dispatched.  ``ServeStats`` holds span durations."""
        assert self.warm, "cold engine — call cold_start() first"
        if decode_steps > self.decode_steps:
            raise ValueError(f"{self.arch}: {decode_steps} decode steps asked "
                             f"of a cache with room for {self.decode_steps}")
        stats = ServeStats()
        with spans.span("engine.run", prompt_tokens=int(tokens.shape[1]),
                        decode_steps=decode_steps) as run:
            with spans.span("engine.upload"):
                batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
                if extras:
                    batch.update({k: jnp.asarray(v) for k, v in extras.items()})
            with spans.span("engine.prefill_run") as prefill:
                logits, caches, _, counts = self._prefill_c(self.params, batch)
                jax.block_until_ready(logits)
                if counts:              # routing of this call, over the MoE layers
                    prefill.attrs.update(
                        {k: int(v) for k, v in jax.device_get(counts).items()})
            out = []
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            routed = self._zero_routed()
            with spans.span("engine.decode") as decode:
                p = jnp.asarray(tokens.shape[1], jnp.int32)
                for i in range(decode_steps):
                    with spans.span("engine.token_fetch"):
                        out.append(np.asarray(tok))
                    with spans.span("engine.step_dispatch"):
                        logits, caches, routed = self._decode_c(
                            self.params, caches, tok, p + i, routed)
                        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                with spans.span("engine.final_wait"):
                    jax.block_until_ready(tok)
                if routed:      # the steps' routing, over the MoE layers
                    decode.attrs.update(
                        {k.replace("moe_", "moe_decode_", 1): int(v)
                         for k, v in jax.device_get(routed).items()})
            with spans.span("engine.logits_fetch"):
                stats.logits = np.asarray(logits)
            generated = np.stack(out, axis=1)
            self.last_used = time.monotonic()
        stats.prefill_s = prefill.seconds
        stats.decode_s = decode.seconds
        stats.run_s = run.seconds
        stats.tokens = decode_steps
        return generated, stats


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
            logits, caches, _, _ = bundle.prefill(p, {"tokens": tokens})
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
