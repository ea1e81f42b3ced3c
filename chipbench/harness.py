"""Build a cell from its files, set it up, measure it, check it.

One process, one chip.  Set-up builds a ``ServerlessRouter`` serving the
cell's configuration file at its widths, and lets its first request make
the engine (init, compile or compile-cache hit) and the rest warm the
serve path.  The window then drives ``ServerlessRouter.invoke`` (router ->
EnginePool -> EngineBackend -> InferenceEngine) from the traffic mix's
arrival schedule, one request at a time, and times each request from when
it was due.  After the window the engine is released, the benchmark's
weights are made again from the seed, the sample of served requests is
compared with the plain reference, and the cell's metrics are read by
their readers.

The configuration reaches the program as data: while a run lasts, the
program's ``registry.build_arch`` answers the configuration's name with
``registry.build`` of a ``ModelConfig`` holding the file's values, and
that bundle's initialisation is ``weights.fill`` keyed by the run's seed.
So the engine serves the file's model with the benchmark's weights, made
once, on the device, in its own cold start.

Host spans (``client.wait``, ``router.invoke``, ``engine.cold_start``,
``engine.serve``, and the dispatches ``engine.prefill`` and
``engine.decode_step``) go into the profiler's trace in a ``--trace 1``
run only; the end-to-end run carries no instrumentation in the window
beyond the host clock.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import pathlib
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import arrivals, check, counts, tracing, weights

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WARMUP_REQUESTS = 3     # the first makes the engine; the rest serve warm
CHECKED_REQUESTS = 16   # 256 served tokens through the reference per run


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                   # "end_to_end" or "per_layer"


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    model: dict                 # configs/<config>.json
    mix: dict                   # traffic/<traffic>.json
    metrics: List[Metric]


@dataclass
class Request:
    index: int
    due: float                  # seconds after the window opened
    prompt: np.ndarray          # (1, prompt_tokens)
    sent: float = math.nan
    done: float = math.nan
    lag: float = math.nan       # send time past max(due, previous done)
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None
    cold: bool = False
    startup: Dict[str, float] = field(default_factory=dict)
    prefill_s: float = math.nan
    decode_s: float = math.nan
    steps: int = 0
    logits: Optional[np.ndarray] = None    # after the last decode step


@dataclass
class Run:
    """What a metric reader sees."""

    cell: Cell
    seed: int
    seconds: float
    device_kind: str
    requests: List[Request]
    setup_s: float
    trace: Optional[tracing.Reduced] = None

    @property
    def served(self) -> List[Request]:
        return [r for r in self.requests if r.tokens is not None]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    model = json.loads((root / conf["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    mine = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in mine else [])]
    metrics = ([Metric(m["name"], m["unit"], "end_to_end") for m in e2e]
               + [Metric(m["name"], m["unit"], "per_layer") for m in layer])
    return Cell(name, w["config"], w["traffic"], w["chips"], model, mix, metrics)


def program_config(model: dict):
    """The program's ``ModelConfig`` holding the configuration file's
    values: every key of the file that names one of its fields, nested
    groups (``moe``, ``ssm``, ...) as their own dataclasses."""
    import dataclasses

    from repro import config

    groups = {"moe": config.MoEConfig, "ssm": config.SSMConfig,
              "xlstm": config.XLSTMConfig, "encoder": config.EncoderConfig,
              "vision": config.VisionConfig}
    names = {f.name for f in dataclasses.fields(config.ModelConfig)}
    kw = {k: v for k, v in model.items() if k in names}
    for k, cls in groups.items():
        if isinstance(kw.get(k), dict):
            kw[k] = cls(**kw[k])
    return config.ModelConfig(**kw)


def read_metric(name: str, run: Run) -> Optional[float]:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class _Probe:
    """Spans and serve statistics, recorded from the benchmark's side of
    the program's calls, and the configuration and weights handed to it:
    ``InferenceEngine.serve``, ``InferenceEngine.cold_start`` and
    ``registry.build_arch`` are wrapped for the life of the run."""

    def __init__(self, tracer: tracing.Tracer, cfg, seed: int):
        self.tracer = tracer
        self.cfg = cfg
        self.seed = seed
        self.template = None        # the weights' shapes and dtypes
        self.current: Optional[Request] = None

    @contextlib.contextmanager
    def installed(self):
        import jax

        from repro.models import registry
        from repro.serving.engine import InferenceEngine

        serve, cold_start = InferenceEngine.serve, InferenceEngine.cold_start
        build_arch = registry.build_arch
        probe, tracer = self, self.tracer

        def bench_build_arch(arch, shape=None, *, smoke=False, max_seq=None):
            if arch != probe.cfg.name:
                return build_arch(arch, shape, smoke=smoke, max_seq=max_seq)
            bundle = registry.build(probe.cfg, shape, max_seq=max_seq)
            template = jax.eval_shape(bundle.init, weights.key(0))
            probe.template = template
            bundle.init = lambda key: weights.fill(template, key)
            return bundle

        def traced_serve(engine, tokens, **kw):
            with tracer.span("engine.serve"):
                out, stats = serve(engine, tokens, **kw)
            if probe.current is not None:
                probe.current.prefill_s = stats.prefill_s
                probe.current.decode_s = stats.decode_s
                probe.current.steps = stats.tokens
                probe.current.logits = stats.logits[0]
            return out, stats

        def traced_cold_start(engine, **kw):
            # the engine keys its init with ``engine.seed``: the run's key
            engine.seed = weights.seed32(probe.seed, "weights")
            with tracer.span("engine.cold_start"):
                bd = cold_start(engine, **kw)
            if tracer.enabled:
                engine._prefill_c = tracer.wrap("engine.prefill",
                                                engine._prefill_c)
                engine._decode_c = tracer.wrap("engine.decode_step",
                                               engine._decode_c)
            return bd

        InferenceEngine.serve = traced_serve
        InferenceEngine.cold_start = traced_cold_start
        registry.build_arch = bench_build_arch
        try:
            yield self
        finally:
            InferenceEngine.serve = serve
            InferenceEngine.cold_start = cold_start
            registry.build_arch = build_arch

    def invoke(self, router, function: str, r: Request, t0: float) -> None:
        self.current = r
        try:
            with self.tracer.span("router.invoke"):
                out, rec = router.invoke(function, r.prompt)
            r.tokens = np.asarray(out)[0]
            r.cold = rec.cold
            if rec.startup is not None:
                r.startup = {p.value: s for p, s in rec.startup.seconds.items()}
        except Exception as e:  # the request failed; the run goes on and counts it
            r.error = f"{type(e).__name__}: {e}"
        finally:
            r.done = time.perf_counter() - t0
            self.current = None


def _window(probe: _Probe, router, function: str, reqs: List[Request],
            seconds: float, drain: bool, t0: float) -> None:
    """Send each request when it is due, or when the server is free if it
    is late.  ``drain`` serves every request due in the window; otherwise
    sending stops when the window closes."""
    free = 0.0
    for r in reqs:
        now = time.perf_counter() - t0
        if not drain and now >= seconds:
            break
        if r.due > now:
            with probe.tracer.span("client.wait"):
                time.sleep(max(0.0, r.due - (time.perf_counter() - t0)))
        r.sent = time.perf_counter() - t0
        r.lag = r.sent - max(r.due, free)
        probe.invoke(router, function, r, t0)
        free = r.done


def _release(router) -> None:
    for replica in list(router.pool.replicas.values()):
        router.pool.release(replica)
    gc.collect()


@dataclass
class Served:
    """What a run's window left: its requests, the benchmark's weights
    (made again on the device, for the reference) and its readings."""

    requests: List[Request]
    weights: object
    setup_s: float
    memory_peak_bytes: Optional[int]
    compiles: int
    tracer: tracing.Tracer
    xplane: Optional[str]


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *,
          t_process: float) -> Served:
    """Set up, run the window, read the peak memory, release the engine,
    make the weights again."""
    import jax

    from repro.serving.router import FunctionDef, ServerlessRouter

    dev = jax.devices()[0]
    m, mix = cell.model, cell.mix
    cfg = program_config(m)
    if mix["loop"] != "open":
        raise ValueError(f"{cell.traffic}: loop {mix['loop']!r} is not built")
    p_len, t_len = mix["prompt_tokens"], mix["output_tokens"]
    due = arrivals.open_schedule(mix["rate_per_s"], seconds,
                                 mix["schedule_seed"])
    n_warm = WARMUP_REQUESTS
    prompts = np.random.default_rng([seed, zlib.crc32(b"prompts")]).integers(
        0, m["vocab_size"], (n_warm + len(due), 1, p_len), dtype=np.int32)
    warm = [Request(-1 - i, 0.0, prompts[i]) for i in range(n_warm)]
    reqs = [Request(i, float(d), prompts[n_warm + i]) for i, d in enumerate(due)]

    router = ServerlessRouter(ttl_s=mix["ttl_s"], use_snapshots=False,
                              memory_budget_gb=16.0)
    fdef = FunctionDef(name=cell.config, arch=cfg.name, max_seq=p_len,
                       decode_steps=t_len, smoke=False,
                       memory_gb=counts.param_bytes(m) / 2 ** 30)
    router.register(fdef)
    tracer = tracing.Tracer(trace)
    compiles = tracing.CompileCounter()
    with _Probe(tracer, cfg, seed).installed() as probe:
        t_warm = time.perf_counter()
        for r in warm:
            probe.invoke(router, fdef.name, r, t_warm)
            if r.error:
                raise RuntimeError(f"warm-up request failed: {r.error}")
        tracer.start()
        with compiles:
            t0 = time.perf_counter()
            setup_s = time.time() - t_process
            with tracer.span("window"):
                _window(probe, router, fdef.name, reqs, seconds,
                        mix["drain"], t0)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        xplane = tracer.stop()
    _release(router)
    bench = weights.make(probe.template, seed)
    return Served(reqs, bench, setup_s, peak, compiles.count, tracer, xplane)


def sent(requests: List[Request]) -> List[Request]:
    return [r for r in requests if math.isfinite(r.sent)]


def compare(cell: Cell, seed: int, served: Served, *,
            fp8: bool = False) -> List[check.Check]:
    """The numbers compared, each with its limit.  With ``fp8`` the
    control's: the float8 reference in the program's place."""
    m, t_len = cell.model, cell.mix["output_tokens"]
    done = sent(served.requests)
    bad = [r for r in done if r.tokens is None or len(r.tokens) != t_len]
    read = [check.readings(served.weights, m, r.prompt[0], r.tokens, r.logits,
                           fp8=fp8)
            for r in check.sample(done, seed, CHECKED_REQUESTS)]
    return [
        check.Check("logit_gap", max(g for g, _ in read) if read else None,
                    m["limits"]["logit_gap"]),
        check.Check("logit_err", max(e for _, e in read) if read else None,
                    m["limits"]["logit_err"]),
        check.Check("requests_failed", float(len(bad)), 0.0),
        check.Check("compiles_in_window", float(served.compiles), 0.0),
    ]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float) -> Tuple[dict, List[check.Check]]:
    """One run of a cell.  Returns the result line and the checks."""
    import jax

    dev = jax.devices()[0]
    served = serve(cell, seed, seconds, trace, t_process=t_process)
    checks = compare(cell, seed, served)
    served.weights = None
    gc.collect()
    by_name = {c.name: c for c in checks}
    reduced = None
    if trace:
        reduced = tracing.reduce(served.xplane)
        served.tracer.close()
    run = Run(cell, seed, seconds, dev.device_kind, served.requests,
              served.setup_s, reduced)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for metric in cell.metrics:
        if metric.kind != kind:
            continue
        value = read_metric(metric.name, run)
        if value is not None:
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": served.memory_peak_bytes}
    done = sent(served.requests)
    result = {"correct": all(c.ok for c in checks), "attempted": len(done),
              "failed": int(by_name["requests_failed"].value),
              "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks
