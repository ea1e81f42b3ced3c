"""Sharding rules + dry-run machinery: divisibility guarantees, full param
coverage, collective-parse sanity, and a true multi-device jit in a
subprocess (XLA_FLAGS must not leak into this process)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro import sharding
from repro.config import ARCH_IDS, SHAPES, get_config, get_shape, supports_shape
from repro.launch.dryrun import collective_bytes


class _FakeMesh:
    shape = {"data": 16, "model": 16}


def _mesh():
    # a real Mesh over 1 device can't have size-16 axes; use the production
    # mesh only inside the subprocess test.  Here we fake the shape dict.
    return _FakeMesh()


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_rules_respect_divisibility(arch, shape_name):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = _mesh()
    rules = sharding.make_rules(cfg, shape, mesh)

    def size(ax):
        if ax is None:
            return 1
        if isinstance(ax, str):
            return mesh.shape[ax]
        return int(jax.numpy.prod(jax.numpy.asarray([mesh.shape[a] for a in ax])))

    if rules["heads"]:
        assert cfg.num_heads % size(rules["heads"]) == 0
    if rules["qkv"]:
        assert cfg.q_dim % size(rules["qkv"]) == 0
        assert rules["heads"] is not None   # qkv sharded only with heads
    if rules["expert"]:
        assert cfg.moe.num_experts % size(rules["expert"]) == 0
    if rules["vocab_param"]:
        assert cfg.vocab_size % size(rules["vocab_param"]) == 0
    if rules["batch"]:
        assert shape.global_batch % size(rules["batch"]) == 0
    if rules.get("cache_seq"):
        assert shape.seq_len % size(rules["cache_seq"]) == 0


def test_collective_bytes_parser():
    hlo = textwrap.dedent("""
      %ag = bf16[2,4096]{1,0} all-gather(%x), replica_groups={}
      %ar = f32[128]{0} all-reduce(%y), to_apply=%add
      %nothing = f32[4]{0} add(%a, %b)
      %a2a = bf16[8,16]{1,0} all-to-all(%z)
    """)
    got = collective_bytes(hlo)
    assert got["all-gather"] == 2 * 4096 * 2
    assert got["all-reduce"] == 128 * 4
    assert got["all-to-all"] == 8 * 16 * 2
    assert "collective-permute" not in got


SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import sharding
from repro.config import get_config, reduced, InputShape
from repro.models import registry
from repro.launch import specs as S
from repro.launch.mesh import make_mesh

# tiny mesh exercising the same code path: (data=2, model=4)
mesh = make_mesh((2, 4), ("data", "model"))
cfg = reduced(get_config("qwen3-moe-30b-a3b"), d_model=256)
shape = InputShape("t", 32, 4, "train")
rules = sharding.make_rules(cfg, shape, mesh)
bundle = registry.build(cfg, max_seq=32)
params = bundle.init(jax.random.key(0))
p_sh = S.params_shardings(jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params), rules, mesh)
params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
batch = {"tokens": jnp.ones((4, 32), jnp.int32),
         "labels": jnp.ones((4, 32), jnp.int32)}
with sharding.use_rules(rules, mesh):
    with mesh:
        loss, metrics = jax.jit(bundle.loss)(params, batch)
# compare against single-device unsharded execution
loss1, _ = jax.jit(bundle.loss)(jax.device_put(jax.tree.map(np.asarray, params)), batch)
print(json.dumps({"sharded": float(loss), "unsharded": float(loss1)}))
"""


def test_sharded_execution_matches_unsharded():
    """Run the MoE model under a real 8-device (2x4) mesh in a subprocess;
    the sharded loss must equal the single-device loss.  The child stays
    on the CPU: a scrubbed environment would let it reach for the TPU
    runtime that another test process may hold."""
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = {"PYTHONPATH": str(repo / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    if "HOME" in os.environ:
        env["HOME"] = os.environ["HOME"]
    res = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_SCRIPT], capture_output=True,
        text=True, env=env, cwd=repo, timeout=500)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert abs(out["sharded"] - out["unsharded"]) < 2e-3, out


def test_long_500k_support_matrix():
    runs = {a: supports_shape(get_config(a), get_shape("long_500k"))
            for a in ARCH_IDS}
    assert runs["xlstm_125m"] and runs["jamba_v01_52b"] and runs["h2o_danube3_4b"]
    assert not runs["starcoder2_15b"] and not runs["arctic_480b"]
    assert sum(runs.values()) == 3
