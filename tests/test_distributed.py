"""Multi-device tests (pipeline parallelism, shard_map collectives, small
dry-run): spawned in subprocesses so the main test process keeps 1 device
(only dryrun.py may set the 512-device flag, per spec)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, ndev: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_pipeline_parallel_matches_sequential():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import build_pipeline_fn

mesh = make_mesh((4,), ("pod",))
S, n_micro, mb, d = 4, 8, 2, 16
ks = jax.random.split(jax.random.key(0), S)
Ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])

def stage_fn(W, x):
    return jnp.tanh(x @ W)

run = build_pipeline_fn(stage_fn, mesh, axis="pod")
x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
out = run(Ws, x)

ref = x
for s in range(S):
    ref = jnp.tanh(ref @ Ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("PIPELINE OK")
""", ndev=4)


def test_compressed_psum_shard_map():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim.compress import compressed_psum

mesh = make_mesh((4,), ("pod",))
g = jax.random.normal(jax.random.key(0), (4, 256)) * 1e-3
res = jnp.zeros((4, 256))

def f(g, r):
    out, nr = compressed_psum(g[0], "pod", r[0])
    return out[None], nr[None]

out, nr = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                        out_specs=(P("pod"), P("pod")))(g, res)
true_mean = g.mean(axis=0)
err = np.abs(np.asarray(out[0]) - np.asarray(true_mean)).max()
scale = np.abs(np.asarray(g)).max() / 127
assert err < 4 * scale, (err, scale)
print("COMPRESSED PSUM OK", err)
""", ndev=4)


def test_small_mesh_dryrun_smoke_config():
    """The full dry-run path (shardings, policy, lower+compile) on a smoke
    config and a 2×2×2 pod×data×model mesh — fast end-to-end coverage."""
    run_py("""
import jax, numpy as np
from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.specs import input_specs, spec_shardings, mesh_policy
from repro.models.config import ShapeConfig
from repro.optim import OptConfig
from repro.runtime import build_train_step, build_serve_step
from jax.sharding import NamedSharding, PartitionSpec as PS

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
shape = ShapeConfig("tiny_train", 32, 8, "train")
for arch in ("yi-6b", "deepseek-v2-lite-16b", "rwkv6-7b"):
    _, cfg = configs.get(arch)
    opt_cfg = OptConfig()
    specs = input_specs(cfg, shape, opt_cfg)
    shards = spec_shardings(cfg, shape, mesh, specs)
    policy = mesh_policy(cfg, shape, mesh)
    fn = build_train_step(cfg, opt_cfg, policy=policy)
    repl = NamedSharding(mesh, PS())
    jitted = jax.jit(fn, in_shardings=(shards["state"], shards["batch"], repl),
                     out_shardings=(shards["state"], None), donate_argnums=(0,))
    c = jitted.lower(specs["state"], specs["batch"], specs["step"]).compile()
    ca = c.cost_analysis(); ca = ca[0] if isinstance(ca,(list,tuple)) else ca
    assert dict(ca).get("flops", 0) > 0
    print(arch, "TRAIN LOWER+COMPILE OK")

shape_d = ShapeConfig("tiny_decode", 64, 8, "decode")
for arch in ("yi-6b", "jamba-1.5-large-398b"):
    _, cfg = configs.get(arch)
    specs = input_specs(cfg, shape_d)
    shards = spec_shardings(cfg, shape_d, mesh, specs)
    policy = mesh_policy(cfg, shape_d, mesh)
    fn = build_serve_step(cfg, policy=policy)
    repl = NamedSharding(mesh, PS())
    jitted = jax.jit(fn, in_shardings=(shards["params"], shards["batch"],
                                       shards["cache"], repl),
                     out_shardings=(None, shards["cache"]), donate_argnums=(2,))
    c = jitted.lower(specs["params"], specs["batch"], specs["cache"],
                     specs["cache_index"]).compile()
    print(arch, "DECODE LOWER+COMPILE OK")
print("SMALL DRYRUN OK")
""", ndev=8, timeout=900)
