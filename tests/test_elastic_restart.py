"""Elastic restart integration: train on a 4-device mesh, 'lose' two
devices, re-plan the mesh with ElasticMeshPlanner, restore the checkpoint
with the new shardings, and continue training — loss continuity asserted.

Runs in subprocesses (device count locks at first jax init)."""
import os
import pathlib
import subprocess
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PHASE1 = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import registry, common
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.train.train_step import make_train_step
from repro.data.pipeline import DataConfig, PipelineState, TokenPipeline, make_train_batch
from repro.checkpoint.manager import CheckpointManager, CheckpointConfig

ckpt_dir = sys.argv[1]
cfg = get_config("qwen3-4b").reduced()
mesh = make_mesh((2, 2), ("data", "model"))
rules = sharding.default_rules(mesh)
api = registry.get(cfg)
p_sh = sharding.param_shardings(api.spec(cfg), mesh, rules)
with jax.set_mesh(mesh):
    params = api.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt_cfg = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20)
    opt = adamw.init(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg, q_chunk=8, kv_chunk=8))
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, 32, 4, seed=1))
    pstate = PipelineState()
    for _ in range(6):
        batch, pstate = make_train_batch(pipe, pstate, cfg)
        params, opt, m = step(params, opt, batch)
mgr = CheckpointManager(CheckpointConfig(ckpt_dir, async_save=False))
mgr.save(6, (params, opt), {"pipeline_step": pstate.step, "loss": float(m["loss"])})
print("PHASE1_LOSS", float(m["loss"]))
"""

_PHASE2 = r"""
import os, sys
# two of four hosts died -> planner gives a 2-device mesh
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import registry
from repro.distributed import sharding
from repro.distributed.fault_tolerance import ElasticMeshPlanner
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.train.train_step import make_train_step
from repro.data.pipeline import DataConfig, PipelineState, TokenPipeline, make_train_batch
from repro.checkpoint.manager import CheckpointManager, CheckpointConfig

ckpt_dir = sys.argv[1]
plan = ElasticMeshPlanner(devices_per_host=1, model_axis=2, global_batch=4).plan(
    alive_hosts=["h0", "h1"], dead_hosts=["h2", "h3"])
assert plan.n_devices == 2 and plan.model == 2, plan
mesh = make_mesh((plan.data, plan.model), ("data", "model"))
cfg = get_config("qwen3-4b").reduced()
api = registry.get(cfg)
rules = sharding.default_rules(mesh)
p_sh = sharding.param_shardings(api.spec(cfg), mesh, rules)

template_p = api.init(jax.random.PRNGKey(0), cfg)
opt_cfg = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20)
template = (template_p, adamw.init(template_p, opt_cfg))
mgr = CheckpointManager(CheckpointConfig(ckpt_dir))
(params, opt), extra, start = mgr.restore(template)
# reshard onto the SURVIVOR mesh: host arrays -> new shardings
with jax.set_mesh(mesh):
    params = jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s), params, p_sh)
    opt = {"m": jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s), opt["m"], p_sh),
           "v": jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s), opt["v"], p_sh),
           "count": jnp.asarray(opt["count"])}
    step = jax.jit(make_train_step(cfg, opt_cfg, q_chunk=8, kv_chunk=8))
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, 32, 4, seed=1))
    pstate = PipelineState(step=int(extra["pipeline_step"]))
    losses = []
    for _ in range(4):
        batch, pstate = make_train_batch(pipe, pstate, cfg)
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
prev = float(extra["loss"])
# continuity: restored training stays in the same loss regime (no re-init jump)
assert abs(losses[0] - prev) < 1.0, (losses[0], prev)
print("PHASE2_OK", prev, losses)
"""


def test_elastic_restart_after_failure():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    with tempfile.TemporaryDirectory() as d:
        p1 = subprocess.run([sys.executable, "-c", _PHASE1, d],
                            capture_output=True, text=True, env=env,
                            timeout=480, cwd=ROOT)
        assert p1.returncode == 0, p1.stderr[-2000:]
        assert "PHASE1_LOSS" in p1.stdout
        p2 = subprocess.run([sys.executable, "-c", _PHASE2, d],
                            capture_output=True, text=True, env=env,
                            timeout=480, cwd=ROOT)
        assert p2.returncode == 0, p2.stderr[-2000:]
        assert "PHASE2_OK" in p2.stdout
