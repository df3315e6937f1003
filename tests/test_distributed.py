"""Distribution tests — run in a subprocess with 8 forced host devices so
the main pytest process keeps its single-device view (the dry-run contract).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.distributed.collectives import ef_int8_allreduce, hierarchical_psum
from repro.distributed.pipeline import pipeline_apply
from repro.distributed.sharding import sharding_rules, single_pod_rules, \
    multi_pod_rules
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as tf
from repro.training.optimizer import AdamW
from repro.training.train_loop import make_train_step, param_pspecs
from repro.training import checkpoint as ckpt

results = {}

# ---- 1. pjit train step: 2x2 mesh == single device -------------------------
cfg = get_config("llama3-8b", tiny=True)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
opt = AdamW(lr=1e-3, warmup_steps=1, total_steps=10)
state = opt.init(params)
data = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

step = make_train_step(cfg, opt)
p1, s1, m1 = jax.jit(step)(params, state, batch)
loss_single = float(m1["loss"])

mesh = make_test_mesh(2, 2)
with mesh, sharding_rules(mesh, single_pod_rules(fsdp=True)):
    specs = param_pspecs(params, mesh)
    shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)
    params_sh = jax.tree_util.tree_map(jax.device_put, params, shardings)
    state_sh = opt.init(params_sh)
    batch_sh = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                for k, v in batch.items()}
    p2, s2, m2 = jax.jit(step)(params_sh, state_sh, batch_sh)
    loss_mesh = float(m2["loss"])
results["pjit_single_vs_mesh"] = abs(loss_single - loss_mesh)

# ---- 2. multi-pod mesh (2x2x2) ---------------------------------------------
mesh3 = make_test_mesh(2, 2, pods=2)
with mesh3, sharding_rules(mesh3, multi_pod_rules(fsdp=True)):
    specs = param_pspecs(params, mesh3)
    shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh3, s), specs)
    params_sh = jax.tree_util.tree_map(jax.device_put, params, shardings)
    state_sh = opt.init(params_sh)
    batch_sh = {k: jax.device_put(v, NamedSharding(mesh3, P(("pod", "data"))))
                for k, v in batch.items()}
    p3, s3, m3 = jax.jit(step)(params_sh, state_sh, batch_sh)
results["pjit_multipod_loss_delta"] = abs(loss_single - float(m3["loss"]))

# ---- 3. elastic reshard: save on 2x2, restore on 2x2x2 ----------------------
import tempfile
ckpt_dir = tempfile.mkdtemp(prefix="repro_elastic_")
ckpt.save(ckpt_dir, 1, p2)
abstract = jax.tree_util.tree_map(
    lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)
with mesh3, sharding_rules(mesh3, multi_pod_rules(fsdp=True)):
    specs3 = param_pspecs(abstract, mesh3)
    sh3 = jax.tree_util.tree_map(lambda s: NamedSharding(mesh3, s), specs3)
    restored, _ = ckpt.restore(ckpt_dir, 1, abstract, sh3)
d = jax.tree_util.tree_map(
    lambda a, b: float(np.max(np.abs(
        np.asarray(jax.device_get(a), np.float32)
        - np.asarray(jax.device_get(b), np.float32)))), p2, restored)
results["elastic_reshard_delta"] = max(jax.tree_util.tree_leaves(d))
import shutil; shutil.rmtree(ckpt_dir, ignore_errors=True)

# ---- 4. pipeline parallelism: 4 stages == dense ------------------------------
pmesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
L, D = 8, 16
keys = jax.random.split(jax.random.PRNGKey(1), L)
blocks = {"w": jax.vmap(lambda k: jax.random.normal(k, (D, D)) * 0.1)(keys)}
x = jax.random.normal(jax.random.PRNGKey(2), (8, D))

def block_fn(p, x):
    return jnp.tanh(x @ p["w"])

dense = x
for i in range(L):
    dense = block_fn({"w": blocks["w"][i]}, dense)
piped = pipeline_apply(block_fn, blocks, x, pmesh, stage_axis="pod",
                       n_micro=4)
results["pipeline_vs_dense"] = float(jnp.max(jnp.abs(dense - piped)))

# ---- 5. collectives: hierarchical psum + int8 EF all-reduce ------------------
mesh3b = make_test_mesh(2, 2, pods=2)
xs = jax.random.normal(jax.random.PRNGKey(3), (8, 16))

def hier(x):
    return hierarchical_psum(x, "pod", "data")

def plain(x):
    return jax.lax.psum(x, ("pod", "data"))

sm = lambda f: jax.shard_map(f, mesh=mesh3b, in_specs=P(None, "model"),
                             out_specs=P(None, "model"), check_vma=False)
a = sm(hier)(xs)
b = sm(plain)(xs)
results["hier_psum_delta"] = float(jnp.max(jnp.abs(a - b)))

g = jax.random.normal(jax.random.PRNGKey(4), (4, 8)) * 0.1
err0 = jnp.zeros((4, 8))

def efar(g, e):
    return ef_int8_allreduce(g, e, ("data",))

mean_g, new_err = jax.shard_map(
    efar, mesh=mesh3b, in_specs=(P(None, None), P(None, None)),
    out_specs=(P(None, None), P(None, None)), check_vma=False)(g, err0)
# all shards hold the same g ⇒ mean == g up to int8 quantisation error
results["ef_int8_error"] = float(jnp.max(jnp.abs(mean_g - g)))
results["ef_feedback_nonzero"] = float(jnp.max(jnp.abs(new_err)))

print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS ")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[-1][len("RESULTS "):])


def test_pjit_mesh_matches_single_device(dist_results):
    assert dist_results["pjit_single_vs_mesh"] < 5e-3


def test_multipod_mesh_runs(dist_results):
    assert dist_results["pjit_multipod_loss_delta"] < 5e-3


def test_elastic_reshard_exact(dist_results):
    assert dist_results["elastic_reshard_delta"] == 0.0


def test_pipeline_parallel_matches_dense(dist_results):
    assert dist_results["pipeline_vs_dense"] < 1e-5


def test_hierarchical_psum_matches_plain(dist_results):
    assert dist_results["hier_psum_delta"] < 1e-5


def test_int8_error_feedback_allreduce(dist_results):
    assert dist_results["ef_int8_error"] < 2e-3     # quantisation bounded
    assert dist_results["ef_feedback_nonzero"] > 0  # residual carried
