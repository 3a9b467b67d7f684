"""Distribution-layer tests on a virtual 8-device mesh (subprocess: the
device-count flag must be set before jax initializes; the main test process
keeps 1 device so every other test sees the real topology)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=540, cwd=REPO,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    """One train step on a (2,4) mesh == the same step on 1 device (allowing
    fp tolerance): validates sharding rules + ZeRO specs numerically."""
    code = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig
from repro.models import api
from repro.dist import sharding as SH, steps as ST
from repro.optim import adamw
from jax.sharding import PartitionSpec as P

# small mesh + tiny model: XLA:CPU collectives rendezvous within 40s even
# on a loaded single-core machine (8 device threads starve otherwise)
cfg = ModelConfig(arch='t', family='dense', n_layers=2, d_model=32, n_heads=4,
                  n_kv_heads=2, d_head=8, d_ff=64, vocab=64,
                  dtype='float32', param_dtype='float32', remat='full',
                  attn_chunk=32, loss_chunk=32)
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
ctx = SH.make_ctx(mesh)
params = api.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw.init(params)
B, S = 4, 32
k = jax.random.PRNGKey(1)
batch = {'tokens': jax.random.randint(k, (B,S), 0, 64),
         'labels': jax.random.randint(k, (B,S), 0, 64),
         'mask': jnp.ones((B,S), jnp.float32)}
ocfg = adamw.AdamWConfig()
step = ST.make_train_step(cfg, ctx, ocfg, microbatches=2)
pspecs = SH.param_specs(cfg, ctx, params)
osl = SH.opt_state_specs(cfg, ctx, pspecs, params)
ospecs = adamw.AdamWState(master=osl, m=osl, v=osl, count=P())
isP = lambda x: isinstance(x, P)
nt = lambda t: jax.tree.map(ctx.ns, t, is_leaf=isP)
jit_step = jax.jit(step, in_shardings=(nt(pspecs), nt(ospecs), None, None),
                   out_shardings=(nt(pspecs), nt(ospecs), None))
p2, o2, m2 = jit_step(params, opt, batch, jax.random.PRNGKey(2))

# single-device reference
ctx0 = None
from repro.models.api import loss_fn
def ref_step(params, opt, batch):
    (l, _), g = jax.value_and_grad(lambda p: loss_fn(cfg, p, batch, rng=jax.random.PRNGKey(2))[0])(params), None
    return l
(l_ref, _), g_ref = jax.value_and_grad(
    lambda p: loss_fn(cfg, p, batch, rng=jax.random.PRNGKey(2)), has_aux=True)(params)
print(json.dumps({'loss_sharded': float(m2['loss']), 'loss_ref': float(l_ref),
                  'gnorm': float(m2['grad_norm'])}))
"""
    res = _run_in_subprocess(code)
    assert abs(res["loss_sharded"] - res["loss_ref"]) < 0.05, res
    assert res["gnorm"] > 0


@pytest.mark.slow
def test_int8_grad_sync_close_to_fp32():
    code = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig
from repro.models import api
from repro.dist import sharding as SH, steps as ST
from repro.optim import adamw

cfg = ModelConfig(arch='t', family='dense', n_layers=2, d_model=32, n_heads=2,
                  n_kv_heads=2, d_head=16, d_ff=64, vocab=64,
                  dtype='float32', param_dtype='float32', remat='none',
                  attn_chunk=32, loss_chunk=32)
from jax.sharding import AxisType
mesh = jax.make_mesh((8, 1), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
ctx = SH.make_ctx(mesh)
params = api.init_params(cfg, jax.random.PRNGKey(0))
B, S = 8, 32
k = jax.random.PRNGKey(1)
batch = {'tokens': jax.random.randint(k, (B,S), 0, 64),
         'labels': jax.random.randint(k, (B,S), 0, 64),
         'mask': jnp.ones((B,S), jnp.float32)}
ocfg = adamw.AdamWConfig()
rng = jax.random.PRNGKey(2)
outs = {}
for sync in ['auto', 'int8']:
    opt = adamw.init(params)
    step = ST.make_train_step(cfg, ctx, ocfg, microbatches=1, grad_sync=sync)
    p2, o2, m = jax.jit(step)(params, opt, batch, rng)
    outs[sync] = (float(m['loss']), float(m['grad_norm']))
rel = abs(outs['auto'][1] - outs['int8'][1]) / max(outs['auto'][1], 1e-9)
print(json.dumps({'loss_auto': outs['auto'][0], 'loss_int8': outs['int8'][0],
                  'gnorm_rel_err': rel}))
"""
    res = _run_in_subprocess(code)
    assert abs(res["loss_auto"] - res["loss_int8"]) < 1e-3, res
    assert res["gnorm_rel_err"] < 0.05, res


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    code = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipeline_apply

from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ('pipe',), axis_types=(AxisType.Auto,))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

def stage(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))
piped = pipeline_apply(stage, n_stages, n_micro, mesh)
y_pipe = jax.jit(piped)({'w': Ws}['w'] if False else Ws, x)

# sequential reference
y_ref = x
for s in range(n_stages):
    y_ref = jax.vmap(lambda xx: stage(Ws[s], xx))(y_ref)
err = float(jnp.max(jnp.abs(y_pipe - y_ref)))
print(json.dumps({'err': err}))
"""
    res = _run_in_subprocess(code)
    assert res["err"] < 1e-5, res


@pytest.mark.slow
def test_rosella_scheduler_shard_sync():
    """Paper §5: scheduler shards sync μ̂ via pmean inside shard_map."""
    code = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import learner as lrn, scheduler as rs

shard_map = jax.shard_map
mesh = jax.make_mesh((8,), ('sched',))
n = 4
lcfg = lrn.default_learner_config(mu_bar=8.0)

def shard_fn(mu_hat_shard):
    st = rs.init_rosella(n, lcfg)
    st = st.replace(learner=st.learner.replace(mu_hat=mu_hat_shard[0]))
    st = rs.sync_shard_estimates(st, 'sched')
    return st.learner.mu_hat[None]

mu_shards = jnp.arange(8*n, dtype=jnp.float32).reshape(8, n)
out = jax.jit(shard_map(shard_fn, mesh=mesh, in_specs=P('sched'),
                        out_specs=P('sched')))(mu_shards)
expected = mu_shards.mean(axis=0)
err = float(jnp.max(jnp.abs(out - expected[None])))
print(json.dumps({'err': err}))
"""
    res = _run_in_subprocess(code)
    assert res["err"] < 1e-5, res
