"""Pipeline parallelism (GPipe over a mesh axis): subprocess host-mesh test."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, n_devices: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pipeline_matches_sequential_and_differentiates():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from repro.train.pipeline import pipeline_apply, split_stages

S, M, B, D = 4, 8, 2, 16   # stages, microbatches, batch, width
L = 8                      # total layers (2 per stage)
# Auto axes: the test indexes the stage-stacked output (outs[-1]) outside
# shard_map, which an Explicit-typed mesh refuses without an out_sharding.
mesh = jax.make_mesh((S,), ('pp',),
                     axis_types=(jax.sharding.AxisType.Auto,))

key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (L, D, D)) * (0.5 / jnp.sqrt(D))
x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))

def layer(wi, h):
    return jnp.tanh(h @ wi)

def stage_fn(stage_w, h):
    # stage_w: (L/S, D, D)
    def body(h, wi):
        return layer(wi, h), None
    h, _ = jax.lax.scan(body, h, stage_w)
    return h

# ---- sequential reference ----
def seq_all(w, x):
    def body(h, wi):
        return layer(wi, h), None
    def one(xm):
        h, _ = jax.lax.scan(body, xm, w)
        return h
    return jax.vmap(one)(x)

ref = seq_all(w, x)

# ---- pipelined ----
w_staged = split_stages(w, S)    # (S, L/S, D, D)

@partial(jax.shard_map, mesh=mesh, in_specs=(P('pp'), P(None)),
         out_specs=P('pp'), check_vma=False)
def pipe(w_local, x_all):
    out = pipeline_apply(lambda p, h: stage_fn(p[0], h), w_local, x_all, 'pp')
    return out[None]             # (1, M, B, D) per stage

outs = pipe(w_staged, x)         # (S, M, B, D)
got = outs[-1]                   # last stage holds the results
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print('OK forward')

# ---- differentiability: grads flow through ppermute ----
def loss_pipe(w_staged, x):
    outs = pipe(w_staged, x)
    return jnp.sum(outs[-1] ** 2)

def loss_seq(w, x):
    return jnp.sum(seq_all(w, x) ** 2)

g_pipe = jax.grad(loss_pipe)(w_staged, x).reshape(L, D, D)
g_seq = jax.grad(loss_seq)(w, x)
np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                           rtol=2e-4, atol=2e-4)
print('OK grads')
""", n_devices=4)
    assert "OK forward" in out and "OK grads" in out
