"""Import boundary: the solver stack stands apart from the LM layers.

The solver core, the serving stack and the hybrid refinement package -
both sharded executors included - load and run without the LM model-config
layer (`repro.configs`, `repro.models`) or the LM sharding rules
(`repro.sharding`, `repro.launch`).  Checked in a fresh interpreter, so no
other test's imports can hide an arrow.
"""
import os
import subprocess
import sys

CODE = """
import sys
import jax, jax.numpy as jnp
import repro.core.blockamc as blockamc
import repro.serve, repro.hybrid
from repro.core.analog import AnalogConfig
from repro.core.mc_sharding import make_mc_mesh
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart

n = 16
cfg = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.05))
ka, kb, kn = jax.random.split(jax.random.PRNGKey(3), 3)
a, b = wishart(ka, n), random_rhs(kb, n)
mesh = make_mc_mesh(1)

keys = jax.random.split(kn, 2)
xs = blockamc.solve_batched_sharded(a, b, keys, cfg, stages=1, mesh=mesh)
assert xs.shape == (2, n)
assert jnp.allclose(xs, blockamc.solve_batched(a, b, keys, cfg, stages=1),
                    rtol=1e-5, atol=1e-6)

As = jnp.stack([a, wishart(jax.random.fold_in(ka, 1), n)])
pp = blockamc.program_packed(As, keys, cfg, stages=1)
bs = jax.random.normal(kb, (2, n, 3))
xp = blockamc.execute_arena_packed_sharded(pp, bs, mesh=mesh)
assert xp.shape == (2, n, 3)
assert jnp.allclose(xp, blockamc.execute_arena_packed(pp, bs),
                    rtol=1e-5, atol=1e-6)

for pkg in ("repro.configs", "repro.models", "repro.sharding",
            "repro.launch"):
    held = sorted(m for m in sys.modules
                  if m == pkg or m.startswith(pkg + "."))
    assert not held, (pkg, held)
print("OK")
"""


def test_solver_stack_loads_without_lm_layers():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout
