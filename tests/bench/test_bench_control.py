"""The control: the plain reference computed one precision step below the
stated one (float32 dots as three bf16 passes, not HIGHEST) fails the
comparison that decides `correct`, where the program passes it.  At the
paper's tenant size (n = 256, two stages on 64^2 arrays, one tenant) on
the CPU; the same control runs on the chip at each cell's own size."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from conftest import ROOT

from bench import check, data
from bench.refs import blockamc as ref
from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_control_fails_where_the_program_passes():
    cfg = _config("fig8-fleet")
    acfg = AnalogConfig(g0=cfg["g0"], array_size=cfg["array_size"],
                        nonideal=NonidealConfig(sigma=cfg["sigma"],
                                                r_wire=cfg["r_wire"]))
    n = cfg["n"]
    with jax.default_device(jax.devices("cpu")[0]):
        a = np.asarray(data.wishart_batch(
            jnp.asarray(data.root_key(5, 1)), 1, n, 4))[0]
    key = data.split_keys(data.root_key(5, 2), 1)
    b = np.random.default_rng(5).uniform(-1, 1, (n, 16)).astype(np.float32)
    x_ref = ref.solve(cfg, a[None], key, b[None])[0]
    served = blockamc.ProgrammedSolver.program(
        jnp.asarray(a), jnp.asarray(key[0]), acfg, cfg["stages"])
    x_prog = np.asarray(served.solve_many(jnp.asarray(b)))
    x_ctrl = np.asarray(ref.solve(cfg, a[None], key, b[None],
                                  be=ref.CONTROL)[0])
    limit = cfg["check"]["max_rel_gap"]
    prog = check.rel_gap(x_prog, x_ref).max()
    ctrl = check.rel_gap(x_ctrl, x_ref).max()
    assert prog < limit < ctrl, (prog, limit, ctrl)


def test_reference_matches_the_program_at_float32_rounding():
    """The reference imports nothing of the program; it must still read
    the same noise draws (key discipline) and the same physics."""
    cfg = dict(_config("fig8d-mc512"), n=64, array_size=16)
    acfg = AnalogConfig(g0=cfg["g0"], array_size=16,
                        nonideal=NonidealConfig(sigma=cfg["sigma"]))
    with jax.default_device(jax.devices("cpu")[0]):
        a = np.asarray(data.wishart_batch(
            jnp.asarray(data.root_key(6, 1)), 1, 64, 4))[0]
    keys = data.split_keys(data.root_key(6, 2), 5)
    b = np.random.default_rng(6).uniform(-1, 1, 64).astype(np.float32)
    x = np.asarray(blockamc.solve_batched(jnp.asarray(a), jnp.asarray(b),
                                          jnp.asarray(keys), acfg, stages=2,
                                          mode="fused"))
    x_ref = ref.solve(cfg, a[None], keys, b[None, :, None])[..., 0]
    gap = check.rel_gap(x[..., None], x_ref[..., None]).max()
    assert gap < 2e-6
    # a different key for one draw is a different answer altogether
    other = ref.solve(cfg, a[None], keys[::-1], b[None, :, None])[..., 0]
    assert check.rel_gap(x[..., None], other[..., None]).max() > 1e-2
