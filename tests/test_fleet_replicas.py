"""A fleet of four replicas, one per device, behind the router's defaults.

One signature's load spreads over every replica in full batches; the
lead replica programs each tenant once and every other replica serves a
bit-identical copy on its own device; the router's spans say where each
request went and what programming cost.  The fleet runs on four host
devices in a subprocess (the device count must be set before JAX starts),
once for the three tests that read it.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Served float32 answers against the float64 run of the same programmed
# plan: the cascade's float32 rounding over a condition number of a few
# tens reads ~1e-6 here; a wrong plan, another draw or a lost column is
# O(1e-2) or more.
PLAN_REL_BOUND = 1e-4

_SCRIPT = r"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart
from repro.runtime import tracing
from repro.serve import ReplicatedSolverFleet, SolverService

N, TENANTS, BURST, SEQ = 16, 16, 128, 64
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))
KEY = jax.random.PRNGKey(5)
mats = [np.asarray(wishart(jax.random.fold_in(KEY, i), N))
        for i in range(TENANTS)]
keys = [jax.random.fold_in(KEY, 100 + i) for i in range(TENANTS)]
ids = [f"t{i}" for i in range(TENANTS)]
rng = np.random.default_rng(0)
rhs = rng.uniform(-1.0, 1.0, (SEQ, N)).astype(np.float32)


class Sink:
    def __init__(self):
        self.spans, self.active = [], True
        self._lock = threading.Lock()


def make_fleet(replicas):
    fleet = ReplicatedSolverFleet(lambda: SolverService(CFG, stages=1),
                                  replicas, devices=jax.devices()[:replicas])
    fleet.start()
    for mid, a, key in zip(ids, mats, keys):
        fleet.program(mid, a, key=key)
    return fleet


def sequential(fleet):
    # one request at a time: each answer is a dispatch of one rhs
    out = []
    for j in range(SEQ):
        fut = fleet.submit(ids[j % TENANTS], rhs[j])
        fleet.flush_now()
        out.append(np.asarray(fut.result(timeout=60).x))
    return np.stack(out)


sink = Sink()
tracing.enable(sink)
fleet = make_fleet(4)
engines = fleet.replica_engines()
lead = engines["r0"]
identical, own_device = True, True
for name, eng in engines.items():
    for mid in ids:
        mine = eng.service.solver(mid)
        ref = lead.service.solver(mid)
        for x, y in zip(jax.tree_util.tree_leaves((mine.finalized,
                                                   mine.arena, mine.flat)),
                        jax.tree_util.tree_leaves((ref.finalized, ref.arena,
                                                   ref.flat))):
            identical &= bool(np.array_equal(np.asarray(x), np.asarray(y)))
            own_device &= x.devices() == {eng.device}
programs = [s[3] for s in sink.spans if s[0] == "fleet.program"]

futs = [fleet.submit(ids[i % TENANTS], rhs[i % SEQ]) for i in range(BURST)]
burst_ok = all(np.all(np.isfinite(f.result(timeout=60).x)) for f in futs)
submits = [s[3] for s in sink.spans if s[0] == "fleet.submit"]
tracing.disable()
answered = {name: e.stats.answered for name, e in engines.items()}
routed = dict(fleet.stats.routed)
moves = fleet.stats.affinity_moves
seq4 = sequential(fleet)
fleet.stop()

one = make_fleet(1)
seq1 = sequential(one)
one.stop()

execute_flat = jax.jit(blockamc.execute_flat, static_argnames=("cfg",))
plan_gap = 0.0
with jax.enable_x64():
    for j in range(SEQ):
        solver = lead.service.solver(ids[j % TENANTS])
        fp64 = jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.asarray(x), jnp.float64)
            if jnp.issubdtype(x.dtype, jnp.floating) else jnp.asarray(x),
            solver.flat)
        ref = np.asarray(execute_flat(fp64, jnp.asarray(rhs[j], jnp.float64),
                                      CFG))
        plan_gap = max(plan_gap,
                       float(np.linalg.norm(seq4[j] - ref)
                             / np.linalg.norm(ref)))

print(json.dumps({
    "devices": [str(e.device) for e in engines.values()],
    "identical": identical, "own_device": own_device,
    "programs": programs, "burst_ok": burst_ok, "burst": BURST,
    "answered": answered, "routed": routed, "moves": moves,
    "submits": [{k: a[k] for k in ("replica", "moved")} for a in submits],
    "seq_equal": bool(np.array_equal(seq4, seq1)),
    "plan_gap": plan_gap}))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_signature_spreads_over_four_replicas(four):
    """16 tenants of one signature, a burst of 128 requests, the router's
    defaults: every replica serves, none serves more than 40%, and the
    signature's affinity moved."""
    assert four["burst_ok"]
    assert len(set(four["devices"])) == 4
    answered = four["answered"]
    assert set(answered) == {"r0", "r1", "r2", "r3"}
    assert sum(answered.values()) == four["burst"]
    assert min(answered.values()) > 0
    assert max(answered.values()) <= 0.4 * four["burst"]
    assert four["routed"] == answered
    assert four["moves"] > 0


def test_program_installs_bit_identical_copies(four):
    """The lead programs each tenant once; the three others serve a copy
    that is bit-identical and lives on their own device.  The four-replica
    fleet answers exactly as a one-replica fleet under the same keys, and
    both are the float64 run of the same programming draw to float32
    rounding."""
    assert four["identical"] and four["own_device"]
    programs = four["programs"]
    assert len(programs) == 16
    assert all(p["replicas"] == 4 and p["copies"] == 3 for p in programs)
    assert sorted(p["tenant"] for p in programs) == sorted(
        f"t{i}" for i in range(16))
    assert four["seq_equal"]
    assert four["plan_gap"] <= PLAN_REL_BOUND


def test_submit_span_names_the_replica_and_the_move(four):
    submits = four["submits"]
    assert len(submits) == four["burst"]
    assert {s["replica"] for s in submits} == {"r0", "r1", "r2", "r3"}
    assert {s["moved"] for s in submits} <= {0, 1}
    assert sum(s["moved"] for s in submits) == four["moves"]
    counts = {r: sum(s["replica"] == r for s in submits)
              for r in ("r0", "r1", "r2", "r3")}
    assert counts == four["routed"]
