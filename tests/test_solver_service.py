"""SolverService error/edge paths + the hybrid `solve_refined` mode.

Happy-path batching coverage lives in test_programmed_solver.py; this file
pins the service's failure discipline (nothing queued is ever dropped, bad
requests are rejected before touching state) and the new refined mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.analog import AnalogConfig
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import random_rhs, wishart
from repro.serve import SolverService

KEY = jax.random.PRNGKey(21)
KA, KB, KN = jax.random.split(KEY, 3)
N = 32
CFG = AnalogConfig(array_size=16, nonideal=NonidealConfig(sigma=0.02))


def _service():
    svc = SolverService(CFG, stages=1)
    a = wishart(KA, N)
    svc.program("m0", a, KN)
    return svc, a


def test_flush_empty_queue_returns_n_by_0():
    svc, _ = _service()
    xs = svc.flush("m0")
    assert xs.shape == (N, 0)
    assert svc.stats("m0").solve_calls == 0     # nothing was solved
    xs = svc.flush("m0", refined=True)          # refined path: same contract
    assert xs.shape == (N, 0)


def test_submit_rejects_mismatched_rhs():
    svc, _ = _service()
    with pytest.raises(ValueError, match="rhs"):
        svc.submit("m0", jnp.zeros((N, 2)))     # matrix, not a vector
    with pytest.raises(ValueError, match="rhs"):
        svc.submit("m0", jnp.zeros((N + 1,)))   # wrong length
    with pytest.raises(ValueError, match="rhs"):
        svc.submit("m0", jnp.zeros(()))         # scalar
    assert svc.pending("m0") == 0               # rejected before queueing


def test_submit_snapshots_the_rhs_buffer():
    """Admission must copy: a caller reusing (and mutating) one buffer
    across submits cannot corrupt an already-queued request."""
    svc, _ = _service()
    buf = np.arange(N, dtype=np.float32)
    # expectation from an independent buffer: jnp.asarray(np_buf) may be
    # zero-copy on CPU, so solving from `buf` itself would race the
    # mutation below inside jax's async dispatch
    want = svc.solver("m0").solve(jnp.asarray(np.arange(N,
                                                        dtype=np.float32)))
    svc.submit("m0", buf)
    buf[:] = 0.0                    # caller reuses the buffer
    xs = svc.flush("m0")
    np.testing.assert_allclose(np.asarray(xs[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_unknown_matrix_id_raises():
    svc, _ = _service()
    with pytest.raises(KeyError):
        svc.solve("nope", jnp.zeros((N,)))
    with pytest.raises(KeyError):
        svc.submit("nope", jnp.zeros((N,)))
    with pytest.raises(KeyError):
        svc.solve_refined("nope", jnp.zeros((N,)))


def test_double_program_replaces_cleanly_or_refuses_over_pending():
    svc, a = _service()
    first = svc.solver("m0")
    svc.solve("m0", random_rhs(KB, N))
    # re-programming with an empty queue replaces solver and resets stats
    a2 = wishart(KB, N)
    svc.program("m0", a2, KN)
    assert svc.solver("m0") is not first
    st = svc.stats("m0")
    assert st.solve_calls == 0 and st.rhs_served == 0
    assert st.program_time_s > 0
    x = svc.solve("m0", random_rhs(KB, N))      # solves the *new* matrix
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(svc.solver("m0").solve(random_rhs(KB, N))),
        rtol=1e-5, atol=1e-6)
    # ...but refuses while right-hand sides are still queued
    svc.submit("m0", random_rhs(KB, N))
    with pytest.raises(RuntimeError, match="pending"):
        svc.program("m0", a, KN)
    assert svc.pending("m0") == 1               # queue untouched by refusal


def test_solve_refined_beats_raw_solve():
    svc, a = _service()
    b = random_rhs(KB, N)
    x_raw = svc.solve("m0", b)
    x_ref = svc.solve_refined("m0", b, tol=1e-6, maxiter=200)
    res_raw = float(jnp.linalg.norm(b - a @ x_raw) / jnp.linalg.norm(b))
    res_ref = float(jnp.linalg.norm(b - a @ x_ref) / jnp.linalg.norm(b))
    assert res_ref <= 1e-5                      # f32 digital refinement
    assert res_ref < res_raw                    # the noisy solve alone
    st = svc.stats("m0")
    assert st.refined_calls == 1 and st.refine_iters >= 1
    assert st.solve_calls == 2 and st.rhs_served == 2


def test_refined_flush_matches_immediate_refined_solves():
    svc, a = _service()
    cols = [jax.random.normal(jax.random.fold_in(KB, j), (N,))
            for j in range(5)]
    for b in cols:
        svc.submit("m0", b)
    xs = svc.flush("m0", refined=True, tol=1e-6, maxiter=200)
    assert xs.shape == (N, 5) and svc.pending("m0") == 0
    for j, b in enumerate(cols):
        res = float(jnp.linalg.norm(b - a @ xs[:, j]) / jnp.linalg.norm(b))
        assert res <= 1e-5
        np.testing.assert_allclose(
            np.asarray(xs[:, j]),
            np.asarray(svc.solve_refined("m0", b, tol=1e-6, maxiter=200)),
            rtol=1e-4, atol=1e-5)
    assert svc.stats("m0").rhs_served == 10     # 5 flushed + 5 immediate
    assert svc.stats("m0").refined_calls == 6


def test_refined_flush_gmres_mode():
    svc, a = _service()
    for j in range(3):
        svc.submit("m0", jax.random.normal(jax.random.fold_in(KB, j), (N,)))
    xs = svc.flush("m0", refined=True, method="gmres", tol=1e-5,
                   maxiter=256, restart=16, use_precond=False)
    assert xs.shape == (N, 3)
    for j in range(3):
        b = jax.random.normal(jax.random.fold_in(KB, j), (N,))
        r = float(jnp.linalg.norm(b - a @ xs[:, j]) / jnp.linalg.norm(b))
        assert r <= 1e-4


# ------------------- front-door validation (admission) --------------------

def test_program_rejects_nonfinite_matrix_before_state_change():
    svc, a = _service()
    bad = np.asarray(a).copy()
    bad[2, 3] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        svc.program("m1", jnp.asarray(bad), KN)
    assert "m1" not in svc.matrix_ids           # nothing half-programmed
    inf = np.asarray(a).copy()
    inf[0, 0] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        svc.program("m1", jnp.asarray(inf), KN)


def test_program_rejects_wrong_dtype_and_shape():
    svc, _ = _service()
    with pytest.raises(ValueError, match="floating"):
        svc.program("m1", jnp.eye(N, dtype=jnp.int32), KN)
    with pytest.raises(ValueError, match="square"):
        svc.program("m1", jnp.zeros((N, N + 1)), KN)
    with pytest.raises(ValueError, match="square"):
        svc.program("m1", jnp.zeros((N,)), KN)
    assert "m1" not in svc.matrix_ids


def test_submit_rejects_nonfinite_and_wrong_dtype():
    svc, _ = _service()
    bad = np.ones(N)
    bad[7] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        svc.submit("m0", bad)
    with pytest.raises(ValueError, match="floating"):
        svc.submit("m0", np.arange(N))          # int64
    assert svc.pending("m0") == 0               # nothing was queued


# (where, input, accepted).  Every case runs under the strictest host-to-
# device guard: "disallow_explicit" also refuses `jnp.asarray`/`device_put`
# of a host array, which plain "disallow" lets through, so the gate must
# reach its verdict from the dtype the input carries, with no upload.
GATE_CASES = {
    "f32": ("submit", lambda: np.ones(N, np.float32), True),
    "f64": ("submit", lambda: np.ones(N, np.float64), True),
    "bf16_jax": ("submit", lambda: jnp.ones(N, jnp.bfloat16), True),
    "int64": ("submit", lambda: np.arange(N), False),
    "bool": ("submit", lambda: np.ones(N, bool), False),
    "complex": ("submit", lambda: np.ones(N, np.complex64), False),
    "int32_jax_matrix": ("program",
                         lambda: jnp.eye(N, dtype=jnp.int32), False),
}


@pytest.mark.parametrize("case", list(GATE_CASES))
def test_dtype_gate_reads_the_dtype_on_the_host(case):
    where, make, accepted = GATE_CASES[case]
    svc, _ = _service()
    x = make()                                  # made before the guard
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        if accepted:
            assert svc.submit("m0", x) == 0
        elif where == "submit":
            with pytest.raises(ValueError, match="floating"):
                svc.submit("m0", x)
        else:
            with pytest.raises(ValueError, match="floating"):
                svc.program("m1", x, KN)
    assert svc.pending("m0") == (1 if accepted else 0)
    assert "m1" not in svc.matrix_ids           # nothing after a rejection


def test_nan_rhs_cannot_corrupt_cobatched_tenants():
    """One tenant's NaN rhs must be rejected at its own front door - a
    co-batched healthy tenant's packed answers stay exactly what they
    would have been (the satellite regression from ISSUE.md)."""
    svc = SolverService(CFG, stages=1)
    a0, a1 = wishart(KA, N), wishart(jax.random.fold_in(KA, 1), N)
    svc.program("good", a0, KN)
    svc.program("evil", a1, jax.random.fold_in(KN, 1))
    good_b = [random_rhs(jax.random.fold_in(KB, j), N) for j in range(2)]
    for b in good_b:
        svc.submit("good", b)
    bad = np.ones(N)
    bad[0] = np.inf
    with pytest.raises(ValueError):
        svc.submit("evil", bad)
    svc.submit("evil", random_rhs(jax.random.fold_in(KB, 9), N))
    answers = svc.flush_all()
    # reference: the same healthy queue flushed alone on a fresh service
    ref = SolverService(CFG, stages=1)
    ref.program("good", a0, KN)
    for b in good_b:
        ref.submit("good", b)
    np.testing.assert_allclose(np.asarray(answers["good"]),
                               np.asarray(ref.flush("good")),
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.isfinite(answers["evil"]))


def test_discard_pending_unblocks_reprogram():
    svc, a = _service()
    svc.submit("m0", random_rhs(KB, N))
    with pytest.raises(RuntimeError, match="pending"):
        svc.program("m0", a, KN)
    assert svc.discard_pending("m0") == 1
    assert svc.pending("m0") == 0
    svc.program("m0", a, KN)                    # now allowed
    assert svc.discard_pending("m0") == 0       # idempotent on empty


def test_per_matrix_cfg_override_rebuckets_only_that_tenant():
    svc, a = _service()
    a1 = wishart(jax.random.fold_in(KA, 2), N)
    svc.program("m1", a1, jax.random.fold_in(KN, 2))
    assert svc.signature("m0") == svc.signature("m1")
    wv = CFG.with_(nonideal=NonidealConfig(sigma=0.02, wv_iters=2))
    svc.program("m1", a1, jax.random.fold_in(KN, 3), cfg=wv)
    assert svc.signature("m0") != svc.signature("m1")
    assert svc.matrix_cfg("m1") is wv
    assert svc.matrix_cfg("m0") is CFG
    # differently-configured tenants still flush together (separate
    # buckets inside one flush_all call)
    svc.submit("m0", random_rhs(KB, N))
    svc.submit("m1", random_rhs(jax.random.fold_in(KB, 1), N))
    answers = svc.flush_all()
    assert set(answers) == {"m0", "m1"}
    for mid, am in (("m0", a), ("m1", a1)):
        b = random_rhs(KB if mid == "m0" else jax.random.fold_in(KB, 1), N)
        r = float(np.linalg.norm(np.asarray(am) @ answers[mid][:, 0]
                                 - np.asarray(b))
                  / np.linalg.norm(np.asarray(b)))
        assert r < 0.6                          # raw analog quality


def test_solve_fallback_is_digital_grade_and_counted():
    svc, a = _service()
    b = random_rhs(KB, N)
    x = svc.solve_fallback("m0", b, tol=1e-6)
    res = float(jnp.linalg.norm(b - a @ x) / jnp.linalg.norm(b))
    assert res <= 1e-5                          # no analog error floor
    bs = jnp.stack([b, random_rhs(jax.random.fold_in(KB, 1), N)], axis=1)
    xs = svc.solve_fallback("m0", bs, tol=1e-6)
    assert xs.shape == (N, 2)
    st = svc.stats("m0")
    assert st.rhs_served == 3 and st.refined_calls == 2
    assert st.refine_iters >= 1                 # digital spend is visible
