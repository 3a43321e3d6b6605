"""Checkpoint layer + durable programmed-state store contract (TESTING.md).

The contract under test:

* flatten keys are `jax.tree_util.keystr` paths, so a dict key `"0"` and
  a sequence index `0` are DIFFERENT leaves - the historical str()-joined
  keys collapsed them, letting a list-shaped checkpoint silently restore
  into a dict-shaped tree;
* every loaded leaf is cross-checked against the manifest's recorded
  shape/dtype: a truncated or rewritten file raises
  `CheckpointCorruptionError`, never a silent cast;
* `extra` manifest metadata (programming signatures, canary trips) rides
  along verbatim;
* `ProgramStore` round-trips a `ProgrammedSolver`'s programmed state
  bit-identically (same conductance stacks => same answers on CPU), and
  its identity layer rejects restores against a different matrix, key or
  plan signature with `StaleCheckpointError` BEFORE any array is read;
* `corrupt(how="truncate")` is caught by the integrity layer;
  `corrupt(how="values")` is manifest-consistent by design - restore
  succeeds but the answers are wrong, which is exactly why the fleet's
  install path re-runs the canary against the ORIGINAL trip threshold
  (that rejection is pinned in test_router.py).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointCorruptionError, CheckpointError,
                              ProgramStore, StaleCheckpointError, latest_step,
                              load_manifest, restore_checkpoint,
                              save_checkpoint)
from repro.core.analog import AnalogConfig
from repro.core.blockamc import ProgrammedSolver, plan_signature
from repro.core.nonideal import NonidealConfig
from repro.data.matrices import wishart

KEY = jax.random.PRNGKey(11)
N = 16
CFG = AnalogConfig(array_size=8, nonideal=NonidealConfig(sigma=0.02))


# ---------------------------------------------------------------------------
# flatten-key aliasing regression
# ---------------------------------------------------------------------------

def test_list_index_and_dict_key_do_not_alias(tmp_path):
    """A checkpoint saved from {"x": [a, b]} must NOT restore into
    {"x": {"0": ..., "1": ...}} - under the old str()-joined keys both
    flattened to "x/0", "x/1" and the restore silently succeeded."""
    a = np.arange(4.0)
    b = np.full(4, 7.0)
    save_checkpoint(str(tmp_path), 0, {"x": [a, b]})
    with pytest.raises(KeyError):
        restore_checkpoint(str(tmp_path), 0,
                           {"x": {"0": np.zeros(4), "1": np.zeros(4)}})


def test_list_tree_roundtrip_exact(tmp_path):
    tree = {"x": [np.arange(4.0), np.full(4, 7.0)], "y": np.eye(3)}
    save_checkpoint(str(tmp_path), 0, tree)
    out = restore_checkpoint(
        str(tmp_path), 0,
        {"x": [np.zeros(4), np.zeros(4)], "y": np.zeros((3, 3))})
    assert np.array_equal(np.asarray(out["x"][0]), tree["x"][0])
    assert np.array_equal(np.asarray(out["x"][1]), tree["x"][1])
    assert np.array_equal(np.asarray(out["y"]), tree["y"])


# ---------------------------------------------------------------------------
# integrity layer: manifest cross-check
# ---------------------------------------------------------------------------

def _leaf_file(directory, step, key):
    manifest = load_manifest(directory, step)
    meta = manifest["leaves"][key]
    return os.path.join(directory, f"step_{step:08d}", meta["file"])


def test_truncated_leaf_raises_corruption(tmp_path):
    save_checkpoint(str(tmp_path), 0, {"w": np.arange(64.0)})
    fpath = _leaf_file(str(tmp_path), 0, "['w']")
    with open(fpath, "r+b") as f:
        f.truncate(os.path.getsize(fpath) // 2)
    with pytest.raises(CheckpointCorruptionError):
        restore_checkpoint(str(tmp_path), 0, {"w": np.zeros(64)})


def test_manifest_shape_mismatch_raises_corruption(tmp_path):
    """A leaf file rewritten with a different shape/dtype than the
    manifest recorded must fail the cross-check - even when its shape
    happens to match the target tree (the silent-cast hazard)."""
    save_checkpoint(str(tmp_path), 0, {"w": np.arange(8.0)})
    fpath = _leaf_file(str(tmp_path), 0, "['w']")
    np.save(fpath, np.arange(8, dtype=np.int32))        # dtype flip
    with pytest.raises(CheckpointCorruptionError):
        restore_checkpoint(str(tmp_path), 0, {"w": np.zeros(8)})


def test_extra_metadata_roundtrip(tmp_path):
    save_checkpoint(str(tmp_path), 3, {"w": np.zeros(2)},
                    extra={"trip": 0.5, "signature": "sig"})
    assert latest_step(str(tmp_path)) == 3
    manifest = load_manifest(str(tmp_path), 3)
    assert manifest["extra"] == {"trip": 0.5, "signature": "sig"}


# ---------------------------------------------------------------------------
# ProgramStore: durable programmed-solver state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def programmed():
    a = wishart(KEY, N)
    solver = ProgrammedSolver.program(a, jax.random.fold_in(KEY, 1), CFG,
                                      stages=1)
    sig = plan_signature(N, 1, CFG)
    return a, jax.random.fold_in(KEY, 1), solver, sig


def test_program_store_roundtrip_bit_identical(tmp_path, programmed):
    a, key, solver, sig = programmed
    store = ProgramStore(str(tmp_path))
    store.save("m", solver, a, key, sig, extra={"trip": 0.5})
    assert store.has("m") and store.matrix_ids() == ["m"]

    restored, meta = store.restore("m", solver, a, key, sig)
    assert meta["trip"] == 0.5
    assert restored.n == solver.n and "mode" not in meta
    b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 2), (N,)))
    x0 = np.asarray(solver.solve(jnp.asarray(b)))
    x1 = np.asarray(restored.solve(jnp.asarray(b)))
    # same conductance stacks => the same fused program => identical bits
    assert np.array_equal(x0, x1)


def test_program_store_stale_rejections(tmp_path, programmed):
    a, key, solver, sig = programmed
    store = ProgramStore(str(tmp_path))
    store.save("m", solver, a, key, sig)

    other_a = wishart(jax.random.fold_in(KEY, 99), N)
    with pytest.raises(StaleCheckpointError):
        store.restore("m", solver, other_a, key, sig)
    with pytest.raises(StaleCheckpointError):
        store.restore("m", solver, a, jax.random.fold_in(KEY, 98), sig)
    other_cfg = AnalogConfig(array_size=8,
                             nonideal=NonidealConfig(sigma=0.05))
    with pytest.raises(StaleCheckpointError):
        store.restore("m", solver, a, key, plan_signature(N, 1, other_cfg))
    with pytest.raises(CheckpointError):
        store.restore("missing", solver, a, key, sig)


def test_program_store_truncate_corruption_detected(tmp_path, programmed):
    a, key, solver, sig = programmed
    store = ProgramStore(str(tmp_path))
    store.save("m", solver, a, key, sig)
    store.corrupt("m", how="truncate")
    with pytest.raises(CheckpointCorruptionError):
        store.restore("m", solver, a, key, sig)


def test_program_store_value_corruption_survives_integrity(
        tmp_path, programmed):
    """how="values" is manifest-consistent: identity and integrity layers
    pass, the restored answers are wrong - only the physics canary (the
    fleet install path) can catch it.  Pin that split here."""
    a, key, solver, sig = programmed
    store = ProgramStore(str(tmp_path))
    store.save("m", solver, a, key, sig)
    store.corrupt("m", how="values")
    restored, _ = store.restore("m", solver, a, key, sig)   # no raise
    b = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 3), (N,)))
    x_good = np.asarray(solver.solve(jnp.asarray(b)))
    x_bad = np.asarray(restored.solve(jnp.asarray(b)))
    assert not np.allclose(x_good, x_bad, atol=1e-6)
