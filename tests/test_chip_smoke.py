"""CPU rehearsal of chip_smoke.py: its phases at a tiny size.

The phases run here with the same checks as on the chip - every answer
analog, every recovery counter zero, answers against float64 numpy and
against a float64 run of their own plans, kernel (interpret mode) vs jnp
parity - while `main` still refuses any backend but a TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY = dict(n=32, stages=2, array_size=8, tenants=4, rhs=2)


def test_serve_phase_tiny():
    out = cs.serve_phase(**TINY)
    assert out["requests"] == TINY["tenants"] * TINY["rhs"]
    assert out["dispatches"] == {"r0": 1}    # one packed dispatch for all
    assert out["err_vs_plan_f64_max"] <= cs.PLAN_ERR_BOUND
    assert out["kernel_in_program"] == {"r0": False}   # the CPU runs jnp


def test_parity_phase_tiny():
    out = cs.parity_phase(**TINY)
    assert out["max_abs_diff"] <= cs.PARITY_ATOL


def test_precision_phase_tiny():
    out = cs.precision_phase(n=64, stages=2, array_size=16, rhs=4)
    assert out["err_vs_numpy_max"] <= cs.PROBE_ERR_BOUND


def test_sharded_phase_tiny():
    assert cs.sharded_phase(**TINY)["shards"] == 1


_FOUR_DEVICES = """
import importlib.util, json
spec = importlib.util.spec_from_file_location("cs", "chip_smoke.py")
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
tiny = json.loads('%s')
print(json.dumps([cs.serve_phase(**tiny, replicas=4),
                  cs.sharded_phase(**tiny)]))
"""


def test_four_device_rehearsal():
    """The --chips 4 phases on four host devices: every replica serves from
    its own device, and the sharded answers equal the one-device ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES % json.dumps(TINY)], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    serve, sharded = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(set(serve["devices"])) == 4
    assert serve["dispatches"] == {f"r{i}": 1 for i in range(4)}
    assert sum(serve["answered"].values()) == TINY["tenants"] * TINY["rhs"]
    assert sharded["shards"] == 4


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert "no TPU found" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_path(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at one fixed path inside the checkout."""
    import jax

    from repro.runtime.compile_cache import use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert use_compile_cache(REPO) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache(REPO) == path
        assert use_compile_cache(REPO) == path      # the same every run
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_last_line_is_the_contract(monkeypatch, capsys):
    """With the device check and the phases stubbed, main ends on exactly
    the one JSON line the chip run is read by."""
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(cs, "device_info", lambda: dict(info))
    monkeypatch.setattr(cs, "use_compile_cache", lambda root: "unused")
    for phase in ("serve_phase", "parity_phase", "precision_phase"):
        monkeypatch.setattr(cs, phase, lambda **kw: {})
    assert cs.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": info}
