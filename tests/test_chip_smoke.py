"""``chip_smoke.py`` on the CPU: its phases at tiny shapes in interpret
mode, against the same host references, and its refusals.

The phase functions are the ones the chip runs; only the sizes differ:
Phase A's docword operand at scale 0.06 (0.08 where four row shards must
divide it), Phase B's layer at 256 -> 512.
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")
# Interpret mode computes in exact f32; the chip's bound is chip_smoke.TOL.
_CPU_TOL = 1e-5


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_a_serves_docword(smoke):
    assert smoke.phase_a(scale=0.06)["max_rel_err"] <= _CPU_TOL


def test_phase_b_trains_and_serves_layer(smoke):
    res = smoke.phase_b(d_in=256, d_out=512, tokens=64)
    assert res["max_rel_err"] <= smoke.TOL


def test_phase_sharded_on_four_fake_devices():
    code = f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("s", {_SCRIPT!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        res = s.phase_sharded(4, scale=0.08, d_in=256, d_out=512,
                              tokens=64)
        assert res["max_rel_err"] <= {_CPU_TOL}, res
        print("SHARDED_SMOKE_OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED_SMOKE_OK" in out.stdout


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_main_refuses_cpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    assert capsys.readouterr().out == ""


def test_script_alone_fails(tmp_path):
    shutil.copy(_SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_placement(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there;
    without it, it sits at the fixed path in the checkout."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        code += "import jax\njax.jit(lambda x: x * 2)(1.0).block_until_ready()"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    path = out.stdout.strip().splitlines()[-1]
    if from_env:
        assert path == str(tmp_path) and os.listdir(tmp_path)
    else:
        assert path == os.path.join(_ROOT, ".jax_cache")


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "INTERPRET", False)   # as on a TPU backend
    assert ops.resolve_interpret(None) is False
    assert ops.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        ops.resolve_interpret(True)
