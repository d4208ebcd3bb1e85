"""The chip entry points, exercised on the CPU.

``chip_smoke.py`` and ``bench.py`` must refuse to produce a number without a
TPU; ``chip_smoke.py --rehearse`` runs every phase at tiny sizes on the CPU
(the rehearsal the on-chip-measurement guide asks for before a chip call);
the compile-cache rule is the one ``slate_tpu.utils.compile_cache`` states.
Each runs in a child process, as a user would start it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, devices=1, timeout=600, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    full.update(env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=full)


def _last_json(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines else None


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_refuses_without_tpu(tmp_path, script):
    p = _run([script], tmp_path, timeout=300)
    assert p.returncode != 0
    assert not any(ln.lstrip().startswith("{")
                   for ln in p.stdout.splitlines()), p.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal(tmp_path, chips):
    p = _run(["chip_smoke.py", "--rehearse", "--chips", str(chips)],
             tmp_path, devices=chips)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = _last_json(p.stdout)
    assert last["ok"] and last["rehearsal"]
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    phases = [json.loads(ln[len("PHASE "):])
              for ln in p.stdout.splitlines() if ln.startswith("PHASE ")]
    want = (["posv_2x2", "gesv_2x2"] if chips == 4 else
            ["serve", "heev", "norm", "posv", "gesv", "gels"])
    assert [ph["phase"] for ph in phases] == want
    assert all(ph["ok"] and ph["residual"] <= ph["tol"] for ph in phases)


_PROBE = ("import jax; from slate_tpu.utils.compile_cache import "
          "enable_compile_cache as e; print(e(), "
          "jax.config.jax_compilation_cache_dir)")


def test_compile_cache_env_wins(tmp_path):
    where = str(tmp_path / "elsewhere")
    p = _run(["-c", _PROBE], tmp_path, timeout=120,
             JAX_COMPILATION_CACHE_DIR=where)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [where, where]


def test_compile_cache_defaults_to_repo(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=120, cwd=str(tmp_path), env=dict(
                           env, PYTHONPATH=REPO))
    assert p.returncode == 0, p.stderr
    fixed = os.path.join(REPO, ".jax_cache")
    assert p.stdout.split() == [fixed, fixed]
