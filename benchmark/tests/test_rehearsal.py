"""Every cell, end to end at a tiny size on the CPU: the rehearsal path the
chip path never takes (the command line refuses a run without a TPU).
Also the faults the check must catch, planted in the system under test
where it produces its answers, and the refusals."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT
from benchlib.harness import Cell, run_cell

PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
# the limits of `correct` hold the f32 solve at n=16384; at n=2048 it
# still reads under them, at n of a few hundred it does not
DENSE = {"n": 2048, "nrhs": 4}
SIZES = {"posv_n16384": DENSE, "potrs_n16384": DENSE}
CELLS = sorted(SIZES)


def rehearse(cell, trace=False, seed=2 ** 31 + 12345):
    return run_cell(ROOT, cell, seed, 0.5, trace, time.perf_counter(),
                    allow_cpu=True, sizes=SIZES[cell], peaks_override=PEAKS)


def test_every_cell_is_rehearsed():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert sorted(w["name"] for w in bench["workloads"]) == CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    r = rehearse(cell)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "compared"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in Cell(ROOT, cell).metrics("end_to_end")}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    for v in r["compared"].values():
        assert 0 <= v["value"] <= v["limit"]


@pytest.fixture
def chip_trace(monkeypatch):
    """The traced run's reduction, from the recorded chip trace (a CPU
    trace has no device plane)."""
    from benchlib import trace

    data = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_small.xplane.pb")
    if not os.path.exists(data):
        pytest.skip("no recorded trace")
    real = trace.reduce_trace(data)
    monkeypatch.setattr(trace, "reduce_trace", lambda path, **kw: real)
    return real


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, chip_trace):
    r = rehearse(cell, trace=True)
    assert r["correct"] is True
    want = {m["name"] for m in Cell(ROOT, cell).metrics("per_layer")}
    assert set(r["metrics"]) == want
    assert r["device"]["busy_s"] == chip_trace["busy_s"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", cell))


# -- faults planted where the answers are produced ---------------------------

def plant_dense_fault(kind, monkeypatch):
    """slate's potrs, which both dense cells' programs call, broken."""
    import slate_tpu
    from slate_tpu.core.matrix import as_array, write_back
    from slate_tpu.linalg import chol

    real = chol.potrs

    def potrs(A, B, opts=None, uplo=None):
        if kind == "unchanged":           # returns its input as the answer
            return write_back(B, as_array(B))
        x = real(A, B, opts, uplo)
        if kind == "altered":
            x = x.at[0, 0].add(1.0)
        else:                             # half the right-hand sides left out
            x = x.at[:, ::2].set(0.0)
        return write_back(B, x)

    monkeypatch.setattr(chol, "potrs", potrs)
    monkeypatch.setattr(slate_tpu, "potrs", potrs)


@pytest.mark.parametrize("kind", ["altered", "unchanged", "half"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_reads_not_correct(cell, kind, monkeypatch):
    plant_dense_fault(kind, monkeypatch)
    r = rehearse(cell)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["compared"].values())


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    import jax

    from benchlib.harness import configure_jax

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        configure_jax(ROOT)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        configure_jax(ROOT)
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# -- refusals -----------------------------------------------------------------

def test_command_without_tpu_exits_nonzero_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "posv_n16384",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout == ""


def test_without_the_program_a_run_fails(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's files the
    system under test is missing: the run fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    code = ("import sys, time; sys.path[:0] = ['benchmark', '.'];"
            "from benchlib.harness import run_cell;"
            "print(run_cell('.', 'posv_n16384', 1, 0.2, False, "
            "time.perf_counter(), allow_cpu=True, sizes={'n': 64, 'nrhs': 2},"
            "peaks_override={'flops_per_s': 1.0, 'bytes_per_s': 1.0}))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "slate_tpu" in p.stderr
    assert p.stdout == ""
