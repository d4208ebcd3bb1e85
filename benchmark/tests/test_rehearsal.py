"""Every cell, end to end at its configuration's rehearsal size on the CPU:
the rehearsal path the chip path never takes (the command line refuses a
run without a TPU).  Also the faults the check must catch, planted by the
configuration where the system under test produces its answers, and the
refusals."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, CELLS, DATA, ROOT
from benchlib import jobs
from benchlib.harness import Cell, run_cell

PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
FAULTS = ("altered", "unchanged", "half")


def rehearse(cell, trace=False, seed=2 ** 31 + 12345):
    sizes = Cell(ROOT, cell).config["rehearsal"]["sizes"]
    return run_cell(ROOT, cell, seed, 0.5, trace, time.perf_counter(),
                    allow_cpu=True, sizes=sizes, peaks_override=PEAKS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_configuration_declares_its_contract(cell):
    """Rehearsal sizes and a recorded chip trace, the faults, the compiled
    texts and a scope vocabulary whose roles' jobs are modelled."""
    c = Cell(ROOT, cell)
    rehearsal = c.config["rehearsal"]
    assert rehearsal["sizes"]
    for name in (rehearsal["trace"]["xplane"], rehearsal["trace"]["hlo"]):
        assert os.path.isfile(os.path.join(DATA, name))
    system = c.system_class()
    assert callable(system.plant_fault) and callable(system.program_texts)
    vocab = c.config["scopes"]
    roles = {r for phases in vocab["drivers"].values()
             for r in phases.values() if r}
    assert roles and set(vocab["jobs"]) <= roles
    for routine in vocab["jobs"].values():
        assert jobs.job(routine, 64, 2)["flops"] > 0


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


def use_recorded_trace(cell, monkeypatch):
    """The traced run's reductions, from the configuration's recorded chip
    trace and the compiled text it ran (a CPU trace has no device plane),
    by the configuration's own scope vocabulary."""
    from benchlib import scopes, trace

    rec = Cell(ROOT, cell).config["rehearsal"]["trace"]
    xplane = os.path.join(DATA, rec["xplane"])
    with open(os.path.join(DATA, rec["hlo"])) as f:
        text = f.read()
    real = trace.reduce_trace(xplane)
    reduce_scopes = scopes.reduce_scopes
    monkeypatch.setattr(trace, "reduce_trace", lambda path, **kw: real)
    monkeypatch.setattr(
        scopes, "reduce_scopes",
        lambda path, texts, drivers, **kw: reduce_scopes(xplane, [text],
                                                         drivers, **kw))
    return real


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, monkeypatch, capsys):
    recorded = use_recorded_trace(cell, monkeypatch)
    r = rehearse(cell, trace=True)
    assert r["correct"] is True
    want = {m["name"] for m in Cell(ROOT, cell).metrics("per_layer")}
    assert set(r["metrics"]) == want
    assert r["device"]["busy_s"] == recorded["busy_s"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", cell))
    # the scope groups beside SETUP and WINDOW; the numbers compared last
    err = capsys.readouterr().err.splitlines()
    scoped = [json.loads(ln[len("SCOPES "):]) for ln in err
              if ln.startswith("SCOPES ")]
    assert len(scoped) == 1 and scoped[0]["scopes"]
    assert all(ln.startswith("CHECK ") for ln in err[-len(r["compared"]):])


def test_an_untraced_run_reduces_no_scopes(monkeypatch, capsys):
    from benchlib import scopes

    def refuse(*a, **kw):
        raise AssertionError("an untraced run reduced scopes")

    monkeypatch.setattr(scopes, "reduce_scopes", refuse)
    r = rehearse(CELLS[0])
    assert r["correct"] is True and "breakdown" not in r
    assert "SCOPES " not in capsys.readouterr().err


# -- faults planted where the answers are produced ---------------------------

@pytest.mark.parametrize("kind", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_reads_not_correct(cell, kind, monkeypatch):
    Cell(ROOT, cell).system_class().plant_fault(kind, monkeypatch)
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
        # an executable cached under other scopes is not taken for this one
        assert jax.config.jax_compilation_cache_include_metadata_in_key
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
