"""A configuration, a traffic mix, the loop that drives it and a metric,
added as new files and new entries of BENCHMARK.json, are found by name,
with no existing file edited; and so are a configuration's rehearsal
sizes, faults, scope vocabulary and recorded trace, by every self-test."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

from conftest import BENCH, ROOT
from benchlib.harness import run_cell


def digest(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, top)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


LOOP = """
import time

import jax


def run(system, traffic, seconds, seed, hooks, annotate):
    hooks.start()
    t0 = time.perf_counter()
    kept = []
    for i in range(traffic["steps"]):
        kept.append((i, jax.block_until_ready(system.step(i))))
    t = time.perf_counter()
    hooks.stop(done=len(kept))
    return {"t0": t0, "t_end": t, "window_s": t - t0, "steps": len(kept),
            "kept": kept, "attempted": len(kept), "failed": 0}
"""


def test_new_files_and_entries_are_found(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = digest(tmp_path / "benchmark")
    b = tmp_path / "benchmark"
    # a configuration: its file of sizes and its driver (beside it, the
    # plain reference it loads)
    cfg = json.load(open(b / "configs" / "dense_spd_solve.json"))
    cfg.update(name="dense_spd_small", n=2048)
    json.dump(cfg, open(b / "configs" / "dense_spd_small.json", "w"))
    src = (b / "configs" / "dense_spd_solve.py").read_text()
    (b / "configs" / "dense_spd_small.py").write_text(src)
    # a traffic mix: data, naming a loop of its own
    mix = json.load(open(b / "traffic" / "posv_back_to_back.json"))
    mix.update(nrhs=3, matrices=1, rhs_blocks=4, loop="fixed_steps",
               steps=5)
    json.dump(mix, open(b / "traffic" / "posv_one_matrix.json", "w"))
    (b / "loops" / "fixed_steps.py").write_text(LOOP)
    # a metric: a reader of its own
    (b / "metrics" / "dense_solves.py").write_text(
        "def read(ctx):\n    return ctx['obs'].get('steps')\n")
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "dense_spd_small", "source": "https://example.org/paper",
        "file": "benchmark/configs/dense_spd_small.json", "reduced": ["n"],
        "why": "a test of discovery"})
    bench["workloads"].append({
        "name": "posv_small", "config": "dense_spd_small",
        "traffic": "posv_one_matrix", "chips": 1, "why": "discovery"})
    bench["end_to_end"].append({
        "name": "dense_solves", "unit": "solves", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["posv_small"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    r = run_cell(str(tmp_path), "posv_small", 5, 0.3, False,
                 time.perf_counter(), allow_cpu=True,
                 peaks_override={"flops_per_s": 1.0, "bytes_per_s": 1.0})
    assert r["correct"] is True
    assert set(r["metrics"]) == {"setup_s", "dense_solves"}
    assert r["metrics"]["dense_solves"]["value"] == r["attempted"] == 5
    after = digest(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before


SYSTEM = """
import os

from benchlib.harness import load_module

_dense = load_module(os.path.join(os.path.dirname(__file__),
                                  "dense_spd_solve.py"),
                     "bench_config_dense_spd_solve")


class System(_dense.System):
    @staticmethod
    def plant_fault(kind, monkeypatch):
        # slate's posv broken where it returns the answer
        import slate_tpu
        from slate_tpu.core.matrix import as_array, write_back

        real = slate_tpu.posv

        def posv(A, B, opts=None, uplo=None):
            b = as_array(B)
            x, info = real(A, B, opts, uplo)
            x = {"unchanged": b, "altered": x.at[0, 0].add(1.0),
                 "half": x.at[:, ::2].set(0.0)}[kind]
            return write_back(B, x), info

        monkeypatch.setattr(slate_tpu, "posv", posv)
"""


def test_a_configuration_brings_its_own_cell(tmp_path):
    """A copied configuration whose vocabulary renames the sweep driver,
    with its own rehearsal sizes, faults, recorded program text and a
    reader of the scope readings, added as new files: the unchanged
    self-tests of the copy collect its cell and pass it, the traced
    rehearsal included."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "benchmark")
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "dense_spd_solve.json"))
    drivers = cfg["scopes"]["drivers"]
    drivers["cholsolve"] = drivers.pop("potrs")
    cfg.update(name="dense_spd_renamed")
    cfg["rehearsal"] = {"sizes": {"n": 2048, "nrhs": 3},
                        "trace": {"xplane": "v5e_scoped.xplane.pb",
                                  "hlo": "v5e_scoped_renamed.hlo.txt"}}
    json.dump(cfg, open(b / "configs" / "dense_spd_renamed.json", "w"))
    (b / "configs" / "dense_spd_renamed.py").write_text(SYSTEM)
    # the program text the recorded trace ran, as a program whose sweep
    # driver is named cholsolve would carry it
    text = (b / "tests" / "data" / "v5e_scoped.hlo.txt").read_text()
    (b / "tests" / "data" / "v5e_scoped_renamed.hlo.txt").write_text(
        text.replace("/potrs/", "/cholsolve/"))
    (b / "metrics" / "renamed.sweep_roofline.py").write_text(
        "def read(ctx):\n"
        "    return (ctx['scopes'] or {}).get('sweep_roofline')\n")
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "dense_spd_renamed", "source": "https://example.org/paper",
        "file": "benchmark/configs/dense_spd_renamed.json", "reduced": [],
        "why": "a test of discovery"})
    bench["workloads"].append({
        "name": "posv_renamed", "config": "dense_spd_renamed",
        "traffic": "posv_back_to_back", "chips": 1, "why": "discovery"})
    bench["per_layer"].append({
        "name": "renamed.sweep_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "dense drivers and kernels",
        "moves": "dense_solve_ms", "workloads": ["posv_renamed"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "benchmark/tests/test_rehearsal.py", "benchmark/tests/test_control.py",
         "-k", "posv_renamed"],
        cwd=tmp_path, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join(
                     [ROOT] + os.environ.get("PYTHONPATH", "").split(
                         os.pathsep))))
    passed = set(re.findall(r"^PASSED (\S+)$", p.stdout, re.M))
    rehearsal = "benchmark/tests/test_rehearsal.py::"
    want = {rehearsal + t + "[posv_renamed]" for t in (
        "test_every_cells_configuration_declares_its_contract",
        "test_cell_runs_and_is_correct",
        "test_traced_run_reports_per_layer_metrics")}
    want |= {f"{rehearsal}test_a_planted_fault_reads_not_correct"
             f"[posv_renamed-{kind}]"
             for kind in ("altered", "unchanged", "half")}
    want.add("benchmark/tests/test_control.py::"
             "test_control_reads_not_correct[posv_renamed]")
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    assert passed == want, p.stdout[-4000:]
    after = digest(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
