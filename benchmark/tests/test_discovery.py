"""A configuration, a traffic mix, the loop that drives it and a metric,
added as new files and new entries of BENCHMARK.json, are found by name,
with no existing file edited."""

import hashlib
import json
import os
import shutil
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
