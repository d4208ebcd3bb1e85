"""Readings that the limits of ``correct`` are set from, in one process.

    python3 benchmark/tests/calibrate.py --workload posv_n16384 \
        --seeds 1,2,...,12 --control-seeds 21,22,23 --seconds 3

On a TPU at the cell's own size, for each seed: the seed's data, a short
window of the cell's own traffic through the timed path, and the run's own
check and verdict, exactly as a run makes them.  The program's programs
are loaded once for all its seeds.  The control puts the configuration's
plain reference in the program's place, at the next precision below the
configuration's (bf16_3x for f32 at HIGHEST), and goes through the same
window, check and verdict: it has to come out not correct.

Prints one JSON line per reading.  The lower reading of a limit is the
largest over the program's seeds, the upper the smallest over the
control's.  ``test_control.py`` runs the same at a size a test can hold.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

from benchlib import harness  # noqa: E402


class _NoHooks:
    counters = {}

    def start(self):
        pass

    def due(self, now):
        return False

    def stop(self, done=None):
        pass


def _annotate(name):
    return contextlib.nullcontext()


def readings(root, workload, seeds, seconds, kind="program", sizes=None,
             allow_cpu=False):
    """One reading per seed.  ``kind`` is ``"program"`` (the system under
    test), ``"control"`` or ``"reference"`` (the plain reference in its
    place, one precision below the configuration's or at it)."""
    cell = harness.Cell(root, workload)
    harness.check_device(cell.chips, harness.load_json(os.path.join(
        cell.bench_dir, "peaks.json"))["devices"], allow_cpu)
    harness.configure_jax(root)
    system = cell.system_class()(cell.config, cell.traffic, seeds[0],
                                 sizes=sizes)
    loop = cell.loop()
    out = []
    try:
        system.load_programs(None if kind == "program"
                             else system.reference_programs(kind))
        for seed in seeds:
            system.seed = seed
            system.make_data()
            system.warm()
            obs = loop(system, system.traffic, seconds, seed, _NoHooks(),
                       _annotate)
            compared, ok = system.check(obs)
            out.append({"kind": kind, "seed": seed,
                        "correct": harness.verdict(compared, ok, obs),
                        "ok": ok, "steps": obs["attempted"],
                        "failed": obs["failed"],
                        "readings": {k: v["value"]
                                     for k, v in compared.items()},
                        "limits": {k: v["limit"]
                                   for k, v in compared.items()}})
    finally:
        system.close()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(HERE))
    t0 = time.perf_counter()
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        seeds = [int(s) for s in seeds.split(",") if s]
        if seeds:
            for r in readings(root, args.workload, seeds, args.seconds,
                              kind=kind):
                print(json.dumps(r), flush=True)
    print(json.dumps({"kind": "done", "seconds": time.perf_counter() - t0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
