#!/usr/bin/env python
"""Sweep driver over the routine tester (≅ test/run_tests.py, 828 lines: size
classes --quick/--xsmall/--small/--medium/--large, shape filters, per-routine
timeout, JUnit XML for CI).

Examples::

    python tools/run_tests.py --quick
    python tools/run_tests.py --small --categories blas3,cholesky --xml out.xml
    python tools/run_tests.py --medium --routines gemm,posv --type s,c --ref
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import xml.etree.ElementTree as ET

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Correctness sweeps run on the CPU unless the caller explicitly opts into a
# platform via SLATE_TESTER_PLATFORM (the sweeps are platform-agnostic).
_plat = os.environ.get("SLATE_TESTER_PLATFORM") or "cpu"
if _plat == "cpu":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from force_cpu import force_cpu_backend

    # grid sweeps need enough virtual devices for the requested p x q
    _vd = None
    _argv = sys.argv[1:]
    for _i, _a in enumerate(_argv):
        _spec = (_a.split("=", 1)[1] if _a.startswith("--grid=")
                 else _argv[_i + 1] if _a == "--grid" and _i + 1 < len(_argv)
                 else None)
        if _spec:
            _p, _q = (int(x) for x in _spec.lower().split("x"))
            _vd = _p * _q
    force_cpu_backend(virtual_devices=_vd)
else:
    os.environ["JAX_PLATFORMS"] = _plat

from slate_tpu.testing import ROUTINES                          # noqa: E402
from slate_tpu.testing.driver import run_sweep                  # noqa: E402
from slate_tpu.testing.sweeper import parse_list                # noqa: E402

SIZE_CLASSES = {
    # dims per class (≅ run_tests.py size classes); nb chosen to exercise blocking
    "quick":  {"dims": [64, 96], "nb": [32], "nrhs": 4},
    "xsmall": {"dims": [128], "nb": [32, 64], "nrhs": 8},
    "small":  {"dims": [256], "nb": [64], "nrhs": 8},
    "medium": {"dims": [512, 768], "nb": [128], "nrhs": 16},
    "large":  {"dims": [1024, 2048], "nb": [256], "nrhs": 16},
    # BASELINE-direction scale row: constant-factor data beyond the pytest
    # pin (the virtual mesh measures constants, not speedup — PERF_CPU.md)
    "xlarge": {"dims": [4096], "nb": [256], "nrhs": 16},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    for cls in SIZE_CLASSES:
        ap.add_argument(f"--{cls}", action="store_true")
    ap.add_argument("--routines", default=None, help="comma list (default: all)")
    ap.add_argument("--categories", default=None, help="comma list of categories")
    ap.add_argument("--type", default="s", help="s,d,c,z")
    ap.add_argument("--tall", action="store_true", help="tall shapes m = 2n")
    ap.add_argument("--wide", action="store_true", help="wide shapes n = 2m")
    ap.add_argument("--ref", action="store_true", help="time numpy reference too")
    ap.add_argument("--timers", action="store_true",
                    help="print per-phase timer maps under eig/svd rows (the "
                         "reference tester's --timer-level 2)")
    ap.add_argument("--metrics", nargs="?", const="metrics.json", default=None,
                    metavar="PATH",
                    help="dump the sweep's metrics.json (slate_tpu.obs "
                         "registry: spans, phase histograms, tester row "
                         "counters, robust events) — default ./metrics.json")
    ap.add_argument("--xml", default=None, help="write JUnit XML here")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid", default=None, metavar="PxQ",
                    help="sweep the distributed drivers on a PxQ process grid "
                         "(virtual devices; the reference tester's p/q dims)")
    args = ap.parse_args(argv)

    cls = next((c for c in SIZE_CLASSES if getattr(args, c)), "quick")
    cfg = SIZE_CLASSES[cls]

    names = sorted(ROUTINES)
    if args.routines:
        names = [r for r in parse_list(args.routines) if r in ROUTINES]
    if args.categories:
        cats = set(parse_list(args.categories))
        names = [r for r in names if ROUTINES[r]["category"] in cats]

    dims = []
    for d in cfg["dims"]:
        m, n = d, d
        if args.tall:
            m = 2 * d
        elif args.wide:
            n = 2 * d
        dims.append((m, n, d))

    def progress(r):
        status = r.status if r.ok else f"** {r.status} **"
        err = r.error if r.error is not None else float("nan")
        gf = f"{r.gflops:8.1f}" if r.gflops is not None else "       -"
        tm = f"{r.time_s:8.4f}" if r.time_s is not None else "       -"
        extra = ""
        iters = (r.details or {}).get("ir_iters")
        if iters is not None:
            extra = f" iters={iters}"
        print(f"{r.routine:16s} {r.params.get('dtype')} "
              f"{r.params['m']:5d}x{r.params['n']:<5d} nb={r.params['nb']:<4d} "
              f"t={tm}s gf={gf} err={err:.2e} {status}{extra} {r.message}",
              flush=True)
        phases = (r.details or {}).get("phases")
        if args.timers and phases:
            # --timer-level-2 analogue: one indented line per phase, hottest
            # first (phase_report already ordered them)
            total = phases.get("total_s", 0.0)
            for k, v in phases.items():
                if k == "total_s":
                    continue
                print(f"    {k:<24s} {v['s']:9.4f}s {v['pct']:5.1f}%",
                      flush=True)
            print(f"    {'total':<24s} {total:9.4f}s", flush=True)

    t0 = time.time()
    grid = (tuple(int(x) for x in args.grid.lower().split("x"))
            if args.grid else None)
    results = run_sweep(names, dims, parse_list(args.type), cfg["nb"],
                        seed=args.seed, nrhs=cfg["nrhs"], ref=args.ref,
                        grid=grid, progress=progress)
    elapsed = time.time() - t0

    npass = sum(1 for r in results if r.status == "pass")
    nskip = sum(1 for r in results if r.status == "skipped")
    nfail = len(results) - npass - nskip
    print(f"\n[{cls}] {len(results)} tests: {npass} pass, {nfail} failed, "
          f"{nskip} skipped in {elapsed:.1f}s")

    if args.metrics:
        from slate_tpu import obs

        print(f"wrote {obs.export_metrics(args.metrics, source='tester')}")

    if args.xml:
        suite = ET.Element("testsuite", name=f"slate_tpu-{cls}",
                           tests=str(len(results)), failures=str(nfail),
                           skipped=str(nskip), time=f"{elapsed:.2f}")
        for r in results:
            p = r.params
            case = ET.SubElement(
                suite, "testcase",
                classname=f"slate_tpu.{ROUTINES[r.routine]['category']}",
                name=f"{r.routine}_{p.get('dtype')}_{p.get('m')}x{p.get('n')}"
                     f"_nb{p.get('nb')}",
                time=f"{r.time_s or 0:.4f}")
            if r.status == "skipped":
                ET.SubElement(case, "skipped", message=r.message)
            elif r.status != "pass":
                ET.SubElement(case, "failure", message=r.message or r.status)
        ET.ElementTree(suite).write(args.xml, encoding="unicode",
                                    xml_declaration=True)
        print(f"wrote {args.xml}")

    return 0 if nfail == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
