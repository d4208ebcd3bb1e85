"""Compile slate's gesv (tournament-pivoted LU, then getrs) once at size n and
report what the compile costs and what the program computes.

    python3 tools/gesv_compile_report.py --n 16384 [--nrhs 16] [--nb 256]
    JAX_PLATFORMS=cpu python3 tools/gesv_compile_report.py --n 4096 \\
        --describe v5e:2x2

On a TPU the program compiles for the chip the process holds; with
``--describe`` it compiles for a described TPU, with no chip.  Prints one
JSON line:

* ``lower_s``, ``compile_s``: host seconds to lower and to compile;
* ``code_bytes``, ``temp_bytes``: the executable's generated code and its
  temporaries (``memory_analysis``);
* ``rss_bytes``, ``rss_growth_bytes``: the process's peak resident memory
  after the compile, and its growth over the compile;
* ``flops_cost_analysis``: XLA's count, which counts each loop body once;
* ``flops``: the traced program's linear algebra (matrix products,
  triangular solves, LUs) with every loop's trip count applied, and
  ``flops_ratio``, its ratio to LAPACK's count for the LU and the two
  sweeps (2n^3/3 + 2n^2 nrhs, to leading order).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _eqn_flops(e) -> float:
    p = e.primitive.name
    if p == "dot_general":
        (lc, rc), (lb, rb) = e.params["dimension_numbers"]
        ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
        free = lambda s, c, b: math.prod(d for i, d in enumerate(s)
                                         if i not in c and i not in b)
        return (2.0 * math.prod(ls[i] for i in lb) * free(ls, lc, lb)
                * free(rs, rc, rb) * math.prod(ls[i] for i in lc))
    if p == "triangular_solve":
        b = e.invars[1].aval.shape
        m, n = b[-2:]
        return math.prod(b[:-2]) * (n * m * m if e.params["left_side"]
                                    else m * n * n)
    if p == "lu":
        s = e.invars[0].aval.shape
        m, n = s[-2:]
        k = min(m, n)
        return math.prod(s[:-2]) * (max(m, n) * k * k - k ** 3 / 3.0)
    return 0.0


def jaxpr_flops(jaxpr, times: float = 1.0) -> float:
    """Linear-algebra flops of a jaxpr, a scan's body counted once per
    step (a ``fori_loop`` with static bounds traces to a scan)."""
    from jax.extend import core

    total = 0.0
    for e in jaxpr.eqns:
        total += times * _eqn_flops(e)
        steps = e.params.get("length", 1) if e.primitive.name == "scan" else 1
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, core.Jaxpr):
                    total += jaxpr_flops(sub, times * steps)
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--nrhs", type=int, default=16)
    ap.add_argument("--nb", type=int, default=256)
    ap.add_argument("--describe", default=None,
                    help="compile for this described TPU topology")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    import slate_tpu as slate

    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.describe)
        sharding = SingleDeviceSharding(topo.devices[0])
    opts = {"method_lu": "calu", "block_size": args.nb}

    def gesv(a, b):
        B = slate.Matrix.from_array(b)
        _, _, info = slate.gesv(slate.Matrix.from_array(a), B, opts)
        return B.array, info

    n = args.n
    specs = (jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=sharding),
             jax.ShapeDtypeStruct((n, args.nrhs), jnp.float32,
                                  sharding=sharding))
    flops = jaxpr_flops(jax.make_jaxpr(gesv)(*specs).jaxpr)
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    t0 = time.perf_counter()
    lowered = jax.jit(gesv).lower(*specs)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    lapack = 2.0 * n ** 3 / 3 + 2.0 * n * n * args.nrhs
    print(json.dumps({
        "n": n, "nrhs": args.nrhs, "nb": args.nb,
        "device": args.describe or jax.devices()[0].device_kind,
        "lower_s": t1 - t0, "compile_s": t2 - t1,
        "code_bytes": ma.generated_code_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "rss_bytes": rss, "rss_growth_bytes": rss - rss0,
        "flops_cost_analysis": ca.get("flops"), "flops": flops,
        "flops_ratio": flops / lapack}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
