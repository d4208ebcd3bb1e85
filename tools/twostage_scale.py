"""Size the two-stage eigensolver's vectors path at BASELINE scale (VERDICT
r3 #4: "size the vectors-path reflector tensor at n=20,000 on paper and in a
compiled memory_analysis").

Compiles each phase of heev(method="two_stage", want_vectors=True) at growing
n on CPU (compile-only — nothing executes), records the compiled module's
argument/output/temp footprints, fits the n² coefficient, and extrapolates to
n=20,000 f32 against a v5e's 16 GB HBM.  Writes TWOSTAGE_SCALE.md.

Usage: JAX_PLATFORMS=cpu python tools/twostage_scale.py
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NB = 128          # stage-1 band width (default_band_nb class)
SIZES = [1024, 2048, 4096]
TARGET_N = 20000


def mem(comp):
    ma = comp.memory_analysis()
    return dict(args=ma.argument_size_in_bytes, out=ma.output_size_in_bytes,
                temp=ma.temp_size_in_bytes)


def compile_phase(fn, *shapes, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def main():
    from slate_tpu.linalg.eig import he2hb, hb2st, unmtr_he2hb

    rows = []
    for n in SIZES:
        r = {"n": n}
        # stage 1: dense -> band, returns (band, Vs, Ts)
        c1 = compile_phase(lambda a: he2hb(a, nb=NB), (n, n))
        r["he2hb"] = mem(c1)
        # stage 2 with vectors: band -> tridiag + dense Q2 (pipelined chase)
        c2 = compile_phase(
            lambda b: hb2st(b, kd=NB, want_vectors=True, pipeline=True),
            (n, n))
        r["hb2st_v"] = mem(c2)
        # back-transform: Q1 applied from stacked reflectors to the n x n Z
        nj = -(-n // NB) - 1
        c3 = compile_phase(
            lambda V, T, C: unmtr_he2hb("left", "n", V, T, C),
            (nj, n, NB), (nj, NB, NB), (n, n))
        r["unmtr"] = mem(c3)
        rows.append(r)
        print(r, flush=True)

    # quadratic fit per phase: bytes ~ a*n^2 + b*n + c (temp is the honest
    # "extra memory" number; args/out follow from the shapes analytically)
    def fit_extrapolate(key):
        ns = np.array([r["n"] for r in rows], float)
        ys = np.array([r[key]["temp"] for r in rows], float)
        A = np.stack([ns**2, ns, np.ones_like(ns)], axis=1)
        coef, *_ = np.linalg.lstsq(A, ys, rcond=None)
        return float(coef @ [TARGET_N**2, TARGET_N, 1.0])

    n = TARGET_N
    nj = -(-n // NB) - 1
    f32 = 4
    analytic = {
        "A / band (n^2)": n * n * f32,
        "Vs (nj, n, nb)": nj * n * NB * f32,
        "Ts (nj, nb, nb)": nj * NB * NB * f32,
        "Q2 dense (n^2)": n * n * f32,
        "Z vectors (n^2)": n * n * f32,
    }
    extraps = {k: fit_extrapolate(k) for k in ("he2hb", "hb2st_v", "unmtr")}

    GB = 1 << 30
    with open(os.path.join(REPO, "TWOSTAGE_SCALE.md"), "w") as f:
        f.write("# Two-stage vectors path at n=20,000 (VERDICT r3 #4)\n\n")
        f.write(f"Compiled-module footprints (f32, nb={NB}, CPU backend —\n"
                "memory_analysis of the same XLA program the TPU compiles; "
                "compile-only, nothing executed).\n\n")
        f.write("| n | phase | args | out | temp |\n|---|---|---|---|---|\n")
        for r in rows:
            for ph in ("he2hb", "hb2st_v", "unmtr"):
                m = r[ph]
                f.write(f"| {r['n']} | {ph} | {m['args']/GB:.3f} GB "
                        f"| {m['out']/GB:.3f} GB | {m['temp']/GB:.3f} GB |\n")
        f.write("\n## Analytic tensor sizes at n=20,000 (f32, nb=128)\n\n")
        f.write("| tensor | bytes |\n|---|---|\n")
        total = 0
        for k, v in analytic.items():
            f.write(f"| {k} | {v/GB:.2f} GB |\n")
            total += v
        f.write(f"| **sum (persistent)** | **{total/GB:.2f} GB** |\n")
        f.write("\n## Quadratic-fit temp extrapolation to n=20,000\n\n")
        f.write("| phase | projected temp |\n|---|---|\n")
        for k, v in extraps.items():
            f.write(f"| {k} | {v/GB:.2f} GB |\n")
        peak = max(
            extraps["he2hb"] + analytic["A / band (n^2)"]
            + analytic["Vs (nj, n, nb)"] + analytic["Ts (nj, nb, nb)"],
            extraps["hb2st_v"] + analytic["Q2 dense (n^2)"]
            + analytic["A / band (n^2)"],
            extraps["unmtr"] + analytic["Vs (nj, n, nb)"]
            + analytic["Z vectors (n^2)"] * 2,
        )
        f.write(f"\n**Projected peak phase footprint ≈ {peak/GB:.1f} GB** "
                "(live persistents + phase temp).  A v5e chip has 16 GB HBM: "
                "the n=20,000 vectors path fits on ONE chip only if the peak "
                "stays under ~14 GB after XLA's buffer reuse; otherwise the "
                "distributed stage-1/back-transform path (parallel/eig_dist) "
                "shards Vs and the gemms, and the single-chip residency "
                "drops to the chase's O(n·kd) windows + Q2.\n")
    print("wrote TWOSTAGE_SCALE.md")


if __name__ == "__main__":
    main()
