"""Capture a jax.profiler trace of the potrf bench body on the real chip
(VERDICT r3 #2: "profile on chip (jax.profiler trace in-repo)").

Writes a TensorBoard-loadable trace to ./tpu_trace/potrf/ — the artifact
that shows where the 0.93x goes (panel chol vs trsm vs trailing gemm vs
dispatch gaps).  Run it on the chip as its only JAX process.
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        print("no TPU; skipping profile capture")
        return 1
    import slate_tpu

    n = int(os.environ.get("PROFILE_POTRF_N", 16384))
    nb = int(os.environ.get("BENCH_POTRF_NB", 2048))
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (n, n), dtype=jnp.float32) / jnp.sqrt(
        jnp.asarray(n, jnp.float32))
    a = jnp.matmul(m, m.T, precision=lax.Precision.HIGHEST) + 2.0 * jnp.eye(
        n, dtype=jnp.float32)
    opts = {"target": "tiled", "block_size": nb}

    def run(x):
        return slate_tpu.potrf(x, opts=opts)[0]

    # warm/compile outside the trace
    float(run(a).ravel()[0])
    out_dir = os.path.join(REPO, "tpu_trace", "potrf")
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir):
        r = run(a + 1e-6 * jnp.eye(n, dtype=a.dtype))
        float(r.ravel()[0])
    print(f"trace captured in {time.perf_counter() - t0:.2f}s -> {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
