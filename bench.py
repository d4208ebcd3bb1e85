"""TPU benchmark driver covering the five BASELINE.md north-star configs.

Runs only on a TPU.  The parent process never imports JAX: it runs a probe
child (``python bench.py --child probe``) and then each config in a child of
its own, one at a time, since a chip belongs to one process.  A probe that
finds no TPU, or a config that fails, makes the run exit non-zero; a failed
config gets no number, and nothing is measured anywhere else.

Prints ONE JSON line: the headline gemm metric, the device it ran on
(``platform``, ``device_kind``, device count from ``jax.devices()``) and a
``[value, vs_baseline]`` pair per config; the full per-config records
(each naming its device) go to ``BENCH_DETAIL.json``.

Precision envelope: the reference's headline is double precision on GPU; TPU has
no f64 ALUs, so the comparable configuration is f32 with
``lax.Precision.HIGHEST`` (bf16-emulated full-precision accumulation — the dtype
the d/z routine family maps to on TPU, SURVEY.md §7 hard-part 6).
``vs_baseline`` divides by measured/estimated cuBLAS/cuSOLVER A100 fp64 rates
for the reference's native configuration (see BASELINES below), so >1.0 beats
the reference hardware's double-precision rate at the same job.

Flop models follow the LAPACK conventions the reference's tester uses
(blas/lapack flops.hh, cited in BASELINE.md): gemm 2n^3; potrf n^3/3;
getrf 2n^3/3; tall-skinny least squares 2n^2(m - n/3); heev values 4n^3/3;
svd values 8n^3/3.  Where our algorithm does *more* arithmetic than the model
(CholeskyQR2 vs Householder QR) the model still counts the *job*, so the rate
is an honest effective rate for the same problem.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DETAIL_PATH = os.path.join(REPO, "BENCH_DETAIL.json")

# A100 80GB fp64 rates for the reference-native configuration (cuBLAS/cuSOLVER;
# gemm figure measured, factorization/eig figures are published-order estimates —
# documented so vs_baseline is interpretable, not a black box).
BASELINES = {
    "gemm": 15000.0,   # cuBLAS dgemm n=4096
    "potrf": 13000.0,  # cuSOLVER/MAGMA dpotrf n=16384 (gemm-rich, near dgemm rate)
    "getrf": 9000.0,   # dgetrf n=16384 (pivoting + panel overhead)
    "gels": 9000.0,    # tall dgels 131072x4096, cholqr path
    "heev": 300.0,     # dsyevd values n=16384 on 4n^3/3 model (the n=4096
                       # config used 150; published-order A100 rates roughly
                       # double from 4k to 16k as the tridiagonal stage
                       # amortizes — VERDICT r2 asked for the BASELINE-scale
                       # config, so the denominator moves with it)
    "svd": 200.0,      # dgesvd values n=16384 on 8n^3/3 model (was 100 at
                       # n=4096; same scaling rationale)
    "norm": 450.0,     # dlange Fro n=16384: bandwidth-bound, ~1.8 TB/s HBM
                       # at 8 B/elem and 2 flops/elem -> ~450 GFLOP/s
    "potrf_la": 13000.0,  # same job/denominator as potrf: the lookahead-
                          # pipelined schedule vs the unrolled tiled one
    "f64gemm": 15000.0,   # A100 cuBLAS dgemm n=4096 — TRUE fp64-class vs
                          # fp64 (the one apples-to-apples ratio; every other
                          # config crosses f32-HIGHEST vs fp64)
    "gesvir": 9000.0,     # A100 dgesv n=4096-class (dgetrf-rate bound);
                          # ours = f32 LU + emulated-f64 IR to double-class
                          # forward error (gesv_f64ir), flops on the 2n^3/3
                          # dgetrf model
    "getrf_pp": 9000.0,   # same job/denominator as getrf: CALU with the
                          # pp panel (Options.lu_panel="pp" — one partial-
                          # pivot subpanel LU instead of the merge tree) so
                          # the two panel schemes read as a direct A/B and
                          # the r5 regression bisection has its second arm
    "svd2s": 150.0,       # dgesvd values n=8192 published-order estimate
                          # (between the n=4096 100 and n=16384 200 rates);
                          # times the SLATE-parity SVD pipeline next to the
                          # fused default
    "heev2s": 225.0,      # dsyevd values n=8192 published-order estimate
                          # (between the n=4096 150 and n=16384 300 rates);
                          # config exists to time the SLATE-parity two-stage
                          # pipeline next to the fused QDWH default
    "serve_mixed": 20000.0,   # solves/s — nominal A100 batched-cuSOLVER
                              # order-of-magnitude for mixed n<=96 small
                              # solves (getrfBatched-class throughput); a
                              # rough denominator documented so the ratio is
                              # a trend line, not a hardware-parity claim.
                              # This config's unit is solves/s, not GFLOP/s:
                              # the serving axis measures throughput + p50/
                              # p99 latency of the slate_tpu.serve queue
                              # under synthetic mixed traffic (ROADMAP 2)
    "serve_scale": 40000.0,   # solves/s — the serve_mixed denominator x2:
                              # the scale axis reports the N=2 executor-pool
                              # warm rate, so its trend line is read against
                              # a two-worker batched-cuSOLVER-class figure.
                              # Unit is warm solves/s at N=2 (scaling gates
                              # — N=2 >= N=1 — ride in the metrics blob)
}

CONFIGS = ["gemm", "norm", "serve_mixed", "serve_scale", "f64gemm", "potrf",
           "potrf_la", "gels", "gesvir", "heev", "svd", "getrf", "getrf_pp",
           "heev2s", "svd2s"]
HEADLINE = "gemm"

# per-config child timeouts: the BASELINE-scale eig/SVD configs and the
# 8-panel CALU programs carry minutes of XLA compile before the first timed
# call
CONFIG_TIMEOUTS = {"heev": 1300, "svd": 1500, "getrf": 1500, "getrf_pp": 1500,
                   "potrf_la": 1300, "heev2s": 1800, "svd2s": 1800}

# ---------------------------------------------------------------------------
# children — each runs in its own process, imports jax lazily
# ---------------------------------------------------------------------------


def _device():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def _emit(obj):
    if isinstance(obj, dict) and "metric" in obj:
        obj = dict(obj, **_device())
        # attach the child's observability blob (slate_tpu.obs registry:
        # driver spans, phase histograms, robust events) so each config's
        # BENCH_DETAIL.json entry carries its metrics.json alongside the
        # rate — only when the library actually ran (probe emits none)
        mod = sys.modules.get("slate_tpu.obs")
        if mod is not None:
            doc = mod.metrics_doc(source="bench")
            if doc.get("metrics"):
                obj["metrics"] = doc
    print(json.dumps(obj), flush=True)


def child_probe():
    import jax.numpy as jnp

    x = jnp.ones((128, 128))
    _emit({"ok": True, "sum": float(jnp.sum(x @ x)), **_device()})


def _chain_rate(body, a0, consts, k_small, k_large, flops_per_iter, repeats=3):
    """GFLOP/s via chain-length delta: timing (k_large - k_small) extra
    iterations of a data-dependent loop inside one jit call cancels dispatch
    and transfer overhead.

    ``body(i, carry, *consts)``: loop-invariant operands come through
    ``consts`` (jit arguments), never closures, so they are not baked into
    the program as constants.  Each timed call ends in
    ``block_until_ready``; every repeat starts from a freshly perturbed
    carry.
    """
    import jax
    from jax import lax

    def timed(k):
        """(min timed sec, compile + first call sec)."""
        fn = jax.jit(lambda c0, *cs: lax.fori_loop(
            0, k, lambda i, c: body(i, c, *cs), c0))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a0, *consts))
        warm = time.perf_counter() - t0
        ts = []
        for j in range(repeats):
            c0 = jax.block_until_ready(a0 + (j + 1) * 1e-7)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(c0, *consts))
            ts.append(time.perf_counter() - t0)
        return min(ts), warm

    t_small, warm_small = timed(k_small)
    t_large, warm_large = timed(k_large)
    info = {"compile_and_first_call_s": warm_small + warm_large}
    per_iter = (t_large - t_small) / (k_large - k_small)
    if per_iter <= 0:
        # short chains on fast ops can lose the delta to timing noise: report
        # the overhead-inclusive total (an under-estimate of the rate) and
        # say so
        per_iter = t_large / k_large
        info["delta_nonpositive"] = True
    return flops_per_iter / per_iter / 1e9, per_iter, info


def child_gemm():
    """dgemm n=4096 (BASELINE config #1; reference examples/ex05_blas.cc).

    Times the framework's gemm driver (slate_tpu.blas.gemm, traced under jit —
    it lowers to one fused XLA matmul at Precision.HIGHEST)."""
    import jax
    import jax.numpy as jnp
    import slate_tpu

    n = 4096
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, n), dtype=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(n, jnp.float32))

    def body(i, c, b, scale):
        # the framework's gemm always computes at lax.Precision.HIGHEST
        # (ops/blas3.py), which is what the f32hi metric name asserts
        return slate_tpu.gemm(scale, c, b, 0.0, c)

    ks, kl = 8, 136
    gflops, per_iter, info = _chain_rate(body, a, (b, scale), ks, kl, 2.0 * n**3)
    _emit({"metric": f"gemm_f32hi_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": per_iter, **info})


def child_potrf():
    """dpotrf n=16384 (BASELINE config #2; reference ex07 / test_posv).

    Times the framework's potrf XLA target (linalg/chol.py: tril(cholesky(A))).
    The loop body perturbs the diagonal with a value data-dependent on the
    previous factor so XLA cannot collapse the chain."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 16384
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (n, n), dtype=jnp.float32) / jnp.sqrt(
        jnp.asarray(n, jnp.float32))
    a = jnp.matmul(m, m.T, precision=lax.Precision.HIGHEST) + 2.0 * jnp.eye(
        n, dtype=jnp.float32)

    import slate_tpu

    # the blocked Tiled target: XLA's fused Cholesky serializes its internal
    # panel steps and crawls at large n on TPU; the framework's right-looking
    # blocked factorization keeps the trailing updates as big MXU gemms —
    # the reason SLATE-style blocking exists (potrf.cc:84-195).
    # BENCH_POTRF_NB overrides for on-chip block-size sweeps;
    # BENCH_POTRF_INVTRSM=1 selects the inverse-apply panel variant
    # (Options.trsm_via_inverse) and marks the metric accordingly so the
    # sweep rows never conflate with the true-trsm baseline.
    import os as _os
    inv = _os.environ.get("BENCH_POTRF_INVTRSM") == "1"
    opts = {"target": "tiled",
            "block_size": int(_os.environ.get("BENCH_POTRF_NB", 2048)),
            "trsm_via_inverse": inv}

    def body(i, c, a):
        ap = a + (1e-6 * c[0, 0]) * jnp.eye(n, dtype=a.dtype)
        return slate_tpu.potrf(ap, opts=opts)[0]

    gflops, per_iter, info = _chain_rate(body, a, (a,), 1, 3, n**3 / 3.0,
                                         repeats=2)
    tag = "_invtrsm" if inv else ""
    _emit({"metric": f"potrf{tag}_f32_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": per_iter, **info})


def child_getrf(panel=None):
    """dgetrf (BASELINE config #3; reference test_gesv). Partial-pivot LU via the
    framework's getrf XLA target (linalg/lu.py: lax.linalg.lu).

    ``panel`` pins Options.lu_panel for the first-class A/B configs
    ("getrf" = tournament, "getrf_pp" = pp); the BENCH_GETRF_PANEL env knob
    remains for ad-hoc sweeps."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 16384
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)

    import slate_tpu

    # tournament pivoting (getrf_tntpiv): CALU keeps the panel work as
    # sorts+gemms — the SURVEY §7 prediction that tournament pivoting is the
    # better-fit default on TPU
    # BENCH_GETRF_NB / BENCH_GETRF_IB override the outer/inner blocking for
    # on-chip sweeps (VERDICT r2 next-step #2 asks for nb in {256,512,1024})
    import os as _os
    panel = panel or _os.environ.get("BENCH_GETRF_PANEL", "tournament")
    # ib defaults to nb (FLAT panel): the round-6 bisection of the r5 getrf
    # regression landed on the r3 two-level split — cost_analysis at the
    # scaled shape shows ib=nb/8 costs 2.96x the bytes accessed of the flat
    # panel for an 11% flop saving (round-6 bench notes, in git history).
    # Two-level stays available as the BENCH_GETRF_IB sweep knob.
    nb_ = int(_os.environ.get("BENCH_GETRF_NB", 2048))
    opts = {"method_lu": "calu", "lu_panel": panel,
            "block_size": nb_,
            "inner_blocking": int(_os.environ.get("BENCH_GETRF_IB", nb_))}

    def body(i, c, a):
        ap = a + (1e-6 * c[0, 0]) * jnp.eye(n, dtype=a.dtype)
        return slate_tpu.getrf(ap, opts=opts)[0]

    gflops, per_iter, info = _chain_rate(body, a, (a,), 1, 3, 2.0 * n**3 / 3.0,
                                         repeats=2)
    tag = "" if panel == "tournament" else f"_{panel}"
    _emit({"metric": f"getrf_calu{tag}_f32_n{n}_gflops",
           "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": per_iter, **info})


def child_gels():
    """Tall-skinny least squares m=131072 n=4096, CholQR path (BASELINE config
    #4; reference test_gels). Times the framework's jittable cholqr2 + solve
    (linalg/qr.py). Rate uses the Householder QR job model 2n^2(m - n/3) so it
    is comparable with the reference's dgeqrf/dgels rate for the same problem."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    m, n = 131072, 4096
    nrhs = 16
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, n), dtype=jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (m, nrhs), dtype=jnp.float32)

    import slate_tpu

    def body(i, bc, a):
        # the framework's CSNE least-squares path (linalg/qr.py gels_cholqr).
        # A must be perturbed by the carry: with a loop-invariant A, XLA
        # hoists the entire O(m n^2) factorization out of the fori_loop and
        # the chain delta times only the thin RHS solve (observed: t(k=3) -
        # t(k=1) = 0.02 s for a 0.65 s job)
        ap = a + 1e-7 * bc[0, 0]
        X = slate_tpu.gels_cholqr(ap, bc)
        return bc + 1e-6 * X[0, 0]

    flops = 2.0 * n * n * (m - n / 3.0) + 4.0 * m * n * nrhs
    gflops, sec, info = _chain_rate(body, b, (a,), 1, 3, flops, repeats=2)
    _emit({"metric": f"gels_cholqr_f32_{m}x{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "m": m, "n": n, "sec_per_call": sec, **info})


def child_heev():
    """Hermitian eigenvalues at BASELINE scale (config #5a: the n=20,000-class
    problem; reference test_heev). Times the framework's heev values driver
    (linalg/eig.py default = fused XLA eigh — QDWH spectral D&C, all-matmul).
    Model: 4n^3/3 (tridiagonal reduction dominates)."""
    import jax
    import jax.numpy as jnp

    n = 16384
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (n, n), dtype=jnp.float32)
    a = (m + m.T) / 2.0

    import slate_tpu

    def body(i, c, a):
        ap = a + (1e-6 * c[0]) * jnp.eye(n, dtype=a.dtype)
        lam = slate_tpu.heev(ap, uplo="lower", want_vectors=False)[0]
        return c + 1e-6 * lam

    c0 = jnp.zeros((n,), jnp.float32)
    gflops, sec, info = _chain_rate(body, c0, (a,), 1, 2, 4.0 * n**3 / 3.0,
                                    repeats=2)
    _emit({"metric": f"heev_vals_f32_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": sec, **info})


def child_svd():
    """Singular values at BASELINE scale (config #5b: the n=20,000-class
    problem; reference test_svd). Times the framework's svd_vals path
    (linalg/svd.py). Model: 8n^3/3."""
    import jax
    import jax.numpy as jnp

    n = 16384
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)

    import slate_tpu

    def body(i, c, a):
        ap = a + (1e-6 * c[0]) * jnp.eye(n, dtype=a.dtype)
        s = slate_tpu.svd_vals(ap)
        return c + 1e-6 * s

    c0 = jnp.zeros((n,), jnp.float32)
    gflops, sec, info = _chain_rate(body, c0, (a,), 1, 2, 8.0 * n**3 / 3.0,
                                    repeats=2)
    _emit({"metric": f"svd_vals_f32_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": sec, **info})


def child_norm():
    """General-matrix norms n=16384 via the Pallas streaming kernels
    (reference device_genorm.cu / test_genorm).  Bandwidth-bound: the metric
    is the Frobenius rate on the 2n^2 flop model (square + add per element);
    the one-norm runs in the same chain so both custom kernels execute on
    hardware.  vs_baseline compares against A100 fp64 dlange at HBM speed."""
    import jax
    import jax.numpy as jnp

    n = 16384
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)

    import slate_tpu

    # BENCH_NORM_IMPL=xla times the plain fused-XLA reduction instead of
    # the Pallas streaming kernels — the on-chip A/B for the 0.26x round-3
    # reading (if XLA's reduction already runs at bandwidth, the fix is a
    # routing default, not a kernel)
    tag = ""
    if os.environ.get("BENCH_NORM_IMPL", "").lower() == "xla":
        from slate_tpu.ops import norms as _norm_ops
        _norm_ops.USE_PALLAS = False
        tag = "_xla"

    def body(i, c, a):
        ap = a + c[0]                      # chain dependence: ~2 HBM passes
        f = slate_tpu.norm("fro", ap)      # 1 pass (Pallas streaming kernel)
        o = slate_tpu.norm("one", ap)      # 1 pass (reuse ap — no extra add)
        return c + 1e-9 * (f + o)

    c0 = jnp.zeros((1,), jnp.float32)
    ks, kl = 4, 20
    # traffic accounting (round-3 review: the old body did ~6 HBM passes per
    # iter while the metric modeled 2, understating the kernel ~3x): one iter
    # is ~4 same-cost bandwidth-bound passes (perturb copy 2, fro 1, one 1),
    # so the fro job (2n^2 flops over its 1 pass) is attributed 1/4 of the
    # iter time.  Exact pass count depends on XLA fusing the perturb-add
    # into the norm reads (then 3); the 1/4 attribution is the conservative
    # end, stated here so the number is interpretable.
    gflops, per_iter, info = _chain_rate(body, c0, (a,), ks, kl,
                                         4.0 * 2.0 * n * n)
    _emit({"metric": f"genorm_fro{tag}_f32_n{n}_gflops",
           "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": per_iter,
           "note": "fro+one+perturb per iter (~4 passes); rate = fro model "
                   "over 1/4 iter time", **info})


def _direct_rate(run, make_input, flops, repeats=3):
    """GFLOP/s for drivers that are not chain-able (multi-call pipelines /
    internal while_loops): warm once, then time ``run`` on a freshly
    perturbed input each repeat, ending each timed call in
    ``block_until_ready``.  Host dispatch is inside the timed region, so
    rates are under-estimates for short jobs."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(run(make_input(0)))   # compile + warm
    warm = time.perf_counter() - t0
    ts = []
    for j in range(repeats):
        x = jax.block_until_ready(make_input(j + 1))
        t0 = time.perf_counter()
        jax.block_until_ready(run(x))
        ts.append(time.perf_counter() - t0)
    sec = min(ts)
    return flops / sec / 1e9, sec, {"compile_and_first_call_s": warm}


def child_potrf_la():
    """potrf through the explicit lookahead pipeline (parallel/pipeline.py,
    potrf.cc:136-177's overlap structure) on a 1-device grid — the
    single-chip analogue of potrf_distributed(lookahead>=2).  Same job and
    denominator as the 'potrf' config, so the two rows read as a direct
    schedule comparison (VERDICT r3 #2)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 16384
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (n, n), dtype=jnp.float32) / jnp.sqrt(
        jnp.asarray(n, jnp.float32))
    a = jnp.matmul(m, m.T, precision=lax.Precision.HIGHEST) + 2.0 * jnp.eye(
        n, dtype=jnp.float32)

    from slate_tpu.parallel.mesh import ProcessGrid
    from slate_tpu.parallel.pipeline import potrf_pipelined

    import os as _os
    nb = int(_os.environ.get("BENCH_POTRF_LA_NB", 2048))
    grid = ProcessGrid(1, 1)

    def make_input(j):
        return a + (1e-6 * j) * jnp.eye(n, dtype=a.dtype)

    gflops, sec, info = _direct_rate(
        lambda x: potrf_pipelined(x, grid, nb=nb),
        make_input, n**3 / 3.0,
        repeats=2)
    _emit({"metric": f"potrf_lookahead_f32_n{n}_gflops",
           "value": round(gflops, 1), "unit": "GFLOP/s", "n": n, "nb": nb,
           "sec_per_call": sec, **info})


def child_f64gemm():
    """Emulated-f64 gemm n=4096 (ops/f64emu.py: exact Ozaki bf16 splitting,
    ~s(s+1)/2 = 28 MXU passes at s=7).  The one config whose vs_baseline is
    fp64-class against fp64 (A100 dgemm) with no precision crossing — the
    d-precision story VERDICT r3 #3 asked to measure, not just claim."""
    import jax
    import jax.numpy as jnp

    n = 4096
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, n), dtype=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(n, jnp.float32))

    from slate_tpu.ops.f64emu import gemm_f64emu

    def body(i, c, b, scale):
        # the full job each iteration: split both operands, 28 bf16 passes,
        # hilo accumulate, collapse (alpha folds in exactly: power of two
        # only when n is a power of 4; the rounding is one f32 multiply)
        return gemm_f64emu(c, b, alpha=scale)

    ks, kl = 2, 8
    gflops, per_iter, info = _chain_rate(body, a, (b, scale), ks, kl,
                                         2.0 * n**3, repeats=2)
    _emit({"metric": f"gemm_f64emu_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "sec_per_call": per_iter,
           "note": "double-precision-class result (Ozaki s=7); honest fp64 "
                   "vs fp64 ratio", **info})


def child_gesvir():
    """gesv_f64ir n=4096: f32 LU factor + emulated-f64 iterative refinement
    to double-class forward error (ops/f64emu.py; the reference's dsgesv
    with the f64 refinement EMULATED).  Rate on the dgetrf 2n^3/3 model +
    the thin IR solves, vs A100 dgesv."""
    import jax
    import jax.numpy as jnp

    n = 4096
    nrhs = 16
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32) + 2.0 * jnp.sqrt(
        jnp.asarray(n, jnp.float32)) * jnp.eye(n, dtype=jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, nrhs),
                          dtype=jnp.float32)

    from slate_tpu.ops.f64emu import gesv_f64ir

    def run(x):
        Xh, Xl, iters, info = gesv_f64ir(x, b)
        return Xh

    def make_input(j):
        return a + (1e-6 * j) * jnp.eye(n, dtype=a.dtype)

    flops = 2.0 * n**3 / 3.0 + 2.0 * n * n * nrhs
    gflops, sec, info = _direct_rate(run, make_input, flops, repeats=2)
    _emit({"metric": f"gesv_f64ir_n{n}_gflops", "value": round(gflops, 1),
           "unit": "GFLOP/s", "n": n, "nrhs": nrhs, "sec_per_call": sec,
           "note": "double-class forward error on f32 hardware; one host "
                   "sync per solve (lax.while_loop IR)", **info})


def child_heev2s():
    """heev values via the SLATE-parity two-stage pipeline (he2hb -> hb2st ->
    Sturm/D&C, linalg/eig.py method='two_stage') at n=8192 — timed next to
    the fused-QDWH default so the method choice is data, not stance
    (VERDICT r3 #4)."""
    import jax
    import jax.numpy as jnp

    n = int(os.environ.get("BENCH_HEEV2S_N", 8192))
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (n, n), dtype=jnp.float32)
    a = (m + m.T) / 2.0

    import slate_tpu

    def run(x):
        # chase_pipeline: the multi-sweep batched chase (hb2st.cc's pass/step
        # concurrency) — the accelerator-shaped stage 2; the sequential
        # window form is for CPU (linalg/eig.py hb2st docstring)
        lam, _ = slate_tpu.heev(x, want_vectors=False, method="two_stage",
                                chase_pipeline=True)
        return lam

    def make_input(j):
        return a + (1e-6 * j) * jnp.eye(n, dtype=a.dtype)

    gflops, sec, info = _direct_rate(run, make_input, 4.0 * n**3 / 3.0,
                                     repeats=2)

    # phase split (heev.cc:126-212's timer-level-2 analogue): time each
    # stage once through the shared Timers/phase_report machinery, blocking
    # per stage so the spans are device time, not dispatch — one chip
    # capture carries the he2hb / chase / tridiag breakdown alongside the
    # end-to-end rate, the same map shape the tester prints under --timers
    import jax
    from slate_tpu.linalg.eig import hb2st, he2hb, sterf
    from slate_tpu.utils.trace import Timers, phase_report

    tm = Timers()
    with tm.time("he2hb"):
        band, Vs, Ts = jax.block_until_ready(he2hb(a))
    with tm.time("hb2st"):
        d, e = jax.block_until_ready(
            hb2st(band, want_vectors=False, pipeline=True))
    with tm.time("sterf"):
        jax.block_until_ready(sterf(d, e))
    phases = phase_report(tm)

    _emit({"metric": f"heev_two_stage_f32_n{n}_gflops",
           "value": round(gflops, 1), "unit": "GFLOP/s", "n": n,
           "sec_per_call": sec, "phases_first_call": phases, **info})


def child_svd2s():
    """Singular values via the SLATE-parity two-stage pipeline (ge2tb ->
    tb2bd -> Golub–Kahan bisection, linalg/svd.py method='two_stage') at
    n=8192 — timed next to the fused-QDWH default, with the ge2tb/tb2bd/
    bdsqr phase split in the record (svd.cc:270-304 timer analogue)."""
    import jax
    import jax.numpy as jnp

    n = int(os.environ.get("BENCH_SVD2S_N", 8192))
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)

    import slate_tpu

    def run(x):
        S, _, _ = slate_tpu.svd(x, want_u=False, want_vt=False,
                                method="two_stage",
                                chase_pipeline=True)
        return S

    def make_input(j):
        return a + (1e-6 * j) * jnp.eye(n, dtype=a.dtype)

    gflops, sec, info = _direct_rate(run, make_input, 8.0 * n**3 / 3.0,
                                     repeats=2)

    from slate_tpu.linalg.svd import bdsqr, ge2tb
    from slate_tpu.utils.trace import Timers, phase_report

    tm = Timers()
    with tm.time("ge2tb"):
        d, e, _, _ = jax.block_until_ready(ge2tb(a, chase_pipeline=True))
    with tm.time("bdsqr"):
        jax.block_until_ready(bdsqr(d, e))
    phases = phase_report(tm)

    _emit({"metric": f"svd_two_stage_f32_n{n}_gflops",
           "value": round(gflops, 1), "unit": "GFLOP/s", "n": n,
           "sec_per_call": sec, "phases_first_call": phases, **info})


def child_serve_mixed():
    """Mixed-traffic serving throughput (slate_tpu.serve; ROADMAP item 2's
    new bench axis): ≥1000 small gesv/posv/gels requests across ≥4 shape
    buckets through the async queue — solves/sec + p50/p99 latency, with
    batch-occupancy and cache hit-rate riding in the metrics blob _emit
    attaches.  Runs the same protocol on CPU and TPU (the problems are
    small; the axis is queue+cache throughput, not peak flops): warm-up
    compiles every (routine, bucket, batch-bucket) executable, then the
    measured pass must take zero cache misses."""
    from slate_tpu.serve.queue import BucketPolicy
    from slate_tpu.serve.workload import (run_continuous_ab,
                                          run_mixed_workload)

    stats = run_mixed_workload(num_requests=1200, seed=0)
    # continuous-batching A/B (ROADMAP 2(a)): interleaved flush-vs-
    # continuous rounds — queue_wait p50 at equal paced load plus the warm
    # throughput ratio and slot-join rate ride in the metric blob.  A
    # tight policy bounds the per-run warmup compile bill.
    ab = run_continuous_ab(
        num_requests=300, seed=0, rounds=2, executors=2,
        dims=(8, 13),
        policy=BucketPolicy(dims=(16, 32), nrhs_dims=(1, 4),
                            batch_dims=(1, 4, 16), max_batch=16))
    _emit({"metric": "serve_mixed_solves_per_sec",
           "value": stats["solves_per_sec"], "unit": "solves/s",
           "requests": stats["requests"], "wall_s": stats["wall_s"],
           "p50_ms": stats["p50_ms"], "p99_ms": stats["p99_ms"],
           "distinct_buckets": stats["distinct_buckets"],
           "routines": stats["routines"],
           "misses_after_warmup": stats["misses_after_warmup"],
           "cache": stats["cache"], "warmup": stats["warmup"],
           "continuous_ab": ab})


def child_serve_scale():
    """Executor-pool scaling axis (multi-executor serving data path): the
    same warm mixed-traffic protocol as serve_mixed run at pool sizes
    N in {1, 2, 4} on one host.  Headline value is the N=2 warm rate
    (scored against the 2x serve_mixed denominator); the N=1/N=4 rates
    and the N2/N1 speedup ride along so regressions in routing, stealing,
    or the dispatch/resolve overlap show up as a trend break even when
    the absolute rate moves with the host."""
    from slate_tpu.serve.workload import run_scale_workload

    out = run_scale_workload(executor_counts=(1, 2, 4), num_requests=900,
                             seed=0)
    sps = out["solves_per_sec"]
    runs = out["runs"]
    # the continuous axis at N=2: same stream under rolling admission —
    # eager dispatch + staged merges/joins must hold the warm rate
    cont = run_scale_workload(executor_counts=(2,), num_requests=900,
                              seed=0, continuous=True)["runs"]["2"]
    _emit({"metric": "serve_scale_n2_solves_per_sec",
           "value": sps["2"], "unit": "solves/s",
           "solves_per_sec": sps,
           "n2_over_n1": round(sps["2"] / max(sps["1"], 1e-9), 3),
           "steals": {n: runs[n].get("steals", 0) for n in runs},
           "misses_after_warmup": {
               n: runs[n].get("misses_after_warmup") for n in runs},
           "p99_ms": {n: runs[n].get("p99_ms") for n in runs},
           "continuous_n2": {
               "solves_per_sec": cont["solves_per_sec"],
               "slot_joins": cont.get("slot_joins"),
               "slot_join_rate": cont.get("slot_join_rate"),
               "queue_wait_p50_ms": cont.get("queue_wait_p50_ms"),
               "misses_after_warmup": cont.get("misses_after_warmup")}})


CHILDREN = {
    "probe": child_probe,
    "serve_mixed": child_serve_mixed,
    "serve_scale": child_serve_scale,
    "norm": child_norm,
    "gemm": child_gemm,
    "potrf": child_potrf,
    "getrf": child_getrf,
    "getrf_pp": lambda: child_getrf(panel="pp"),
    "gels": child_gels,
    "heev": child_heev,
    "svd": child_svd,
    "potrf_la": child_potrf_la,
    "f64gemm": child_f64gemm,
    "gesvir": child_gesvir,
    "heev2s": child_heev2s,
    "svd2s": child_svd2s,
}


def run_child(name):
    """Child entry: compile cache first, then refuse anything but a TPU."""
    sys.path.insert(0, REPO)
    from slate_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = _device()
    if dev["platform"] != "tpu":
        sys.exit(f"bench: no TPU (JAX platform {dev['platform']!r})")
    CHILDREN[name]()


# ---------------------------------------------------------------------------
# parent — orchestration; never imports jax
# ---------------------------------------------------------------------------


def _run_child(name, timeout):
    t0 = time.time()
    try:
        p = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--child", name],
                           capture_output=True, text=True, timeout=timeout,
                           cwd=REPO)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"timeout after {timeout} s",
                "elapsed": time.time() - t0}
    elapsed = time.time() - t0
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    if p.returncode == 0 and lines:
        try:
            out = json.loads(lines[-1])
            out.update({"ok": True, "elapsed": elapsed})
            return out
        except json.JSONDecodeError:
            pass
    return {"ok": False, "error": f"rc={p.returncode}",
            "stderr_tail": p.stderr[-2000:], "elapsed": elapsed}


def main(only=None) -> int:
    configs = [c for c in CONFIGS if not only or c in only]
    probe = _run_child("probe", timeout=300)
    if not probe.get("ok") or probe.get("platform") != "tpu":
        print("bench: no TPU; nothing measured: "
              + json.dumps({k: probe.get(k) for k in
                            ("platform", "error", "stderr_tail")}),
              file=sys.stderr)
        return 2
    device = {k: probe[k] for k in ("platform", "device_kind", "n_devices")}
    detail = {"device": device, "configs": {}}
    failed = []
    for name in configs:
        res = _run_child(name, timeout=CONFIG_TIMEOUTS.get(name, 900))
        if res.get("ok") and isinstance(res.get("value"), (int, float)):
            res["vs_baseline"] = round(res["value"] / BASELINES[name], 3)
        else:
            failed.append(name)
        detail["configs"][name] = res
    with open(DETAIL_PATH, "w") as f:
        json.dump(detail, f, indent=1, default=str)
        f.write("\n")
    head = detail["configs"].get(HEADLINE, {})
    print(json.dumps({
        "metric": head.get("metric", "gemm_f32hi_n4096_gflops"),
        "value": head.get("value"), "unit": "GFLOP/s",
        "vs_baseline": head.get("vs_baseline"), "device": device,
        "configs": {n: [r.get("value"), r.get("vs_baseline")]
                    for n, r in detail["configs"].items()},
        "failed": failed, "detail": os.path.basename(DETAIL_PATH)}))
    return 1 if failed else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None, choices=sorted(CHILDREN))
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of configs to run")
    ns = ap.parse_args()
    if ns.child:
        run_child(ns.child)
    else:
        sel = set(ns.only.split(",")) if ns.only else None
        unknown = (sel or set()) - set(CONFIGS)
        if unknown:
            sys.exit(f"unknown configs {sorted(unknown)}; valid: {CONFIGS}")
        sys.exit(main(only=sel))
