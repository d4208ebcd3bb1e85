#!/usr/bin/env python3
"""Drive slate_tpu's main path once on the chip, through the public API.

One chip (no arguments) runs six phases at BASELINE.md's sizes, each checked
on the host against a plain reference:

* ``posv``  -- ``slate.posv`` on a ``HermitianMatrix`` n=16384 f32, 16 RHS
* ``gesv``  -- ``slate.gesv`` on a ``Matrix`` n=16384 f32, 16 RHS
* ``gels``  -- ``slate.gels`` on a tall 131072x4096 f32 matrix, 16 RHS
* ``heev``  -- ``slate.heev`` values only, n=4096 f32, through the reference's
  two-stage pipeline (he2hb -> hb2st -> sterf, BASELINE #5)
* ``norm``  -- ``slate.norm`` Fro on 16384^2 f32; asserts from the compiled HLO
  that the Pallas kernel (``tpu_custom_call``) is what runs
* ``serve`` -- 256 ``serve.make_requests`` through ``serve.solve_many`` twice
  (second pass all cache hits), then through a warmed
  ``ServeQueue(executors=2)``; ends with ``serve.shutdown()`` and no thread
  of the phase left alive

Solves are gated by the f64 scaled residual against the tester's own ``_tol``
(``slate_tpu/testing/routines.py``); heev by the trace and Frobenius
identities; serve by ``np.linalg`` on each request.

``--chips 4`` runs only the sharded path: ``slate.posv`` and ``slate.gesv``
with operands bound to a 2x2 ``ProcessGrid``, each compared with the one-chip
solve of the same seeded system.

The dense phases are the public calls under ``jax.jit`` (a cold chip run is
mostly compile): all programs are traced up front and compiled one at a time
on a worker thread while the main thread runs the phases, then each phase
runs its program twice.  Each phase prints one ``PHASE {...}`` line: compile
seconds (on the worker), first-call and warm-call seconds (host clock around
work ending in ``block_until_ready``), process ``peak_bytes_in_use``, the
residual and its limit.  The last stdout line is the contract line
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero and prints no result.
``--rehearse`` is for this repo's CPU rehearsal only: it admits the CPU
backend and shrinks every size.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FULL = {"posv_n": 16384, "gesv_n": 16384, "gels_m": 131072, "gels_n": 4096,
        "heev_n": 4096, "norm_n": 16384, "serve_requests": 256, "nrhs": 16}
TINY = {"posv_n": 256, "gesv_n": 256, "gels_m": 2048, "gels_n": 64,
        "heev_n": 128, "norm_n": 512, "serve_requests": 24, "nrhs": 4}

# JAX's persistent-cache events: every cached compile request, the requests
# served from the cache, and the entries written (compiles over 1 s)
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
                 "/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}


class Smoke:
    def __init__(self, seed, jax, slate, sizes):
        self.jax = jax
        self.slate = slate
        self.sizes = sizes
        self.rng_seed = seed
        self.failed = []
        self.compiled = {}       # phase name -> future of (executable, s)
        self.cache = dict.fromkeys(_CACHE_EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        key = _CACHE_EVENTS.get(event)
        if key:
            self.cache[key] += 1

    # -- helpers ------------------------------------------------------------

    def rng(self, salt):
        return np.random.default_rng([self.rng_seed, salt])

    def peak_bytes(self):
        stats = [d.memory_stats() or {} for d in self.jax.local_devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        return None if None in peaks else max(peaks)

    def tol(self, m, n, k, mult=1.0):
        from slate_tpu.testing.routines import _tol

        return _tol({"m": m, "n": n, "k": k, "dtype": np.float32}) * mult

    def run(self, name, fn):
        print(f"-- phase {name}", flush=True)
        t0 = time.perf_counter()
        try:
            rec = fn()
        except Exception:  # recorded and reported; the script exits non-zero
            traceback.print_exc()
            self.failed.append(name)
            print("PHASE " + json.dumps({"phase": name, "ok": False}),
                  flush=True)
            return
        finally:
            # drop the phase's program too: executables live in device memory
            self.compiled.pop(name, None)
            gc.collect()
        rec = {"phase": name, **rec,
               "phase_s": time.perf_counter() - t0,
               "peak_bytes_in_use": self.peak_bytes()}
        ok = rec.get("residual") is not None and rec["residual"] <= rec["tol"]
        ok = ok and all(rec.get("checks", {}).values())
        rec["ok"] = bool(ok)
        print("PHASE " + json.dumps(rec), flush=True)
        if not ok:
            self.failed.append(name)

    def block(self, *xs):
        for x in xs:
            self.jax.block_until_ready(x)
        return xs

    # -- one-chip phases ----------------------------------------------------

    def spd(self, n, salt):
        r = self.rng(salt).standard_normal((n, n), dtype=np.float32)
        a = (r + r.T) * np.float32(0.5)
        # semicircle radius of the symmetric part is sqrt(2n): shift past it
        a[np.diag_indices(n)] += np.float32(3.0 * np.sqrt(2.0 * n))
        return a

    @staticmethod
    def rows64(a, step=4096):
        """(row offset, f64 copy of a block of rows): host checks in f64
        without an f64 copy of the whole matrix (the host's memory is shared
        with the compiles)."""
        for i in range(0, a.shape[0], step):
            yield i, a[i:i + step].astype(np.float64)

    def residual_norms(self, a, x, b):
        """(||A x - b||, ||A||), Frobenius norms, in f64."""
        x64 = np.asarray(x, np.float64)
        r2 = a2 = 0.0
        for i, blk in self.rows64(a):
            r2 += np.sum((blk @ x64 - b[i:i + len(blk)]) ** 2)
            a2 += np.sum(blk ** 2)
        return np.sqrt(r2), np.sqrt(a2)

    def solve_residual(self, a, x, b):
        """The tester's scaled residual ||A x - b|| / (||A|| ||x||)."""
        r, an = self.residual_norms(a, x, b)
        return float(r / (an * np.linalg.norm(np.asarray(x, np.float64))))

    # Each dense phase is one jitted program over the public API (wrappers,
    # driver, write-back).  All are traced up front and compiled in phase
    # order on the worker thread (a cold chip run is mostly compile); each
    # phase then waits for its own program and runs it twice.

    def programs(self):
        slate, sz = self.slate, self.sizes
        f32 = self.jax.numpy.float32

        def spec(*shape):
            return self.jax.ShapeDtypeStruct(shape, f32)

        def posv(a, b):
            B = slate.Matrix.from_array(b)
            _, info = slate.posv(
                slate.HermitianMatrix.from_array(slate.Uplo.Lower, a), B)
            return B.array, info

        def gesv(a, b):
            X, _, info = slate.gesv(slate.Matrix.from_array(a), b)
            return X, info

        def gels(a, b):
            return slate.gels(slate.Matrix.from_array(a), b)

        # the reference's two-stage pipeline (BASELINE #5): a 60 MB program
        # at n=4096 that compiles in 19 s; the "fused" default (XLA's QDWH
        # eigh, one QDWH per size bucket) is 1.45 GB of code and 386-423 s
        # of compile on the chip's host (my described-v5e compiles and chip
        # runs, PR 21)
        def heev(a):
            lam, z = slate.heev(
                slate.HermitianMatrix.from_array(slate.Uplo.Lower, a),
                want_vectors=False, method="two_stage")
            if z is not None:
                raise AssertionError("heev returned vectors")
            return lam

        def norm(a):
            return slate.norm("fro", slate.Matrix.from_array(a))

        n1, n2, k = sz["posv_n"], sz["gesv_n"], sz["nrhs"]
        m, n, h, nn = sz["gels_m"], sz["gels_n"], sz["heev_n"], sz["norm_n"]
        # in phase order (the worker compiles in this order), beside the
        # serve phase
        return {"heev": (heev, (spec(h, h),)),
                "norm": (norm, (spec(nn, nn),)),
                "posv": (posv, (spec(n1, n1), spec(n1, k))),
                "gesv": (gesv, (spec(n2, n2), spec(n2, k))),
                "gels": (gels, (spec(m, n), spec(m, k)))}

    def start_compiles(self, programs):
        """Trace every program here, then compile them in phase order on one
        worker thread, overlapping the main thread's runs, host checks and
        the serve phase.  One at a time: the one-chip machine ends a command
        at 40 GiB of host memory, and gesv n=16384 alone takes ~19 GB to
        compile (my chip runs, PR 21: two compiles at once ran it out)."""
        self.pool = ThreadPoolExecutor(max_workers=1)

        def compile_one(lowered):
            t0 = time.perf_counter()
            exe = lowered.compile()
            return exe, time.perf_counter() - t0

        for name, (fn, specs) in programs.items():
            lowered = self.jax.jit(fn).lower(*specs)     # traced here
            self.compiled[name] = self.pool.submit(compile_one, lowered)

    def run_compiled(self, name, *args):
        """(outputs of the second call, record): compile seconds (on the
        worker), first and second call seconds."""
        exe, compile_s = self.compiled[name].result()
        t0 = time.perf_counter()
        self.block(*self.jax.tree.leaves(exe(*args)))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = exe(*args)
        self.block(*self.jax.tree.leaves(out))
        t2 = time.perf_counter() - t0
        return out, exe, {"compile_s": compile_s, "first_call_s": t1,
                          "wall_s": t2}

    def phase_posv(self):
        jnp = self.jax.numpy
        n, k = self.sizes["posv_n"], self.sizes["nrhs"]
        a = self.spd(n, 1)
        b = self.rng(2).standard_normal((n, k), dtype=np.float32)
        (x, info), _, rec = self.run_compiled("posv", jnp.asarray(a),
                                              jnp.asarray(b))
        return {"n": n, "nrhs": k, **rec,
                "residual": self.solve_residual(a, x, b),
                "tol": self.tol(n, n, k), "checks": {"info0": int(info) == 0}}

    def phase_gesv(self):
        jnp = self.jax.numpy
        n, k = self.sizes["gesv_n"], self.sizes["nrhs"]
        a = self.rng(3).standard_normal((n, n), dtype=np.float32)
        b = self.rng(4).standard_normal((n, k), dtype=np.float32)
        (x, info), _, rec = self.run_compiled("gesv", jnp.asarray(a),
                                              jnp.asarray(b))
        return {"n": n, "nrhs": k, **rec,
                "residual": self.solve_residual(a, x, b),
                "tol": self.tol(n, n, k), "checks": {"info0": int(info) == 0}}

    def phase_gels(self):
        jnp = self.jax.numpy
        m, n, k = self.sizes["gels_m"], self.sizes["gels_n"], self.sizes["nrhs"]
        a = self.rng(5).standard_normal((m, n), dtype=np.float32)
        b = self.rng(6).standard_normal((m, k), dtype=np.float32)
        x, _, rec = self.run_compiled("gels", jnp.asarray(a), jnp.asarray(b))
        # the tester's gels check: normal-equations residual
        # ||A^T (A x - b)|| / (||A||^2 ||x||), limit 100 * _tol
        x64 = np.asarray(x, np.float64)[:n]
        g, a2 = np.zeros((n, k)), 0.0
        for i, blk in self.rows64(a):
            g += blk.T @ (blk @ x64 - b[i:i + len(blk)])
            a2 += np.sum(blk ** 2)
        err = float(np.linalg.norm(g) / (a2 * np.linalg.norm(x64)))
        return {"m": m, "n": n, "nrhs": k, **rec,
                "residual": err, "tol": self.tol(m, n, k, mult=100.0),
                "checks": {"shape": tuple(np.shape(x)) == (n, k)}}

    def phase_heev(self):
        jnp = self.jax.numpy
        n = self.sizes["heev_n"]
        r = self.rng(7).standard_normal((n, n), dtype=np.float32)
        a = (r + r.T) * np.float32(0.5)
        lam, _, rec = self.run_compiled("heev", jnp.asarray(a))
        lam = np.asarray(lam, np.float64)
        a64 = a.astype(np.float64)
        fro = np.linalg.norm(a64)
        # sum(lambda) = trace(A) and sum(lambda^2) = ||A||_F^2
        err_trace = abs(lam.sum() - np.trace(a64)) / (np.sqrt(n) * fro)
        err_fro = abs(np.sqrt(np.sum(lam ** 2)) - fro) / fro
        return {"n": n, **rec,
                "residual": float(max(err_trace, err_fro)),
                "err_trace": float(err_trace), "err_fro": float(err_fro),
                "tol": self.tol(n, n, n),
                "checks": {"ascending": bool(np.all(np.diff(lam) >= 0))}}

    def phase_norm(self):
        jnp = self.jax.numpy
        n = self.sizes["norm_n"]
        a = self.rng(8).standard_normal((n, n), dtype=np.float32)
        val, exe, rec = self.run_compiled("norm", jnp.asarray(a))
        ref = float(np.linalg.norm(a.astype(np.float64)))
        # the program that ran holds the Pallas kernel, not the XLA path
        pallas = "tpu_custom_call" in exe.as_text()
        on_tpu = self.jax.default_backend() == "tpu"
        return {"n": n, **rec, "residual": abs(float(val) - ref) / ref,
                "tol": self.tol(n, n, n), "pallas_kernel_compiled": pallas,
                "checks": {"pallas": pallas or not on_tpu}}

    def phase_serve(self):
        from slate_tpu import obs, serve
        from slate_tpu.serve.queue import ServeQueue

        def counter(name):
            return sum(obs.counter(name).series().values())

        def check(reqs, results):
            worst = 0.0
            ok = True
            for (routine, a, b), (x, info) in zip(reqs, results):
                a64, b64 = a.astype(np.float64), b.astype(np.float64)
                if routine == "gels":
                    ref = np.linalg.lstsq(a64, b64, rcond=None)[0]
                    lim = self.tol(*a.shape, b.shape[1], mult=100.0)
                else:
                    ref = np.linalg.solve(a64, b64)
                    lim = self.tol(*a.shape, b.shape[1])
                err = (np.linalg.norm(np.asarray(x, np.float64) - ref)
                       / np.linalg.norm(ref))
                worst = max(worst, err / lim)
                ok = ok and int(info) == 0
            return worst, ok

        threads0 = set(threading.enumerate())
        reqs = serve.make_requests(self.sizes["serve_requests"],
                                   seed=self.rng_seed)
        hit0, miss0 = (counter("slate_serve_cache_hits_total"),
                       counter("slate_serve_cache_misses_total"))
        t0 = time.perf_counter()
        first = serve.solve_many(reqs)
        t1 = time.perf_counter() - t0
        hit1, miss1 = (counter("slate_serve_cache_hits_total"),
                       counter("slate_serve_cache_misses_total"))
        t0 = time.perf_counter()
        second = serve.solve_many(reqs)
        t2 = time.perf_counter() - t0
        hit2, miss2 = (counter("slate_serve_cache_hits_total"),
                       counter("slate_serve_cache_misses_total"))
        w1, ok1 = check(reqs, first)
        w2, ok2 = check(reqs, second)

        q = ServeQueue(executors=2)
        try:
            combos = sorted({(r, a.shape[0], a.shape[1], b.shape[1])
                             for r, a, b in reqs})
            q.warmup(combos, dtype=reqs[0][1].dtype)
            miss_q0 = counter("slate_serve_cache_misses_total")
            t0 = time.perf_counter()
            tickets = [q.submit(r, a, b) for r, a, b in reqs]
            queued = [t.result(timeout=600.0) for t in tickets]
            tq = time.perf_counter() - t0
            miss_q = counter("slate_serve_cache_misses_total") - miss_q0
            executors = sorted({t.executor for t in tickets})
        finally:
            q.close()
        wq, okq = check(reqs, queued)
        serve.shutdown()
        leftover = [t for t in threading.enumerate()
                    if t not in threads0 and t.is_alive()]
        for t in leftover:
            t.join(timeout=10.0)
        leftover = [t.name for t in leftover if t.is_alive()]
        # residual here is the worst forward error against np.linalg over
        # every request, in units of that request's own limit
        return {"requests": len(reqs), "wall_s": t2, "compile_s": t1 - t2,
                "queue_s": tq, "executors": executors,
                "first_pass": {"hits": hit1 - hit0, "misses": miss1 - miss0},
                "second_pass": {"hits": hit2 - hit1, "misses": miss2 - miss1},
                "queue_misses_after_warmup": miss_q,
                "leftover_threads": leftover,
                "residual": max(w1, w2, wq), "tol": 1.0,
                "checks": {"info0": ok1 and ok2 and okq,
                           "second_pass_all_hits": miss2 == miss1
                           and hit2 - hit1 > 0,
                           "queue_warm": miss_q == 0,
                           "threads_stopped": not leftover}}

    # -- four-chip phases ---------------------------------------------------

    def grid_2x2(self):
        from slate_tpu.parallel import ProcessGrid

        grid = ProcessGrid(2, 2)
        mesh = grid.mesh.devices
        layout = [[{"id": d.id, "coords": list(getattr(d, "coords", []))}
                   for d in row] for row in mesh]
        print("GRID " + json.dumps(layout), flush=True)
        # mesh neighbours along each axis must be physical ICI neighbours
        for a, b in [(mesh[0, 0], mesh[1, 0]), (mesh[0, 1], mesh[1, 1]),
                     (mesh[0, 0], mesh[0, 1]), (mesh[1, 0], mesh[1, 1])]:
            ca, cb = getattr(a, "coords", None), getattr(b, "coords", None)
            if ca is not None and cb is not None:
                dist = sum(abs(x - y) for x, y in zip(ca, cb))
                if dist != 1:
                    raise AssertionError(
                        f"mesh neighbours {a.id}/{b.id} are {dist} hops apart")
        return grid

    def bytes_in_use(self):
        return [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in self.jax.local_devices()]

    def dist_programs(self, grid):
        """The sharded posv/gesv programs (operands bound to the 2x2 grid,
        so the drivers take their distributed routes) and the one-chip
        programs they are compared with."""
        jax, slate = self.jax, self.slate
        k = self.sizes["nrhs"]
        f32 = jax.numpy.float32
        sharded = grid.spec()

        def wrap(routine, a):
            if routine == "posv":
                return slate.HermitianMatrix.from_array(
                    slate.Uplo.Lower, a, p=2, q=2, grid=grid)
            return slate.Matrix.from_array(a, p=2, q=2, grid=grid)

        def posv(a, b):
            B = slate.Matrix.from_array(b, p=2, q=2, grid=grid)
            _, info = slate.posv(wrap("posv", a), B)
            return B.array, info

        def gesv(a, b):
            X, _, info = slate.gesv(
                wrap("gesv", a), slate.Matrix.from_array(b, p=2, q=2,
                                                         grid=grid))
            return X, info

        def specs(n):
            return (jax.ShapeDtypeStruct((n, n), f32, sharding=sharded),
                    jax.ShapeDtypeStruct((n, k), f32, sharding=sharded))

        one = self.programs()
        self.wrap = wrap
        return {"posv_2x2": (posv, specs(self.sizes["posv_n"])),
                "posv_1chip": one["posv"],
                "gesv_2x2": (gesv, specs(self.sizes["gesv_n"])),
                "gesv_1chip": one["gesv"]}

    def phase_dist(self, routine):
        jnp = self.jax.numpy
        n, k = self.sizes[f"{routine}_n"], self.sizes["nrhs"]
        if routine == "posv":
            a = self.spd(n, 11)
        else:
            a = self.rng(12).standard_normal((n, n), dtype=np.float32)
        b = self.rng(13).standard_normal((n, k), dtype=np.float32)
        gc.collect()
        before = self.bytes_in_use()
        A = self.wrap(routine, a)
        B = self.slate.Matrix.from_array(b, p=2, q=2, grid=self.grid)
        self.block(A.array, B.array)
        gc.collect()
        held = [x - y for x, y in zip(self.bytes_in_use(), before)]
        shards = {}
        for s in A.array.addressable_shards:
            shards[s.device.id] = shards.get(s.device.id, 0) + s.data.nbytes
        # the allocator's own count where the backend keeps one (TPU), else
        # the shards' sizes (CPU rehearsal)
        share = [h / a.nbytes for h in held] if any(held) else [
            v / a.nbytes for v in shards.values()]
        (x4, info4), _, rec4 = self.run_compiled(f"{routine}_2x2", A.array,
                                                 B.array)
        x4h, info4 = np.asarray(x4, np.float64), int(info4)
        del A, B, x4
        # the comparison: the same seeded system on one chip, no grid
        (x1, info1), _, rec1 = self.run_compiled(
            f"{routine}_1chip", jnp.asarray(a), jnp.asarray(b))
        x1h, info1 = np.asarray(x1, np.float64), int(info1)
        del x1
        self.compiled.pop(f"{routine}_1chip")
        # agreement in the residual norm ||A (x4 - x1)|| / (||A|| ||x1||):
        # bounded by the two backward errors whatever cond(A) is; the plain
        # forward difference is reported beside it
        diff, an = self.residual_norms(a, x4h - x1h, np.zeros(b.shape))
        agree = float(diff / (an * np.linalg.norm(x1h)))
        forward = float(np.linalg.norm(x4h - x1h) / np.linalg.norm(x1h))
        res4 = self.solve_residual(a, x4h, b)
        res1 = self.solve_residual(a, x1h, b)
        tol = self.tol(n, n, k)
        return {"n": n, "nrhs": k, **rec4,
                "one_chip": {**rec1, "residual": res1},
                "residual": max(res4, res1), "residual_2x2": res4,
                "agreement": agree, "forward_difference": forward,
                "tol": tol, "shard_devices": sorted(shards),
                "shard_bytes_per_device": shards,
                "matrix_bytes": a.nbytes, "bytes_held_per_device": held,
                "share_per_device": share,
                "checks": {"info0": info4 == 0 and info1 == 0,
                           "four_devices": len(shards) == 4,
                           "quarter_each": all(0.2 <= s <= 0.35
                                               for s in share),
                           "agree": agree <= 2 * tol}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: admit the CPU backend, tiny sizes")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from slate_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import slate_tpu as slate

    if jax.config.jax_enable_x64:
        raise SystemExit("chip_smoke: x64 must stay off")
    sizes = TINY if args.rehearse else FULL
    print("DEVICE " + json.dumps({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "jax": jax.__version__,
        "compile_cache": cache_dir, "seed": args.seed,
        # builds native/*.so from the committed sources when absent
        "native": slate.native.backend()}), flush=True)

    smoke = Smoke(args.seed, jax, slate, sizes)
    if args.chips == 4:
        smoke.grid = smoke.grid_2x2()
        smoke.start_compiles(smoke.dist_programs(smoke.grid))
        smoke.run("posv_2x2", lambda: smoke.phase_dist("posv"))
        smoke.run("gesv_2x2", lambda: smoke.phase_dist("gesv"))
    else:
        smoke.start_compiles(smoke.programs())
        # serve first: its own compiles overlap the dense programs'; gels,
        # the largest (2 GiB operand, 8.6 GB of temporaries), runs last
        for name in ("serve", "heev", "norm", "posv", "gesv", "gels"):
            smoke.run(name, getattr(smoke, f"phase_{name}"))
    smoke.pool.shutdown()
    print("CACHE " + json.dumps(smoke.cache), flush=True)
    if smoke.failed:
        print(f"chip_smoke: failed phases: {smoke.failed}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
