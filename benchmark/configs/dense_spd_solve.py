"""The dense SPD solve as the window drives it: SLATE's public ``posv``, or
``potrs`` on a factor that ``potrf`` made in set-up, on ``HermitianMatrix``
and ``Matrix`` wrappers, each compiled as one program.

The traffic names the routine.  ``posv``: step i solves system
``i % matrices`` with right-hand sides ``i % rhs_blocks``.  ``potrs``: the
one matrix is factored once in set-up and step i solves with right-hand
sides ``i % rhs_blocks``.  The matrices and right-hand sides are made on
the device from the seed in one jitted call.
"""

from __future__ import annotations

import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _reference():
    from benchlib.harness import load_module

    return load_module(os.path.join(_HERE, "dense_spd_solve_reference.py"),
                       "bench_dense_spd_solve_reference")


def _jobs():
    from benchlib import jobs

    return jobs


class System:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 sizes: dict | None = None):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        sizes = sizes or {}
        self.n = int(sizes.get("n", config["n"]))
        self.nrhs = int(sizes.get("nrhs", traffic["nrhs"]))
        self.routine = traffic["routine"]
        self.matrices = int(traffic["matrices"])
        self.rhs_blocks = int(traffic["rhs_blocks"])
        self.A = self.B = self.L = None
        self.exe = {}

    # -- set-up ---------------------------------------------------------------

    def programs(self):
        """The public calls, one function per routine."""
        import slate_tpu as slate

        def posv(a, b):
            B = slate.Matrix.from_array(b)
            _, info = slate.posv(
                slate.HermitianMatrix.from_array(slate.Uplo.Lower, a), B)
            return B.array, info

        def potrf(a):
            return slate.potrf(
                slate.HermitianMatrix.from_array(slate.Uplo.Lower, a))

        def potrs(l, b):
            B = slate.Matrix.from_array(b)
            slate.potrs(slate.HermitianMatrix.from_array(slate.Uplo.Lower, l),
                        B)
            return B.array

        return {"posv": posv, "potrf": potrf, "potrs": potrs}

    def setup(self, phase):
        with phase("imports"):
            import jax  # noqa: F401

            import slate_tpu  # noqa: F401  (the system under test)
        with phase("data"):
            self.make_data()
        with phase("programs"):
            self.load_programs()
        with phase("warmup"):
            self.warm()

    def make_data(self):
        """The seed's matrices and right-hand sides, on the device."""
        import jax
        import jax.numpy as jnp

        from benchlib import gen

        make = jax.jit(gen.dense_pool_fn(
            self.n, self.nrhs, self.matrices, self.rhs_blocks,
            jnp.dtype(self.config["dtype"]),
            float(self.traffic["spd_shift_radii"])))
        self.A, self.B = make(gen.device_key(self.seed, 1))
        jax.block_until_ready((self.A, self.B))

    def load_programs(self, progs=None):
        """Compile the routine's programs: the public calls, or ``progs``
        with the same signatures (the reference in their place)."""
        import jax
        import jax.numpy as jnp

        progs = progs or self.programs()
        dtype = jnp.dtype(self.config["dtype"])
        a_spec = jax.ShapeDtypeStruct((self.n, self.n), dtype)
        b_spec = jax.ShapeDtypeStruct((self.n, self.nrhs), dtype)
        if self.routine == "posv":
            self.exe["posv"] = jax.jit(progs["posv"]).lower(
                a_spec, b_spec).compile()
        else:
            self.exe["potrf"] = jax.jit(progs["potrf"]).lower(
                a_spec).compile()
            self.exe["potrs"] = jax.jit(progs["potrs"]).lower(
                a_spec, b_spec).compile()

    def warm(self):
        """One call of each program; for potrs, the factor the window
        reuses (its info is checked with the answers)."""
        import jax

        if self.routine == "posv":
            jax.block_until_ready(self.exe["posv"](self.A[0], self.B[0]))
        else:
            self.L, self.factor_info = self.exe["potrf"](self.A[0])
            jax.block_until_ready(self.exe["potrs"](self.L, self.B[0]))

    # -- the window -------------------------------------------------------------

    def step(self, i):
        b = self.B[i % self.rhs_blocks]
        if self.routine == "posv":
            return self.exe["posv"](self.A[i % self.matrices], b)
        return self.exe["potrs"](self.L, b), None

    def reference_programs(self, kind: str = "control"):
        """The plain reference's programs for :meth:`load_programs`; see
        ``dense_spd_solve_reference.programs``."""
        return _reference().programs(kind)

    def counters(self):
        return {}

    def job(self, routine: str | None = None):
        """The job model of one call of ``routine``, by default of the
        traffic's routine, at this cell's sizes."""
        return _jobs().job(routine or self.routine, self.n, self.nrhs,
                           itemsize=np.dtype(self.config["dtype"]).itemsize)

    def program_texts(self):
        """The compiled HLO text of every program loaded for the window."""
        return [exe.as_text() for exe in self.exe.values()]

    @staticmethod
    def plant_fault(kind: str, monkeypatch):
        """Break slate's potrs, which both routines' programs call, where it
        produces the answer: ``altered`` (one entry), ``unchanged`` (the
        right-hand sides returned as the answer) or ``half`` (half the
        right-hand sides' answers left out)."""
        import slate_tpu
        from slate_tpu.core.matrix import as_array, write_back
        from slate_tpu.linalg import chol

        real = chol.potrs

        def potrs(A, B, opts=None, uplo=None):
            if kind == "unchanged":
                return write_back(B, as_array(B))
            x = real(A, B, opts, uplo)
            if kind == "altered":
                x = x.at[0, 0].add(1.0)
            else:
                x = x.at[:, ::2].set(0.0)
            return write_back(B, x)

        monkeypatch.setattr(chol, "potrs", potrs)
        monkeypatch.setattr(slate_tpu, "potrs", potrs)

    # -- the check ------------------------------------------------------------

    def check(self, obs):
        """The scaled residual of every kept step, in f64 on the host."""
        import jax
        import jax.numpy as jnp

        ref = _reference()
        kept = sorted(obs["kept"], key=lambda kv: kv[0])
        obs["kept"] = None
        xs = [(i, np.asarray(x)) for i, (x, _) in kept]
        infos = [int(info) for _, (_, info) in kept if info is not None]
        if self.routine == "potrs":
            infos.append(int(self.factor_info))
        self.L = None
        worst = 0.0
        for p in range(self.matrices):
            mine = [(i, x) for i, x in xs if i % self.matrices == p]
            if not mine:
                continue
            a_norm = float(jnp.linalg.norm(self.A[p]))
            a = np.asarray(jax.device_get(self.A[p]))
            res = ref.scaled_residuals(
                a, [x for _, x in mine],
                [np.asarray(self.B[i % self.rhs_blocks]) for i, _ in mine],
                a_norm=a_norm)
            # a non-finite answer reads as infinitely wrong, not as 0
            worst = max([worst] + [r if np.isfinite(r) else np.inf
                                   for r in res])
        self.A = self.B = None
        name = f"{self.routine}_residual"
        compared = {name: {"value": float(worst),
                           "limit": float(self.config["limits"][name])}}
        ok = bool(xs) and all(v == 0 for v in infos)
        return compared, ok

    def close(self):
        self.exe.clear()
        self.A = self.B = self.L = None
