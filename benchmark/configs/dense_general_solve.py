"""The dense nonsymmetric solve as the window drives it: SLATE's public
``gesv`` on ``Matrix`` wrappers, compiled as one program (``getrf`` through
the tournament-pivoted LU, then ``getrs``).

Step i solves system ``i % matrices`` with right-hand sides
``i % rhs_blocks``.  Matrices and right-hand sides are the tester's default
kind, ``rand`` (entries uniform on [0, 1)), made on the device from the seed
in one jitted call.  The check, the job models and the compiled texts are
the dense SPD configuration's (the same residual, ``test_gesv.cc``'s).

The program's phases are its scope contract: a program whose lowered text
does not name every ``getrf``/``getrs`` phase of the configuration's
vocabulary that does work is refused after lowering and before compiling,
since a trace of it could not be split (and an LU whose panel loop is not
rolled takes minutes to compile).
"""

from __future__ import annotations

import os
import re

from benchlib.harness import Refused, load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
_dense = load_module(os.path.join(_HERE, "dense_spd_solve.py"),
                     "bench_config_dense_spd_solve")


def _reference():
    return load_module(os.path.join(_HERE,
                                    "dense_general_solve_reference.py"),
                       "bench_dense_general_solve_reference")


def rand_pool_fn(n: int, nrhs: int, matrices: int, rhs_blocks: int, dtype):
    """A function of a PRNG key returning ``(As, Bs)``: ``matrices``
    ``(n, n)`` and ``rhs_blocks`` ``(n, nrhs)`` arrays of entries uniform on
    [0, 1), each its own array so that the window indexes a tuple."""
    import jax

    def make(key):
        keys = jax.random.split(key, matrices + 1)
        As = tuple(jax.random.uniform(k, (n, n), dtype) for k in keys[:-1])
        b = jax.random.uniform(keys[-1], (rhs_blocks, n, nrhs), dtype)
        return As, tuple(b[i] for i in range(rhs_blocks))

    return make


def phases_missing(text: str, drivers: dict) -> list:
    """The ``<driver>/<phase>`` names of ``drivers`` that no location of a
    lowered program's ``text`` carries, of the phases that do work: a
    ``wrapper`` phase may compile to nothing (``getrs/store`` hands back an
    array), and one with no role is only reported."""
    return [f"{d}/{p}" for d, phases in drivers.items()
            for p, role in phases.items() if role not in (None, "wrapper")
            and not re.search(rf'(?:^|[/"]){d}/{p}(?=[/"])', text, re.M)]


class System(_dense.System):
    def programs(self):
        """The public call: ``gesv`` with the configuration's LU method and
        block size."""
        import slate_tpu as slate

        opts = {"method_lu": self.config["method_lu"],
                "block_size": int(self.config["nb"])}

        def gesv(a, b):
            B = slate.Matrix.from_array(b)
            _, _, info = slate.gesv(slate.Matrix.from_array(a), B, opts)
            return B.array, info

        return {"gesv": gesv}

    def make_data(self):
        """The seed's matrices and right-hand sides, on the device."""
        import jax
        import jax.numpy as jnp

        from benchlib import gen

        make = jax.jit(rand_pool_fn(self.n, self.nrhs, self.matrices,
                                    self.rhs_blocks,
                                    jnp.dtype(self.config["dtype"])))
        self.A, self.B = make(gen.device_key(self.seed, 1))
        jax.block_until_ready((self.A, self.B))

    def load_programs(self, progs=None):
        """Compile ``gesv``: the public call, after its lowered text is found
        to name every phase of the configuration's scopes, or ``progs`` with
        the same signature (the reference in its place)."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.config["dtype"])
        lowered = jax.jit((progs or self.programs())["gesv"]).lower(
            jax.ShapeDtypeStruct((self.n, self.n), dtype),
            jax.ShapeDtypeStruct((self.n, self.nrhs), dtype))
        if progs is None:
            missing = phases_missing(lowered.as_text(debug_info=True),
                                     self.config["scopes"]["drivers"])
            if missing:
                raise Refused("the gesv program names no phase "
                              + ", ".join(missing)
                              + "; its trace could not be split by phase")
        self.exe["gesv"] = lowered.compile()

    def warm(self):
        import jax

        jax.block_until_ready(self.exe["gesv"](self.A[0], self.B[0]))

    def step(self, i):
        return self.exe["gesv"](self.A[i % self.matrices],
                                self.B[i % self.rhs_blocks])

    def reference_programs(self, kind: str = "control"):
        """The plain reference's ``gesv`` for :meth:`load_programs`; see
        ``dense_general_solve_reference.programs``."""
        return _reference().programs(kind)

    @staticmethod
    def plant_fault(kind: str, monkeypatch):
        """Break slate's getrs, which gesv calls, where it produces the
        answer: ``altered`` (one entry), ``unchanged`` (the right-hand sides
        returned as the answer) or ``half`` (half the right-hand sides'
        answers left out).  The sweeps still run, so the program names its
        phases and passes the scope contract."""
        import slate_tpu
        from slate_tpu.core.matrix import as_array, write_back
        from slate_tpu.linalg import lu

        real = lu.getrs

        def getrs(LU, perm, B, opts=None, trans=False):
            b = as_array(B)
            # the real sweeps still run: the program keeps its phases
            x = as_array(real(LU, perm, B, opts, trans))
            if kind == "unchanged":
                x = b + 0.0 * x
            elif kind == "altered":
                x = x.at[0, 0].add(1.0)
            else:
                x = x.at[:, ::2].set(0.0)
            return write_back(B, x)

        monkeypatch.setattr(lu, "getrs", getrs)
        monkeypatch.setattr(slate_tpu, "getrs", getrs)
