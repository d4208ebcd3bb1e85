"""The dense general solve's scope contract: a program whose lowered text
does not name the LU phases that do work is refused after lowering and
before compiling, and the run exits 2 with no result line (a tree without
the phases, or with an LU that takes minutes to compile, fails at once).
And the recorded gesv chip trace: every moment of busy time lands in a
``getrf`` or ``getrs`` phase."""

import json
import os
import time

import pytest

from conftest import BENCH, DATA, ROOT
from benchlib import harness
from benchlib.scopes import UNSCOPED, readings, reduce_scopes

CELL = "gesv_n16384"
with open(os.path.join(BENCH, "configs", "dense_general_solve.json")) as _f:
    CONFIG = json.load(_f)


def test_a_program_without_lu_phases_is_refused(monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    import slate_tpu
    from slate_tpu.core.matrix import as_array, write_back

    def unscoped_gesv(A, B, opts=None):
        x = jnp.linalg.solve(as_array(A), as_array(B))
        return write_back(B, x), None, jnp.zeros((), jnp.int32)

    def no_compile(self, *args, **kwargs):
        raise AssertionError("a refused program was compiled")

    real = harness.run_cell

    def on_cpu(root, workload, seed, seconds, trace, t_process):
        return real(root, workload, seed, seconds, trace, t_process,
                    allow_cpu=True, sizes=CONFIG["rehearsal"]["sizes"],
                    peaks_override={"flops_per_s": 1e12, "bytes_per_s": 1e11})

    monkeypatch.setattr(slate_tpu, "gesv", unscoped_gesv)
    monkeypatch.setattr(jax.stages.Lowered, "compile", no_compile)
    monkeypatch.setattr(harness, "run_cell", on_cpu)
    t0 = time.perf_counter()
    rc = harness.main(["--workload", CELL, "--seed", "7", "--seconds", "0.2",
                       "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert time.perf_counter() - t0 < 60
    for phase in ("getrf/select", "getrf/swap", "getrf/panel",
                  "getrf/update", "getrs/forward", "getrs/backward"):
        assert phase in err, err


def test_slates_gesv_names_every_working_phase():
    """The program the cell runs lowers with every phase the contract
    asks for, and the check reads its lowered text as the chip's would."""
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(ROOT, CELL)
    system = cell.system_class()(cell.config, cell.traffic, 1,
                                 sizes=cell.config["rehearsal"]["sizes"])
    n, k = system.n, system.nrhs
    text = jax.jit(system.programs()["gesv"]).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((n, k), jnp.float32)).as_text(debug_info=True)
    module = harness.load_module(
        os.path.join(BENCH, "configs", "dense_general_solve.py"),
        "bench_config_dense_general_solve")
    drivers = CONFIG["scopes"]["drivers"]
    assert module.phases_missing(text, drivers) == []
    # a name that only contains a phase's name is not the phase
    assert module.phases_missing(text.replace("getrf/select", "getrf/selectx"),
                                 drivers) == ["getrf/select"]


def test_recorded_gesv_trace_lies_in_lu_phases():
    """slate's gesv at n=512, nb=128, three steps on a v5e, joined to its
    compiled text by the configuration's vocabulary: every LU phase that
    does work took device time, under 1% is left unscoped, and the roles
    read as shares and rooflines."""
    rec = CONFIG["rehearsal"]["trace"]
    with open(os.path.join(DATA, rec["hlo"])) as f:
        text = f.read()
    drivers = CONFIG["scopes"]["drivers"]
    red = reduce_scopes(os.path.join(DATA, rec["xplane"]), [text], drivers)
    assert [p[:2] for p in red["programs"]] == [["jit_gesv", 3]]
    assert {f"{d}/{p}" for d, ph in drivers.items() for p, role in ph.items()
            if role not in (None, "wrapper")} <= set(red["groups"])
    assert red["groups"].get(UNSCOPED, 0.0) < 0.01 * red["busy_s"]
    assert sum(red["groups"].values()) == pytest.approx(red["busy_s"])
    r = readings(red, 3, drivers, {"update": 1e-6, "sweep": 1e-7})
    assert {"select_share", "exchange_share", "panel_share", "update_share",
            "sweep_share", "update_roofline", "sweep_roofline"} <= set(r)
