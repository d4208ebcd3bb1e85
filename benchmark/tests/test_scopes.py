"""The scope reduction: device time by the program's named scopes, idle
time inside and between programs; on synthetic planes, on the small chip
trace of ``record_trace.py`` and on the scoped one of
``record_scoped_trace.py``."""

import json
import os
import types

import pytest

from conftest import BENCH
from benchlib.jobs import job, least_time_s
from benchlib.scopes import (UNSCOPED, breakdown, instruction_scopes,
                             readings, reduce_scopes, scope_of)
from benchlib.trace import reduce_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "v5e_small.xplane.pb")
SCOPED = os.path.join(DATA, "v5e_scoped")
with open(os.path.join(BENCH, "configs", "dense_spd_solve.json")) as _f:
    DENSE = json.load(_f)["scopes"]
DRIVERS = DENSE["drivers"]
LU = {"getrf": {"select": "factor", "swap": "factor", "panel": "factor",
                "update": "factor", "info": None},
      "getrs": {"permute": "wrapper", "forward": "sweep",
                "backward": "sweep", "store": "wrapper"}}

HLO = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %n0 = f32[4]{0} negate(%p0), metadata={op_name="jit(f)/posv/potrs/store/neg"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%p), index=1
  %inner = f32[4]{0} fusion(%g), kind=kLoop, calls=%fused_a
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %inner)
}

%cond (q: (s32[], f32[4])) -> pred[] {
  %q = (s32[], f32[4]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

ENTRY %main.1 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fa = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/posv/potrf/prep/add" stack_frame_id=1}
  %chol = f32[4]{0} custom-call(%fa), custom_call_target="Cholesky", metadata={op_name="jit(f)/posv/potrf/potrf/factor/cholesky"}
  %anon = f32[4]{0} fusion(%chol), kind=kLoop, calls=%fused_a
  %copy = f32[4]{0} copy(%a), metadata={op_name="a"}
  %fwd = f32[4]{0} custom-call(%copy, %anon), custom_call_target="TriangularSolve", metadata={op_name="jit(f)/posv/potrs/forward/triangular_solve"}
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4]{0}) tuple(%z, %fwd)
  %w = (s32[], f32[4]{0}) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(f)/posv/potrs/backward/triangular_solve"}
  ROOT %out = f32[4]{0} get-tuple-element(%w), index=1
}
"""


@pytest.mark.parametrize("op_name, drivers, scope", [
    ("jit(posv)/posv/potrf/factor/cholesky", DRIVERS, "potrf/factor"),
    ("jit(posv)/posv/potrf/potrf/factor/cholesky", DRIVERS, "potrf/factor"),
    ("jit(posv)/posv/potrs/prep/jit(tril)/select_n", DRIVERS, "potrs/prep"),
    ("jit(potrs)/potrs/backward/triangular_solve", DRIVERS, "potrs/backward"),
    ("jit(posv)/posv/potrf/prep/jit(tril)/iota", DRIVERS, "potrf/prep"),
    ("jit(posv)/posv/solve/potrs", DRIVERS, None),
    ("b", DRIVERS, None),
    # a phase is looked up under its own driver only
    ("jit(potrs)/potrs/factor/x", DRIVERS, None),
    # another configuration's vocabulary names its own drivers
    ("jit(gesv)/gesv/getrf/select/argmax", LU, "getrf/select"),
    ("jit(gesv)/gesv/getrf/select/argmax", DRIVERS, None),
    ("jit(posv)/posv/potrf/factor/cholesky", LU, None),
])
def test_scope_is_the_innermost_driver_phase(op_name, drivers, scope):
    assert scope_of(op_name, drivers) == scope


def test_instructions_take_their_own_operands_users_or_callers_scope():
    s = instruction_scopes(HLO, DRIVERS)
    assert s["fa"] == "potrf/prep"
    assert s["chol"] == "potrf/factor"
    assert s["fwd"] == "potrs/forward"
    assert s["w"] == "potrs/backward"
    # no metadata: the scope of its operand
    assert s["anon"] == "potrf/factor"
    # an argument's relayout: the scope of its user
    assert s["copy"] == "potrs/forward"
    # a loop body's operation: the scope of the loop that calls the body
    assert s["inner"] == "potrs/backward"
    assert s["n0"] == "potrs/store"


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


@pytest.fixture
def synthetic(monkeypatch):
    """Three executions of ``jit_f`` (the last runs past the window) and one
    of a program whose text is not given, in a window of 1000 ns from 0."""
    import jax.profiler

    def op(name):
        return f"%{name} = f32[4]{{0}} fusion(f32[4]{{0}} %a)"

    ops = [(op("fa"), 100, 20), (op("chol"), 120, 40), (op("w"), 170, 25),
           (op("inner"), 175, 10),              # inside the loop
           (op("copy"), 300, 10), (op("fwd"), 315, 30), (op("nameless"), 350, 5),
           ("%x = f32[4]{0} add()", 600, 50),   # the unlisted program
           (op("fa"), 950, 100)]                # runs past the window
    modules = [("jit_f(1)", 90, 110), ("jit_f(1)", 295, 65),
               ("jit_g(2)", 590, 70), ("jit_f(1)", 940, 120)]
    pd = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}),
        _plane("/host:CPU", {"main": [("bench.window", 0, 1000),
                                      ("bench.sync", 100, 200)]})])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: pd)
    return pd


def test_every_moment_of_device_time_lands_in_one_group(synthetic):
    r = reduce_scopes("synthetic.xplane.pb", [HLO], DRIVERS)
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops: 100-160, 170-195, 300-310, 315-345, 350-355, 600-650, 950-1000
    assert r["busy_s"] == pytest.approx(230e-9)
    g = {k: v * 1e9 for k, v in r["groups"].items()}
    assert g == pytest.approx({
        "potrf/prep": 20 + 50, "potrf/factor": 40,
        # the loop's own 15 ns, and its body operation's 10
        "potrs/backward": 25, "potrs/forward": 10 + 30,
        UNSCOPED: 5, "(program jit_g)": 50})
    assert sum(r["groups"].values()) == pytest.approx(r["busy_s"])
    # idle inside executions: 90-100, 160-170, 195-200, 295-300, 310-315,
    # 345-350, 355-360, 590-600, 650-660, 940-950
    assert r["in_program_s"] == pytest.approx(75e-9)
    assert r["in_program_s"] + r["between_programs_s"] == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["programs"] == [["jit_f", 3, pytest.approx(235e-9)],
                             ["jit_g", 1, pytest.approx(70e-9)]]


def test_busy_time_is_the_trace_reductions(synthetic):
    assert reduce_scopes("synthetic.xplane.pb", [HLO], DRIVERS)["busy_s"] == \
        pytest.approx(reduce_trace("synthetic.xplane.pb")["busy_s"])


def test_readings_and_breakdown():
    red = {"busy_s": 10.0, "window_s": 12.0, "in_program_s": 1.5,
           "between_programs_s": 0.5, "programs": [["jit_posv", 5, 11.0]],
           "groups": {"potrf/prep": 2.0, "potrf/factor": 5.0,
                      "potrf/store": 0.5, "potrs/prep": 0.5,
                      "potrs/forward": 0.75, "potrs/backward": 1.0,
                      UNSCOPED: 0.25}}
    r = readings(red, 5, DRIVERS, {"factor": 0.1, "sweep": 0.02})
    assert r == pytest.approx({"wrapper_share": 30.0, "factor_share": 50.0,
                               "sweep_share": 17.5,
                               "factor_roofline": 10.0,
                               "sweep_roofline": 100 * 0.02 / 0.35,
                               "host_gap_ms": 100.0})
    # a role whose scopes took no device time reads no roofline, and a
    # share of 0
    no_factor = dict(red, groups=dict(red["groups"], **{"potrf/factor": 0.0}))
    r = readings(no_factor, 5, DRIVERS, {"factor": 0.1, "sweep": 0.02})
    assert "factor_roofline" not in r and r["factor_share"] == 0.0
    b = breakdown(red, top=2)
    assert b == {"scopes": [["potrf/factor", 5.0], ["potrf/prep", 2.0]],
                 "programs": [["jit_posv", 5, 11.0]]}


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_chip_trace_idles_between_programs():
    """Three steps of one program with 2 ms host pauses between them: the
    pauses are idle time between programs.  On the trace's clock the device
    runs each step about 1.1-1.3 ms before the host's ``bench.call`` begins
    (the planes' clocks are offset), so the first step's execution lies
    before the host's ``bench.window`` and two executions fall in it."""
    r = reduce_scopes(SMALL, [], DRIVERS)
    assert r["busy_s"] == pytest.approx(reduce_trace(SMALL)["busy_s"])
    assert r["between_programs_s"] >= 3 * 0.002
    assert r["in_program_s"] >= 0
    assert r["in_program_s"] + r["between_programs_s"] == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert [p[:2] for p in r["programs"]] == [["jit__lambda", 2]]
    # no text given: the program's time is its own group
    assert list(r["groups"]) == ["(program jit__lambda)"]


@pytest.mark.skipif(not os.path.exists(SCOPED + ".xplane.pb"),
                    reason="no recorded scoped trace")
def test_recorded_scoped_chip_trace():
    """slate's posv at n=512 on a v5e, joined to its compiled text: the
    factor and the two sweeps are named, little is left unscoped."""
    with open(SCOPED + ".hlo.txt") as f:
        text = f.read()
    r = reduce_scopes(SCOPED + ".xplane.pb", [text], DRIVERS)
    assert [p[:2] for p in r["programs"]] == [["jit_posv", 3]]
    assert {"potrf/factor", "potrs/forward", "potrs/backward"} <= set(
        r["groups"])
    assert r["groups"].get(UNSCOPED, 0) < 0.05 * r["busy_s"]
    assert sum(r["groups"].values()) == pytest.approx(r["busy_s"])
    assert r["between_programs_s"] >= 3 * 0.002


def legacy_readings(red, steps, least_s):
    """The four numbers as ``scopes.readings`` computed them before the
    vocabulary was the configuration's: phases matched by name under
    either driver, potrf's and potrs's least times."""
    def phases(*names):
        return sum(v for g, v in red["groups"].items()
                   if "/" in g and g.split("/", 1)[1] in names)

    return {"host_gap_ms": red["between_programs_s"] * 1e3 / steps,
            "wrapper_share": 100 * phases("prep", "mask", "store")
            / red["busy_s"],
            "factor_roofline": 100 * least_s["potrf"]
            / (phases("factor") / steps),
            "sweep_roofline": 100 * least_s["potrs"]
            / (phases("forward", "backward") / steps)}


@pytest.mark.skipif(not os.path.exists(SCOPED + ".xplane.pb"),
                    reason="no recorded scoped trace")
def test_recorded_scoped_trace_readings_by_role():
    """posv at n=512, 16 right-hand sides, three steps on a v5e: every
    moment of busy time in a group, none unscoped, and the role readings of
    the configuration's vocabulary are the phase readings they replace."""
    with open(SCOPED + ".hlo.txt") as f:
        text = f.read()
    red = reduce_scopes(SCOPED + ".xplane.pb", [text], DRIVERS)
    assert sum(red["groups"].values()) == pytest.approx(red["busy_s"],
                                                        rel=0.01)
    assert red["groups"].get(UNSCOPED, 0.0) == 0.0
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    least = {r: least_time_s(job(r, 512, 16)["flops"],
                             job(r, 512, 16)["bytes"], peaks)[0]
             for r in ("potrf", "potrs")}
    got = readings(red, 3, DRIVERS, {role: least[routine] for role, routine
                                     in DENSE["jobs"].items()})
    want = legacy_readings(red, 3, least)
    assert {k: got[k] for k in want} == pytest.approx(want, rel=1e-12)
    # as the reduction read this trace before the vocabulary moved
    assert want == pytest.approx({"host_gap_ms": 3.9095103333333334,
                                  "wrapper_share": 21.745171201237547,
                                  "factor_roofline": 1.394619861075619,
                                  "sweep_roofline": 2.871458990124009})
