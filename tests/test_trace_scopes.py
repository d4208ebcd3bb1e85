"""Named regions inside compiled programs: ``trace_block`` is a
``jax.named_scope``, so the operations a jitted solve compiles to carry the
solve's phase names in their HLO ``op_name``; spans taken while a call is
traced are labelled apart from eager calls."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as slate
from slate_tpu import obs
from slate_tpu.utils import trace

N, NRHS = 512, 16
A_SPEC = jax.ShapeDtypeStruct((N, N), jnp.float32)
B_SPEC = jax.ShapeDtypeStruct((N, NRHS), jnp.float32)
PHASE = re.compile(r"(?:^|/)(potrf|potrs)/(prep|factor|mask|info|store|"
                   r"forward|backward)(?=/|$)")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
KERNEL = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .* (fusion|custom-call)\(")


def compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def kernels(text):
    """``{instruction: op_name or None}`` of the fusions and custom calls
    that run as device operations (those outside fused computations)."""
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out, comp = {}, None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = KERNEL.match(line)
        if m and comp not in called:
            op = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = op.group(1) if op else None
    return out


def phase(op_name):
    """The innermost ``potrf/<phase>`` or ``potrs/<phase>`` of an op_name."""
    found = PHASE.findall(op_name or "")
    return "/".join(found[-1]) if found else None


def check_scoped(text, expect):
    """Every kernel traced from the program (its op_name starts ``jit(``;
    the compiler's own relayouts of arguments carry the argument's name or
    nothing) is inside a phase scope, and each kernel named in ``expect``
    (``{(target, phase)}``) is there."""
    ks = kernels(text)
    traced = {k: v for k, v in ks.items() if v and v.startswith("jit(")}
    assert traced
    assert {k: v for k, v in traced.items() if phase(v) is None} == {}
    found = set()
    for line in text.splitlines():
        m = re.search(r'custom_call_target="([^"]*)"', line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op:
            found.add((m.group(1), phase(op.group(1))))
    assert expect <= found, found


def public_programs(uplo=slate.Uplo.Lower):
    def posv(a, b):
        B = slate.Matrix.from_array(b)
        _, info = slate.posv(
            slate.HermitianMatrix.from_array(slate.Uplo.Lower, a), B)
        return B.array, info

    def potrf(a):
        return slate.potrf(slate.HermitianMatrix.from_array(uplo, a))

    def potrs(l, b):
        B = slate.Matrix.from_array(b)
        slate.potrs(slate.HermitianMatrix.from_array(slate.Uplo.Lower, l), B)
        return B.array

    return posv, potrf, potrs


def test_trace_block_names_the_compiled_operations():
    def f(x):
        with trace.trace_block("outer"):
            with trace.trace_block("inner", n=3):
                return jnp.sin(x) * 2.0

    text = compiled_text(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    assert re.search(r'op_name="jit\(f\)/outer/inner/sin"', text)


def test_trace_block_adds_nothing_but_metadata():
    def plain(x):
        return jnp.tril(x) @ x.T

    def scoped(x):
        with trace.trace_block("a"):
            y = jnp.tril(x)
        with trace.trace_block("b"):
            return y @ x.T

    def strip(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                      r"\n(?:[^\n]+\n)*", "\n", text)
        return re.sub(r"HloModule \S+", "HloModule m", text)

    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    assert strip(compiled_text(plain, spec)) == strip(
        compiled_text(scoped, spec))


def test_posv_phases_cover_its_compiled_kernels():
    posv, _, _ = public_programs()
    check_scoped(compiled_text(posv, A_SPEC, B_SPEC), {
        ("lapack_spotrf_ffi", "potrf/factor"),
        ("lapack_strsm_ffi", "potrs/forward"),
        ("lapack_strsm_ffi", "potrs/backward")})


@pytest.mark.parametrize("routine", ["potrf", "potrs"])
def test_factor_reuse_phases_cover_their_compiled_kernels(routine):
    """potrf and potrs compiled apart, as a caller that reuses one factor
    for many solves runs them."""
    _, potrf, potrs = public_programs()
    if routine == "potrf":
        text = compiled_text(potrf, A_SPEC)
        check_scoped(text, {("lapack_spotrf_ffi", "potrf/factor")})
        kinds = {phase(v) for v in kernels(text).values() if v}
        assert {"potrf/factor", "potrf/info"} <= kinds
    else:
        check_scoped(compiled_text(potrs, A_SPEC, B_SPEC), {
            ("lapack_strsm_ffi", "potrs/forward"),
            ("lapack_strsm_ffi", "potrs/backward")})


@pytest.mark.parametrize("uplo", ["lower", "upper"])
def test_potrf_prep_is_the_upper_triangles_transpose_alone(uplo):
    """A lower-stored factor reads the stored array as it is: ``potrf/prep``
    compiles to nothing.  An upper-stored one is transposed once there."""
    _, potrf, _ = public_programs(slate.Uplo.from_string(uplo))
    text = compiled_text(potrf, A_SPEC)
    check_scoped(text, {("lapack_spotrf_ffi", "potrf/factor")})
    prep_kernels = [k for k, v in kernels(text).items()
                    if phase(v) == "potrf/prep"]
    prep_ops = re.findall(r'= \S+ ([\w\-]+)\(.*op_name="[^"]*potrf/prep/',
                          text)
    if uplo == "lower":
        assert prep_kernels == [] and prep_ops == []
    else:
        assert len(prep_kernels) == 1
        assert prep_ops.count("transpose") == 1
        assert set(prep_ops) <= {"transpose", "copy", "fusion"}, prep_ops


class TestTracedSpans:
    @pytest.fixture(autouse=True)
    def _fresh_registry(self):
        obs.reset()
        yield
        obs.reset()

    @staticmethod
    def samples(routine):
        c = obs.REGISTRY.get("slate_spans_total")
        return {key: v for key, v in c.series().items()
                if dict(key).get("routine") == routine}

    def test_spans_taken_while_tracing_are_labelled_traced(self):
        posv, _, _ = public_programs()
        a = np.eye(32, dtype=np.float32) * 2
        b = np.ones((32, 2), np.float32)
        f = jax.jit(posv)
        for _ in range(3):
            jax.block_until_ready(f(a, b))
        spans = self.samples("posv")
        # three calls, one trace: one sample, traced
        assert list(spans.values()) == [1.0]
        assert dict(next(iter(spans))).get("traced") == "true"
        inner = self.samples("potrf")
        assert list(inner.values()) == [1.0]
        assert dict(next(iter(inner)))["parent"] == "posv"
        assert dict(next(iter(inner))).get("traced") == "true"

    def test_eager_calls_and_traces_never_share_a_series(self):
        a = np.eye(32, dtype=np.float32) * 2
        b = np.ones((32, 2), np.float32)
        slate.posv(a, b.copy())
        jax.jit(lambda a, b: slate.posv(a, b)[0])(a, b)
        spans = self.samples("posv")
        assert sorted(dict(k).get("traced", "-") for k in spans) == [
            "-", "true"]
        h = obs.REGISTRY.get("slate_span_seconds")
        assert [h.snapshot(**dict(k))["count"] for k in spans] == [1, 1]


def test_eager_trace_block_lands_on_the_profiler_clock(tmp_path):
    """Run eagerly, a region is a profiler annotation with no ``trace.on()``,
    on the profiler's host timeline beside the device operations."""
    assert not trace.is_on()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.trace_block("eager_region"):
            jax.block_until_ready(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    import glob

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "eager_region" in names


LU_PHASE = re.compile(r"(?:^|/)(getrf/(?:select|swap|panel|update|info)|"
                      r"getrs/(?:permute|forward|backward|store))(?=/|$)")
LU_N, LU_NB = 512, 128
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                         r"([\w\-]+)\(")


def lu_phase(op_name):
    """The innermost ``getrf/<phase>`` or ``getrs/<phase>`` of an op_name."""
    found = LU_PHASE.findall(op_name or "")
    return found[-1] if found else None


@pytest.fixture(scope="module")
def gesv_text():
    """slate's gesv through the tournament-pivoted LU, compiled at n=512,
    nb=ib=128, as the dense general solve's benchmark program calls it."""
    def gesv(a, b):
        B = slate.Matrix.from_array(b)
        _, _, info = slate.gesv(slate.Matrix.from_array(a), B,
                                {"method_lu": "calu", "block_size": LU_NB})
        return B.array, info

    return compiled_text(gesv, jax.ShapeDtypeStruct((LU_N, LU_N), jnp.float32),
                         B_SPEC)


def lu_ops(text):
    """``{(opcode or custom-call target, phase)}`` of every instruction."""
    out = set()
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op:
            target = re.search(r'custom_call_target="([^"]*)"', line)
            out.add((target.group(1) if target else m.group(2),
                     lu_phase(op.group(1))))
    return out


@pytest.mark.parametrize("op, where", [
    ("dot", "getrf/update"),                 # the trailing update
    ("lapack_sgetrf_ffi", "getrf/select"),   # the tournament's merge LUs
    ("gather", "getrf/swap"),                # the dirty-row exchange
    ("scatter", "getrf/swap"),
    ("lapack_strsm_ffi", "getrf/panel"),     # L21 and U12
    ("lapack_strsm_ffi", "getrs/forward"),   # the sweeps' diagonal blocks
    ("lapack_strsm_ffi", "getrs/backward"),
])
def test_gesv_phases_name_the_lu_operations(gesv_text, op, where):
    assert (op, where) in lu_ops(gesv_text)


def test_gesv_kernels_all_lie_in_lu_phases(gesv_text):
    """Every kernel traced from the program is inside a getrf or getrs
    phase, the rolled panel loops' own control included; the compiler's
    relayouts of arguments carry the argument's name or nothing."""
    traced = {k: v for k, v in kernels(gesv_text).items()
              if v and v.startswith("jit(")}
    assert traced
    assert {k: v for k, v in traced.items() if lu_phase(v) is None} == {}
    assert {"getrf/select", "getrf/swap", "getrf/panel", "getrf/update",
            "getrf/info", "getrs/permute", "getrs/forward",
            "getrs/backward"} <= {lu_phase(v) for v in traced.values()}
