"""Fast CPU perf pins for the hot-path kernel shapes (the CI gate the round-6
issue asks for): cost_analysis/launch-plan assertions that fail BEFORE a
capture window is spent when a code change regresses the compiled shape of

* the Pallas norm kernels (bytes touched, (8, 128)-tile alignment),
* the CALU panel schemes (flop counts vs the 2n^3/3 model; pp <= tournament),
* the blocked Tiled potrf (the shipping bench path's flop envelope).

Pins carry slack around the numbers measured at authoring time (round-6 bench
notes, in git history) — they gate kernel SHAPE, not machine speed, so they
hold on any backend.  All shapes compile in seconds on CPU.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from slate_tpu.testing import cost_analysis_dict


class TestNormPins:
    """Pallas-norm traffic evidence (ops/pallas_norms.py kernel_plan): the
    streaming kernels must read HBM exactly once and keep native-tile
    alignment — the committed form of the on-chip claim the next capture
    window confirms."""

    def test_pallas_plan_bench_shape(self):
        from slate_tpu.ops import pallas_norms as pn

        plan = pn.kernel_plan(16384, 16384, jnp.float32, kind="col")
        # bytes touched == the array (no padding at this shape); the
        # exactly-once half is measured on the traced index_map below
        assert plan["bytes_in"] == 16384 * 16384 * 4
        assert plan["padded_shape"] == (16384, 16384)
        assert plan["sublane_aligned"] and plan["lane_aligned"]
        assert plan["out_block"][0] == pn._SUBLANE
        # TRACED single-pass evidence: the real kernel's input index_map
        # visits every block exactly once at the bench shape (a revisiting
        # index_map — a genuine multi-pass traffic regression — fails here
        # even with the grid unchanged)
        for kind in ("col", "row"):
            traced = pn.traced_plan(16384, 16384, jnp.float32, kind=kind)
            assert traced["single_pass"], (kind, traced)
            assert traced["grid"] == pn.kernel_plan(
                16384, 16384, jnp.float32, kind=kind)["grid"]

    def test_pallas_plan_never_multipasses(self):
        from slate_tpu.ops import pallas_norms as pn

        for m, n in [(300, 200), (8191, 8193), (512, 70000)]:
            for kind in ("col", "row"):
                traced = pn.traced_plan(m, n, jnp.float32, kind=kind)
                assert traced["single_pass"], (m, n, kind)
                plan = pn.kernel_plan(m, n, jnp.float32, kind=kind)
                assert plan["pad_ratio"] < 2.1, (m, n, kind)

    def test_xla_fallback_bytes_bounded(self):
        """The jnp fallback (off-TPU path) must stay a fused reduction:
        authoring-time CPU compile touches ~3-4x the input (XLA materializes
        |A|-class intermediates — the measured motivation for the Pallas
        path); gate at 5x so a future change that materializes more round
        trips fails here."""
        from slate_tpu.ops import norms

        n = 1024
        a = jnp.zeros((n, n), jnp.float32)
        in_bytes = n * n * 4
        for which in ("fro", "one", "inf", "max"):
            comp = jax.jit(lambda x, w=which: norms.genorm(w, x)).lower(
                a).compile()
            got = cost_analysis_dict(comp).get("bytes accessed", 0.0)
            assert got <= 5.0 * in_bytes, (which, got / in_bytes)


class TestLuPanelPins:
    """CALU panel-scheme flop pins at the scaled bench shape (flat panels,
    the shipping bench configuration after the round-6 regression
    bisection)."""

    N, NB = 512, 128
    MODEL = 2 * N**3 / 3

    def _cost(self, scheme):
        from slate_tpu.linalg.lu import _getrf_tntpiv_fn

        a = jnp.zeros((self.N, self.N), jnp.float32)
        fn = _getrf_tntpiv_fn(self.N, self.N, self.NB, self.NB, "float32",
                              scheme)
        return cost_analysis_dict(fn.lower(a).compile())

    def test_flat_panel_flop_envelope(self):
        """Measured 0.666x of 2n^3/3 at authoring time (XLA folds/elides some
        panel work at this size); gate in [0.5, 1.15] — a blowup past the
        model means a hot-path rework re-introduced redundant panel flops."""
        for scheme in ("tournament", "pp"):
            flops = self._cost(scheme).get("flops", 0.0)
            assert 0.5 * self.MODEL <= flops <= 1.15 * self.MODEL, (
                scheme, flops / self.MODEL)

    def test_pp_no_costlier_than_tournament(self):
        """The pp panel replaces the merge tree with one panel LU — it must
        never compile to MORE flops or bytes than the tournament (that would
        invalidate the A/B's premise)."""
        ct = self._cost("tournament")
        cp = self._cost("pp")
        assert cp.get("flops", 0.0) <= 1.02 * ct.get("flops", 1.0)
        assert cp.get("bytes accessed", 0.0) <= \
            1.05 * ct.get("bytes accessed", 1.0)

    def test_flat_panel_traffic_envelope(self):
        """The r5 regression mechanism was a ~3x bytes-accessed blowup from
        the two-level split (round-6 bisection).  The shipping flat-panel
        config measured 2.53e7 bytes at this shape (24x the 1.05e6-byte
        array); gate at 1.6x the measured value so a traffic regression of
        the two-level kind fails before a capture is spent."""
        bytes_t = self._cost("tournament").get("bytes accessed", 0.0)
        assert bytes_t <= 1.6 * 2.53e7, bytes_t


class TestPotrfPins:
    def test_tiled_flop_envelope(self):
        """The shipping potrf bench path (blocked Tiled driver): measured
        0.96x of n^3/3 (the blocked-herk trailing update trims the square
        update's redundant half).  Gate at 1.1x — the lookahead pipeline
        compiles to ~2x this at the same job (the round-6 Tiled-vs-pipeline
        decision evidence), so a default swap or a trailing-
        update regression fails here."""
        from slate_tpu.linalg.chol import _potrf_tiled_fn

        n, nb = 512, 128
        a = jnp.zeros((n, n), jnp.float32)
        comp = _potrf_tiled_fn(n, nb, "float32", inv_trsm=False).lower(
            a).compile()
        flops = cost_analysis_dict(comp).get("flops", 0.0)
        assert flops <= 1.1 * n**3 / 3, flops / (n**3 / 3)


class TestCollectivePins:
    """Distributed collective-volume envelopes (the round-8 scaling gate):
    every routine in the scaling-audit registry recompiles on a P=2 CPU mesh
    and its compiled collective bytes/sites must stay inside the envelopes
    pinned in SCALING_PINS.json (written by ``tools/gen_scaling.py
    --update-pins``).  A schedule change that widens a gathered panel or
    swaps a psum for an all-gather fails here — in CPU seconds — before a
    capture window is spent, exactly like the flop/traffic pins above gate
    the single-chip kernels."""

    PINS_PATH = os.path.join(os.path.dirname(__file__), "..",
                             "SCALING_PINS.json")

    @pytest.fixture(scope="class")
    def pins(self):
        if not os.path.exists(self.PINS_PATH):
            pytest.skip("SCALING_PINS.json not generated "
                        "(run tools/gen_scaling.py --update-pins)")
        with open(self.PINS_PATH) as f:
            return json.load(f)

    def test_p2_collective_volume_within_envelopes(self, pins):
        """The gate itself: recompute the full P=2 audit and run it through
        the same ``check_pins`` the CI scaling-audit step uses (one envelope
        implementation, no drift).  Audited-but-unpinned routines fail too —
        a shrunk pin file must not pass vacuously.  Failures list every
        regressed routine, not just the first."""
        from slate_tpu import obs
        from slate_tpu.obs.scaling import check_pins

        rows = obs.audit_all([pins.get("P", 2)])
        bad = check_pins(rows, pins)
        assert not bad, "collective-volume regressions:\n  " + \
            "\n  ".join(bad)
