"""Distributed bulge chase: the hb2st pipelined schedule sharded over a mesh.

The reference confines stage 2 to rank 0 (src/hb2st.cc scheduling consumed on
one process; src/heev.cc:137-160 gathers the band there), and rounds 1-4 of
this repo mirrored that: ``heev_distributed`` replicated the band and every
device replayed the same chase.  This module goes past the reference: the
band's column range is partitioned into P contiguous segments, each device
runs only the chase fronts whose window anchor falls in its segment, and
neighbors reconcile through two tiny ``ppermute`` exchanges per round:

- a (2b+1)x(2b+1) boundary-square DELTA in each direction.  Concurrent
  fronts write element-disjoint footprints (the schedule spaces live fronts
  2b-1 apart - the same commutativity the reference's thread scheduler and
  our batched single-device rounds rely on), so neighbor copies of the
  overlap reconcile by pure addition;
- at most one CROSSING reflector (v, tau, s): a front advances b columns
  per round and fronts are 2b-1 apart, so per boundary per round at most
  one front hops segments, carrying its v_prev to the next owner.

Collective volume is O(b^2 + b) per round - independent of n - versus the
O(n * b) band replication the rank-0 design ships once.  Per-device window
work drops from the full front set (~n/2b batched windows per round) to
~n/(2bP).

Schedule (identical to linalg/eig.py:_hb2st_chase_pipelined): sweep s runs
hebr1 at round t=2s and its hebr2/hebr3 step r (window anchor
j = (t-2s)b+1+s, i = j+b) at round t = 2s+r-1; front ownership is by the
anchor column j.  hebr1 ownership is by the sweep's r=1 anchor j = s+1, so
the hebr1 -> first-hebr2 handoff (same round, shared v0) never crosses a
boundary; the window's one-column reach below s+1 is why tiles carry a
single extra left column.

Results match the single-device pipelined chase bit-for-bit in the same
XLA configuration: same windows, same reflectors, same order per front
(pinned by tests/test_chase_dist.py against _hb2st_chase_pipelined).

The two kernels here (hb2st and tb2bd) share the segmentation idea but are
kept as separate builders on purpose: they differ in left margin (1 vs
b+1), exchange-square anchor (boundary-1 vs boundary-b-1), mirror writes
(Hermitian only), carried reflector family (v vs u), and per-window math —
a parameterized common scaffold was tried and read worse than the ~80
shared lines it saved.  Both are pinned output-for-output against their
single-device schedules, which is what keeps the pair honest.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.exceptions import slate_assert
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument

AX = (ROW_AXIS, COL_AXIS)                  # flattened device axis


def _shift_right(x, P_):
    """Each device receives its LEFT neighbor's value (device 0: zeros)."""
    return lax.ppermute(x, AX, [(i, i + 1) for i in range(P_ - 1)])


def _shift_left(x, P_):
    """Each device receives its RIGHT neighbor's value (device P-1: zeros)."""
    return lax.ppermute(x, AX, [(i + 1, i) for i in range(P_ - 1)])


@lru_cache(maxsize=16)
def _chase_dist_fn(mesh, n: int, b: int, seg: int, want_vectors: bool,
                   dtype_str: str):
    """Build the jitted shard_map chase for static (mesh, n, b, seg)."""
    from ..linalg.eig import _hebr1_window
    from ..linalg import householder as hh

    P_ = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    dt = jnp.dtype(dtype_str)
    n_sweeps = max(n - 2, 0)
    m_max = max(-(-(n - 1) // b), 1)
    T = 2 * n_sweeps + m_max
    B_loc = seg // (2 * b - 1) + 1          # max co-resident fronts/segment
    S_cap = B_loc + 2                        # v_prev store keys (mod-S_cap)
    M = seg + 4 * b + 4                      # local tile (real+halo+zero-land)
    lz = seg + 2 * b + 2                     # zero-land anchor (local)
    W_pad = P_ * seg + 4 * b + 4             # strip width (cols never sharded)
    sq = 2 * b + 1                           # boundary-square edge
    ar_b = jnp.arange(b)

    def local_fn(strip):                     # (seg, W_pad): rows [c0, c0+seg)
        p = lax.axis_index(AX)
        c0 = p * seg
        c1 = c0 + seg
        g0 = jnp.maximum(c0 - 1, 0)          # tile origin (global)
        # overlapping tile: left neighbor's tail row + my strip + the 2b-row
        # right halo (one neighbor suffices: seg >= 2b+2), zero-padded up to
        # the tile height (the tail rows are zero-land, zeroed below anyway)
        prev_tail = _shift_right(strip[-1:], P_)
        next_head = _shift_left(strip[: 2 * b], P_)
        zpad = jnp.zeros((M + 1 - (1 + seg + 2 * b), W_pad), dt)
        rows_ext = jnp.concatenate([prev_tail, strip, next_head, zpad], 0)
        off = g0 - (c0 - 1)                  # 1 on device 0, else 0
        tile = lax.dynamic_slice(rows_ext, (off, jnp.zeros_like(off)),
                         (M, W_pad))
        tile = lax.dynamic_slice(tile, (jnp.zeros_like(g0), g0), (M, M))
        # zero everything past real+halo: the slice drags neighbor data into
        # what must be this device's zero-land
        re = c1 + 2 * b - g0
        arM = jnp.arange(M)
        keep = (arM < re)[:, None] & (arM < re)[None, :]
        tile = jnp.where(keep, tile, jnp.zeros((), dt))
        lL = 0                               # the tile origin IS the left
        #                                      boundary square (g0 = c0-1,
        #                                      clamped with c0 on device 0)
        lR = c1 - 1 - g0                     # right boundary square (local)

        stv0 = jnp.zeros((S_cap, b), dt)
        stt0 = jnp.zeros((S_cap,), dt)
        nvs = n_sweeps + 1 if want_vectors else 1
        Vs0 = jnp.zeros((nvs, m_max, b), dt)
        taus0 = jnp.zeros((nvs, m_max), dt)

        def round_body(t, carry):
            tile, stv, stt, Vs, taus = carry
            snapL = lax.dynamic_slice(tile, (lL, lL), (sq, sq))
            snapR = lax.dynamic_slice(tile, (lR, lR), (sq, sq))

            # ---- hebr1: owned by the device of its r=1 anchor s0+1 -------
            s0 = t // 2
            start = (2 * s0 == t) & (s0 < n_sweeps)
            own1 = start & (s0 + 1 >= c0) & (s0 + 1 < c1)
            a1 = jnp.where(own1, s0 - g0, lz)
            W1 = lax.dynamic_slice(tile, (a1, a1), (b + 1, b + 1))
            W1, v0, tau0 = _hebr1_window(W1)
            tile = lax.dynamic_update_slice(tile, W1, (a1, a1))
            k0 = jnp.where(own1, s0 % S_cap, S_cap)      # OOB -> dropped
            stv = stv.at[k0].set(v0, mode="drop")
            stt = stt.at[k0].set(tau0, mode="drop")
            if want_vectors:
                sv = jnp.where(own1, s0, n_sweeps)
                Vs = Vs.at[sv, 0].set(jnp.where(own1, v0, Vs[sv, 0]))
                taus = taus.at[sv, 0].set(jnp.where(own1, tau0, taus[sv, 0]))

            # ---- batched hebr2+hebr3 over my live fronts -----------------
            # fronts at round t: sweep s at anchor j = t*b+1 - s*(2b-1),
            # step r = t-2s+1; mine are the (<= B_loc) consecutive s with
            # j in [c0, c1)
            s_start = -((c1 - t * b - 2) // (2 * b - 1))
            s_q = s_start + jnp.arange(B_loc)
            j_q = t * b + 1 - s_q * (2 * b - 1)
            r_q = t - 2 * s_q + 1
            m_s = -(-(n - 1 - s_q) // b)
            active = ((s_q >= 0) & (s_q < n_sweeps) & (r_q >= 1)
                      & (r_q < m_s) & (j_q >= c0) & (j_q < c1))
            li = jnp.where(active, j_q + b - g0, lz + b)
            ljj = jnp.where(active, j_q - g0, lz)
            vp = stv[s_q % S_cap]
            tp = stt[s_q % S_cap]
            rows = li[:, None] + ar_b[None, :]           # (B_loc, b)
            cols = ljj[:, None] + ar_b[None, :]
            Wb = tile[rows[:, :, None], cols[:, None, :]]
            Wv = jnp.einsum("bij,bj->bi", Wb, vp)
            Wb = Wb - tp[:, None, None] * Wv[:, :, None] * jnp.conj(vp)[:, None, :]
            v, tau, _ = hh.larfg(Wb[:, :, 0])
            vW = jnp.einsum("bi,bij->bj", jnp.conj(v), Wb)
            Wb = Wb - jnp.conj(tau)[:, None, None] * v[:, :, None] * vW[:, None, :]
            tile = tile.at[rows[:, :, None], cols[:, None, :]].set(Wb)
            tile = tile.at[cols[:, :, None], rows[:, None, :]].set(
                jnp.conj(jnp.swapaxes(Wb, -1, -2)))
            Db = tile[rows[:, :, None], rows[:, None, :]]
            Dv = jnp.einsum("bi,bij->bj", jnp.conj(v), Db)
            Db = Db - jnp.conj(tau)[:, None, None] * v[:, :, None] * Dv[:, None, :]
            Dw = jnp.einsum("bij,bj->bi", Db, v)
            Db = Db - tau[:, None, None] * Dw[:, :, None] * jnp.conj(v)[:, None, :]
            tile = tile.at[rows[:, :, None], rows[:, None, :]].set(Db)
            kq = jnp.where(active, s_q % S_cap, S_cap)
            stv = stv.at[kq].set(v, mode="drop")
            stt = stt.at[kq].set(tau, mode="drop")
            if want_vectors:
                s_c = jnp.where(active, s_q, n_sweeps)
                r_c = jnp.where(active, r_q, 0)
                Vs = Vs.at[s_c, r_c].set(
                    jnp.where(active[:, None], v, Vs[s_c, r_c]))
                taus = taus.at[s_c, r_c].set(
                    jnp.where(active, tau, taus[s_c, r_c]))

            # ---- neighbor reconciliation ---------------------------------
            dL = lax.dynamic_slice(tile, (lL, lL), (sq, sq)) - snapL
            dR = lax.dynamic_slice(tile, (lR, lR), (sq, sq)) - snapR
            crossing = active & (j_q >= c1 - b)          # at most one
            cvalid = jnp.any(crossing).astype(jnp.int32)
            cs = jnp.sum(jnp.where(crossing, s_q, 0))
            cv = jnp.sum(jnp.where(crossing[:, None], v, 0), axis=0)
            ct = jnp.sum(jnp.where(crossing, tau, 0))
            # rightward: my dR + crossing reflector -> right neighbor
            rdelta = _shift_right(dR, P_)
            rv = _shift_right(cv, P_)
            rt = _shift_right(ct, P_)
            rs = _shift_right(cs, P_)
            rvalid = _shift_right(cvalid, P_)
            # leftward: my dL -> left neighbor
            ldelta = _shift_left(dL, P_)
            tile = lax.dynamic_update_slice(
                tile, lax.dynamic_slice(tile, (lL, lL), (sq, sq)) + rdelta,
                (lL, lL))
            tile = lax.dynamic_update_slice(
                tile, lax.dynamic_slice(tile, (lR, lR), (sq, sq)) + ldelta,
                (lR, lR))
            kin = jnp.where(rvalid == 1, rs % S_cap, S_cap)
            stv = stv.at[kin].set(rv, mode="drop")
            stt = stt.at[kin].set(rt, mode="drop")
            return tile, stv, stt, Vs, taus

        tile, stv, stt, Vs, taus = lax.fori_loop(
            0, T, round_body, (tile, stv0, stt0, Vs0, taus0))

        # owned diagonal + subdiagonal segments (global x in [c0, c1))
        lx = jnp.arange(seg) + (c0 - g0)
        d_loc = jnp.real(tile[lx, lx])
        e_loc = tile[lx + 1, lx]             # e[x] = T[x+1, x]
        if want_vectors:
            Vs = lax.psum(Vs, AX)
            taus = lax.psum(taus, AX)
        return d_loc, e_loc, Vs, taus

    out_specs = (P(AX), P(AX), P(None), P(None))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(P(AX, None),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


@lru_cache(maxsize=16)
def _tb2bd_dist_fn(mesh, n: int, b: int, seg: int, want_vectors: bool,
                   dtype_str: str):
    """shard_map bidiagonal chase (tb2bd) for static (mesh, n, b, seg).

    Same segmentation as the Hermitian kernel with three differences that
    follow from the upper-band geometry (svd.py:_tb2bd_chase_pipelined):
    - the gebr1 window at (s, s+1) reaches b+1 columns left of its sweep's
      r=1 anchor j = s+b+1, so tiles carry a b+1 left margin (vs 1);
    - no mirror writes (the band is not Hermitian), and the exchange square
      sits at [boundary-b-1, boundary+b): gebr2 rows dip b below the
      anchor, gebr1 a further 1;
    - TWO reflector families: v (right) is generated fresh per step, u
      (left) is the carried one — the crossing payload ships (u, tauu, s).
    """
    from ..linalg import householder as hh

    P_ = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    dt = jnp.dtype(dtype_str)
    n_sweeps = max(n - 1, 0)
    m_max = max(-(-(n - 1) // b), 1)
    T = 2 * n_sweeps + m_max
    B_loc = seg // (2 * b - 1) + 1
    S_cap = B_loc + 2
    lm = b + 1                               # left margin (gebr1 reach)
    M = seg + 2 * b + lm + 2 * b + 3         # real+halo + zero-land
    lz = seg + 2 * b + lm + 1                # zero-land i-anchor (local)
    W_pad = P_ * seg + M                     # strip width (cols never sharded)
    sq = 2 * b + 1                           # exchange-square edge
    ar_b = jnp.arange(b)

    def local_fn(strip):                     # (seg, W_pad): rows [c0, c0+seg)
        p = lax.axis_index(AX)
        c0 = p * seg
        c1 = c0 + seg
        g0 = jnp.maximum(c0 - lm, 0)         # tile origin (global)
        prev_tail = _shift_right(strip[-lm:], P_)
        next_head = _shift_left(strip[: 2 * b], P_)
        zpad = jnp.zeros((M + lm - (lm + seg + 2 * b), W_pad), dt)
        rows_ext = jnp.concatenate([prev_tail, strip, next_head, zpad], 0)
        off = g0 - (c0 - lm)                 # lm on device 0, else 0
        tile = lax.dynamic_slice(rows_ext, (off, jnp.zeros_like(off)),
                                 (M, W_pad))
        tile = lax.dynamic_slice(tile, (jnp.zeros_like(g0), g0), (M, M))
        re = c1 + 2 * b - g0
        arM = jnp.arange(M)
        keep = (arM < re)[:, None] & (arM < re)[None, :]
        tile = jnp.where(keep, tile, jnp.zeros((), dt))
        lL = jnp.maximum(c0 - b - 1, 0) - g0  # left exchange square (local)
        lR = c1 - b - 1 - g0                  # right exchange square (local)

        stu0 = jnp.zeros((S_cap, b), dt)
        stt0 = jnp.zeros((S_cap,), dt)
        nvs = n_sweeps + 1 if want_vectors else 1
        Us0 = jnp.zeros((nvs, m_max, b), dt)
        tauus0 = jnp.zeros((nvs, m_max), dt)
        Vs0 = jnp.zeros((nvs, m_max, b), dt)
        tauvs0 = jnp.zeros((nvs, m_max), dt)

        def round_body(t, carry):
            tile, stu, stt, Us, tauus, Vs, tauvs = carry
            snapL = lax.dynamic_slice(tile, (lL, lL), (sq, sq))
            snapR = lax.dynamic_slice(tile, (lR, lR), (sq, sq))

            # ---- gebr1: owned by the device of its r=1 anchor s0+b+1 -----
            s0 = t // 2
            start = (2 * s0 == t) & (s0 < n_sweeps)
            # ownership anchor: the r=1 front's column for the same-round u0
            # handoff; tail sweeps (s0+b+1 >= n) have no r=1 front, so their
            # anchor clamps to the last real column (the last device's tile
            # still contains the whole (s0, s0+1) window)
            jown = jnp.minimum(s0 + b + 1, n - 1)
            own1 = start & (jown >= c0) & (jown < c1)
            a1 = jnp.where(own1, s0 - g0, lz)
            W = lax.dynamic_slice(tile, (a1, a1 + 1), (b + 1, b))
            v0, tauv0, _ = hh.larfg(jnp.conj(W[0, :]))
            W = hh.apply_right(tauv0, v0, W)
            u0, tauu0, _ = hh.larfg(W[1:, 0])
            W = W.at[1:, :].set(hh.apply_left(tauu0, u0, W[1:, :]))
            tile = lax.dynamic_update_slice(tile, W, (a1, a1 + 1))
            k0 = jnp.where(own1, s0 % S_cap, S_cap)
            stu = stu.at[k0].set(u0, mode="drop")
            stt = stt.at[k0].set(tauu0, mode="drop")
            if want_vectors:
                sv = jnp.where(own1, s0, n_sweeps)
                Vs = Vs.at[sv, 0].set(jnp.where(own1, v0, Vs[sv, 0]))
                tauvs = tauvs.at[sv, 0].set(
                    jnp.where(own1, tauv0, tauvs[sv, 0]))
                Us = Us.at[sv, 0].set(jnp.where(own1, u0, Us[sv, 0]))
                tauus = tauus.at[sv, 0].set(
                    jnp.where(own1, tauu0, tauus[sv, 0]))

            # ---- batched gebr2+gebr3 over my live fronts -----------------
            # front (s, r=t-2s+1) at diagonal anchor j = (t+1)b+1 - s(2b-1)
            s_start = -((c1 - (t + 1) * b - 2) // (2 * b - 1))
            s_q = s_start + jnp.arange(B_loc)
            j_q = (t + 1) * b + 1 - s_q * (2 * b - 1)
            r_q = t - 2 * s_q + 1
            active = ((s_q >= 0) & (s_q < n_sweeps) & (r_q >= 1)
                      & (j_q < n) & (j_q >= c0) & (j_q < c1))
            li = jnp.where(active, j_q - b - g0, lz)       # gebr2 row anchor
            ljj = jnp.where(active, j_q - g0, lz + b)      # col/diag anchor
            up = stu[s_q % S_cap]
            tp = stt[s_q % S_cap]
            rows_i = li[:, None] + ar_b[None, :]
            cols_j = ljj[:, None] + ar_b[None, :]
            # gebr2: left-apply previous u, then new right v zeroing row 0
            Wb = tile[rows_i[:, :, None], cols_j[:, None, :]]
            uW = jnp.einsum("bi,bij->bj", jnp.conj(up), Wb)
            Wb = Wb - jnp.conj(tp)[:, None, None] * up[:, :, None] * uW[:, None, :]
            v, tauv, _ = hh.larfg(jnp.conj(Wb[:, 0, :]))
            Wv = jnp.einsum("bij,bj->bi", Wb, v)
            Wb = Wb - tauv[:, None, None] * Wv[:, :, None] * jnp.conj(v)[:, None, :]
            tile = tile.at[rows_i[:, :, None], cols_j[:, None, :]].set(Wb)
            # gebr3: right-apply v on the diagonal window, new left u
            Db = tile[cols_j[:, :, None], cols_j[:, None, :]]
            Dv = jnp.einsum("bij,bj->bi", Db, v)
            Db = Db - tauv[:, None, None] * Dv[:, :, None] * jnp.conj(v)[:, None, :]
            u, tauu, _ = hh.larfg(Db[:, :, 0])
            uD = jnp.einsum("bi,bij->bj", jnp.conj(u), Db)
            Db = Db - jnp.conj(tauu)[:, None, None] * u[:, :, None] * uD[:, None, :]
            tile = tile.at[cols_j[:, :, None], cols_j[:, None, :]].set(Db)
            kq = jnp.where(active, s_q % S_cap, S_cap)
            stu = stu.at[kq].set(u, mode="drop")
            stt = stt.at[kq].set(tauu, mode="drop")
            if want_vectors:
                s_c = jnp.where(active, s_q, n_sweeps)
                r_c = jnp.where(active, r_q, 0)
                Vs = Vs.at[s_c, r_c].set(
                    jnp.where(active[:, None], v, Vs[s_c, r_c]))
                tauvs = tauvs.at[s_c, r_c].set(
                    jnp.where(active, tauv, tauvs[s_c, r_c]))
                Us = Us.at[s_c, r_c].set(
                    jnp.where(active[:, None], u, Us[s_c, r_c]))
                tauus = tauus.at[s_c, r_c].set(
                    jnp.where(active, tauu, tauus[s_c, r_c]))

            # ---- neighbor reconciliation ---------------------------------
            dL = lax.dynamic_slice(tile, (lL, lL), (sq, sq)) - snapL
            dR = lax.dynamic_slice(tile, (lR, lR), (sq, sq)) - snapR
            crossing = active & (j_q >= c1 - b)
            cvalid = jnp.any(crossing).astype(jnp.int32)
            cs = jnp.sum(jnp.where(crossing, s_q, 0))
            cu = jnp.sum(jnp.where(crossing[:, None], u, 0), axis=0)
            ct = jnp.sum(jnp.where(crossing, tauu, 0))
            rdelta = _shift_right(dR, P_)
            ru = _shift_right(cu, P_)
            rt = _shift_right(ct, P_)
            rs = _shift_right(cs, P_)
            rvalid = _shift_right(cvalid, P_)
            ldelta = _shift_left(dL, P_)
            tile = lax.dynamic_update_slice(
                tile, lax.dynamic_slice(tile, (lL, lL), (sq, sq)) + rdelta,
                (lL, lL))
            tile = lax.dynamic_update_slice(
                tile, lax.dynamic_slice(tile, (lR, lR), (sq, sq)) + ldelta,
                (lR, lR))
            kin = jnp.where(rvalid == 1, rs % S_cap, S_cap)
            stu = stu.at[kin].set(ru, mode="drop")
            stt = stt.at[kin].set(rt, mode="drop")
            return tile, stu, stt, Us, tauus, Vs, tauvs

        tile, stu, stt, Us, tauus, Vs, tauvs = lax.fori_loop(
            0, T, round_body,
            (tile, stu0, stt0, Us0, tauus0, Vs0, tauvs0))

        lx = jnp.arange(seg) + (c0 - g0)
        d_loc = tile[lx, lx]
        e_loc = tile[lx, lx + 1]             # e[x] = B[x, x+1]
        if want_vectors:
            Us = lax.psum(Us, AX)
            tauus = lax.psum(tauus, AX)
            Vs = lax.psum(Vs, AX)
            tauvs = lax.psum(tauvs, AX)
        return d_loc, e_loc, Us, tauus, Vs, tauvs

    out_specs = (P(AX), P(AX), P(None), P(None), P(None), P(None))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(P(AX, None),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


@instrument
def tb2bd_chase_distributed(Bfull: jax.Array, kd: int, grid: ProcessGrid,
                            want_vectors: bool = False):
    """Segment-parallel bidiagonal chase (the SVD stage 2) over ``grid``.

    ``Bfull``: square upper band (bandwidth ``kd``), dense storage.  Returns
    ``(d_c, e_c, Us, tauus, Vs, tauvs)`` matching
    ``linalg.svd._tb2bd_chase_pipelined`` (reflector stacks are zeros when
    ``want_vectors=False``).
    """
    n = Bfull.shape[-1]
    b = int(kd)
    P_ = grid.size
    slate_assert(b >= 2 and n > 1, "tb2bd chase needs kd >= 2 and n > 1")
    seg = -(-n // P_)
    slate_assert(seg >= 2 * b + 2,
                 f"segment {seg} too narrow for bandwidth {b} on {P_} devices"
                 " (need n/P >= 2*kd+2); use the replicated chase")
    M = seg + 2 * b + (b + 1) + 2 * b + 3
    W_pad = P_ * seg + M
    Bp = jnp.zeros((P_ * seg, W_pad), Bfull.dtype)
    Bp = Bp.at[:n, :n].set(Bfull)
    fn = _tb2bd_dist_fn(grid.mesh, n, b, seg, bool(want_vectors),
                        str(Bfull.dtype))
    d_all, e_all, Us, tauus, Vs, tauvs = fn(Bp)
    d_c = d_all[:n]
    e_c = e_all[: n - 1]
    n_sweeps = max(n - 1, 0)
    return (d_c, e_c, Us[:n_sweeps], tauus[:n_sweeps],
            Vs[:n_sweeps], tauvs[:n_sweeps])


@instrument
def hb2st_chase_distributed(Afull: jax.Array, kd: int, grid: ProcessGrid,
                            want_vectors: bool = False):
    """Segment-parallel bulge chase over ``grid``'s flattened device list.

    ``Afull``: the full Hermitian band matrix (dense storage, bandwidth
    ``kd``), replicated on the host side like the rank-0 design's input.
    Returns ``(d, e_complex, Vs, taus)`` matching
    ``linalg.eig._hb2st_chase_pipelined`` (``Vs``/``taus`` are zeros when
    ``want_vectors=False``).
    """
    n = Afull.shape[-1]
    b = int(kd)
    P_ = grid.size
    slate_assert(b >= 2 and n > 2, "chase needs kd >= 2 and n > 2")
    seg = -(-n // P_)
    slate_assert(seg >= 2 * b + 2,
                 f"segment {seg} too narrow for bandwidth {b} on {P_} devices"
                 " (need n/P >= 2*kd+2); use the replicated chase")
    W_pad = P_ * seg + 4 * b + 4
    Ap = jnp.zeros((P_ * seg, W_pad), Afull.dtype)
    Ap = Ap.at[:n, :n].set(Afull)
    fn = _chase_dist_fn(grid.mesh, n, b, seg, bool(want_vectors),
                        str(Afull.dtype))
    d_all, e_all, Vs, taus = fn(Ap)
    d = d_all[:n]
    e_c = e_all[: n - 1]
    n_sweeps = max(n - 2, 0)
    return d, e_c, Vs[:n_sweeps], taus[:n_sweeps]
