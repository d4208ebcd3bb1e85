"""Distributed LU with tournament pivoting over the process grid.

Reference analogues:

* ``src/getrf.cc:22-260`` — partial-pivot LU: panel factor + pivot MPI_Bcast +
  row swaps + trailing update, with lookahead.
* ``src/getrf_tntpiv.cc:161-230`` + ``src/internal/internal_getrf_tntpiv.cc`` —
  CALU tournament pivoting: block-local partially-pivoted panel LUs, then a
  reduction tree over candidate pivot rows.
* ``src/internal/internal_swap.cc`` — permuteRows MPI row exchanges.
* ``src/gesv.cc`` — getrf + getrs.

TPU re-design (not a translation):

- **Tournament pivoting is the default** (SURVEY.md §7 hard-part 1): the
  reference's partial-pivot panel needs one maxloc allreduce per column; the
  tournament needs one candidate all-gather per *panel*, which is the
  communication-avoiding shape that fits ICI collectives.  Each mesh row
  factors its local panel chunk with ``lax.linalg.lu`` (one batched XLA op),
  winners are reduced in a single stacked LU over the gathered candidates —
  the reference's binary tree collapsed into one round, optimal for the
  p ≤ 64 mesh rows a pod slice has.
- **Row swaps are gathers**: only the ≤ 2·nb "dirty" rows move, fetched with a
  masked ``psum`` along the p axis and scattered locally — the reference's
  pairwise MPI row exchanges (internal_swap.cc) become two collectives of
  O(nb · n/q) bytes per panel.
- **Fixed-shape pipeline**: the whole factorization is ONE ``lax.fori_loop``
  over panels with full-width masked updates — O(1) program size and compile
  time regardless of nt (the reference's O(nt) OpenMP task unroll, and the
  compile-time hazard of Python-unrolled drivers, both disappear).  The masked
  full-width trailing gemm trades ~3× the minimal flop count for perfectly
  static MXU-shaped matmuls; on TPU the large fused (n/p × nb)·(nb × n/q)
  updates run at near-peak, which is the right end of that trade
  (pallas_guide.md: prefer static shapes + big matmuls over tight flop counts).
- Layout is the (p, q) block sharding of the process grid; the matrix is
  padded with an identity tail to align panels to shard boundaries
  (pad-and-mask edge policy, SURVEY.md §7 hard-part 5).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.exceptions import slate_assert
from ..robust import RetryPolicy, first_bad_index, guard_shards, inject
from ..utils.trace import trace_event
from .distribute import ceil_mult, lcm as _lcm
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from .pivot import (exchange_rows as _exchange_rows,
                    select_pivots, step_permutation)
from ..obs import instrument


def _panel_tail(A_loc, pan, LUkk, k0, grow, gcol, pi, qi, mr, mc, nb):
    """Shared post-factor panel pipeline of the 2-D LU variants (tournament
    and nopiv — parallel/rbt.py): panel L via trsm against Ukk, packed
    L\\U write-back on the owner mesh column, U row band psum-bcast along p,
    masked full-width trailing gemm.  One implementation so the two
    factorizations cannot drift."""
    po = k0 // mr
    roff = k0 - po * mr
    qo = k0 // mc
    off = k0 - qo * mc

    Ukk = jnp.triu(LUkk)
    # L below the block: X = pan · Ukk^{-1}, valid for rows ≥ k0+nb
    X = lax.linalg.triangular_solve(Ukk, pan, left_side=False, lower=False)
    below = grow >= (k0 + nb)
    Lmask = jnp.where(below[:, None], X, jnp.zeros_like(X))

    # write the packed panel column back (owner mesh column only): rows < k0
    # keep U history; block rows get packed L\U; rows below get L.  Every
    # device knows LUkk (replicated by the psum before the factor).
    in_blk = (grow >= k0) & (grow < k0 + nb)
    packed = jnp.where(in_blk[:, None],
                       lax.dynamic_update_slice(
                           jnp.zeros((mr, nb), pan.dtype), LUkk,
                           (roff, jnp.int32(0))),
                       jnp.where(below[:, None], Lmask, pan))
    newA = lax.dynamic_update_slice(A_loc, packed, (jnp.int32(0), off))
    A_loc = jnp.where(qi == qo, newA, A_loc)

    # U row band: U = Lkk^{-1} · A[k0:k0+nb, :], bcast along p
    rb = lax.dynamic_slice(A_loc, (roff, jnp.int32(0)), (nb, mc))
    rb = jnp.where(pi == po, rb, jnp.zeros_like(rb))
    rb = lax.psum(rb, ROW_AXIS)                # (nb, mc) everywhere
    U_loc = lax.linalg.triangular_solve(jnp.tril(LUkk), rb,
                                        left_side=True, lower=True,
                                        unit_diagonal=True)
    ucols = gcol >= (k0 + nb)
    Umask = jnp.where(ucols[None, :], U_loc, jnp.zeros_like(U_loc))
    new_rows = jnp.where(ucols[None, :], U_loc, rb)
    rowband = lax.dynamic_update_slice(A_loc, new_rows, (roff, jnp.int32(0)))
    A_loc = jnp.where(pi == po, rowband, A_loc)

    # trailing update: full-width masked MXU gemm
    return A_loc - jnp.matmul(Lmask, Umask, precision=lax.Precision.HIGHEST)


def _lu_diag_info(A_loc, grow, gcol, npad):
    """First bad U diagonal (0 or non-finite), psum-assembled — the
    reduce_info analogue shared by the 2-D LU variants."""
    dmask = grow[:, None] == gcol[None, :]
    drow = jnp.sum(jnp.where(dmask, A_loc, jnp.zeros_like(A_loc)), axis=1)
    diag = jnp.zeros((npad,), A_loc.dtype).at[grow].set(drow)
    diag = lax.psum(lax.psum(diag, ROW_AXIS), COL_AXIS)
    # shared info kernel (robust.first_bad_index, reduce_info semantics)
    return first_bad_index((diag == 0) | ~jnp.isfinite(diag))


@lru_cache(maxsize=32)
def _getrf_dist_fn(mesh, npad: int, nb: int, dtype_str: str,
                   lu_panel: str = "tournament"):
    """Build the jitted shard_map tournament-LU over an npad×npad matrix.
    ``lu_panel`` selects the panel pivot scheme (Options.lu_panel: CALU
    tournament rounds or one gathered partial-pivot LU, pivot.py)."""
    p, q = mesh.shape[ROW_AXIS], mesh.shape[COL_AXIS]
    mr, mc = npad // p, npad // q          # local shard shape
    nt = npad // nb                        # panel count (static)
    assert mr % nb == 0 and mc % nb == 0

    def local_fn(A_loc):
        pi = lax.axis_index(ROW_AXIS)
        qi = lax.axis_index(COL_AXIS)
        grow = pi * mr + jnp.arange(mr, dtype=jnp.int32)   # global row of my rows
        gcol = qi * mc + jnp.arange(mc, dtype=jnp.int32)

        def extract_panel(A_loc, k0):
            """My rows of panel columns [k0, k0+nb): owner mesh column
            contributes, psum along q = the reference's panel listBcast."""
            qo = k0 // mc
            off = k0 - qo * mc
            pan = lax.dynamic_slice(A_loc, (jnp.int32(0), off), (mr, nb))
            pan = jnp.where(qi == qo, pan, jnp.zeros_like(pan))
            return lax.psum(pan, COL_AXIS)

        def step(k, carry):
            A_loc, perm = carry
            k0 = (k * nb).astype(jnp.int32) if hasattr(k, "astype") else k * nb
            pan = extract_panel(A_loc, k0)

            # ---- panel pivot selection + ipiv-compatible step permutation
            # (shared machinery, pivot.py; internal_getrf_tntpiv analogue)
            piv = select_pivots(lu_panel, pan, grow, k0, nb, p, ROW_AXIS)
            stepperm = step_permutation(piv, k0, npad, nb)
            perm = perm[stepperm]

            # ---- apply the row permutation: only dirty rows move
            # (shared machinery, pivot.py); dirty positions are within
            # {k0..k0+nb-1} ∪ piv
            S = jnp.concatenate([k0 + jnp.arange(nb, dtype=jnp.int32), piv])
            A_loc = _exchange_rows(A_loc, S, stepperm[S], pi, mr, ROW_AXIS)

            # ---- panel factorization on the permuted panel
            pan = extract_panel(A_loc, k0)
            po = k0 // mr
            roff = k0 - po * mr
            blk = lax.dynamic_slice(pan, (roff, jnp.int32(0)), (nb, nb))
            blk = jnp.where(pi == po, blk, jnp.zeros_like(blk))
            blk = lax.psum(blk, ROW_AXIS)              # diag block everywhere
            LUkk, _, blkperm = lax.linalg.lu(blk)
            # fold the intra-block pivoting into the global permutation and
            # physically reorder rows [k0, k0+nb) (they live on mesh row po)
            seg = jnp.take(perm, k0 + blkperm)
            perm = lax.dynamic_update_slice(perm, seg, (k0,))
            blk_rows = A_loc[jnp.clip(roff + blkperm, 0, mr - 1)]
            A_perm = lax.dynamic_update_slice(A_loc, blk_rows, (roff, jnp.int32(0)))
            A_loc = jnp.where(pi == po, A_perm, A_loc)
            pan_blk = pan[jnp.clip(roff + blkperm, 0, mr - 1)]
            pan = jnp.where(pi == po,
                            lax.dynamic_update_slice(pan, pan_blk, (roff, jnp.int32(0))),
                            pan)

            # ---- shared post-factor pipeline (panel L, packed write, U row
            # band, trailing gemm — one source of truth with the nopiv
            # variant, parallel/rbt.py)
            A_loc = _panel_tail(A_loc, pan, LUkk, k0, grow, gcol, pi, qi,
                                mr, mc, nb)
            return A_loc, perm

        perm0 = jnp.arange(npad, dtype=jnp.int32)
        A_loc, perm = lax.fori_loop(0, nt, step, (A_loc, perm0))

        # info: first bad diagonal of U (functional, reduce_info analogue)
        info = _lu_diag_info(A_loc, grow, gcol, npad)
        return A_loc, perm, info

    spec = P(ROW_AXIS, COL_AXIS)
    # perm/info are computed identically on every shard (their inputs are all
    # psum/all_gather results), but the vma system cannot prove replication
    # through the swap fori_loops — the unsharded out_specs assert it.
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P(None), P()), check_vma=False)
    return jax.jit(fn)


@lru_cache(maxsize=32)
def _getrf_tall_fn(mesh, mpad: int, npc: int, nb: int, dtype_str: str,
                   lu_panel: str = "tournament"):
    """Jitted 1-D TSLU over an mpad×npc tall matrix: rows block-sharded over
    the *flattened* mesh (every device owns all columns), tournament panels
    over the flat axis, trailing updates as fully local MXU gemms.

    The reference's ``src/getrf.cc:22-260`` factors any m×n over the grid;
    this is its tall regime re-shaped for TPU: with columns local, the panel
    needs no column broadcast at all, and the only collectives per panel are
    the candidate all-gather (tournament, getrf_tntpiv.cc) and two masked
    psums (dirty-row exchange + U row-band broadcast) — O(nb·(P·nb + npc))
    bytes each.  Work is O(m n²/P): the square-embedding detour (round 2) and
    its O(m³) flops are gone.
    """
    AX = (ROW_AXIS, COL_AXIS)                  # flattened device axis
    nprocs = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    mr = mpad // nprocs
    nt = npc // nb
    assert mr % nb == 0

    def local_fn(A_loc):                       # (mr, npc) per device
        ri = lax.axis_index(AX)
        grow = ri * mr + jnp.arange(mr, dtype=jnp.int32)
        gcol = jnp.arange(npc, dtype=jnp.int32)

        def step(k, carry):
            A_loc, perm = carry
            k0 = (k * nb).astype(jnp.int32) if hasattr(k, "astype") else k * nb

            # ---- panel pivot selection + ipiv-compatible step permutation
            # (shared machinery, pivot.py)
            pan = lax.dynamic_slice(A_loc, (jnp.int32(0), k0), (mr, nb))
            piv = select_pivots(lu_panel, pan, grow, k0, nb, nprocs, AX)
            stepperm = step_permutation(piv, k0, mpad, nb)
            perm = perm[stepperm]

            # ---- dirty-row exchange (≤ 2nb rows move, full local width;
            # shared machinery, pivot.py)
            S = jnp.concatenate([k0 + jnp.arange(nb, dtype=jnp.int32), piv])
            A_loc = _exchange_rows(A_loc, S, stepperm[S], ri, mr, AX)

            # ---- diagonal block factor (rows [k0,k0+nb) live on device po)
            po = k0 // mr
            roff = k0 - po * mr
            pan2 = lax.dynamic_slice(A_loc, (jnp.int32(0), k0), (mr, nb))
            blk = lax.dynamic_slice(pan2, (roff, jnp.int32(0)), (nb, nb))
            blk = jnp.where(ri == po, blk, jnp.zeros_like(blk))
            blk = lax.psum(blk, AX)
            LUkk, _, blkperm = lax.linalg.lu(blk)
            # fold intra-block pivots into the global permutation + reorder
            seg = jnp.take(perm, k0 + blkperm)
            perm = lax.dynamic_update_slice(perm, seg, (k0,))
            blk_rows = A_loc[jnp.clip(roff + blkperm, 0, mr - 1)]
            A_perm = lax.dynamic_update_slice(A_loc, blk_rows,
                                              (roff, jnp.int32(0)))
            A_loc = jnp.where(ri == po, A_perm, A_loc)
            pan2 = lax.dynamic_slice(A_loc, (jnp.int32(0), k0), (mr, nb))

            # ---- panel L: X = pan · Ukk^{-1} for rows below the block
            Ukk = jnp.triu(LUkk)
            X = lax.linalg.triangular_solve(Ukk, pan2, left_side=False,
                                            lower=False)
            below = grow >= (k0 + nb)
            Lmask = jnp.where(below[:, None], X, jnp.zeros_like(X))
            in_blk = (grow >= k0) & (grow < k0 + nb)
            packed = jnp.where(in_blk[:, None],
                               lax.dynamic_update_slice(
                                   jnp.zeros((mr, nb), pan2.dtype), LUkk,
                                   (roff, jnp.int32(0))),
                               jnp.where(below[:, None], Lmask, pan2))
            A_loc = lax.dynamic_update_slice(A_loc, packed, (jnp.int32(0), k0))

            # ---- U row band (owner bcast) + masked trailing columns
            rb = lax.dynamic_slice(A_loc, (roff, jnp.int32(0)), (nb, npc))
            rb = jnp.where(ri == po, rb, jnp.zeros_like(rb))
            rb = lax.psum(rb, AX)              # (nb, npc) everywhere
            U_band = lax.linalg.triangular_solve(jnp.tril(LUkk), rb,
                                                 left_side=True, lower=True,
                                                 unit_diagonal=True)
            ucols = gcol >= (k0 + nb)
            Umask = jnp.where(ucols[None, :], U_band, jnp.zeros_like(U_band))
            new_rows = jnp.where(ucols[None, :], U_band, rb)
            rowband = lax.dynamic_update_slice(A_loc, new_rows,
                                               (roff, jnp.int32(0)))
            A_loc = jnp.where(ri == po, rowband, A_loc)

            # ---- trailing update: one fully local MXU gemm
            A_loc = A_loc - jnp.matmul(Lmask, Umask,
                                       precision=lax.Precision.HIGHEST)
            return A_loc, perm

        perm0 = jnp.arange(mpad, dtype=jnp.int32)
        A_loc, perm = lax.fori_loop(0, nt, step, (A_loc, perm0))

        # info: first zero diagonal of U (cols ∩ my rows, psum-assembled;
        # shared kernel robust.first_bad_index)
        on_diag = (grow[:, None] == gcol[None, :])
        drow = jnp.sum(jnp.where(on_diag, A_loc, jnp.zeros_like(A_loc)),
                       axis=1)
        in_range = grow < npc
        diag = jnp.zeros((npc,), A_loc.dtype).at[
            jnp.where(in_range, grow, npc)].add(
                jnp.where(in_range, drow, jnp.zeros_like(drow)), mode="drop")
        diag = lax.psum(diag, AX)
        info = first_bad_index(diag == 0)
        return A_loc, perm, info

    spec = P(AX, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P(None), P()), check_vma=False)
    return jax.jit(fn)


@instrument
def getrf_tall_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256,
                           lu_panel: str = "tournament"):
    """1-D TSLU for tall matrices (m > n) over the flattened mesh.

    Returns ``(LU, perm, info)`` with ``A[perm] = L @ U`` in O(m n²/P) work —
    the mesh form of the reference's tall ``getrf.cc`` regime, replacing
    round 2's O(m³) square embedding.  Rows are padded to P·nb blocks and
    columns to nb multiples; pad columns carry unit pivots on pad rows so
    they never disturb the real factorization.
    """
    m, n = A.shape[-2:]
    slate_assert(m >= n, "getrf_tall_distributed expects m >= n")
    slate_assert(lu_panel in ("tournament", "pp"),
                 f"lu_panel must be 'tournament' or 'pp', got {lu_panel!r}")
    nb = max(1, min(nb, n))
    unit = nb * grid.p * grid.q
    npc = ceil_mult(n, nb)
    mpad = ceil_mult(m, unit)
    if mpad - m < npc - n:      # need a pad row per pad column
        mpad += unit
    if (mpad, npc) != (m, n):
        Ap = jnp.zeros((mpad, npc), A.dtype)
        Ap = Ap.at[:m, :n].set(A)
        if npc > n:             # unit pivots for pad columns, on pad rows
            Ap = Ap.at[m + jnp.arange(npc - n), n + jnp.arange(npc - n)].set(1)
    else:
        Ap = A
    mesh = grid.mesh
    Ap = jax.device_put(Ap, jax.sharding.NamedSharding(
        mesh, P((ROW_AXIS, COL_AXIS), None)))
    LU, perm, info = _getrf_tall_fn(mesh, mpad, npc, nb, str(Ap.dtype),
                                    lu_panel)(Ap)
    if mpad > m:
        # pad columns carry their unit pivot on a PAD row, so each pad column
        # deterministically swaps one pad row into the head — positions
        # [n, npc) of the head hold pad rows and their displaced real rows sit
        # in the tail.  (Unlike the square embedding, this is the *generic*
        # case, not a singularity signal.)  Repair both halves of the
        # truncation: the perm entry AND the L row, gathered from the padded
        # position where the displaced real row actually resides — valid
        # because row r of P·A_pad satisfies A[r] = L_pad[pos(r), :n] @ U for
        # every real row wherever it sits.
        head = perm[:m]
        bad = head >= m
        tail = perm[m:]
        key = jnp.where(tail < m, tail, mpad)
        order = jnp.argsort(key)             # tail slots sorted by row value
        cum = jnp.cumsum(bad) - 1            # index among bad slots
        repl = jnp.sort(key)[jnp.clip(cum, 0, key.shape[0] - 1)]
        srcpos = (m + order)[jnp.clip(cum, 0, order.shape[0] - 1)]
        perm = jnp.where(bad, repl, head)
        LUm = jnp.where(bad[:, None],
                        LU[jnp.clip(srcpos, 0, mpad - 1)], LU[:m])
        # a pad row inside the first n positions means a REAL column went
        # singular (its zero U diagonal already set info <= n); pad-column
        # info (> n) is the benign embedding diagonal
        info = jnp.where(info > n, jnp.int32(0), info)
        return LUm[:, :n], perm, info
    perm = perm[:m]
    info = jnp.where(info > n, jnp.int32(0), info)
    return LU[:m, :n], perm, info


@instrument
def getrf_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 256,
                      lu_panel: str = "tournament"):
    """Distributed tournament-pivoted LU over the process grid.

    Returns ``(LU, perm, info)`` with ``A[perm] = L @ U`` (L unit-lower, U
    upper, packed into one sharded array) — the distributed form of
    ``linalg.lu.getrf_tntpiv`` and the analogue of ``src/getrf_tntpiv.cc``.

    ``lu_panel`` (Options.lu_panel) selects panel pivoting: "tournament"
    (CALU candidate rounds, the communication-avoiding default) or "pp"
    (one gathered partial-pivot panel LU — exact LAPACK selection at
    O(m·nb) gather bytes per panel; the first-class A/B of the single-chip
    ``_getrf_tntpiv_fn`` schemes).

    Tall inputs (m > n) route to ``getrf_tall_distributed`` — 1-D TSLU over
    the flattened mesh with O(m n²/P) work (round 2's O(m³) square embedding
    is gone; the reference's getrf.cc handles the same regime on its 2-D
    grid, but with columns local the tall panel needs no broadcast at all).

    Wide inputs (m < n) factor the leading m×m block — partial pivoting never
    looks past column m — and finish the trailing columns with one sharded
    unit-lower solve, U[:, m:] = L^{-1} (P A)[:, m:] (the same split the
    reference's getrf uses once the diagonal runs out).
    """
    m, n = A.shape[-2:]
    slate_assert(A.ndim == 2, "getrf_distributed expects a 2-D matrix")
    slate_assert(lu_panel in ("tournament", "pp"),
                 f"lu_panel must be 'tournament' or 'pp', got {lu_panel!r}")
    if m > n:
        return getrf_tall_distributed(A, grid, nb=nb, lu_panel=lu_panel)
    if m < n:
        from .solvers import trsm_distributed

        LU1, perm, info = getrf_distributed(A[:, :m], grid, nb=nb,
                                            lu_panel=lu_panel)
        L = jnp.tril(LU1, -1) + jnp.eye(m, dtype=LU1.dtype)
        U2 = trsm_distributed(L, jnp.take(A[:, m:], perm, axis=0), grid,
                              lower=True, conj_trans=False)
        return jnp.concatenate([LU1, U2], axis=1), perm, info
    # clamp the block size so the padding unit never dwarfs the problem
    # (default nb=256 on a small matrix would otherwise pad to nb*lcm(p,q))
    nb = max(1, min(nb, n))
    unit = nb * _lcm(grid.p, grid.q)
    npad = ceil_mult(m, unit)
    if npad > n:
        # one allocation covers both the tall embedding (cols n..m) and the
        # divisibility padding (rows/cols m..npad): unit diagonal throughout
        Ap = jnp.zeros((npad, npad), A.dtype)
        Ap = Ap.at[:m, :n].set(A)
        idx = jnp.arange(n, npad)
        Ap = Ap.at[idx, idx].set(1)
    else:
        Ap = A
    Ap = jax.device_put(Ap, grid.spec())
    LU, perm, info = _getrf_dist_fn(grid.mesh, npad, min(nb, npad),
                                    str(Ap.dtype), lu_panel)(Ap)
    if npad > m:
        # pad rows never win a tournament against real rows (their entries in
        # real columns are zero) — except when a trailing block is exactly
        # singular, where a zero pad row can tie and be selected.  Repair the
        # truncated perm so it remains a permutation of [0,m): out-of-range
        # entries are replaced, in position order, by the unused values that
        # were displaced past position m (only reachable when info != 0).
        head = perm[:m]
        bad = head >= m
        tail = perm[m:]
        repl = jnp.sort(jnp.where(tail < m, tail, npad))   # unused values first
        perm = jnp.where(bad, repl[jnp.cumsum(bad) - 1], head)
        # a repaired position means a pad row's (zero) L entries landed inside
        # the leading m rows — the factorization there is NOT a clean LU of A,
        # so a pad-column info must not be silenced into success
        fallback = jnp.where(jnp.any(bad), jnp.argmax(bad).astype(jnp.int32) + 1,
                             jnp.int32(0))
        info = jnp.where(info > n, fallback, info)
    else:
        perm = perm[:m]
        # rows n..m of the embedding columns are real rows, so pivoting there
        # cannot corrupt the leading n columns: pad-column info is benign
        info = jnp.where(info > n, jnp.int32(0), info)
    LU = LU[:m, :n]
    return LU, perm, info


@instrument
def getrs_distributed(LU: jax.Array, perm: jax.Array, B: jax.Array,
                      grid: ProcessGrid):
    """Solve A X = B given the distributed LU: X = U^{-1} L^{-1} B[perm]
    (src/getrs.cc: permuteRows + two work::trsm sweeps)."""
    from .solvers import trsm_distributed

    Bp = jnp.take(B, perm, axis=0)
    n = LU.shape[-1]
    eye = jnp.eye(n, dtype=LU.dtype)
    L = jnp.tril(LU, -1) + eye
    U = jnp.triu(LU)
    Y = trsm_distributed(L, Bp, grid, lower=True, conj_trans=False)
    return trsm_distributed(U, Y, grid, lower=False, conj_trans=False)


@instrument
def gesv_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                     nb: int = 256, lu_panel: str = "tournament"):
    """Distributed general solve A X = B (src/gesv.cc = getrf + getrs).

    Runs under the failed-shard guard (robust.guard_shards): when a fault
    plan simulates a dead device (shard_fail at the "output" point), a
    non-finite result re-runs factor AND solve from the intact input — the
    honest recovery.  Zero extra host syncs when no chaos is active.

    Returns ``(X, info)``.
    """
    state = {}

    def run():
        LU, perm, info = getrf_distributed(inject("gesv_distributed", A),
                                           grid, nb=nb, lu_panel=lu_panel)
        state["info"] = info
        return getrs_distributed(LU, perm, B, grid)

    X, _ = guard_shards("gesv_distributed", run, RetryPolicy(max_retries=1))
    return X, state["info"]


@instrument
def gesv_mixed_distributed(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                           nb: int = 256, max_iterations: int = 30):
    """Distributed mixed-precision solve (src/gesv_mixed.cc over the mesh):
    tournament-LU factor in the next precision down (f64->f32, c128->c64;
    f32 has no lower rung — XLA's LU rejects bf16), working-precision
    iterative refinement, full-precision sharded fallback when IR stalls.

    Returns (X, perm, info, iters, converged_via_ir).
    """
    from .solvers import _ir_refine_distributed, _lower_dtype

    lo = _lower_dtype(A.dtype)
    if lo is None:
        LU, perm, info = getrf_distributed(A, grid, nb=nb)
        return getrs_distributed(LU, perm, B, grid), perm, info, 0, True
    LU, perm, info = getrf_distributed(A.astype(lo), grid, nb=nb)

    def solve_lo(R):
        return getrs_distributed(LU, perm, R.astype(lo), grid)

    X, iters, ok = _ir_refine_distributed(A, B, solve_lo, grid,
                                          max_iterations)
    if not bool(ok):                      # the solve's single host sync
        # mixed→full ladder (robust.LADDERS["gesv_mixed_distributed"])
        trace_event("fallback", routine="gesv_mixed_distributed", to="full")
        LU, perm, info = getrf_distributed(A, grid, nb=nb)
        return (getrs_distributed(LU, perm, B, grid), perm, info, int(iters),
                False)
    return X, perm, info, int(iters), True


@instrument
def gesv_mixed_gmres_distributed(A: jax.Array, B: jax.Array,
                                 grid: ProcessGrid, nb: int = 256, opts=None):
    """Distributed GMRES-IR (src/gesv_mixed_gmres.cc over the mesh): FGMRES in
    working precision with sharded matvecs, right-preconditioned by the
    low-precision tournament-LU solve (factor sharded, solves in-trace).
    Single-RHS like the reference.  Returns (X, perm, info, restarts,
    converged); falls back to the full-precision sharded solve on stall.
    """
    from ..core.types import Options
    from ..linalg.lu import _gmres_ir, _require_single_rhs, lu_factored_solve
    from .eig_dist import _shard
    from .solvers import _lower_dtype

    opts = Options.make(opts)
    _require_single_rhs(B, "gesv_mixed_gmres_distributed")
    vec = B.ndim == 1
    B2 = B[:, None] if vec else B       # the sharded solves need 2-D RHS

    def fallback():
        LUf, permf, infof = getrf_distributed(A, grid, nb=nb)
        Xf = getrs_distributed(LUf, permf, B2, grid)
        return (Xf[:, 0] if vec else Xf), permf, infof

    lo = opts.factor_precision or _lower_dtype(A.dtype)
    if lo is None:
        Xf, permf, infof = fallback()
        return Xf, permf, infof, 0, True
    LU, perm, info = getrf_distributed(A.astype(lo), grid, nb=nb)
    # sharding *constraints*, not device_put: GSPMD pads grid-indivisible n
    LUs = _shard(LU, grid)
    As = _shard(A, grid)

    def matvec(x):
        return jnp.matmul(As, x, precision=lax.Precision.HIGHEST)

    def precond(r):
        z = lu_factored_solve(LUs, perm, r.astype(lo)[:, None])
        return z[:, 0].astype(B.dtype)

    X, restarts, converged = _gmres_ir(matvec, precond, B, opts,
                                       "gesv_mixed_gmres_distributed")
    if not converged:
        if not opts.use_fallback_solver:
            return X, perm, info, int(restarts), False
        trace_event("fallback", routine="gesv_mixed_gmres_distributed",
                    to="full")
        Xf, permf, infof = fallback()
        return Xf, permf, infof, int(restarts), False
    return X, perm, info, int(restarts), True
