"""LU family: getrf (partial-pivot / nopiv / tournament) + getrs / gesv / getri and the
mixed-precision + random-butterfly solver variants.

Reference analogue (SURVEY.md §2.4 LU row): ``src/getrf.cc`` (partial pivoting with the
multithreaded panel of internal_getrf.cc + MPI pivot broadcast), ``src/getrf_nopiv.cc``,
``src/getrf_tntpiv.cc`` (CALU tournament pivoting), ``src/{getrs,gesv,getri,getriOOP}.cc``,
``src/gesv_mixed.cc`` (f32 factor + f64 iterative refinement), ``src/gesv_mixed_gmres.cc``
(GMRES-IR), ``src/gesv_rbt.cc`` + ``src/gerbt.cc`` (random butterfly transform).

TPU re-design:

* **Pivot representation.** The reference keeps per-panel ``Pivots`` (tile index +
  offset, types.hh:84-117) and swaps rows pairwise over MPI (internal_swap.cc).  Row
  swaps are hostile to an SPMD machine; instead every factorization returns a *global
  permutation vector* ``perm`` (PA = LU, perm[i] = source row) and row exchanges become
  one XLA gather — the TPU-native form of permuteRows.  ``perm_to_pivots`` converts to
  LAPACK/reference-style ipiv for API parity.

* **Panel factorization.** The reference panel is a thread-team with an MPI maxloc
  reduction per column (internal_getrf.cc:77-115).  Here the panel is
  ``lax.linalg.lu`` on the tall block column — XLA's native partially-pivoted LU —
  and the blocked driver composes panels exactly like getrf.cc's task loop: panel ->
  permute left/right -> row trsm -> trailing gemm (the hot loop, getrf.cc:173-230).

* **Tournament pivoting (CALU)** maps *better* to TPU than partial pivoting: each
  round is a batched LU over row blocks + a tree reduction that halves the candidate
  set (getrf_tntpiv.cc's panel; SURVEY.md §7 notes this is the better-fit default).
  Implemented with static shapes: candidates are padded to nb rows per block.

* **RBT** (gesv_rbt.cc:94-172): depth-d butterfly transforms are a perfect fit —
  structured +/- mixing expressed as reshapes and elementwise ops, then nopiv LU.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax

from ..core.exceptions import SlateError, slate_assert
from ..core.matrix import BaseMatrix, as_array, distribution_grid, write_back
from ..core.types import MethodLU, Options, Target
from ..robust import (RetryPolicy, Rung, SolveReport, first_bad_index, inject,
                      run_ladder)
from ..utils.trace import trace_block, trace_event
from .chol import _ir_solve
from ..obs import counter, instrument
from ..ops.pallas_tntpiv import merge_winners


# ---------------------------------------------------------------------------
# pivots utilities
# ---------------------------------------------------------------------------


def perm_to_pivots(perm):
    """Convert a permutation vector to LAPACK-style sequential ipiv (1-based),
    the reference's Pivots representation (types.hh:84-117).

    O(n) with a position map instead of the O(n²) ``list.index`` scan (round-1
    review: the ipiv path crawled for large n)."""
    import numpy as np

    p = np.asarray(perm)
    n = p.shape[0]
    rows = np.arange(n)            # rows[i] = original row at position i
    pos = np.arange(n)             # pos[r]  = current position of original row r
    ipiv = np.zeros(n, dtype=np.int64)
    for k in range(n):
        j = pos[p[k]]
        ipiv[k] = j + 1
        rk, rj = rows[k], rows[j]
        rows[k], rows[j] = rj, rk
        pos[rj], pos[rk] = k, j
    return ipiv


def pivots_to_perm(ipiv):
    """Inverse of perm_to_pivots: replay the 1-based sequential row interchanges
    into the permutation vector our getrs/getri consume."""
    import numpy as np

    ip = np.asarray(ipiv).tolist()
    rows = list(range(len(ip)))
    for k, one_based in enumerate(ip):
        j = int(one_based) - 1
        rows[k], rows[j] = rows[j], rows[k]
    return np.asarray(rows, dtype=np.int64)


def _compose_perm(outer, inner):
    """perm = outer ∘ inner: result[i] = inner[outer[i]]."""
    return jnp.take(inner, outer)


def _lu_info(U_diag) -> jax.Array:
    """First zero/NaN U pivot, LAPACK-style — the shared info kernel
    (robust.first_bad_index, the reference's reduce_info semantics)."""
    return first_bad_index(jnp.isnan(U_diag) | (U_diag == 0))


# ---------------------------------------------------------------------------
# nopiv panel kernel (used by getrf_nopiv and the RBT solver)
# ---------------------------------------------------------------------------


def _lu_nopiv_unblocked(a):
    """Unblocked LU without pivoting on a square block via rank-1 updates
    (≅ tile-level getrf_nopiv; Tile_getrf_nopiv semantics)."""
    n = a.shape[-1]

    def body(k, m):
        col = m[:, k] / m[k, k]
        col = jnp.where(jnp.arange(n) > k, col, m[:, k])
        m = m.at[:, k].set(col)
        row_mask = (jnp.arange(n)[:, None] > k) & (jnp.arange(n)[None, :] > k)
        update = jnp.outer(col, m[k, :])
        return jnp.where(row_mask, m - update, m)

    return lax.fori_loop(0, n, body, a)


_LU_NOPIV_BASE = 128


def _lu_nopiv_blocked(a):
    """Recursive blocked LU without pivoting: factor the leading half, two
    triangular solves, one Schur-complement MXU gemm, recurse on the trailing
    half.  The unblocked rank-1 loop runs only at the <=128 base — at nb=2048
    the rank-1 form alone moves ~70 GB of HBM per block (2048 sweeps over a
    16 MB tile) and dominated the whole CALU factorization."""
    n = a.shape[-1]
    if n <= _LU_NOPIV_BASE:
        return _lu_nopiv_unblocked(a)
    h = n // 2
    a11, a12 = a[..., :h, :h], a[..., :h, h:]
    a21, a22 = a[..., h:, :h], a[..., h:, h:]
    f11 = _lu_nopiv_blocked(a11)
    u12 = lax.linalg.triangular_solve(f11, a12, left_side=True, lower=True,
                                      unit_diagonal=True)
    l21 = lax.linalg.triangular_solve(f11, a21, left_side=False, lower=False)
    s = a22 - jnp.matmul(l21, u12, precision=lax.Precision.HIGHEST)
    f22 = _lu_nopiv_blocked(s)
    return jnp.concatenate(
        [jnp.concatenate([f11, u12], axis=-1),
         jnp.concatenate([l21, f22], axis=-1)], axis=-2)


@lru_cache(maxsize=32)
def _getrf_nopiv_fn(m: int, n: int, nb: int, dtype_str: str):
    nt = -(-min(m, n) // nb)

    def fn(A):
        for k in range(nt):
            k0, k1 = k * nb, min((k + 1) * nb, min(m, n))
            blk = _lu_nopiv_blocked(A[k0:k1, k0:k1])
            A = A.at[k0:k1, k0:k1].set(blk)
            if k1 < m:
                L21 = lax.linalg.triangular_solve(
                    blk, A[k1:m, k0:k1], left_side=False, lower=False)  # X U = B
                A = A.at[k1:m, k0:k1].set(L21)
            if k1 < n:
                U12 = lax.linalg.triangular_solve(
                    blk, A[k0:k1, k1:n], left_side=True, lower=True,
                    unit_diagonal=True)
                A = A.at[k0:k1, k1:n].set(U12)
            if k1 < m and k1 < n:
                A = A.at[k1:m, k1:n].add(
                    -jnp.matmul(A[k1:m, k0:k1], A[k0:k1, k1:n],
                                precision=lax.Precision.HIGHEST))
        return A

    return jax.jit(fn)


def getrf_nopiv(A, opts=None):
    """LU without pivoting (src/getrf_nopiv.cc). Returns (LU, info)."""
    opts = Options.make(opts)
    a = inject("getrf_nopiv", as_array(A))
    m, n = a.shape[-2:]
    with trace_block("getrf_nopiv", m=m, n=n):
        out = _getrf_nopiv_fn(m, n, min(opts.block_size, m, n), str(a.dtype))(a)
    info = _lu_info(jnp.diagonal(out, axis1=-2, axis2=-1))
    return write_back(A, out), info


# ---------------------------------------------------------------------------
# partial-pivot getrf
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _getrf_tiled_fn(m: int, n: int, nb: int, dtype_str: str):
    """Blocked right-looking partially-pivoted LU (getrf.cc task loop, software-
    pipelined for XLA)."""
    kmax = min(m, n)
    nt = -(-kmax // nb)

    def fn(A):
        perm = jnp.arange(m)
        for k in range(nt):
            k0, k1 = k * nb, min((k + 1) * nb, kmax)
            # --- panel (≅ internal::getrf_panel, getrf.cc:92-120) ---
            panel = A[k0:m, k0:k1]
            plu, _, pperm = lax.linalg.lu(panel)
            L_pan = jnp.tril(plu[:, : k1 - k0], -1)
            U_pan = jnp.triu(plu[: k1 - k0, :])
            # permute trailing + left columns and the global perm (row gather —
            # TPU-native permuteRows, internal_swap.cc analogue)
            gperm = jnp.concatenate([jnp.arange(k0), k0 + pperm])
            A = jnp.take(A, gperm, axis=0)
            perm = jnp.take(perm, gperm)
            A = A.at[k0:m, k0:k1].set(L_pan + jnp.pad(
                U_pan, ((0, m - k0 - (k1 - k0)), (0, 0))))
            if k1 < n:
                # row trsm (≅ lookahead/trailing trsm, getrf.cc:121-155)
                L11 = jnp.tril(plu[: k1 - k0, :], -1) + jnp.eye(
                    k1 - k0, dtype=A.dtype)
                U12 = lax.linalg.triangular_solve(
                    L11, A[k0:k1, k1:n], left_side=True, lower=True,
                    unit_diagonal=True)
                A = A.at[k0:k1, k1:n].set(U12)
                if k1 < m:
                    # trailing gemm — the hot loop (getrf.cc:173-230)
                    A = A.at[k1:m, k1:n].add(
                        -jnp.matmul(A[k1:m, k0:k1], U12,
                                    precision=lax.Precision.HIGHEST))
        return A, perm

    return jax.jit(fn)


#: XLA's TPU LU holds an (m, 128) column block of its panel, twice, in scoped
#: VMEM (custom call ``LuDecompositionBlock``).  The scope, by device kind,
#: where it was measured: on a v5e an m=16384 f32 LU asks for 16.07M of its
#: 16 MiB and does not compile ("Ran out of memory in memory space vmem").
#: Other TPU generations are not measured and keep partial pivoting.
_XLA_LU_VMEM_SCOPE = {"TPU v5 lite": 16 * 2**20}


def _operand_device(a):
    """The device an operand sits on: its own for a concrete array; for a
    traced one (or a host array), JAX's default device, where the program
    runs unless its inputs are placed elsewhere."""
    try:
        return next(iter(a.devices()))
    except (AttributeError, jax.errors.ConcretizationTypeError):
        return jax.devices()[0]


def _auto_method(A) -> MethodLU:
    """MethodLU.Auto: partial pivoting through XLA's fused LU, except where
    the operand's device kind is known to overflow that LU's VMEM scope --
    there tournament pivoting (CALU), whose LUs run on nb-row leaves, is the
    one that compiles.  Grid-bound operands keep their distributed route."""
    a = as_array(A)
    scope = _XLA_LU_VMEM_SCOPE.get(_operand_device(a).device_kind)
    panel = 2 * a.shape[-2] * 128 * jnp.dtype(a.dtype).itemsize
    if (scope is not None and distribution_grid(A) is None
            and panel > scope - 2**20):
        return MethodLU.CALU
    return MethodLU.PartialPiv


@instrument
def getrf(A, opts=None):
    """Pivoted LU: returns (LU, perm, info) with A[perm] = L U
    (src/getrf.cc:22-260; dispatch over MethodLU like gesv's select_algo).

    MethodLU.Auto is partial pivoting, except on a TPU whose XLA LU panel
    would overflow its VMEM (a v5e at m >= 16384 f32): there it is tournament
    pivoting, with its own pivots and growth bound (``_auto_method``).
    MethodLU.CALU routes to tournament pivoting (getrf_tntpiv), NoPiv to getrf_nopiv
    (perm = identity), RBT is reserved for gesv_rbt.
    """
    opts = Options.make(opts)
    # validated up front, on EVERY path: a typo'd lu_panel must raise, never
    # silently run the other panel scheme (parity-audit behavior contract)
    slate_assert(opts.lu_panel in ("tournament", "pp"),
                 f"lu_panel must be 'tournament' or 'pp', got {opts.lu_panel!r}")
    method = opts.method_lu
    if method == MethodLU.Auto:
        method = _auto_method(A)
    if method == MethodLU.NoPiv:
        lu_, info = getrf_nopiv(A, opts)
        return lu_, jnp.arange(as_array(A).shape[-2]), info
    if method == MethodLU.CALU:
        return getrf_tntpiv(A, opts)
    if method != MethodLU.PartialPiv:
        raise SlateError(f"unsupported MethodLU {method}")

    grid = distribution_grid(A)
    a_chk = inject("getrf", as_array(A))
    if grid is not None:
        # wrapper bound to a >1-device grid: tournament-pivoted distributed LU
        # (the mesh form of getrf_tntpiv; reference getrf.cc consumes the
        # construction-time distribution the same way).  Wide inputs factor the
        # leading square block + one sharded trsm; tall inputs ride the 1-D
        # TSLU (O(m n²/P); the round-2 square embedding and its m <= 2n
        # caller guard are gone).  Options.lu_panel reaches the mesh panel
        # too ("pp" = gathered partial-pivot panel, pivot.partialpiv_piv).
        from ..parallel import getrf_distributed

        lu_, perm, info = getrf_distributed(a_chk, grid, nb=opts.block_size,
                                            lu_panel=opts.lu_panel)
        write_back(A, lu_)
        return lu_, perm, info

    a = a_chk
    m, n = a.shape[-2:]
    target = opts.target
    if target == Target.Auto:
        target = Target.XLA
    with trace_block("getrf", m=m, n=n, target=str(target)):
        if target == Target.XLA:
            plu, _, perm = lax.linalg.lu(a)
            out = plu
        else:
            out, perm = _getrf_tiled_fn(m, n, min(opts.block_size, m, n),
                                        str(a.dtype))(a)
    info = _lu_info(jnp.diagonal(out, axis1=-2, axis2=-1))
    return write_back(A, out), perm, info


# ---------------------------------------------------------------------------
# tournament pivoting (CALU)
# ---------------------------------------------------------------------------


def _phase(name):
    """The scope of one phase of the CALU factorization: its operations'
    ``op_name`` carry ``getrf/<name>`` wherever the call sits, inside a
    rolled panel loop too (``getrf/select``, ``swap``, ``panel``,
    ``update``, ``info``)."""
    return trace_block("getrf/" + name)


def _merge_winners(v2):
    """The rows partial pivoting promotes in each (2w, w) block of ``v2``
    (one block, or a batch), in pivot order: ``lax.linalg.lu(v2)[2][...,
    :w]``.  The Pallas kernel, which steps a level's merges together and
    forms no factor, takes them where it applies -- f32 on a TPU, ``w`` a
    multiple of 128; elsewhere XLA's LU, which steps through a batch one
    block at a time.  Counts the merges at trace time, by implementation,
    in ``lu_tournament_merges``."""
    w = v2.shape[-1]
    impl = ("pallas" if _operand_device(v2).platform == "tpu"
            and v2.dtype == jnp.float32 and w % 128 == 0 else "xla")
    counter("lu_tournament_merges",
            "tournament pair merges traced, by implementation").inc(
                v2.size // (2 * w * w), impl=impl)
    if impl == "pallas":
        return merge_winners(v2.reshape(-1, 2 * w, w)).reshape(
            v2.shape[:-2] + (w,))
    return lax.linalg.lu(v2)[2][..., :w]


def _tournament_panel(panel, nb, live):
    """Select nb pivot rows of a tall panel by tournament (getrf_tntpiv.cc panel:
    block-local partially-pivoted LUs, then a binary reduction tree over winners;
    internal_getrf_tntpiv.cc / Tile_getrf_tntpiv.hh semantics, re-expressed as a
    static tree of batched pair merges, :func:`_merge_winners`).  The
    leaves are the panel's full nb-row blocks; a ragged tail block joins
    the root merge, which orders the winners.

    Only the panel's first ``live`` rows (a count that a rolled panel loop
    traces) are candidates, and the rows past them are zero.  The tree runs
    over every leaf the panel holds, with the leaves past the full ones
    zeroed: a merge with a zero block returns the other block's rows, which
    is what passing an odd block on to the next level does, so the winners
    are those of the tree over the live rows alone.

    Returns the winning local row indices (length nb; live >= nb).
    """
    mp, w = panel.shape
    nfull = live // nb
    leaves = mp // nb
    rows = jnp.arange(leaves * nb)
    V = jnp.where((rows < nfull * nb)[:, None], panel[:leaves * nb],
                  0).reshape(leaves, nb, w)
    I = rows.reshape(leaves, nb)
    # uniform leaves (nb rows each) reduce as ONE batched merge per tree
    # level -- TPU executes ops sequentially, so the reference's independent
    # per-pair merges must be a batch, not a Python loop of separate calls
    while V.shape[0] > 1:
        nblk = V.shape[0]
        half = nblk // 2
        V2 = jnp.concatenate([V[0:2 * half:2], V[1:2 * half:2]], axis=1)
        I2 = jnp.concatenate([I[0:2 * half:2], I[1:2 * half:2]], axis=1)
        take = _merge_winners(V2)               # batched pair merges
        V2 = jnp.take_along_axis(V2, take[:, :, None], axis=1)
        I2 = jnp.take_along_axis(I2, take, axis=1)
        if nblk % 2:
            V2 = jnp.concatenate([V2, V[2 * half:]], axis=0)
            I2 = jnp.concatenate([I2, I[2 * half:]], axis=0)
        V, I = V2, I2
    sub, idx = V[0], I[0]
    tail = nfull * nb
    padded = jnp.concatenate([panel, jnp.zeros((nb, w), panel.dtype)])
    sub = jnp.concatenate([sub, lax.dynamic_slice_in_dim(padded, tail, nb)])
    idx = jnp.concatenate([idx, tail + jnp.arange(nb)])
    return jnp.take(idx, _merge_winners(sub))


#: A rolled factorization has at least this many panel runs (each one loop
#: over its panels, compiled once), and at most this many panels in a run
#: once there are more: 64 panels (n=16384, nb=256) roll into 10 runs of 6-7.
_MIN_RUNS, _RUN_PANELS = 8, 7


def _panel_runs(nt: int) -> list:
    """Lengths of the runs of full-width outer panels that roll into one loop
    each.  A run's windows are sized for its first panel, so its later
    panels also compute on rows and columns past the matrix (zero padding):
    10 runs cost the n=16384 factorization ~1.15x its flops.  The longer
    runs come last, where the trailing extent is smallest."""
    count = min(nt, max(_MIN_RUNS, -(-nt // _RUN_PANELS)))
    if not count:
        return []
    base, extra = divmod(nt, count)
    return [base] * (count - extra) + [base + 1] * extra


def _solve_upper_right(u, b, rows: int = 128):
    """``b u^-1`` for an upper-triangular ``u``, as one batched solve over
    blocks of ``rows`` rows where they divide ``b``.  TPU lays a tall solve's
    result out column-major; written into a rolled loop's matrix, that would
    have the compiler keep the whole matrix column-major and relay it out
    around every row exchange (two full copies a panel).  A batch of square
    blocks is laid out row-major, like the matrix."""
    h, w = b.shape
    if h % rows:
        return lax.linalg.triangular_solve(u, b, left_side=False, lower=False)
    k = h // rows
    return lax.linalg.triangular_solve(
        jnp.broadcast_to(u, (k, w, w)), b.reshape(k, rows, w),
        left_side=False, lower=False).reshape(h, w)


@lru_cache(maxsize=32)
def _getrf_tntpiv_fn(m: int, n: int, nb: int, ib: int, dtype_str: str,
                     panel_scheme: str = "tournament"):
    """Two-level CALU (getrf_tntpiv.cc:161-230 + its ib inner blocking).

    Tournament merge flops scale as (panel width)² per candidate row, so
    pivot selection runs on narrow ib-wide subpanels while the trailing
    update stays an nb-wide MXU gemm — the same nb/ib split the reference
    uses (Option::InnerBlocking), which took the n=16384 bench config from
    ~6.5 to the flat-panel tournament's missing third of peak.

    ``panel_scheme="pp"`` selects pivots with ONE partial-pivot LU of the
    ib-wide subpanel instead of the merge tree: the tournament's log2(m/ib)
    levels are each a column-sequential batched LU (~6 x ib sequential
    elimination steps per panel at the bench shape), while a single panel
    LU is ib steps — the selection quality of classic partial pivoting at a
    sixth of the sequential depth.  (The round-2 finding that fused
    lax.linalg.lu "does not finish" was for the FULL n-wide matrix, not an
    ib-wide panel.)

    The outer panel loop is rolled, so the program does not grow with the
    number of panels: the full-width panels split into runs
    (:func:`_panel_runs`), each one ``lax.fori_loop`` whose body works on
    windows of the sizes its first panel needs, placed at the current
    panel.  The matrix is padded with zero rows and columns for the windows
    of a run's later panels to run past its end: zero rows never win a
    pivot, and the factor's updates of zeros stay zero.  No window is
    masked, which keeps the loop's matrix in the row-major layout its row
    exchanges need (a select between a solve's output and a slice of the
    matrix would have the compiler relayout the whole matrix around every
    exchange).  The last, narrower panel is traced with static offsets.
    Each phase is a named scope (:func:`_phase`)."""
    kmax = min(m, n)
    nt = kmax // nb
    runs = _panel_runs(nt)
    pad = (max(runs, default=1) - 1) * nb      # the longest run's overhang
    mp = m + pad

    def inner_step(A, perm, k0, d, top, off, w, width):
        """Factor the subpanel of columns [c0, c0+w), c0 = k0 + off:
        tournament + dirty-row swap + nopiv block factor + L21, then update
        the outer panel's columns [c0+w, k0+width) only.  The row windows
        have the height rows ``top:m`` need, ``d`` more than the live rows
        below c0 (``d = k0 - r0`` in a run starting at r0)."""
        c0 = k0 + off
        c1 = c0 + w
        R = m - top
        with _phase("select"):
            panel = lax.dynamic_slice(A, (c0, c0), (R, w))
            if panel_scheme == "pp":
                # classic partial pivoting on the subpanel: the permutation's
                # first w entries are the rows the elimination promoted to the
                # top — exactly the pivot rows, discarding the factor
                _, _, pperm = lax.linalg.lu(panel)
                winners = pperm[:w]
            else:
                winners = _tournament_panel(panel, w, R - d)
        with _phase("swap"):
            # dirty-rows-only exchange (permuteRows analogue): winners move to
            # the top w window slots and the displaced occupants fill the
            # vacated winner slots — ≤ 2w rows move, vs the full-matrix
            # compaction gather (4x the HBM traffic at the n=16384 bench)
            ar = jnp.arange(R)
            is_w = jnp.zeros(R, dtype=bool).at[winners].set(True)
            big = mp + w                                   # OOB sentinel
            disp = jnp.sort(jnp.where(~is_w[:w], jnp.arange(w), big))
            vac = jnp.sort(jnp.where(is_w & (ar >= w), ar, big))[:w]
            # slot i of vac pairs with slot i of disp — their valid counts
            # match by construction; rows past the padded matrix are dropped
            S = c0 + jnp.concatenate([jnp.arange(w), vac])       # dirty dst
            src = jnp.clip(c0 + jnp.concatenate([winners, disp]), 0, m - 1)
            A = A.at[S].set(A[src], mode="drop")
            perm = perm.at[S].set(perm[src], mode="drop")
        with _phase("panel"):
            # nopiv factor of the permuted subpanel (pivots already chosen)
            blk = _lu_nopiv_blocked(lax.dynamic_slice(A, (c0, c0), (w, w)))
            A = lax.dynamic_update_slice(A, blk, (c0, c0))
            if R > w:
                L21 = _solve_upper_right(
                    blk, lax.dynamic_slice(A, (c1, c0), (R - w, w)))
                A = lax.dynamic_update_slice(A, L21, (c1, c0))
            cw = width - off - w
            if cw:
                U12 = lax.linalg.triangular_solve(
                    blk, lax.dynamic_slice(A, (c0, c1), (w, cw)),
                    left_side=True, lower=True, unit_diagonal=True)
                A = lax.dynamic_update_slice(A, U12, (c0, c1))
        if cw and R > w:
            with _phase("update"):
                win = lax.dynamic_slice(A, (c1, c1), (R - w, cw))
                A = lax.dynamic_update_slice(
                    A, win - jnp.matmul(L21, U12,
                                        precision=lax.Precision.HIGHEST),
                    (c1, c1))
        return A, perm

    def outer_step(A, perm, k0, r0, width):
        """One outer panel of ``width`` columns at k0: inner ib-wide
        tournament panels, updates confined to the outer panel's columns,
        then the outer row trsm against the panel's unit-lower factor (the
        solve reads only the strict lower triangle) and the big trailing MXU
        gemm (the hot loop, getrf.cc:173-230), on windows of the sizes the
        run's first panel (at r0) needs."""
        d = k0 - r0
        for off in range(0, width, ib):
            A, perm = inner_step(A, perm, k0, d, r0 + off, off,
                                 min(ib, width - off), width)
        e = r0 + width              # the windows' extents are m - e, n - e
        k1 = k0 + width
        if e < n:
            with _phase("panel"):
                U12 = lax.linalg.triangular_solve(
                    lax.dynamic_slice(A, (k0, k0), (width, width)),
                    lax.dynamic_slice(A, (k0, k1), (width, n - e)),
                    left_side=True, lower=True, unit_diagonal=True)
                A = lax.dynamic_update_slice(A, U12, (k0, k1))
            if e < m:
                with _phase("update"):
                    win = lax.dynamic_slice(A, (k1, k1), (m - e, n - e))
                    L21 = lax.dynamic_slice(A, (k1, k0), (m - e, width))
                    A = lax.dynamic_update_slice(
                        A, win - jnp.matmul(L21, U12,
                                            precision=lax.Precision.HIGHEST),
                        (k1, k1))
        return A, perm

    def fn(A):
        with _phase("swap"):
            perm = jnp.arange(m)
            if pad:
                A = jnp.pad(A, ((0, pad), (0, pad)))
        r0 = 0
        for length in runs:
            def body(i, carry, r0=r0):
                return outer_step(*carry, r0 + i * nb, r0, nb)

            # the loop's own counter and test
            with _phase("panel"):
                A, perm = lax.fori_loop(0, length, body, (A, perm))
            r0 += length * nb
        if r0 < kmax:                # the last, narrower panel
            A, perm = outer_step(A, perm, r0, r0, kmax - r0)
        if pad:
            with _phase("swap"):
                A = A[:m, :n]
        return A, perm

    return jax.jit(fn)


@instrument
def getrf_tntpiv(A, opts=None):
    """Tournament-pivoted (CALU) LU (src/getrf_tntpiv.cc:161-230).
    Returns (LU, perm, info)."""
    opts = Options.make(opts)
    a = inject("getrf_tntpiv", as_array(A))
    m, n = a.shape[-2:]
    nb = min(opts.block_size, m, n)
    ib = max(1, min(opts.inner_blocking, nb))
    slate_assert(opts.lu_panel in ("tournament", "pp"),
                 f"lu_panel must be 'tournament' or 'pp', got {opts.lu_panel!r}")
    with trace_block("getrf_tntpiv", m=m, n=n):
        out, perm = _getrf_tntpiv_fn(m, n, nb, ib, str(a.dtype),
                                     opts.lu_panel)(a)
    with _phase("info"):
        info = _lu_info(jnp.diagonal(out, axis1=-2, axis2=-1))
    return write_back(A, out), perm, info


# ---------------------------------------------------------------------------
# solves
# ---------------------------------------------------------------------------


#: Rows of the factor each step of a rolled triangular sweep solves.
_SWEEP_BLOCK = 256


def _rolled_sweep(f, b, lower: bool):
    """Solve ``T x = b`` for the unit-lower (``lower``) or the upper
    triangle ``T`` of a packed LU factor ``f`` by blocked substitution, one
    loop over row blocks: each step multiplies the block's rows of ``f`` by
    the solution so far (zero where not yet solved, so the rest of the row
    adds nothing) and solves the block's diagonal triangle.  The compiled
    program is the size of one step, whatever ``n``; XLA's own
    TriangularSolve unrolls its blocks (81 MB of TPU code at n=16384)."""
    n = f.shape[-1]
    bs = min(_SWEEP_BLOCK, n)
    full = n // bs * bs

    def step(x, r0, size):
        rows = lax.dynamic_slice_in_dim(f, r0, size)
        r = lax.dynamic_slice_in_dim(b, r0, size) - jnp.matmul(
            rows, x, precision=lax.Precision.HIGHEST)
        d = lax.dynamic_slice_in_dim(rows, r0, size, axis=1)
        xi = lax.linalg.triangular_solve(d, r, left_side=True, lower=lower,
                                         unit_diagonal=lower)
        return lax.dynamic_update_slice_in_dim(x, xi, r0, 0)

    x = jnp.zeros_like(b)
    if lower:
        x = lax.fori_loop(0, full // bs, lambda i, x: step(x, i * bs, bs), x)
        if full < n:
            x = step(x, full, n - full)
    else:
        if full < n:
            x = step(x, full, n - full)
        x = lax.fori_loop(0, full // bs,
                          lambda i, x: step(x, full - (i + 1) * bs, bs), x)
    return x


def lu_factored_solve(plu, perm, rhs):
    """Permute rows + unit-lower solve + upper solve from a packed LU factor —
    the shared kernel of gesv_core, the *_mixed preconditioners, and
    gecondest (getrs sweeps in rolled loops, :func:`_rolled_sweep`)."""
    pb = jnp.take(rhs, perm, axis=0) if perm is not None else rhs
    y = lax.linalg.triangular_solve(plu, pb, left_side=True, lower=True,
                                    unit_diagonal=True)
    return lax.linalg.triangular_solve(plu, y, left_side=True, lower=False)


def gesv_core(a, b):
    """Pure single-matrix gesv kernel: partially-pivoted LU + the two
    triangular sweeps, nothing else — no wrappers, no fault injection, no
    trace blocks, no host syncs.  This is the vmap-first core the batched
    serving layer (:mod:`slate_tpu.serve`) maps over a leading batch axis
    (``lax.linalg.lu`` batches natively, so ``jax.vmap(gesv_core)`` is one
    fused batched program).  Returns ``(x, perm, info)`` with a per-matrix
    LAPACK info from the U diagonal."""
    plu, _, perm = lax.linalg.lu(a)
    info = _lu_info(jnp.diagonal(plu, axis1=-2, axis2=-1))
    x = lu_factored_solve(plu, perm, b)
    return x, perm, info


def getrs(LU, perm, B, opts=None, trans=False):
    """Solve op(A) X = B from the LU factor (src/getrs.cc: permuteRows(Forward) +
    work::trsm(L) + work::trsm(U); here: one gather + two TriangularSolves).

    ``trans``: False/'n' solves A X = B; True/'t' solves A^T X = B; 'c' solves
    A^H X = B (the LAPACK trans codes)."""
    lu_ = as_array(LU)
    b = as_array(B)
    code = ({False: "n", True: "t"}.get(trans, trans) or "n")
    code = str(code).lower()[0]
    # the phases' scopes name the compiled operations (getrs/permute, ...)
    with trace_block("getrs"):
        if code in ("t", "c"):
            conj = code == "c"
            # op(A) x = b  =>  U^op y = b; L^op z = y; x = perm^{-1} scatter
            with trace_block("forward"):
                y = lax.linalg.triangular_solve(lu_, b, left_side=True,
                                                lower=False, transpose_a=True,
                                                conjugate_a=conj)
            with trace_block("backward"):
                z = lax.linalg.triangular_solve(lu_, y, left_side=True,
                                                lower=True, unit_diagonal=True,
                                                transpose_a=True,
                                                conjugate_a=conj)
            with trace_block("permute"):
                x = jnp.zeros_like(z).at[perm].set(z) if perm is not None \
                    else z
        else:
            with trace_block("permute"):
                pb = jnp.take(b, perm, axis=0) if perm is not None else b
            with trace_block("forward"):
                y = _rolled_sweep(lu_, pb, lower=True)
            with trace_block("backward"):
                x = _rolled_sweep(lu_, y, lower=False)
        with trace_block("store"):
            return write_back(B, x)


def getrs_nopiv(LU, B, opts=None, trans=False):
    """Solve from a pivot-free LU factor (src/getrs_nopiv.cc): the two triangular
    sweeps with no row permutation."""
    return getrs(LU, None, B, opts, trans=trans)


@instrument
def gesv(A, B, opts=None):
    """Solve A X = B (src/gesv.cc = getrf + getrs).

    The factorization is ``getrf``'s: with MethodLU.Auto, tournament
    pivoting (CALU) where the operand's TPU cannot compile XLA's LU panel.
    Returns (X, perm, info); with ``Options(solve_report=True)``,
    (X, perm, info, SolveReport)."""
    opts = Options.make(opts)
    lu_, perm, info = getrf(A, opts if not opts.solve_report
                            else opts.replace(solve_report=False))
    X = getrs(lu_, perm, B, opts)
    if opts.solve_report:
        report = SolveReport(routine="gesv", info=int(info),
                             precision_used=str(as_array(lu_).dtype),
                             fallback_chain=(str(opts.method_lu),)).finalize()
        report.recovered = report.info == 0
        return X, perm, info, report
    return X, perm, info


def gesv_nopiv(A, B, opts=None):
    """Solve A X = B without pivoting, escalating to partial pivoting on breakdown.

    The declared ladder (src/gesv_nopiv.cc +
    robust.LADDERS["gesv_nopiv"]): a nopiv breakdown (zero pivot, info > 0,
    or non-finite X) re-solves with partial pivoting from the *pristine*
    operand when Option::UseFallbackSolver holds — the recovery the reference
    leaves to the caller.  Detecting the breakdown costs one host sync (a
    fused info+isfinite verdict, trivial next to the O(n³) factor); pipelined
    callers who want the old zero-sync alias pass
    ``Options(use_fallback_solver=False)``.  Returns (X, perm, info); with
    ``Options(solve_report=True)``, (X, perm, info, SolveReport)."""
    opts = Options.make(opts)
    base = opts.replace(method_lu="nopiv", solve_report=False)
    from ..robust import active

    if (not opts.use_fallback_solver and not opts.solve_report
            and opts.max_retries <= 0 and active() is None):
        # single-rung ladder with nothing to observe it: the ok verdict could
        # never trigger an escalation, so skip the ladder machinery and its
        # host sync + isfinite reduction — the original zero-sync alias
        return gesv(A, B, base)
    a0, b0 = as_array(A), as_array(B)   # immutable snapshots: rungs re-solve
    #                                     from intact inputs, never a half-
    #                                     written factor
    report = SolveReport(routine="gesv_nopiv") if opts.solve_report else None
    policy = RetryPolicy.from_options(opts, "gesv_nopiv")

    def _operand():
        # a Matrix wrapper keeps its in-place factor write-back: restore the
        # pristine operand first (a prior rung left ITS factor in the
        # wrapper), then let gesv factor the wrapper itself.  Plain arrays
        # just use the snapshot.
        if isinstance(A, BaseMatrix):
            write_back(A, a0)
            return A
        return a0

    def nopiv_rung():
        out = gesv(_operand(), b0, base)
        ok = bool((out[2] == 0) & jnp.all(jnp.isfinite(as_array(out[0]))))
        return out, ok

    def pp_rung():
        out = gesv(_operand(), b0, base.replace(method_lu="partialpiv"))
        return out, bool(out[2] == 0)

    rungs = [Rung("nopiv", nopiv_rung)]
    if opts.use_fallback_solver:
        rungs.append(Rung("partialpiv", pp_rung))
    X, perm, info = run_ladder("gesv_nopiv", rungs, policy, report)
    X = write_back(B, as_array(X))
    if report is not None:
        report.info = int(info)
        report.precision_used = str(a0.dtype)
        return X, perm, info, report.finalize()
    return X, perm, info


def getri(LU, perm, opts=None):
    """Inverse from the LU factor (src/getri.cc): solves A X = I against the
    factored (LU, perm) pair from getrf, writing the inverse back over the
    factor — the reference's in-place contract."""
    lu_ = as_array(LU)
    n = lu_.shape[-1]
    X = getrs(lu_, perm, jnp.eye(n, dtype=lu_.dtype), opts)
    return write_back(LU, X)


def getri_oop(LU, perm, B, opts=None):
    """Out-of-place inverse (src/getriOOP.cc): writes A^{-1} into B from the
    factored (LU, perm) pair, leaving the factor intact for reuse."""
    lu_ = as_array(LU)
    n = lu_.shape[-1]
    X = getrs(lu_, perm, jnp.eye(n, dtype=lu_.dtype), opts)
    return write_back(B, X)


# ---------------------------------------------------------------------------
# mixed precision + GMRES-IR
# ---------------------------------------------------------------------------


@instrument
def gesv_mixed(A, B, opts=None):
    """Low-precision LU factor + working-precision iterative refinement
    (src/gesv_mixed.cc:23-40,106+), run as the declared mixed→full escalation
    ladder (robust.LADDERS["gesv_mixed"]; Option::UseFallbackSolver gates the
    second rung, gesv_mixed.cc:93-96).  Returns (X, perm, info, iters); with
    ``Options(solve_report=True)``, (..., SolveReport)."""
    from .chol import _lower_precision

    opts = Options.make(opts)
    a0 = as_array(A)        # pristine snapshot: each rung re-enters the input
    #                         injection site, so a call_index=0 input fault is
    #                         transient under escalation (the ladder recovers
    #                         from intact data, never a corrupted copy)
    b = as_array(B)
    plain = opts.replace(solve_report=False)
    lo = opts.factor_precision or _lower_precision(a0.dtype)
    report = SolveReport(routine="gesv_mixed") if opts.solve_report else None
    if lo is None:
        a_in = inject("gesv_mixed", a0)
        # no fault fired → pass the original operand through, so a Matrix
        # wrapper keeps its in-place factor write-back (pre-ladder contract)
        src = A if (a_in is a0 and isinstance(A, BaseMatrix)) else a_in
        X, perm, info = gesv(src, b, plain)
        X = write_back(B, as_array(X))
        if report is not None:
            report.record_rung("full")
            report.info, report.precision_used = int(info), str(a0.dtype)
            report.recovered = report.info == 0
            return X, perm, info, jnp.int32(0), report.finalize()
        return X, perm, info, jnp.int32(0)

    state = {"iters": jnp.int32(0)}

    def mixed_rung():
        a = inject("gesv_mixed", a0)
        with trace_block("gesv_mixed", lo=str(lo)):
            plu, _, perm = lax.linalg.lu(a.astype(lo))
            plu = inject("gesv_mixed", plu, point="factor")
            info = _lu_info(jnp.diagonal(plu, axis1=-2, axis2=-1))

            def solve_lo(rhs):
                return lu_factored_solve(plu, perm, rhs.astype(lo))

            x, iters, converged = _ir_solve(a, b, solve_lo, opts)
        state["iters"] = iters
        return (x, perm, info), bool(converged)

    def full_rung():
        a_in = inject("gesv_mixed", a0)
        # no fault fired → original wrapper through, preserving its in-place
        # factor write-back (the mixed rung never touched its storage)
        src = A if (a_in is a0 and isinstance(A, BaseMatrix)) else a_in
        X, perm, info = gesv(src, b, plain)
        return (as_array(X), perm, info), bool(info == 0)

    rungs = [Rung("mixed", mixed_rung)]
    if opts.use_fallback_solver:
        rungs.append(Rung("full", full_rung))
    x, perm, info = run_ladder("gesv_mixed", rungs,
                               RetryPolicy.from_options(opts, "gesv_mixed"),
                               report)
    X = write_back(B, x)
    if report is not None:
        report.info = int(info)
        report.iters = int(state["iters"])
        report.precision_used = (str(jnp.dtype(lo)) if report.fallback_chain
                                 == ("mixed",) else str(a0.dtype))
        return X, perm, info, state["iters"], report.finalize()
    return X, perm, info, state["iters"]


def _fgmres(matvec, precond, b, x0, restart, tol, max_restarts):
    """Restarted FGMRES with right preconditioning (src/gesv_mixed_gmres.cc uses
    GMRES-IR the same way).  The restart loop is a ``lax.while_loop`` with an
    on-device convergence test — no per-restart host sync (round-1 review: the
    ``float()`` in the old loop blocked dispatch every cycle); a NaN residual
    fails the ``resid > tol`` predicate and exits, preserving the NaN-safe
    fallback verdict."""

    def cycle(x):
        r = b - matvec(x)
        beta = jnp.linalg.norm(r)
        V = jnp.zeros((restart + 1,) + b.shape, dtype=b.dtype)
        Z = jnp.zeros((restart,) + b.shape, dtype=b.dtype)
        H = jnp.zeros((restart + 1, restart), dtype=b.dtype)
        V = V.at[0].set(r / jnp.where(beta == 0, 1, beta))
        for j in range(restart):       # static unroll: Krylov dim is small
            z = precond(V[j])
            w = matvec(z)
            # modified Gram-Schmidt
            for i in range(j + 1):
                hij = jnp.vdot(V[i], w)
                H = H.at[i, j].set(hij)
                w = w - hij * V[i]
            hn = jnp.linalg.norm(w)
            H = H.at[j + 1, j].set(hn)
            V = V.at[j + 1].set(w / jnp.where(hn == 0, 1, hn))
            Z = Z.at[j].set(z)
        # least squares min ||beta e1 - H y||
        e1 = jnp.zeros(restart + 1, dtype=b.dtype).at[0].set(beta)
        y, *_ = jnp.linalg.lstsq(H, e1)
        return x + jnp.tensordot(y, Z, axes=1)

    tol = jnp.asarray(tol, jnp.real(b).dtype)

    def cond(carry):
        x, restarts, resid = carry
        return (resid > tol) & (restarts < max_restarts)

    def body(carry):
        x, restarts, _ = carry
        x = cycle(x)
        return x, restarts + 1, jnp.linalg.norm(b - matvec(x))

    x, restarts, _ = lax.while_loop(
        cond, body, (x0, jnp.int32(0), jnp.linalg.norm(b - matvec(x0))))
    return x, restarts


def _require_single_rhs(b, routine: str):
    """GMRES-IR drivers take one RHS like the reference — enforced up front, for
    every dtype, so the contract doesn't depend on whether a lower precision
    exists."""
    if b.ndim != 1 and b.shape[-1] != 1:
        raise SlateError(f"{routine} supports a single RHS (matches reference)")


def _gmres_ir(matvec, precond, b, opts, routine: str):
    """Shared GMRES-IR body for gesv_mixed_gmres / posv_mixed_gmres: tolerance,
    restarted FGMRES, NaN-safe convergence verdict.
    Returns (x shaped like b, restarts, converged)."""
    squeeze = b.ndim == 1
    _require_single_rhs(b, routine)
    bv = b.reshape(-1) if not squeeze else b
    n = bv.shape[0]
    eps = jnp.finfo(jnp.real(bv).dtype).eps
    # tolerance stays traced: the whole GMRES-IR (restart loop included — it
    # is a lax.while_loop in _fgmres) dispatches with zero device→host round
    # trips; callers sync exactly once on the returned verdict
    tol = jnp.asarray(
        opts.tolerance if opts.tolerance is not None
        else float(eps) * (n ** 0.5),
        jnp.real(bv).dtype) * jnp.linalg.norm(bv)
    x, restarts = _fgmres(matvec, precond, bv, precond(bv), restart=min(30, n),
                          tol=tol, max_restarts=opts.max_iterations // 10 + 1)
    resid = jnp.linalg.norm(bv - matvec(x))
    converged = resid <= tol * 10        # NaN residual fails this, forcing fallback
    return (x if squeeze else x[:, None]), restarts, converged


@instrument
def gesv_mixed_gmres(A, B, opts=None):
    """GMRES-IR: FGMRES in working precision, right-preconditioned by the
    low-precision LU solve (src/gesv_mixed_gmres.cc). Single-RHS path like the
    reference (it restricts to nrhs == 1). Returns (X, perm, info, iters)."""
    from .chol import _lower_precision

    opts = Options.make(opts)
    a = as_array(A)
    b = as_array(B)
    _require_single_rhs(b, "gesv_mixed_gmres")
    lo = opts.factor_precision or _lower_precision(a.dtype)
    if lo is None:
        # solve_report stays off here: gesv would otherwise append a report
        # and break this 3-way unpack (gesv_mixed_gmres has no report form)
        X, perm, info = gesv(A, B, opts.replace(solve_report=False))
        return X, perm, info, jnp.int32(0)

    with trace_block("gesv_mixed_gmres", lo=str(lo)):
        plu, _, perm = lax.linalg.lu(a.astype(lo))
        info = _lu_info(jnp.diagonal(plu, axis1=-2, axis2=-1))

        def precond(r):
            z = lu_factored_solve(plu, perm, r.astype(lo)[:, None])
            return z[:, 0].astype(b.dtype)

        def matvec(x):
            return jnp.matmul(a, x, precision=lax.Precision.HIGHEST)

        x_out, restarts, converged = _gmres_ir(matvec, precond, b, opts,
                                               "gesv_mixed_gmres")

    if opts.use_fallback_solver and not converged:
        # mixed_gmres→full ladder (robust.LADDERS) — open-coded because the
        # GMRES machinery already returned its verdict; event keeps the
        # escalation visible in the chrome trace
        trace_event("fallback", routine="gesv_mixed_gmres", to="full")
        X, perm, info = gesv(A, B, opts.replace(solve_report=False))
        return X, perm, info, jnp.int32(-1)
    return write_back(B, x_out), perm, info, jnp.int32(restarts)


# ---------------------------------------------------------------------------
# random butterfly transform (RBT)
# ---------------------------------------------------------------------------


def rbt_generate(key, n, depth, dtype):
    """Generate the diagonals of a depth-d recursive butterfly transform
    (src/internal/internal_gerbt.cc rbt_generate; matgen random signs).

    Each level d has a diagonal of exp(r/10)-distributed entries like the classic
    RBT construction; returns [depth, n] array of diagonal values.
    """
    r = jax.random.uniform(key, (depth, n), minval=-0.5, maxval=0.5)
    return jnp.exp(r / 10.0).astype(dtype)


def _butterfly_apply(W, x, transpose=False):
    """Apply the depth-d butterfly U (or U^T) to the leading axis of x.

    One level on a vector v of length 2h: with diagonals (r1, r2):
        B v = [r1*v1 + r2*v2, r1*v1 - r2*v2] / sqrt(2)
    Levels nest recursively on halves (gerbt.cc applies tile-wise; here the
    recursion is expressed with reshapes so XLA fuses it into a few elementwise ops).
    """
    depth, n = W.shape
    levels = range(depth - 1, -1, -1) if transpose else range(depth)
    y = x
    for d in levels:
        nblk = 2 ** (depth - 1 - d)
        h = n // (2 * nblk)
        r = W[d] / jnp.sqrt(jnp.asarray(2.0, x.dtype))
        shape = (nblk, 2, h) + x.shape[1:]
        yv = y.reshape(shape)
        rv = r.reshape(nblk, 2, h)
        rv = rv.reshape(rv.shape + (1,) * (x.ndim - 1))
        if not transpose:
            a = rv[:, 0] * yv[:, 0]
            bpart = rv[:, 1] * yv[:, 1]
            top, bot = a + bpart, a - bpart
        else:
            # B^T w: v1 = r1*(w1 + w2), v2 = r2*(w1 - w2)
            top = rv[:, 0] * (yv[:, 0] + yv[:, 1])
            bot = rv[:, 1] * (yv[:, 0] - yv[:, 1])
        y = jnp.stack([top, bot], axis=1).reshape(x.shape)
    return y


def gerbt(Wu, Wv, A):
    """Two-sided butterfly transform A' = U^T A V (src/gerbt.cc)."""
    a = as_array(A)
    a1 = _butterfly_apply(Wu, a, transpose=True)
    a2 = _butterfly_apply(Wv, a1.T, transpose=True).T
    return write_back(A, a2)


@instrument
def gesv_rbt(A, B, opts=None, key=None):
    """Solve via random butterfly transform + nopiv LU + refinement
    (src/gesv_rbt.cc:94-172), run as the declared RBT→partial-pivot
    escalation ladder (robust.LADDERS["gesv_rbt"]): when the butterfly fails
    to tame the matrix (nopiv breakdown or IR stall) the pivoted solve takes
    over from the pristine operand.  Returns (X, info, iters); with
    ``Options(solve_report=True)``, (X, info, iters, SolveReport)."""
    opts = Options.make(opts)
    a0 = as_array(A)        # pristine snapshot: each rung re-enters the input
    #                         injection site (transient-fault contract; the
    #                         pivoted escalation really does take over from
    #                         intact data, as the docstring promises)
    b = as_array(B)
    grid = distribution_grid(A)
    if grid is not None:
        # construction-time grid: the sharded butterfly + nopiv-LU + IR path
        # (parallel/rbt.py), like every other driver's grid dispatch
        from ..parallel.rbt import gesv_rbt_distributed

        X, info, iters, via_rbt = gesv_rbt_distributed(
            inject("gesv_rbt", a0), b, grid, depth=opts.depth,
            nb=min(opts.block_size, a0.shape[-1]), key=key,
            max_iterations=opts.max_iterations,
            use_fallback=opts.use_fallback_solver, tol=opts.tolerance)
        X = write_back(B, X)
        if opts.solve_report:
            chain = ("rbt",) if via_rbt else ("rbt", "partialpiv")
            report = SolveReport(routine="gesv_rbt", info=int(info),
                                 iters=int(iters),
                                 precision_used=str(a0.dtype),
                                 fallback_chain=chain).finalize()
            report.recovered = report.info == 0 and (
                via_rbt or opts.use_fallback_solver)
            return X, info, iters, report
        return X, info, iters
    n = a0.shape[-1]
    depth = opts.depth
    # pad n to a multiple of 2^depth for the butterfly recursion
    pad = (-n) % (2 ** depth)
    key = key if key is not None else jax.random.PRNGKey(42)
    ku, kv = jax.random.split(key)
    np_ = n + pad
    plain = opts.replace(solve_report=False)
    report = SolveReport(routine="gesv_rbt") if opts.solve_report else None
    state = {"iters": jnp.int32(0)}

    def rbt_rung():
        a = inject("gesv_rbt", a0)
        Wu = rbt_generate(ku, np_, depth, a.dtype)
        Wv = rbt_generate(kv, np_, depth, a.dtype)
        ap = jnp.pad(a, ((0, pad), (0, pad)))
        if pad:
            ap = ap.at[jnp.arange(n, np_), jnp.arange(n, np_)].set(1)
        with trace_block("gesv_rbt", n=n, depth=depth):
            at = _butterfly_apply(Wu, ap, transpose=True)
            at = _butterfly_apply(Wv, at.T, transpose=True).T
            lu_p, info = getrf_nopiv(at, plain)
            lu_p = inject("gesv_rbt", lu_p, point="factor")

            def solve_rbt(rhs):
                rp = jnp.pad(rhs, ((0, pad),) + ((0, 0),) * (rhs.ndim - 1))
                y = _butterfly_apply(Wu, rp, transpose=True)
                z = lax.linalg.triangular_solve(lu_p, y, left_side=True,
                                                lower=True, unit_diagonal=True)
                w = lax.linalg.triangular_solve(lu_p, z, left_side=True,
                                                lower=False)
                x = _butterfly_apply(Wv, w, transpose=False)
                return x[:n]

            x, iters, converged = _ir_solve(a, b, solve_rbt, opts)
        state["iters"] = iters
        return (x, info), bool(converged)

    def pp_rung():
        a_in = inject("gesv_rbt", a0)
        # no fault fired → original wrapper through, preserving its in-place
        # factor write-back (the rbt rung factors a transformed copy only)
        src = A if (a_in is a0 and isinstance(A, BaseMatrix)) else a_in
        X, _, info = gesv(src, b, plain)
        return (as_array(X), info), bool(info == 0)

    rungs = [Rung("rbt", rbt_rung)]
    if opts.use_fallback_solver:
        rungs.append(Rung("partialpiv", pp_rung))
    x, info = run_ladder("gesv_rbt", rungs,
                         RetryPolicy.from_options(opts, "gesv_rbt"), report)
    X = write_back(B, x)
    if report is not None:
        report.info = int(info)
        report.iters = int(state["iters"])
        report.precision_used = str(a0.dtype)
        return X, info, state["iters"], report.finalize()
    return X, info, state["iters"]
