"""Distributed eigenvalue / SVD / norm drivers over the process grid.

Reference analogues: ``src/heev.cc:68-225`` (the longest distributed pipeline:
scale -> he2hb on the grid -> he2hbGather to rank 0 -> hb2st on rank 0 ->
sterf/steqr/stedc -> redistribute -> back-transforms), ``src/svd.cc:99-141``
(same shape via ge2tb/tb2bd/bdsqr), and the ``internal::norm`` reductions the
``norm`` driver runs over distributed tiles.

TPU re-design:

* **Stage 1 is where the flops are** (O(n^2 nb) gemms per panel, O(n^3)
  total) — it runs as an *explicit shard_map* pipeline (round-3 rewrite:
  the round-2 GSPMD form compiled sharded but replicated the loop state —
  see ``_he2hb_shard_fn``): 1-D block rows, one panel all-gather + one
  W-psum per step, the reference's listBcast collapsed into the mesh
  collectives (SURVEY.md §5.8 mapping).
* **Stage 2 is sequential by nature** (bulge chasing) and cheap (O(n^2 kd));
  the band is *replicated* across the mesh — the exact analogue of
  ``he2hbGather`` pulling the band to rank 0 (heev.cc:133-135) — and chased
  locally, like the reference runs hb2st on rank 0 only (heev.cc:137-160).
* **Back-transforms are gemms** and run sharded again (the reference
  redistributes Z to 1-D for unmtr_hb2st then back, heev.cc:193-205; here the
  resharding is one device_put).
* Norms are one jitted masked reduction with sharded input — XLA lowers the
  reduction to per-shard partials + a psum, which is ``internal::norm``'s
  partial-tile reduction + MPI allreduce.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from ..obs import instrument


@lru_cache(maxsize=32)
def _constrain_fn(mesh, row_shard: bool, col_shard: bool):
    spec = NamedSharding(mesh, P(ROW_AXIS if row_shard else None,
                                 COL_AXIS if col_shard else None))
    return jax.jit(lambda a: lax.with_sharding_constraint(a, spec))


def _shard(x, grid: ProcessGrid, row: bool = True, col: bool = True):
    """Place x block-sharded on the grid via a sharding constraint —
    unlike device_put this tolerates non-divisible shapes (GSPMD pads)."""
    return _constrain_fn(grid.mesh, row, col)(x)


AX = (ROW_AXIS, COL_AXIS)                  # flattened device axis


@lru_cache(maxsize=32)
def _he2hb_shard_fn(mesh, npad: int, nb: int, dtype_str: str):
    """Explicit shard_map he2hb over the flattened mesh (src/he2hb.cc, 729
    LoC of grid QR panels + ttqrt trees + two-sided updates).

    Round-2 review finding: the old GSPMD form (`with_sharding_constraint` +
    jit around the sequential fori_loop) compiled with sharded operands but
    ran 7x *slower* on a 2x4 mesh than one device — the partitioner inserted
    per-panel resharding instead of the algorithm's natural collectives.
    This version owns the layout: 1-D block rows (columns local), the panel
    gathered once per step (O(n·nb) bytes), the replicated O(n·nb²) panel QR
    recomputed on every device (far cheaper than shipping factors), and the
    two-sided O(n²·nb) block-reflector gemms fully local except ONE psum for
    W = V^H A.  Two collectives per panel, total O(n²) bytes.
    """
    from ..linalg import householder as hh

    nprocs = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    mr = npad // nprocs
    nt = npad // nb
    nj = max(nt - 1, 0)
    prec = lax.Precision.HIGHEST

    def local_fn(A_loc):                   # (mr, npad)
        ri = lax.axis_index(AX)
        r0 = (ri * mr).astype(jnp.int32)
        grow = r0 + jnp.arange(mr, dtype=jnp.int32)
        gcol = jnp.arange(npad, dtype=jnp.int32)

        def body(j, carry):
            A_loc, Vs_loc, Ts = carry
            k0 = (j * nb).astype(jnp.int32) if hasattr(j, "astype") else j * nb
            off = k0 + nb
            P_loc = lax.dynamic_slice(A_loc, (jnp.int32(0), k0), (mr, nb))
            P_full = lax.all_gather(P_loc, AX).reshape(npad, nb)
            _, V, taus = hh.panel_qr_masked(P_full, off, nb)
            T = hh.build_T(V, taus)
            V_loc = lax.dynamic_slice(V, (r0, jnp.int32(0)), (mr, nb))
            # left apply Q^H A: W = V^H A rides the mesh's one psum
            W = lax.psum(jnp.matmul(jnp.conj(V_loc).T, A_loc, precision=prec),
                         AX)                                     # (nb, npad)
            A_loc = A_loc - jnp.matmul(
                V_loc, jnp.matmul(jnp.conj(T).T, W, precision=prec),
                precision=prec)
            # right apply (Q^H A) Q: V replicated => fully local gemms
            Y = jnp.matmul(A_loc, V, precision=prec)             # (mr, nb)
            A_loc = A_loc - jnp.matmul(jnp.matmul(Y, T, precision=prec),
                                       jnp.conj(V).T, precision=prec)
            Vs_loc = lax.dynamic_update_slice(Vs_loc, V_loc[None], (j, 0, 0))
            Ts = lax.dynamic_update_slice(Ts, T[None], (j, 0, 0))
            return A_loc, Vs_loc, Ts

        Vs0 = jnp.zeros((max(nj, 1), mr, nb), A_loc.dtype)
        Ts0 = jnp.zeros((max(nj, 1), nb, nb), A_loc.dtype)
        A_loc, Vs_loc, Ts = lax.fori_loop(0, nj, body, (A_loc, Vs0, Ts0))
        band_loc = jnp.where(
            jnp.abs(grow[:, None] - gcol[None, :]) <= nb, A_loc,
            jnp.zeros_like(A_loc))
        return band_loc, Vs_loc, Ts

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=P(AX, None),
                       out_specs=(P(AX, None), P(None, AX, None), P(None)),
                       check_vma=False)
    return jax.jit(fn)


@lru_cache(maxsize=32)
def _unmtr_he2hb_shard_fn(mesh, npad: int, ncols: int, nb: int, nj: int,
                          descending: bool, conj_q: bool, dtype_str: str):
    """Left-side stage-1 back-transform on the sharded reflector stack
    (src/unmtr_he2hb.cc): per block one psum for W = V^H C, the rest local."""
    nprocs = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    mr = npad // nprocs
    prec = lax.Precision.HIGHEST

    def local_fn(Vs_loc, Ts, C_loc):       # (nj, mr, nb), (nj, nb, nb), (mr, ncols)
        def body(jj, C_loc):
            j = nj - 1 - jj if descending else jj
            V_loc = lax.dynamic_index_in_dim(Vs_loc, j, 0, keepdims=False)
            T = lax.dynamic_index_in_dim(Ts, j, 0, keepdims=False)
            Tm = jnp.conj(T).T if conj_q else T
            W = lax.psum(jnp.matmul(jnp.conj(V_loc).T, C_loc, precision=prec),
                         AX)
            return C_loc - jnp.matmul(V_loc,
                                      jnp.matmul(Tm, W, precision=prec),
                                      precision=prec)

        return lax.fori_loop(0, nj, body, C_loc)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None, AX, None), P(None), P(AX, None)),
                       out_specs=P(AX, None), check_vma=False)
    return jax.jit(fn)


@instrument
def he2hb_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 64):
    """Distributed stage-1 band reduction A = Q band Q^H over the flattened
    mesh.  Returns ``(band, Vs, Ts)``: band (n, n) bandwidth-nb, Vs sharded
    (nj, n, nb) reflector rows, Ts (nj, nb, nb) replicated."""
    from .distribute import ceil_mult

    n = A.shape[-1]
    nprocs = grid.p * grid.q
    npad = ceil_mult(n, nb * nprocs)
    if npad > n:
        Ap = jnp.zeros((npad, npad), A.dtype)
        Ap = Ap.at[:n, :n].set(A)
        idx = jnp.arange(n, npad)
        Ap = Ap.at[idx, idx].set(1)
    else:
        Ap = A
    Ap = jax.device_put(Ap, NamedSharding(grid.mesh, P(AX, None)))
    band, Vs, Ts = _he2hb_shard_fn(grid.mesh, npad, nb, str(Ap.dtype))(Ap)
    return band[:n, :n], Vs, Ts


@instrument
def unmtr_he2hb_distributed(Vs: jax.Array, Ts: jax.Array, C: jax.Array,
                            grid: ProcessGrid, conj_q: bool = False):
    """Apply the stage-1 Q (NoTrans, left) from the sharded reflector stack to
    a row-sharded C: Q C = H_0 ... H_{nj-1} C applied descending (conj_q
    flips to ascending Q^H C)."""
    nj, npad, nb = Vs.shape
    n, ncols = C.shape[-2:]
    if npad > n:
        Cp = jnp.zeros((npad, ncols), C.dtype).at[:n].set(C)
    else:
        Cp = C
    Cp = jax.device_put(Cp, NamedSharding(grid.mesh, P(AX, None)))
    out = _unmtr_he2hb_shard_fn(grid.mesh, npad, ncols, nb, nj,
                                not conj_q, conj_q, str(Cp.dtype))(Vs, Ts, Cp)
    return out[:n]


def _twostage_stage12(A, grid: ProcessGrid, nb: int,
                      chase_pipeline: bool, chase_distributed: bool,
                      want_tape: bool):
    """Shared two-stage prologue for the distributed eig drivers: nb clamps,
    safe scaling, sharded stage 1, band replication, and the chase (with the
    segment-parallel eligibility floor applied in ONE place so the full and
    subset drivers cannot diverge).

    Returns the 8-tuple ``(d, e_c, Vcs, tcs, Vs1, Ts1, factor, nb_eff)`` —
    ``nb_eff`` is the clamped bandwidth the caller must reuse for the
    back-transforms.  With ``want_tape=False`` the reflector tape entries
    (``Vcs``, ``tcs``) are None and ``e_c`` is already the real ``|e|``."""
    from ..linalg.eig import _safe_scale, hb2st, hb2st_reflectors

    n = A.shape[-1]
    nb = max(2, min(nb, max(2, n // 2)))
    # clamp against the nb·nprocs padding granularity: pad stays ≤ ~n/4, so
    # the O(n²·nb) stage-1 gemms never run on a matrix 2× the real linear
    # size for unaligned n (the chase below uses the same clamped kd)
    nprocs = grid.p * grid.q
    if n >= 8 * nprocs:
        nb = max(2, min(nb, -(-n // (4 * nprocs))))
    a, factor = _safe_scale(A)
    # stage 1 on the mesh: explicit shard_map panel pipeline (he2hb.cc)
    band, Vs1, Ts1 = he2hb_distributed(a, grid, nb=nb)
    # he2hbGather analogue: replicate the (cheap) band for the local chase
    band = jax.device_put(band, grid.replicated())
    nband = band.shape[-1]
    use_dist_chase = (chase_distributed and nb >= 2 and nband > 2
                      and -(-nband // nprocs) >= 2 * nb + 2)
    if use_dist_chase:
        from .chase_dist import hb2st_chase_distributed

        d, e_c, Vcs, tcs = hb2st_chase_distributed(band, nb, grid,
                                                   want_vectors=want_tape)
    elif want_tape:
        d, e_c, Vcs, tcs = hb2st_reflectors(band, kd=nb,
                                            pipeline=chase_pipeline)
    else:
        # hb2st already returns the real |e|; jnp.abs below is a no-op
        d, e_c = hb2st(band, kd=nb, want_vectors=False,
                       pipeline=chase_pipeline)
        Vcs = tcs = None
    # single exit: the tape-less form drops the reflectors and realizes |e|
    if not want_tape:
        return d, jnp.abs(e_c), None, None, Vs1, Ts1, factor, nb
    return d, e_c, Vcs, tcs, Vs1, Ts1, factor, nb


@instrument
def heev_range_distributed(A: jax.Array, grid: ProcessGrid, il: int, iu: int,
                           nb: int = 64, want_vectors: bool = True,
                           chase_pipeline: bool = False,
                           chase_distributed: bool = False):
    """Distributed subset eigensolve: the k = iu-il eigenpairs with ascending
    indices [il, iu) over the mesh (no reference analogue at any scale).

    Stage 1 (the O(n²·nb) flops) runs sharded (he2hb_distributed); the
    chase runs replicated or segment-parallel per ``chase_distributed``;
    the subset tridiagonal work is O(n·k) bisection + stein; the chase
    back-transform applies Q2 to the THIN (n, k) block via the reverse
    sweep accumulation (replicated — O(n²·k/b) total, small next to stage
    1); and the stage-1 back-transform rides the mesh
    (unmtr_he2hb_distributed on k columns, one psum per block).
    Returns (lam (k,), Z (n, k) row-sharded or None).
    """
    from ..core.exceptions import slate_assert
    from ..linalg.eig import _phase_vector
    from ..linalg.householder import sweep_accumulate
    from ..linalg.sturm import stein, sterf_bisect

    n = A.shape[-1]
    slate_assert(0 <= il < iu <= n,
                 f"index range [{il}, {iu}) invalid for n={n}")
    if n < 8:
        lam, z = jnp.linalg.eigh(A)
        return (lam[il:iu], z[:, il:iu]) if want_vectors \
            else (lam[il:iu], None)
    if not want_vectors:
        d, e, _, _, _, _, factor, _ = _twostage_stage12(
            A, grid, nb, chase_pipeline, chase_distributed, want_tape=False)
        lam = sterf_bisect(d, e, il=il, iu=iu)
        return lam * factor, None
    d, e_c, Vcs, tcs, Vs1, Ts1, factor, nb_eff = _twostage_stage12(
        A, grid, nb, chase_pipeline, chase_distributed, want_tape=True)
    e = jnp.abs(e_c)
    lam = sterf_bisect(d, e, il=il, iu=iu)
    dt = Vcs.dtype
    Zt = stein(d, e, lam).astype(dt)
    ph = _phase_vector(e_c.astype(dt))
    X = ph[:, None] * Zt
    nband = d.shape[0]
    z = jnp.conj(sweep_accumulate(Vcs, tcs, nband, nb_eff,
                                  Q0=jnp.conj(X).T, reverse=True)).T
    z = unmtr_he2hb_distributed(Vs1, Ts1, z[:n], grid, conj_q=False)
    return lam * factor, z


@lru_cache(maxsize=32)
def _ge2tb_shard_fn(mesh, mpad: int, npc: int, nreal: int, nb: int,
                    dtype_str: str):
    """Explicit shard_map ge2tb band reduction (src/ge2tb.cc): alternating
    QR column panels (left apply — one all-gather + one psum, like
    ``_he2hb_shard_fn``) and LQ row panels, whose right applies are FULLY
    local in the 1-D row layout (columns resident; only the nb-row panel
    extraction psums).  Three O(n·nb)-byte collectives per panel."""
    from ..linalg import householder as hh

    nprocs = mesh.shape[ROW_AXIS] * mesh.shape[COL_AXIS]
    mr = mpad // nprocs
    ncv = npc // nprocs                    # Vv rows live sharded too
    nt = max(-(-nreal // nb), 1)
    prec = lax.Precision.HIGHEST

    def local_fn(A_loc):                   # (mr, npc)
        ri = lax.axis_index(AX)
        r0 = (ri * mr).astype(jnp.int32)
        grow = r0 + jnp.arange(mr, dtype=jnp.int32)

        def body(j, carry):
            A_loc, Vu_loc, Tu, Vv, Tv = carry
            k0 = (j * nb).astype(jnp.int32) if hasattr(j, "astype") else j * nb
            # --- QR column panel (pivots on the diagonal)
            P_loc = lax.dynamic_slice(A_loc, (jnp.int32(0), k0), (mr, nb))
            P_full = lax.all_gather(P_loc, AX).reshape(mpad, nb)
            _, V, taus = hh.panel_qr_masked(P_full, k0, nb)
            T = hh.build_T(V, taus)
            V_loc = lax.dynamic_slice(V, (r0, jnp.int32(0)), (mr, nb))
            W = lax.psum(jnp.matmul(jnp.conj(V_loc).T, A_loc, precision=prec),
                         AX)                                     # (nb, npc)
            A_loc = A_loc - jnp.matmul(
                V_loc, jnp.matmul(jnp.conj(T).T, W, precision=prec),
                precision=prec)
            Vu_loc = lax.dynamic_update_slice(Vu_loc, V_loc[None], (j, 0, 0))
            Tu = lax.dynamic_update_slice(Tu, T[None], (j, 0, 0))
            # --- LQ row panel (pivots one block right): extract nb rows
            S = k0 + jnp.arange(nb, dtype=jnp.int32)
            loc = S - r0
            own = (loc >= 0) & (loc < mr)
            Prow = A_loc[jnp.clip(loc, 0, mr - 1)]
            Prow = jnp.where(own[:, None], Prow, jnp.zeros_like(Prow))
            Prow = lax.psum(Prow, AX)                            # (nb, npc)
            _, Vr, tausr = hh.panel_lq_masked(Prow, k0 + nb, nb)
            Tr = hh.build_T(Vr, tausr)
            # right apply: columns are local => zero collectives
            Y = jnp.matmul(A_loc, Vr, precision=prec)            # (mr, nb)
            A_loc = A_loc - jnp.matmul(jnp.matmul(Y, Tr, precision=prec),
                                       jnp.conj(Vr).T, precision=prec)
            Vr_loc = lax.dynamic_slice(Vr, ((ri * ncv).astype(jnp.int32),
                                            jnp.int32(0)), (ncv, nb))
            Vv = lax.dynamic_update_slice(Vv, Vr_loc[None], (j, 0, 0))
            Tv = lax.dynamic_update_slice(Tv, Tr[None], (j, 0, 0))
            return A_loc, Vu_loc, Tu, Vv, Tv

        Vu0 = jnp.zeros((nt, mr, nb), A_loc.dtype)
        Tu0 = jnp.zeros((nt, nb, nb), A_loc.dtype)
        Vv0 = jnp.zeros((nt, ncv, nb), A_loc.dtype)
        Tv0 = jnp.zeros((nt, nb, nb), A_loc.dtype)
        A_loc, Vu_loc, Tu, Vv, Tv = lax.fori_loop(
            0, nt, body, (A_loc, Vu0, Tu0, Vv0, Tv0))
        gcol = jnp.arange(npc, dtype=jnp.int32)
        band_loc = jnp.where(
            (gcol[None, :] >= grow[:, None])
            & (gcol[None, :] - grow[:, None] <= nb), A_loc,
            jnp.zeros_like(A_loc))
        return band_loc, Vu_loc, Tu, Vv, Tv

    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=P(AX, None),
        out_specs=(P(AX, None), P(None, AX, None), P(None),
                   P(None, AX, None), P(None)),
        check_vma=False)
    return jax.jit(fn)


def _apply_stacked_left(Vs: jax.Array, Ts: jax.Array, C: jax.Array,
                        grid: ProcessGrid, conj_q: bool = False):
    """Left-apply a stacked block-reflector factor through the sharded unmtr
    sweep regardless of how Vs arrived (sharded from he2hb/ge2tb, or
    replicated like the right-side Vv): rows pad to a mesh-divisible count
    (zero reflector rows act as identity) and reshard in one device_put."""
    from .distribute import ceil_mult

    nj, nv, nb = Vs.shape
    nprocs = grid.p * grid.q
    nvp = ceil_mult(nv, nprocs)
    if nvp > nv:
        Vs = jnp.concatenate(
            [Vs, jnp.zeros((nj, nvp - nv, nb), Vs.dtype)], axis=1)
    Vs = jax.device_put(Vs, NamedSharding(grid.mesh, P(None, AX, None)))
    return unmtr_he2hb_distributed(Vs, Ts, C, grid, conj_q=conj_q)


@instrument
def ge2tb_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 64):
    """Distributed stage-1 general->band reduction A = U band V^H over the
    flattened mesh.  Returns ``(band, (Vu, Tu), (Vv, Tv))``: band (m, n)
    upper-bandwidth nb, Vu sharded reflector rows, Vv replicated (applied
    from the right — columns are local in this layout)."""
    from ..core.exceptions import slate_assert
    from .distribute import ceil_mult

    m, n = A.shape[-2:]
    slate_assert(m >= n, "ge2tb_distributed requires m >= n")
    nprocs = grid.p * grid.q
    mpad = ceil_mult(m + nb, nb * nprocs)
    # pad so the last panel never clamps AND Vv rows shard evenly; reflector
    # entries on pad columns are exactly zero (the padded A columns are), so
    # keeping the full npc rows loses nothing and stays sharded
    npc = ceil_mult(n + nb, nprocs)
    Ap = jnp.zeros((mpad, npc), A.dtype).at[:m, :n].set(A)
    Ap = jax.device_put(Ap, NamedSharding(grid.mesh, P(AX, None)))
    band, Vu, Tu, Vv, Tv = _ge2tb_shard_fn(grid.mesh, mpad, npc, n, nb,
                                           str(Ap.dtype))(Ap)
    return band[:m, :n], (Vu, Tu), (Vv, Tv)


@instrument
def heev_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 64,
                     want_vectors: bool = True, method_eig: str = "dc",
                     chase_pipeline: bool = False,
                     chase_distributed: bool = False):
    """Distributed Hermitian eigensolve over the (p, q) mesh (src/heev.cc).

    Returns (ascending eigenvalues, Z or None); Z comes back sharded on the
    grid.  ``method_eig='dc'`` solves the tridiagonal with stedc.

    ``chase_distributed=True`` runs stage 2 segment-parallel over the mesh
    (parallel/chase_dist.py) instead of replicating the band chase on every
    device — past the reference, which confines hb2st to rank 0
    (heev.cc:137-160).  Requires n/P >= 2*nb+2 (falls back to the
    replicated chase below that floor).
    """
    from ..linalg.eig import sterf
    from ..linalg.stedc import stedc as _stedc

    n = A.shape[-1]
    if n < 8:
        # no meaningful band structure below one panel — local fused solve
        # (the single-device heev makes the same switch)
        lam, z = (jnp.linalg.eigh(A) if want_vectors
                  else (jnp.linalg.eigvalsh(A), None))
        return lam, z
    if not want_vectors:
        d, e, _, _, _, _, factor, _ = _twostage_stage12(
            A, grid, nb, chase_pipeline, chase_distributed, want_tape=False)
        # values-only always takes sterf — D&C inherently carries vectors
        # (merge z-couplings ARE eigenvector rows), exactly why the reference
        # routes no-vector solves to sterf too (heev.cc:208-215)
        lam = sterf(d, e)
        return lam * factor, None
    # vectors: the chase tape is the cheap O(n² kd) part and replays
    # replicated; the Q2 accumulation — 97% of the profiled vectors time —
    # shards over mesh rows with zero collectives (round-5; was replicated)
    d, e_c, Vcs, tcs, Vs, Ts, factor, nb = _twostage_stage12(
        A, grid, nb, chase_pipeline, chase_distributed, want_tape=True)
    e = jnp.abs(e_c)
    Q2 = hb2st_q_distributed(Vcs, tcs, e_c, d.shape[0], grid)
    if method_eig == "bisection":
        # bisection values + batched inverse-iteration vectors (the method
        # the reference leaves unimplemented, enums.hh:363); the vmapped
        # tridiagonal solves replay replicated — they are O(n²) like the
        # chase — and the back-transforms below ride the mesh
        from ..linalg.sturm import stein, sterf_bisect

        lam = sterf_bisect(d, e)
        Zt = stein(d, e, lam)
    elif method_eig == "dc":
        # distributed D&C: the merge basis-update gemms ride the mesh
        lam, Zt = _stedc(d, e, grid=grid)
    else:
        # MethodEig.QR: real QR iteration with the Z update sharded over
        # mesh rows (steqr.cc's 1-D redistribute + local-row rotations)
        lam, Zt = steqr_distributed(d, e, grid)
    # chase back-transform is the same O(n³) order as the merges — it rides
    # the mesh too rather than replicating on every device
    from .summa import gemm_padded

    Z = gemm_padded(Q2, Zt.astype(Q2.dtype), grid)
    # stage-1 back-transform on the sharded reflector stack (one psum per
    # block; unmtr_he2hb.cc)
    Z = unmtr_he2hb_distributed(Vs, Ts, Z, grid, conj_q=False)
    return lam * factor, Z


@instrument
def svd_range_distributed(A: jax.Array, grid: ProcessGrid, il: int, iu: int,
                          nb: int = 64, want_vectors: bool = True,
                          chase_pipeline: bool = False,
                          chase_distributed: bool = False):
    """Distributed top-k/subset SVD: the singular triplets with DESCENDING
    indices [il, iu) over the mesh (no reference analogue at any scale).

    Sharded ge2tb stage 1, tb2bd chase (replicated or segment-parallel),
    index-targeted GK bisection (only the 2j target indices of the ±σ
    spectrum), stein vectors, thin reverse-accumulated chase
    back-transforms, and thin mesh stage-1 back-transforms.  Returns
    (S (j,) descending, U (m, j) or None, VT (j, n) or None).
    """
    from ..core.exceptions import slate_assert
    from ..linalg.eig import _safe_scale
    from ..linalg.householder import sweep_accumulate
    from ..linalg.sturm import stein, sterf_bisect
    from ..linalg.svd import (_bidiag_phases, _gk_form, _gk_split,
                              _tb2bd_run_chase, tb2bd_reflectors)

    m, n = A.shape[-2:]
    if m < n:
        S, V, UT = svd_range_distributed(jnp.conj(A).T, grid, il, iu, nb=nb,
                                         want_vectors=want_vectors,
                                         chase_pipeline=chase_pipeline,
                                         chase_distributed=chase_distributed)
        if not want_vectors:
            return S, None, None
        return S, jnp.conj(UT).T, jnp.conj(V).T
    k = n
    slate_assert(0 <= il < iu <= k,
                 f"index range [{il}, {iu}) invalid for min(m,n)={k}")
    j = iu - il
    if k < 8:
        if want_vectors:
            out = jnp.linalg.svd(A, full_matrices=False)
            return out[1][il:iu], out[0][:, il:iu], out[2][il:iu, :]
        return jnp.linalg.svd(A, compute_uv=False)[il:iu], None, None
    nb = max(2, min(nb, max(2, k - 1)))
    nprocs = grid.p * grid.q
    if k >= 8 * nprocs:
        nb = max(2, min(nb, -(-k // (4 * nprocs))))
    a, factor = _safe_scale(A)
    band, Uf, Vf = ge2tb_distributed(a, grid, nb=nb)
    band = jax.device_put(band, grid.replicated())
    sq = band[:k, :k]
    use_dist_chase = (chase_distributed and nb >= 2 and k > 2
                      and -(-k // nprocs) >= 2 * nb + 2)
    if want_vectors:
        if use_dist_chase:
            from .chase_dist import tb2bd_chase_distributed

            d_c, e_c, Us, tauus, Vcs, tauvs = tb2bd_chase_distributed(
                sq, nb, grid, want_vectors=True)
        else:
            d_c, e_c, Us, tauus, Vcs, tauvs = tb2bd_reflectors(
                sq, nb, pipeline=chase_pipeline)
    else:
        if use_dist_chase:
            from .chase_dist import tb2bd_chase_distributed

            d_c, e_c, *_ = tb2bd_chase_distributed(sq, nb, grid,
                                                   want_vectors=False)
        else:
            d_c, e_c, *_ = _tb2bd_run_chase(sq, nb, chase_pipeline)
    d, e = jnp.abs(d_c), jnp.abs(e_c)
    zero_d, tgk_off = _gk_form(d, e)
    lam_desc = sterf_bisect(zero_d, tgk_off,
                            il=2 * k - iu, iu=2 * k - il)[::-1]
    sig = jnp.maximum(lam_desc, 0.0)
    if not want_vectors:
        return sig * factor, None, None
    Z = stein(zero_d, tgk_off, lam_desc)
    U2t, V2t = _gk_split(Z, sq.dtype)
    pu, pw = _bidiag_phases(d_c, e_c, sq.dtype)
    Xu = pu[:, None] * U2t
    Xv = pw[:, None] * V2t
    Uu = jnp.conj(sweep_accumulate(Us, tauus, k, nb,
                                   Q0=jnp.conj(Xu).T, reverse=True)).T
    Vv = jnp.conj(sweep_accumulate(Vcs, tauvs, k, nb,
                                   Q0=jnp.conj(Xv).T, reverse=True)).T
    U = jnp.zeros((m, j), sq.dtype).at[:k, :].set(Uu)
    U = _apply_stacked_left(Uf[0], Uf[1], U, grid)
    Vfull = jnp.zeros((n, j), sq.dtype).at[:k, :].set(Vv)
    Vfull = _apply_stacked_left(Vf[0], Vf[1], Vfull, grid)
    return sig * factor, U, jnp.conj(Vfull).T


@lru_cache(maxsize=16)
def _hb2st_q_shard_fn(mesh, n: int, npad: int):
    """Row-sharded chase-vectors accumulation (the ~97%-of-time phase of the
    distributed two-stage vectors path, PERF_CPU.md): the reflector tape
    (Vs, taus) is replicated — it is the cheap O(n²) part — and each device
    accumulates its own row block of Q2 via ``sweep_accumulate(Q0=rows)``,
    building its identity block locally from iota (no host-side O(n²) eye
    is ever materialized).  Every update is a column operation, so the
    module contains ZERO collectives; the reference reaches the same shape
    by redistributing Z to 1-D rows for unmtr_hb2st (heev.cc:193-205)."""
    from ..linalg.householder import sweep_accumulate

    nproc = mesh.size
    rl = npad // nproc

    def local_fn(Vs, taus, phase):
        row0 = lax.axis_index(AX).astype(jnp.int32) * rl
        rows = row0 + lax.broadcasted_iota(jnp.int32, (rl, n), 0)
        cols = lax.broadcasted_iota(jnp.int32, (rl, n), 1)
        q0 = (rows == cols).astype(Vs.dtype)
        q = sweep_accumulate(Vs, taus, n, Vs.shape[-1], Q0=q0)
        return q * phase[None, :]

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None), P(None), P(None)),
                       out_specs=P(AX, None), check_vma=False)
    return jax.jit(fn)


def _sweep_q_distributed(Vs, taus, phase, n: int, grid: ProcessGrid):
    """Row-sharded sweep accumulation with a column-phase postmultiply —
    shared by the hb2st Q2 and the tb2bd U2/V2 builds."""
    nproc = grid.p * grid.q
    npad = -(-n // nproc) * nproc
    Q = _hb2st_q_shard_fn(grid.mesh, n, npad)(Vs, taus,
                                              phase.astype(Vs.dtype))
    return Q[:n]


@instrument
def hb2st_q_distributed(Vs, taus, e_c, n: int, grid: ProcessGrid):
    """Q2 of the hb2st chase, rows sharded on the flattened mesh."""
    from ..linalg.eig import _phase_vector

    return _sweep_q_distributed(Vs, taus, _phase_vector(e_c.astype(Vs.dtype)),
                                n, grid)


@lru_cache(maxsize=16)
def _steqr_shard_fn(mesh):
    """Row-sharded tridiagonal QR iteration (src/steqr.cc:52-82).

    The reference redistributes Z into a 1-D row layout, every rank runs the
    identical host QR iteration on the replicated (D, E) scalars, and each
    rank applies the plane rotations to its local rows only.  Here: the
    (d, e) while_loop replays identically on every device inside shard_map
    (deterministic, so every shard sees the same rotation chain) and each
    device absorbs each sweep into its (npad/nproc, n) row block with a
    local MXU gemm.  The compiled module contains ZERO collectives — row
    parallelism is the whole story, exactly the reference's design point.
    """
    from ..linalg.steqr_qr import steqr_qr

    def local_fn(d, e, z_loc):
        return steqr_qr(d, e, z_loc)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None), P(None), P(AX, None)),
                       out_specs=(P(None), P(AX, None)), check_vma=False)
    return jax.jit(fn)


@instrument
def steqr_distributed(d, e, grid: ProcessGrid, Z=None):
    """Distributed steqr: eigenvalues replicated, eigenvector matrix returned
    row-sharded on the flattened mesh.  ``Z`` (optional) is the matrix to
    accumulate into (defaults to identity, yielding Q itself)."""
    d = jnp.asarray(d)
    n = d.shape[0]
    nproc = grid.p * grid.q
    Z0 = jnp.eye(n, dtype=d.dtype) if Z is None else jnp.asarray(Z)
    m = Z0.shape[0]
    npad = -(-m // nproc) * nproc
    if npad != m:
        Z0 = jnp.pad(Z0, ((0, npad - m), (0, 0)))
    lam, Zo = _steqr_shard_fn(grid.mesh)(d, jnp.asarray(e), Z0)
    return lam, Zo[:m]


@instrument
def hegv_distributed(itype: int, A: jax.Array, B: jax.Array,
                     grid: ProcessGrid, nb: int = 64,
                     want_vectors: bool = True):
    """Distributed generalized Hermitian eigensolve A x = lambda B x
    (src/hegv.cc over the mesh): sharded potrf(B) -> hegst transform (sharded
    triangular solves / gemms) -> heev_distributed -> sharded back-transform.

    Returns (ascending eigenvalues, X or None).
    """
    from ..core.exceptions import SlateError
    from ..linalg.eig import hegst
    from .solvers import potrf_distributed, trsm_distributed

    L = potrf_distributed(B, grid, nb=max(nb, 32))
    # SPD verdict stays traced until the END: the whole pipeline (transform,
    # eigensolve, back-transform — all bounded loops, NaN-safe) dispatches
    # with a single host sync, instead of blocking on L's diagonal up front
    spd_ok = jnp.all(jnp.isfinite(jnp.diagonal(L)))
    C = hegst(itype, _shard(A, grid), L)
    lam, Z = heev_distributed(C, grid, nb=nb, want_vectors=want_vectors)
    if want_vectors:
        if itype in (1, 2):
            X = trsm_distributed(L, Z, grid, lower=True, conj_trans=True)
        else:
            X = jnp.matmul(jnp.tril(L), Z, precision=lax.Precision.HIGHEST)
    else:
        X = None
    if not bool(spd_ok):                  # the solve's single host sync
        raise SlateError("hegv_distributed: B not positive definite")
    return lam, X


@instrument
def svd_distributed(A: jax.Array, grid: ProcessGrid, nb: int = 64,
                    want_vectors: bool = True, chase_pipeline: bool = False,
                    method_svd: str = "auto",
                    chase_distributed: bool = False):
    """Distributed SVD over the (p, q) mesh (src/svd.cc pipeline).

    Returns (S descending, U or None, VT or None); U/VT come back sharded.
    Wide inputs run on the conjugate transpose (U/VT swap), like the
    reference's LQ pre-step (svd.cc:224+).  ``method_svd='bisection'``
    solves the bidiagonal stage by GK bisection (+ stein vectors).
    ``chase_distributed=True`` runs the tb2bd stage segment-parallel over
    the mesh (parallel/chase_dist.py) instead of replicating it; requires
    n/P >= 2*nb+2 (falls back to the replicated chase below that floor).
    """
    from ..linalg.eig import _safe_scale
    from ..linalg.svd import _bidiag_phases, bdsqr, tb2bd

    m, n = A.shape[-2:]
    if min(m, n) < 8:
        out = jnp.linalg.svd(A, full_matrices=False) if want_vectors else \
            (jnp.linalg.svd(A, compute_uv=False), None, None)
        if want_vectors:
            U, S, VT = out[0], out[1], out[2]
            return S, U, VT
        return out[0], None, None
    if m < n:
        S, V, UT = svd_distributed(jnp.conj(A).T, grid, nb=nb,
                                   want_vectors=want_vectors,
                                   chase_pipeline=chase_pipeline,
                                   method_svd=method_svd,
                                   chase_distributed=chase_distributed)
        if not want_vectors:
            return S, None, None
        return S, jnp.conj(UT).T, jnp.conj(V).T
    if m >= 2 * n:
        # tall pre-step (svd.cc:224+): QR first — the reference QRs very tall
        # inputs so the bidiagonalization runs on the square R.  With vectors,
        # the 2-D CAQR tree over the mesh supplies Q, R and U = Q @ U_R is one
        # sharded gemm; values-only skips Q entirely (singular values of R ==
        # singular values of A for any QR), taking R from the sharded
        # CholeskyQR2 Gram tree.
        if not want_vectors:
            # Householder-quality R from the 1-D TSQR tree (no Gram squaring,
            # no 2-D CAQR Q accumulation)
            from .qr_dist import tsqr_distributed

            _, R = tsqr_distributed(A, grid)
            S, _, _ = svd_distributed(R[:n, :n], grid, nb=nb,
                                      want_vectors=False,
                                      chase_pipeline=chase_pipeline,
                                      method_svd=method_svd,
                                      chase_distributed=chase_distributed)
            return S, None, None
        from .qr_dist import geqrf_distributed

        Q, R = geqrf_distributed(A, grid, nb=max(nb, 32))
        S, UR, VT = svd_distributed(R[:n, :n], grid, nb=nb,
                                    want_vectors=True,
                                    chase_pipeline=chase_pipeline,
                                    method_svd=method_svd,
                                    chase_distributed=chase_distributed)
        U = jnp.matmul(Q[:, :n], UR, precision=lax.Precision.HIGHEST)
        return S, _shard(U, grid), VT
    k = n
    nb = max(2, min(nb, max(2, k - 1)))
    # same padding-granularity clamp as heev_distributed
    nprocs = grid.p * grid.q
    if k >= 8 * nprocs:
        nb = max(2, min(nb, -(-k // (4 * nprocs))))
    a, factor = _safe_scale(A)
    # stage 1 on the mesh: explicit shard_map panel pipeline (ge2tb.cc)
    band, Uf, Vf = ge2tb_distributed(a, grid, nb=nb)
    band = jax.device_put(band, grid.replicated())
    sq = band[:k, :k]
    use_dist_chase = (chase_distributed and nb >= 2 and k > 2
                      and -(-k // (grid.p * grid.q)) >= 2 * nb + 2)
    if use_dist_chase:
        from .chase_dist import tb2bd_chase_distributed
    if k > 2 and want_vectors:
        # reflector-level chase (replicated, the cheap part) + BOTH vector
        # accumulations sharded over mesh rows with zero collectives
        # (round 5 — the same 97%-phase split as the heev chase)
        from ..linalg.svd import _bidiag_phases as _phases
        from ..linalg.svd import tb2bd_reflectors

        if use_dist_chase:
            d_c, e_c, Us, tauus, Vcs, tauvs = tb2bd_chase_distributed(
                sq, nb, grid, want_vectors=True)
        else:
            d_c, e_c, Us, tauus, Vcs, tauvs = tb2bd_reflectors(
                sq, nb, pipeline=chase_pipeline)
        pu, pw = _phases(d_c, e_c, a.dtype)
        d, e = jnp.abs(d_c), jnp.abs(e_c)
        U2 = _sweep_q_distributed(Us, tauus, pu, k, grid)
        V2 = _sweep_q_distributed(Vcs, tauvs, pw, k, grid)
        VT2 = jnp.conj(V2).T
    elif k > 2:
        if use_dist_chase:
            d_c, e_c, _, _, _, _ = tb2bd_chase_distributed(
                sq, nb, grid, want_vectors=False)
            d, e = jnp.abs(d_c), jnp.abs(e_c)
        else:
            out = tb2bd(sq, nb, want_vectors=False,
                        pipeline=chase_pipeline)
            d, e = out[0], out[1]
        U2, VT2 = None, None
    else:
        d_c = jnp.diagonal(sq)
        e_c = jnp.diagonal(sq, offset=1)
        pu, pw = _bidiag_phases(d_c, e_c, a.dtype)
        d, e = jnp.abs(d_c), jnp.abs(e_c)
        U2, VT2 = jnp.diag(pu), jnp.conj(jnp.diag(pw)).T
    bd_method = {"bisection": "bisect", "dc": "dense"}.get(method_svd, "auto")
    S, Ub, VTb = bdsqr(d, e, want_vectors=want_vectors, method=bd_method)
    if not want_vectors:
        return S * factor, None, None
    # U = Q_u [U2 Ub; 0],  VT = (VTb VT2) Q_v^H — sharded reflector sweeps
    # (one psum per block, _unmtr_he2hb_shard_fn)
    Uin = jnp.zeros((m, k), a.dtype).at[:k, :k].set(
        jnp.matmul(U2, Ub.astype(U2.dtype), precision=lax.Precision.HIGHEST))
    U = _apply_stacked_left(Uf[0], Uf[1], Uin, grid)
    Vin = jnp.conj(jnp.matmul(VTb.astype(VT2.dtype), VT2,
                              precision=lax.Precision.HIGHEST)).T
    Vfull = _apply_stacked_left(Vf[0], Vf[1], Vin, grid)
    return S * factor, U, jnp.conj(Vfull).T


@lru_cache(maxsize=64)
def _norm_dist_fn(mesh, kind: str, uplo: str, dtype_str: str):
    spec = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(a):
        x = lax.with_sharding_constraint(a, spec)
        if uplo == "lower":
            x = jnp.tril(x)
        elif uplo == "upper":
            x = jnp.triu(x)
        ax = jnp.abs(x)
        if kind == "max":
            return jnp.max(ax)
        if kind == "one":
            return jnp.max(jnp.sum(ax, axis=-2))
        if kind == "inf":
            return jnp.max(jnp.sum(ax, axis=-1))
        # fro
        return jnp.sqrt(jnp.sum(ax * ax))

    return jax.jit(fn)


@instrument
def norm_distributed(kind, A: jax.Array, grid: ProcessGrid,
                     uplo: str = "general"):
    """Distributed matrix norm (src/norm.cc over internal::genorm partials +
    MPI allreduce; here one sharded masked reduction — XLA emits the per-shard
    partials and the psum).  kind: max | one | inf | fro."""
    from ..core.types import Norm

    k = Norm.from_string(kind) if not isinstance(kind, Norm) else kind
    name = {Norm.Max: "max", Norm.One: "one", Norm.Inf: "inf",
            Norm.Fro: "fro"}[k]
    return _norm_dist_fn(grid.mesh, name, uplo, str(jnp.asarray(A).dtype))(A)


@lru_cache(maxsize=8)
def _col_norms_fn(mesh):
    spec = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))

    def fn(a):
        a = lax.with_sharding_constraint(a, spec)
        return jnp.max(jnp.abs(a), axis=-2)

    return jax.jit(fn)


@instrument
def col_norms_distributed(A: jax.Array, grid: ProcessGrid) -> jax.Array:
    """Distributed column max-norms (internal::colNorms analogue)."""
    return _col_norms_fn(grid.mesh)(A)
