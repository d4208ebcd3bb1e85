"""Software-pipelined (lookahead) distributed Cholesky over an explicit
shard_map — the reference's lookahead task pipeline in SPMD form.

This is a production path: ``potrf_distributed(..., lookahead >= 2)`` — and
through it the ``slate.potrf`` driver's ``Option::Lookahead`` — routes here
(round-2 review: "lookahead is a demo no production driver calls").

Reference analogue: ``src/potrf.cc:84-195`` — the OpenMP task DAG gives the
next panel column a *high-priority* update task so its factorization and
broadcast overlap the bulk trailing update (``potrf.cc:136-177`` lookahead
columns; SURVEY.md §2.6 "pipeline lookahead").

TPU re-design: there is no task runtime — the same overlap is expressed as a
*dependency structure*.  Each fori_loop step, in trace order:

1. **prioritized column update**: the owner of panel k+1 applies panel k to
   that one block column only (cheap);
2. **next-panel factor + broadcast**: the updated column is psum-broadcast
   (masked-contribution trick ≅ tileBcast, BaseMatrix.hh:1999) and factored
   redundantly on every device (replicated O(n·nb²) work — cheaper than a
   second broadcast);
3. **bulk trailing update**: all remaining local columns get the rank-nb
   gemm update from panel k.

Step 3 has no data dependency on step 2's collective, so XLA's latency-hiding
scheduler can run the ICI broadcast for panel k+1 *under* the trailing-update
gemm of panel k — the software-pipelined form of lookahead = 1.  The layout is
1-D block-cyclic over the flattened mesh (column j lives on device j mod d),
the distribution ScaLAPACK uses for exactly this reason: every step keeps all
devices busy in the trailing update.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

from .mesh import ProcessGrid
from ..linalg.chol import _chol_blocked
from ..obs import instrument

_AXIS = "d"


@lru_cache(maxsize=32)
def _potrf_pipelined_fn(mesh, n: int, nb: int, d: int, dtype_str: str):
    nt = n // nb
    nt_loc = nt // d

    def local_cols(me):
        """Global block-column index of each local slot: j(s) = s*d + me."""
        return jnp.arange(nt_loc) * d + me

    def factor_panel(col, k):
        """Factor global block column k from its updated full-height column:
        diag Cholesky + panel trsm, rows above the diagonal block zeroed
        (internal::potrf + internal::trsm, potrf.cc:96-119)."""
        rows = jnp.arange(n)
        start = k * nb
        D = lax.dynamic_slice(col, (start, 0), (nb, nb))
        Lkk = _chol_blocked(D)
        below = jnp.where((rows >= start + nb)[:, None], col, 0)
        panel = lax.linalg.triangular_solve(
            Lkk, below, left_side=False, lower=True,
            conjugate_a=True, transpose_a=True)
        panel = lax.dynamic_update_slice(panel, Lkk, (start, 0))
        return jnp.where((rows >= start)[:, None], panel, 0)

    def apply_panel(Lloc, P_k, k, me, j_min):
        """Rank-nb update of every local column with global index >= j_min:
        L[:, j] -= P_k @ P_k[rows of block j]^H (internal::herk/gemm trailing
        update, potrf.cc:136-148)."""
        js = local_cols(me)                            # (nt_loc,)
        Gall = P_k.reshape(nt, nb, nb)
        G = Gall[js]                                   # (nt_loc, nb, nb)
        upd = jnp.einsum("nk,smk->nsm", P_k, jnp.conj(G),
                         precision=lax.Precision.HIGHEST)
        upd = upd.reshape(n, nt_loc * nb)
        mask = jnp.repeat(js >= j_min, nb)[None, :]
        return Lloc - jnp.where(mask, upd, 0)

    def body(k, carry):
        Lloc, P_k = carry
        me = lax.axis_index(_AXIS)
        owner1 = (k + 1) % d
        slot1 = jnp.minimum((k + 1) // d, nt_loc - 1)
        valid1 = k + 1 < nt

        # -- 1. prioritized update of global column k+1 on its owner --------
        col1 = lax.dynamic_slice(Lloc, (0, slot1 * nb), (n, nb))
        G1 = lax.dynamic_slice(P_k, ((k + 1) % nt * nb, 0), (nb, nb))
        col1_upd = col1 - jnp.matmul(P_k, jnp.conj(G1).T,
                                     precision=lax.Precision.HIGHEST)
        mine1 = (me == owner1) & valid1
        # -- 2. broadcast + factor panel k+1 (masked-psum bcast) -----------
        contrib = jnp.where(mine1, col1_upd, jnp.zeros_like(col1_upd))
        bc = lax.psum(contrib, _AXIS)
        kp1 = jnp.minimum(k + 1, nt - 1)
        P_next = factor_panel(bc, kp1)
        P_next = jnp.where(valid1, P_next, jnp.zeros_like(P_next))
        # owner writes its updated (factored) column back
        col1_new = jnp.where(mine1, P_next, col1)
        Lloc = lax.dynamic_update_slice(Lloc, col1_new, (0, slot1 * nb))
        # -- 3. bulk trailing update (independent of step 2's collective) --
        Lloc = apply_panel(Lloc, P_k, k, me, j_min=k + 2)
        return Lloc, P_next

    def fn(Lloc):
        me = lax.axis_index(_AXIS)
        # prologue: factor + broadcast panel 0
        col0 = lax.dynamic_slice(Lloc, (0, 0), (n, nb))
        contrib = jnp.where(me == 0, col0, jnp.zeros_like(col0))
        bc = lax.psum(contrib, _AXIS)
        P0 = factor_panel(bc, 0)
        Lloc = jnp.where(me == 0,
                         lax.dynamic_update_slice(Lloc, P0, (0, 0)), Lloc)
        Lloc, _ = lax.fori_loop(0, nt, body, (Lloc, P0))
        return Lloc

    spec = P(None, _AXIS)
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


@instrument
def potrf_pipelined(Af: jax.Array, grid: ProcessGrid, nb: int = 256) -> jax.Array:
    """Distributed lower Cholesky with explicit lookahead pipelining over the
    flattened mesh (1-D block-cyclic columns).  Returns the dense lower factor
    (gathered layout).  See module docstring for the overlap structure.
    """
    n0 = Af.shape[-1]
    d = grid.size
    # the kernel only needs nt % d == 0; clamping nb to ceil(n0/d) bounds the
    # identity-tail padding at one block column per device
    nb = max(1, min(nb, -(-n0 // d)))
    unit = nb * d
    npad = -(-n0 // unit) * unit
    if npad != n0:
        Ap = jnp.zeros((npad, npad), Af.dtype).at[:n0, :n0].set(Af)
        idx = jnp.arange(n0, npad)
        Ap = Ap.at[idx, idx].set(1)
    else:
        Ap = Af
    n = npad
    nt = n // nb
    devices = np.array(grid.mesh.devices).ravel()
    mesh1d = Mesh(devices, (_AXIS,))

    # block-cyclic column permutation: shard s of device m holds global
    # block-column s*d + m; the sharded axis layout is device-major, so
    # pre-permute columns into (device, slot) order and undo after (shared
    # layout bridge with redistribute, distribute.cyclic_permutation)
    from .distribute import cyclic_permutation

    fwd_cols = cyclic_permutation(n, nb, d)
    inv_cols = np.argsort(fwd_cols)

    Aperm = jnp.take(Ap, jnp.asarray(fwd_cols), axis=1)
    Aperm = jax.device_put(Aperm, NamedSharding(mesh1d, P(None, _AXIS)))
    Lperm = _potrf_pipelined_fn(mesh1d, n, nb, d, str(Ap.dtype))(Aperm)
    L = jnp.take(Lperm, jnp.asarray(inv_cols), axis=1)
    L = jnp.tril(L)
    return L[:n0, :n0] if npad != n0 else L
