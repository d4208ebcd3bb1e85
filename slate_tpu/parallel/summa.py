"""Distributed matrix multiply over the process grid.

Reference analogue: ``src/gemmC.cc:55-160`` — the stationary-C pipeline that
broadcasts block-column k of A and block-row k of B across the grid (listBcastMT with
``lookahead`` prefetch tasks), then rank-nb updates local C tiles with batched gemm.

TPU re-design — two algorithms, both inside ``shard_map`` over the (p, q) mesh:

* :func:`gemm_allgather` — all-gather A along q and B along p, one local matmul.
  This is SUMMA with the panel loop fully aggregated; on TPU the ICI all-gather is a
  hardware-optimal ring, and the single big local matmul keeps the MXU at full tilt.
  Memory cost O(mK/p + Kn/q) per device.  This is also exactly what GSPMD emits for a
  jitted ``A @ B`` with these shardings — provided explicitly so the pipeline
  structure is visible and testable.

* :func:`gemm_ring` — the pipelined form (Cannon-style): K stays sharded; at each of
  the ``steps`` iterations every device multiplies its resident A/B panels and
  ``ppermute``-rotates them along the mesh axes.  Memory cost O(1) extra panels, and
  the rotation of step t+1 overlaps the matmul of step t (XLA async collectives) —
  the TPU-native expression of the reference's lookahead bcast tasks
  (gemmC.cc:104-121).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.exceptions import slate_assert
from .mesh import COL_AXIS, ProcessGrid, ROW_AXIS
from .collectives import ring_shift
from ..obs import instrument


@lru_cache(maxsize=32)
def _allgather_fn(mesh, precision):
    def local(a, b):
        # a: (m/p, K/q) -> (m/p, K); b: (K/p, n/q) -> (K, n/q)
        a_full = lax.all_gather(a, COL_AXIS, axis=1, tiled=True)
        b_full = lax.all_gather(b, ROW_AXIS, axis=0, tiled=True)
        return jnp.matmul(a_full, b_full, precision=precision)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS)),
                       out_specs=P(ROW_AXIS, COL_AXIS))
    return jax.jit(fn)


@instrument
def gemm_allgather(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                   precision=lax.Precision.HIGHEST) -> jax.Array:
    """C = A @ B with A, B, C block-sharded (p, q). One all-gather per operand."""
    m, k = A.shape[-2:]
    k2, n = B.shape[-2:]
    slate_assert(k == k2, f"gemm inner dims {k} != {k2}")
    slate_assert(m % grid.p == 0 and n % grid.q == 0
                 and k % grid.p == 0 and k % grid.q == 0,
                 f"shapes ({m},{k})x({k2},{n}) must divide the {grid.p}x{grid.q} grid "
                 "(pad to tile multiples first)")
    A = jax.device_put(A, grid.spec())
    B = jax.device_put(B, grid.spec())
    return _allgather_fn(grid.mesh, precision)(A, B)


@lru_cache(maxsize=32)
def _ring_fn(mesh, p, q, precision):
    steps = q  # == p; K panels rotate around the q-ring / p-ring

    def local(a, b):
        # Cannon skew: row i shifts its A panel left by i; col j shifts B up by j.
        i = lax.axis_index(ROW_AXIS)
        j = lax.axis_index(COL_AXIS)
        # variable-shift skew via cumulative single shifts expressed as a gather:
        # ppermute needs static perms, so skew by selecting source with i/j offsets.
        a = _skew(a, COL_AXIS, q, i)
        b = _skew(b, ROW_AXIS, p, j)
        # first multiply peeled so the carry starts shard-varying
        c = jnp.matmul(a, b, precision=precision)

        def body(t, carry):
            a, b, c = carry
            a = ring_shift(a, COL_AXIS, 1, q)   # rotate left
            b = ring_shift(b, ROW_AXIS, 1, p)   # rotate up
            c = c + jnp.matmul(a, b, precision=precision)
            return a, b, c

        a, b, c = lax.fori_loop(0, steps - 1, body, (a, b, c))
        return c

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS)),
                       out_specs=P(ROW_AXIS, COL_AXIS))
    return jax.jit(fn)


def _skew(x, axis_name, size, shift):
    """Rotate ``x`` along ``axis_name`` by a *traced* per-shard amount ``shift``.

    ppermute permutations are static, so a data-dependent skew is built from
    log2-style doubling: shift decomposes into binary powers, each applied with a
    static ppermute under a ``where`` mask (Cannon's initial alignment)."""
    step = 1
    while step < size:
        bit = (shift // step) % 2
        shifted = ring_shift(x, axis_name, step, size)
        x = jnp.where(bit.astype(bool), shifted, x)
        step *= 2
    return x


@instrument
def gemm_ring(A: jax.Array, B: jax.Array, grid: ProcessGrid,
              precision=lax.Precision.HIGHEST) -> jax.Array:
    """Cannon's algorithm on a square p×p grid: K stays resident, panels rotate on
    ICI each step (the pipelined / lookahead form)."""
    slate_assert(grid.p == grid.q, "gemm_ring requires a square grid (Cannon)")
    m, k = A.shape[-2:]
    _, n = B.shape[-2:]
    slate_assert(m % grid.p == 0 and k % grid.p == 0 and k % grid.q == 0
                 and n % grid.q == 0, "shapes must divide the grid")
    A = jax.device_put(A, grid.spec())
    B = jax.device_put(B, grid.spec())
    return _ring_fn(grid.mesh, grid.p, grid.q, precision)(A, B)


@instrument
def summa_gemm(alpha, A, B, beta, C, opts=None, grid: ProcessGrid | None = None):
    """Full gemm entry point for the L5 API (blas.gemm with MethodGemm.SUMMA):
    C = alpha op(A) op(B) + beta C over the default grid of all visible devices.

    Operands may be Matrix wrappers (their op flags apply) or raw arrays; ragged
    shapes are zero-padded to grid-divisible sizes and sliced back — the reference
    handles ragged edge tiles natively, XLA wants uniform shards (SURVEY.md §7
    hard-part 5).
    """
    from ..core.matrix import as_array
    from .distribute import pad2d

    grid = grid or ProcessGrid()
    a, b, c = as_array(A), as_array(B), as_array(C)
    m, n = a.shape[-2], b.shape[-1]
    kmult = grid.p * grid.q
    ap = pad2d(a, grid.p, kmult)
    bp = pad2d(b, kmult, grid.q)
    prod = gemm_distributed(ap, bp, grid)[:m, :n]
    return alpha * prod + beta * c


@instrument
def gemm_distributed(A, B, grid: ProcessGrid, method: str = "auto",
                     precision=lax.Precision.HIGHEST) -> jax.Array:
    """Dispatch like src/gemm.cc select_algo: ring (pipelined) on square grids with
    K large enough to amortize skew, else all-gather SUMMA."""
    if method == "auto":
        method = "ring" if (grid.p == grid.q and grid.p > 1
                            and A.shape[-1] >= 4 * grid.p) else "allgather"
    if method == "ring":
        return gemm_ring(A, B, grid, precision)
    return gemm_allgather(A, B, grid, precision)


@instrument
def gemm_padded(A: jax.Array, B: jax.Array, grid: ProcessGrid,
                precision=lax.Precision.HIGHEST) -> jax.Array:
    """``gemm_distributed`` for arbitrary shapes: zero-pads both operands to
    grid-tile multiples (the pad-and-mask edge policy, SURVEY §7 hard-part 5),
    runs the sharded product, slices the result — the convenience form every
    composition layer (inversion, LQ, ScaLAPACK skin) should call instead of
    hand-padding."""
    from ..core.exceptions import slate_assert
    from .distribute import lcm, pad2d

    m, k = A.shape[-2:]
    n = B.shape[-1]
    slate_assert(k == B.shape[-2],
                 f"gemm inner dims {k} != {B.shape[-2]} (padding would mask it)")
    mult = lcm(grid.p, grid.q)
    Ap = pad2d(A, grid.p, mult)
    Bp = pad2d(B, mult, grid.q)
    C = gemm_distributed(Ap, Bp, grid, precision=precision)
    return C[..., :m, :n] if C.shape[-2:] != (m, n) else C
