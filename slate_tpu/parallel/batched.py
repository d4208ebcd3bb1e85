"""Batch-parallel solves: the leading batch axis sharded over the mesh.

Reference analogue: SLATE's batch-BLAS tier (PAPER.md L1) distributes
*independent* problems, not tiles of one problem — on TPU that means the
batch axis is the natural mesh axis.  Each device vmap-solves its local
shard of the stack with the same pure cores the serving layer compiles
(:func:`slate_tpu.linalg.gesv_core`), and the program contains **zero
collectives**: the batch tier is embarrassingly parallel, which is exactly
what the SCALING.md audit row for this module documents (collective bytes
= 0 at every P — the one distributed routine whose communication budget is
identically nothing).

The serving queue stays single-device (its buckets are small); this entry
is for bulk offline batches — thousands of same-bucket solves in one
sharded call (``slate_tpu.serve`` handles the mixed-traffic front end).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec

from ..core.exceptions import slate_assert
from ..linalg.chol import posv_core
from ..linalg.lu import gesv_core
from ..obs import instrument
from .mesh import COL_AXIS, ROW_AXIS, ProcessGrid


def _batch_sharded(core, grid: ProcessGrid, a, b, n_out: int):
    """shard_map the vmapped core over the batch axis (both mesh axes
    flattened — P = p*q shards, no collectives)."""
    P = grid.p * grid.q
    slate_assert(a.ndim == 3 and b.ndim == 3,
                 f"batched distributed solve needs (batch, m, n) operands, "
                 f"got {a.shape} / {b.shape}")
    slate_assert(a.shape[0] % P == 0,
                 f"batch {a.shape[0]} must divide the grid size {P} evenly "
                 f"(pad the batch to a multiple — serve.BucketPolicy's "
                 f"batch rounding does)")
    spec = PartitionSpec((ROW_AXIS, COL_AXIS))
    fn = jax.shard_map(lambda al, bl: jax.vmap(core)(al, bl),
                   mesh=grid.mesh,
                   in_specs=(spec, spec),
                   out_specs=tuple([spec] * n_out),
                   check_vma=False)
    return jax.jit(fn)(a, b)


@instrument
def gesv_batched_distributed(a, b, grid: ProcessGrid):
    """Batched gesv with the batch axis sharded over the grid's devices.

    ``a`` (batch, n, n), ``b`` (batch, n, nrhs); batch must be a multiple of
    ``grid.p * grid.q``.  Returns ``(x, perm, info)`` with per-request perm
    and info, exactly like :func:`slate_tpu.serve.gesv_batched` (which
    handles the escalation ladder; this entry is the raw sharded kernel)."""
    return _batch_sharded(gesv_core, grid, a, b, 3)


@instrument
def posv_batched_distributed(a, b, grid: ProcessGrid):
    """Batched SPD solve with the batch axis sharded over the grid (full
    Hermitian operands).  Returns ``(x, info)`` per request."""
    return _batch_sharded(posv_core, grid, a, b, 2)
