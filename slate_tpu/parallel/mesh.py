"""Process grid over a TPU device mesh.

Reference analogue: the p×q MPI/BLACS process grid every SLATE matrix carries
(``BaseMatrix.hh:161-164`` ``gridinfo()``, ``func.hh:178-186`` 2D block-cyclic maps,
``MatrixStorage.hh:494-499``).  The reference asks MPI for a communicator and computes
each rank's (p, q) coordinate; here the grid *is* a ``jax.sharding.Mesh`` with axes
``("p", "q")`` over the slice's devices, and a "rank" is the flattened mesh coordinate.

Multi-host note: a ``Mesh`` built from ``jax.devices()`` spans all hosts of a pod slice
automatically (ICI for intra-slice axes, DCN across slices) — there is no separate
multi-node code path, which is the core simplification over MPI.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import grid as grid_funcs
from ..core.exceptions import slate_assert
from ..core.types import GridOrder

ROW_AXIS = "p"
COL_AXIS = "q"


class ProcessGrid:
    """A p×q grid of devices playing the role of the reference's MPI process grid.

    ``order`` mirrors the reference's ``GridOrder`` (func.hh): Col means ranks run down
    columns first (rank = i%p + (j%q)*p), the ScaLAPACK default.
    """

    def __init__(self, p: Optional[int] = None, q: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 order: GridOrder = GridOrder.Col):
        devices = list(devices if devices is not None else jax.devices())
        if p is None and q is None:
            p, q = grid_funcs.grid_size(len(devices))
        elif p is None:
            p = len(devices) // q
        elif q is None:
            q = len(devices) // p
        slate_assert(p >= 1 and q >= 1 and p * q <= len(devices),
                     f"grid {p}x{q} needs p, q >= 1 and p*q <= {len(devices)} devices")
        self.p, self.q = int(p), int(q)
        self.order = GridOrder.from_string(order)
        dev_grid = np.array(devices[:p * q])
        # Mesh axes are (p, q); Col order lays ranks down columns, so the flattened
        # device index runs fastest over p — transpose the reshape accordingly.
        if self.order == GridOrder.Col:
            dev_grid = dev_grid.reshape(self.q, self.p).T
        else:
            dev_grid = dev_grid.reshape(self.p, self.q)
        self.mesh = Mesh(dev_grid, (ROW_AXIS, COL_AXIS))
        self.tile_rank = grid_funcs.process_2d_grid(self.order, self.p, self.q)

    # -- reference gridinfo() ------------------------------------------------
    @property
    def size(self) -> int:
        return self.p * self.q

    def gridinfo(self) -> Tuple[GridOrder, int, int]:
        return self.order, self.p, self.q

    def coords(self, rank: int) -> Tuple[int, int]:
        """(row, col) coordinate of a flattened rank (BLACS pcoord analogue)."""
        if self.order == GridOrder.Col:
            return rank % self.p, rank // self.p
        return rank // self.q, rank % self.q

    @property
    def rank(self) -> int:
        """This process's flattened grid rank (Cblacs_pcoord's myrow/mycol
        inverse).  Under single-controller SPMD every device is addressable,
        so the controller's rank is the first grid position owned by one of
        this process's local devices — 0 in single-process runs, and the
        process's first device slot under jax.distributed (multi-host).
        Cached: the mesh is fixed at construction and tileIsLocal reads this
        per tile."""
        cached = getattr(self, "_rank", None)
        if cached is not None:
            return cached
        local = set(jax.local_devices())
        flat = (self.mesh.devices.T if self.order == GridOrder.Col
                else self.mesh.devices).ravel()
        rank = -1   # no local device on this grid -> this process owns nothing
        for r, d in enumerate(flat):
            if d in local:
                rank = r
                break
        self._rank = rank
        return rank

    # -- shardings -----------------------------------------------------------
    def spec(self, row_shard: bool = True, col_shard: bool = True,
             extra_leading: int = 0) -> NamedSharding:
        """NamedSharding for a 2-D array: rows over p, cols over q (either optional)."""
        parts = [None] * extra_leading
        parts += [ROW_AXIS if row_shard else None, COL_AXIS if col_shard else None]
        return NamedSharding(self.mesh, PartitionSpec(*parts))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def row_spec(self) -> NamedSharding:
        """1-D row distribution (rows over the whole flattened grid) for tall panels —
        the reference's 1D grids (func.hh process_1d_grid)."""
        return NamedSharding(self.mesh, PartitionSpec((ROW_AXIS, COL_AXIS)))

    def __repr__(self) -> str:
        return (f"ProcessGrid({self.p}x{self.q}, order={self.order}, "
                f"devices={self.size})")
