"""Native runtime (native/slate_rt.cpp via ctypes) + Python fallback equivalence
(≅ unit_test/test_Memory.cc, test_func.cc)."""

import numpy as np

import slate_tpu
from slate_tpu import native
from slate_tpu.core import func as grid_funcs
from slate_tpu.core.types import GridOrder


class TestOwnerMap:
    def test_matches_lambda_col(self):
        om = native.owner_map(7, 5, 2, 3, GridOrder.Col)
        fn = grid_funcs.process_2d_grid(GridOrder.Col, 2, 3)
        for i in range(7):
            for j in range(5):
                assert om[i, j] == fn(i, j)

    def test_matches_lambda_row(self):
        om = native.owner_map(6, 6, 3, 2, GridOrder.Row)
        fn = grid_funcs.process_2d_grid(GridOrder.Row, 3, 2)
        assert all(om[i, j] == fn(i, j) for i in range(6) for j in range(6))

    def test_python_fallback_equivalent(self, monkeypatch):
        om_native = native.owner_map(9, 11, 2, 2, GridOrder.Col)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        assert native.backend() == "python"
        om_py = native.owner_map(9, 11, 2, 2, GridOrder.Col)
        np.testing.assert_array_equal(om_native, om_py)

    def test_local_tiles_partition(self):
        mt, nt, p, q = 8, 9, 2, 3
        seen = set()
        for rank in range(p * q):
            tiles = native.local_tiles(mt, nt, p, q, rank)
            for (i, j) in map(tuple, tiles):
                assert (i, j) not in seen
                seen.add((i, j))
        assert len(seen) == mt * nt     # every tile owned exactly once

    def test_redist_plan(self):
        src, dst, moved = native.redist_plan(6, 6, (2, 2), (3, 2))
        assert src.shape == dst.shape == (6, 6)
        assert moved == int(np.count_nonzero(src != dst))
        # same grid -> nothing moves
        _, _, moved0 = native.redist_plan(6, 6, (2, 2), (2, 2))
        assert moved0 == 0


class TestMemoryPool:
    def test_alloc_free_cycle(self):
        pool = native.MemoryPool(block_bytes=1 << 20, nblocks=4)
        ids = [pool.alloc() for _ in range(4)]
        assert sorted(ids) == [0, 1, 2, 3]
        assert pool.in_use == 4 and pool.capacity == 4 and pool.peak == 4
        assert pool.alloc() == -1             # exhausted
        assert pool.free(ids[0])
        assert pool.in_use == 3
        assert not pool.free(ids[0])          # double free detected
        assert pool.alloc() == ids[0]         # block recycled
        assert pool.peak == 4

    def test_bad_id_rejected(self):
        pool = native.MemoryPool(64, 2)
        assert not pool.free(99)
        assert not pool.free(-1)


class TestMatrixIntegration:
    def test_owner_map_root_view(self):
        A = slate_tpu.Matrix(8 * 16, 6 * 16, nb=16, p=2, q=3)
        om = A.owner_map()
        assert om.shape == (8, 6)
        assert all(om[i, j] == A.tileRank(i, j) for i in range(8) for j in range(6))

    def test_owner_map_transposed_view(self):
        A = slate_tpu.Matrix(4 * 8, 3 * 8, nb=8, p=2, q=2)
        T = A.T
        om = T.owner_map()
        assert om.shape == (T.mt, T.nt)
        assert all(om[i, j] == T.tileRank(i, j)
                   for i in range(T.mt) for j in range(T.nt))

    def test_local_tiles_match_owner_map(self):
        A = slate_tpu.Matrix(6 * 8, 6 * 8, nb=8, p=2, q=2)
        om = A.owner_map()
        for rank in range(4):
            tiles = {tuple(t) for t in A.local_tiles(rank)}
            expect = {(i, j) for i in range(6) for j in range(6)
                      if om[i, j] == rank}
            assert tiles == expect
