#!/usr/bin/env python
"""Two-process jax.distributed CPU tier — the analogue of the reference CI's
``mpirun -np 4`` runs (.github/workflows/test.sh:48): the same SPMD code path
with a real multi-*process* world, catching cross-host bugs (global vs local
device indexing, process-spanning collectives) that the single-process
8-device mesh cannot.

Launches 2 worker processes (this script re-execs itself with --worker), each
owning 4 virtual CPU devices, forming one 8-device global mesh spanning the
process boundary.  Each worker runs:

- a global psum over all 8 devices (the cross-process collective floor),
- a (2, 4) process-grid SUMMA gemm whose row axis spans the two processes,
- a distributed Cholesky solve through the same ProcessGrid the in-process
  tests use, validating the grid code is process-count agnostic.

Exit code 0 = both workers verified their shard of the results.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

NPROC = 2
LOCAL_DEVICES = 4


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker(coord: str, pid: int) -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "tools"))
    from force_cpu import force_cpu_backend

    # each worker must own exactly LOCAL_DEVICES virtual devices; an ambient
    # device-count flag (e.g. the test-suite's =8) would win inside
    # force_cpu_backend's already-present check, so strip it first
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", flags)
    force_cpu_backend(virtual_devices=LOCAL_DEVICES)
    import jax

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=NPROC, process_id=pid)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) == NPROC * LOCAL_DEVICES, f"global devices: {len(devs)}"
    assert len(jax.local_devices()) == LOCAL_DEVICES

    # --- 1) global psum across the process boundary -------------------------
    mesh = Mesh(np.array(devs).reshape(NPROC, LOCAL_DEVICES), ("p", "q"))
    flat = Mesh(np.array(devs), ("d",))

    @jax.jit
    def allsum(x):
        def body(s):
            return jax.lax.psum(s, "d")
        return jax.shard_map(body, mesh=flat, in_specs=P("d"), out_specs=P())(x)

    n = NPROC * LOCAL_DEVICES
    x = jnp.arange(n, dtype=jnp.float32)
    xs = jax.device_put(x, NamedSharding(flat, P("d")))
    total = allsum(xs)
    # out_specs=P() replicates the scalar to every device; read this
    # process's addressable copy (a cross-process float() would need a gather)
    got = float(np.asarray(total.addressable_shards[0].data))
    assert got == n * (n - 1) / 2, got

    # --- 2) SUMMA gemm on the (2, 4) grid spanning both processes -----------
    from slate_tpu.parallel import ProcessGrid, gemm_allgather

    grid = ProcessGrid(NPROC, LOCAL_DEVICES, devices=devs)
    rng = np.random.default_rng(0)            # same seed -> same global operands
    m = k = nn = 32
    A = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    B = jnp.asarray(rng.standard_normal((k, nn)).astype(np.float32))
    C = gemm_allgather(A, B, grid)
    ref = np.asarray(A) @ np.asarray(B)
    for shard in C.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, ref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-4)
    print(f"worker {pid}: summa OK", flush=True)

    # --- 3) distributed Cholesky solve through the same grid ----------------
    from slate_tpu.parallel import posv_distributed

    M = rng.standard_normal((m, m)).astype(np.float32)
    spdh = M @ M.T + m * np.eye(m, dtype=np.float32)
    Bh = rng.standard_normal((m, 4)).astype(np.float32)
    X = posv_distributed(jnp.asarray(spdh), jnp.asarray(Bh), grid, nb=8)
    Xref = np.linalg.solve(spdh, Bh)
    # verify this process's addressable shards only (a full np.asarray would
    # need a cross-process gather)
    for shard in X.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, Xref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-3)
    print(f"worker {pid}: posv OK", flush=True)

    # --- 4) tournament-pivoted LU spanning the process boundary -------------
    from slate_tpu.parallel import gesv_distributed

    G = rng.standard_normal((m, m)).astype(np.float32) + m * np.eye(
        m, dtype=np.float32)
    Xg, info = gesv_distributed(jnp.asarray(G), jnp.asarray(Bh), grid, nb=8)
    assert int(np.asarray(info.addressable_shards[0].data)) == 0
    Xgref = np.linalg.solve(G, Bh)
    for shard in Xg.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, Xgref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-3)
    print(f"worker {pid}: gesv OK", flush=True)

    # --- 5) explicit shard_map rank-k update (herk panel broadcast) ---------
    from slate_tpu.parallel import herk_distributed

    Ah = rng.standard_normal((m, 8)).astype(np.float32)
    Ch = rng.standard_normal((m, m)).astype(np.float32)
    Hk = herk_distributed(1.0, jnp.asarray(Ah), 0.5, jnp.asarray(Ch), grid)
    href = np.where(np.tril(np.ones((m, m), bool)),
                    Ah @ Ah.T + 0.5 * Ch, Ch)
    for shard in Hk.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, href[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-3)
    print(f"worker {pid}: herk OK", flush=True)

    # --- 6) round-3 stragglers across the process boundary: compact-band
    # Cholesky and CA-Aasen (their window psums / tournament all-gathers ride
    # the same flattened mesh axis pair)
    from slate_tpu.parallel import (dense_to_band_lower, hesv_distributed,
                                    pbsv_distributed)

    kd = 3
    Abd = np.zeros((m, m), np.float32)
    for j in range(1, kd + 1):
        v = rng.standard_normal(m - j).astype(np.float32)
        Abd += np.diag(v, j) + np.diag(v, -j)
    Abd += np.diag(np.abs(rng.standard_normal(m)).astype(np.float32)
                   + 4 * kd)
    Ab = dense_to_band_lower(jnp.asarray(np.tril(Abd)), kd)
    Xb, infob = pbsv_distributed(Ab, jnp.asarray(Bh), grid, kd, nb=8)
    Xbref = np.linalg.solve(Abd, Bh)
    for shard in Xb.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, Xbref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-3)
    print(f"worker {pid}: pbsv OK", flush=True)

    Hm = rng.standard_normal((m, m)).astype(np.float32)
    Hm = (Hm + Hm.T) / 2
    Xh, infoh = hesv_distributed(jnp.asarray(Hm), jnp.asarray(Bh), grid, nb=8)
    Xhref = np.linalg.solve(Hm, Bh)
    for shard in Xh.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, Xhref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]], atol=1e-2)
    print(f"worker {pid}: hesv OK", flush=True)

    # --- 7) round-4: distributed RBT solve (sharded butterfly + nopiv LU +
    # IR — its psums/trsm partitions are process-count agnostic like the
    # rest) across the two-process boundary
    from slate_tpu.parallel import gesv_rbt_distributed

    Gm = rng.standard_normal((m, m)).astype(np.float32)
    Xr, infor, _, _ = gesv_rbt_distributed(jnp.asarray(Gm), jnp.asarray(Bh),
                                           grid, depth=2, nb=8)
    Xrref = np.linalg.solve(Gm, Bh)
    for shard in Xr.addressable_shards:
        r0, c0 = (sl.start or 0 for sl in shard.index)
        blk = np.asarray(shard.data)
        np.testing.assert_allclose(
            blk, Xrref[r0:r0 + blk.shape[0], c0:c0 + blk.shape[1]],
            atol=1e-2)
    print(f"worker {pid}: rbt OK", flush=True)

    # --- 8) round-5: segment-parallel bulge chase — its per-round boundary
    # deltas and crossing-reflector ppermutes ride the flattened mesh axis,
    # so between devices 3 and 4 they cross the PROCESS boundary every round
    from slate_tpu.parallel import hb2st_chase_distributed
    from slate_tpu.linalg.eig import _hb2st_chase_pipelined

    nc, bc = 48, 2
    Mc = rng.standard_normal((nc, nc)).astype(np.float32)
    symc = (Mc + Mc.T) / 2
    iic = np.arange(nc)
    bandc = jnp.asarray(np.where(np.abs(iic[:, None] - iic[None, :]) <= bc,
                                 symc, 0))
    d_ref, e_ref, _, _ = _hb2st_chase_pipelined(bandc, bc)   # local replay
    dd, ee, _, _ = hb2st_chase_distributed(bandc, bc, grid)
    d_ref_np, e_ref_np = np.asarray(d_ref), np.asarray(e_ref)
    for shard in dd.addressable_shards:
        (sl,) = shard.index
        np.testing.assert_allclose(np.asarray(shard.data), d_ref_np[sl],
                                   atol=1e-4)
    for shard in ee.addressable_shards:
        (sl,) = shard.index
        np.testing.assert_allclose(np.asarray(shard.data), e_ref_np[sl],
                                   atol=1e-4)
    print(f"worker {pid}: chase OK", flush=True)

    jax.distributed.shutdown()
    print(f"worker {pid}: OK", flush=True)


def main() -> int:
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    procs = []
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    for pid in range(NPROC):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             coord, str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    deadline = time.time() + 600
    rc = 0
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=max(10, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out = "(timeout)"
        if p.returncode != 0:
            rc = 1
        outs.append(out)
        print(f"--- worker {i} (rc={p.returncode}) ---\n{out}")
    # some jaxlib builds ship no multiprocess support for the CPU backend at
    # all (collectives raise INVALID_ARGUMENT at the first cross-process op).
    # That is an environment limitation, not a regression in this tree —
    # report an honest SKIP instead of a false FAIL so the single-process
    # 8-device tier (which covers the same SPMD code path) stays the gate.
    if rc != 0 and any("Multiprocess computations aren't implemented on the "
                       "CPU backend" in o for o in outs):
        print("MULTIPROCESS SKIP (jaxlib CPU backend lacks multiprocess "
              "collectives)")
        return 0
    print("MULTIPROCESS", "PASS" if rc == 0 else "FAIL")
    return rc


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker(sys.argv[2], int(sys.argv[3]))
    else:
        sys.exit(main())
