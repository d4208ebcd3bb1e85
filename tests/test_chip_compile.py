"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed here and compiles for a chip that is described,
not attached: what it refuses here (a Mosaic kernel that will not lower, a
program that does not fit the chip) would fail on the chip too.  Every
compile is of a real-width program:

* the five Pallas norm launches at 16384^2 f32 (genorm fro/max/one/inf and
  col_norms_max), each checked for ``tpu_custom_call`` in the compiled HLO;
* the tournament's Pallas merge kernel at the benchmark's width (a level of
  one and of 32 stacked 512x256 blocks), and the rolled tournament-pivoted
  LU that calls it, which must keep its matrix row-major;
* the batched serve programs (``gesv``/``posv``/``gels`` cores, vmapped by
  the serve layer's own builder) at the largest bucket the serve workload
  generator draws.

The topology is described inside a module-scoped fixture, never at import
(only one process may load the TPU library; see the on-chip-measurement
guide), so every xdist worker collects the same tests.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 16384


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile cannot be read back without a chip: keep it
    # out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the norm kernels to their TPU lowering: the backend here is the
    CPU, where they would otherwise trace in interpret mode."""
    from slate_tpu.ops import pallas_norms

    monkeypatch.setattr(pallas_norms, "_interpret", lambda: False)
    jax.clear_caches()
    yield pallas_norms
    jax.clear_caches()


@pytest.fixture
def merge_kernel(monkeypatch):
    """Steer the tournament to the Pallas merge kernel's TPU lowering: its
    operands read as on a TPU, and the kernel compiles through Mosaic."""
    from types import SimpleNamespace

    from slate_tpu.linalg import lu
    from slate_tpu.ops import pallas_tntpiv

    monkeypatch.setattr(pallas_tntpiv, "_interpret", lambda: False)
    monkeypatch.setattr(lu, "_operand_device",
                        lambda a: SimpleNamespace(platform="tpu"))
    lu._getrf_tntpiv_fn.cache_clear()
    jax.clear_caches()
    yield pallas_tntpiv
    lu._getrf_tntpiv_fn.cache_clear()
    jax.clear_caches()


def _compile(fn, *args):
    # the chip runs with x64 off (conftest turns it on for the CPU tests);
    # Mosaic refuses the i64 block indices x64 would give the kernels
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("which", ["fro", "max", "one", "inf", "colmax"])
def test_pallas_norm_compiles_for_v5e(one_chip, mosaic, which):
    a = jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=one_chip)
    if which == "colmax":
        compiled = _compile(mosaic.col_norms_max, a)
    else:
        compiled = _compile(lambda x: mosaic.genorm(x, which), a)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("routine", ["gesv", "posv", "gels"])
def test_serve_program_compiles_for_v5e(one_chip, routine):
    from slate_tpu.serve import batched
    from slate_tpu.serve.queue import BucketPolicy
    from slate_tpu.serve.workload import DEFAULT_DIMS

    policy = BucketPolicy()
    n = max(DEFAULT_DIMS)
    m = 2 * n if routine == "gels" else n          # make_requests' shapes
    # nrhs 4: the largest of make_requests' default nrhs_pool
    bm, bn, br = policy.bucket(routine, m, n, 4)
    batch = policy.max_batch
    a = jax.ShapeDtypeStruct((batch, bm, bn), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((batch, bm, br), jnp.float32, sharding=one_chip)
    compiled = _compile(batched.batched_build(routine + "_batched"), a, b)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16 * 10**9           # fits one v5e's 16 GB of HBM


@pytest.mark.parametrize("nblk", [1, 32])
def test_merge_kernel_compiles_for_v5e(one_chip, merge_kernel, nblk):
    v2 = jax.ShapeDtypeStruct((nblk, 512, 256), jnp.float32,
                              sharding=one_chip)
    assert "tpu_custom_call" in _compile(merge_kernel.merge_winners,
                                         v2).as_text()


def test_getrf_tntpiv_with_merge_kernel_keeps_row_major(one_chip,
                                                        merge_kernel):
    """The rolled factorization that selects through the merge kernel holds
    its matrix row-major: a kernel operand transposed outside the kernel
    had the compiler keep the loop's matrix column-major, with two copies
    of the whole matrix a panel (~250 ms a solve at n=16384 on a v5e)."""
    from slate_tpu.linalg import lu

    n = 1024
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    text = _compile(lu._getrf_tntpiv_fn(n, n, 256, 256, "float32"),
                    a).as_text()
    assert "tpu_custom_call" in text
    assert not re.search(rf"f32\[{n},{n}\]{{0,1[^}}]*}} copy\(", text)
