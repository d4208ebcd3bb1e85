"""Pallas TPU kernel for one level of the tournament-pivoting reduction tree:
the pair merges of CALU's pivot selection, stepped together.

Reference analogue: ``internal_getrf_tntpiv.cc`` / ``Tile_getrf_tntpiv.hh``,
where each tree node stacks two candidate blocks and keeps the ``w`` rows that
partial pivoting of the stack promotes.  XLA's LU (the custom call
``LuDecompositionBlock``) does the same merge but steps through a batch one
block at a time: a merge is bound by the latency of each column step's
argmax -> scale -> rank-1 chain, so a batch of two costs twice one.  Here a
kernel call holds a chunk of the batch's blocks in VMEM and runs column step
``k`` for all of them before step ``k+1``, so the chain's latency is paid
once a chunk.

Each block is transposed into VMEM, ``(w, 2w)``: a column of the merge is one
sublane row (lane = candidate row), so the step's argmax, pivot and
multipliers are lane vectors, and the rank-1 update runs over the remaining
columns of the current 128-column tile only.  (Transposed outside the
kernel, the operand made XLA hold the whole rolled LU's matrix column-major,
with two full copies a panel.)  Pivoting is implicit: chosen rows are masked
out of later argmaxes, nothing moves.  Ties go to the lowest row index, as
LAPACK's ``i?amax``.  After a tile's 128 steps the later tiles get the
tile's whole update as MXU products at ``Precision.HIGHEST``: the pivot rows
of the later columns are gathered (a product with the steps' one-hot pivot
rows), solved against the tile's unit-lower ``L11`` (inverted by recursive
doubling of its diagonal blocks) and subtracted as one
``(rows, 128) @ (128, 2w)`` product, the blocking of XLA's own LU.
Everything is f32; no factor is returned.

On the CPU test backend the kernel runs through the Pallas interpreter
(``interpret=True``), as ``pallas_norms`` does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_norms import _LANE, _interpret

#: Most blocks a kernel call steps together (the chunk).  A 512x256 block is
#: 512 KiB, held once as the call's input and once as its working copy.
_CHUNK = 16
#: Columns a rolled step loop covers; the rows it updates are those of its
#: first column onwards, so later loops of a tile update fewer rows.
_STRIDE = 32

_HI = lax.Precision.HIGHEST


def _dot(a, b, contract_b: int = 0):
    """``a @ b`` (``contract_b=0``) or ``a @ b.T`` (1), f32 at HIGHEST."""
    return lax.dot_general(a, b, (((1,), (contract_b,)), ((), ())),
                           precision=_HI, preferred_element_type=jnp.float32)


def _unit_upper_inverse(u):
    """``u^-1`` for a 128x128 unit upper-triangular ``u`` (whose diagonal is
    not read), by doubling the inverted diagonal blocks: with ``b`` the
    inverse of the block diagonal of block size ``s`` and ``n`` the
    off-diagonal blocks within the blocks of size ``2s``, ``(b^-1 + n)^-1 =
    b - b n b`` exactly (``n b n = 0``).  Recursive triangular inversion, as
    stable as LAPACK's trtri; a product of powers of the strict part
    cancels, and loses the later tiles' pivots to rounding."""
    r = lax.broadcasted_iota(jnp.int32, u.shape, 0)
    c = lax.broadcasted_iota(jnp.int32, u.shape, 1)
    b = jnp.where(r == c, 1.0, 0.0)
    s = 1
    while s < _LANE:
        pair = (r & -2 * s) == (c & -2 * s)
        n = jnp.where(pair & ((r & s) < (c & s)), u, 0.0)
        b = b - _dot(_dot(b, n), b)
        s *= 2
    return b


def _later_update(t_ref, e_ref, l_ref, b, t1: int):
    """Apply a finished tile's elimination to the rows ``t1:`` of block
    ``b`` (the later columns of the merge, transposed): gather their pivot
    rows ``X^T = T e^T``, solve ``U^T = X^T L11^-T`` and subtract
    ``U^T l``."""
    et, lt = e_ref[b], l_ref[b]                     # (128, 2w) each
    l11t = _dot(lt, et, 1)                          # [i, k] = l_i[p_k]
    later = t_ref[b, t1:, :]
    y = _dot(_dot(later, et, 1), _unit_upper_inverse(l11t))
    t_ref[b, t1:, :] = later - _dot(y, lt)


def _kernel(v_ref, out_ref, t_ref, e_ref, l_ref, *, bc: int, w: int):
    """One chunk: each block transposed into ``t_ref``, then the column steps
    for all ``bc`` blocks in lockstep.  ``e_ref`` and ``l_ref`` hold the
    current tile's one-hot pivot rows and multipliers, by step; ``out_ref``
    takes the winners."""
    h = 2 * w

    def transpose(b, _):
        t_ref[b] = v_ref[b].T

    lax.fori_loop(0, bc, transpose, None)
    lanes = lax.broadcasted_iota(jnp.int32, (bc, 1, h), 2)
    slots = lax.broadcasted_iota(jnp.int32, (bc, 1, w), 2)
    act = jnp.ones((bc, 1, h), jnp.int32)           # 1 while still a candidate
    win = jnp.zeros((bc, 1, w), jnp.int32)
    for t0 in range(0, w, _LANE):
        for q0 in range(0, _LANE, _STRIDE):
            r0, r1 = t0 + q0, t0 + _LANE            # rows this loop updates
            below = lax.broadcasted_iota(jnp.int32, (bc, r1 - r0, 1), 1)

            def step(s, carry, t0=t0, q0=q0, r0=r0, r1=r1, below=below):
                act, win = carry
                c = q0 + s                          # column within the tile
                col = t_ref[:, pl.ds(t0 + c, 1), :]                 # (bc,1,h)
                live = act > 0
                # candidates by |value|; a NaN candidate ranks below every
                # number, a chosen row below every candidate
                mag = jnp.where(live, jnp.where(col == col, jnp.abs(col), -1.0),
                                -2.0)
                best = jnp.max(mag, axis=2, keepdims=True)
                p = jnp.min(jnp.where(mag == best, lanes, h), axis=2,
                            keepdims=True)
                hit = lanes == p
                piv = jnp.sum(jnp.where(hit, col, 0.0), axis=2, keepdims=True)
                ok = (piv != 0) & (piv == piv)
                # a zero pivot eliminates nothing (LAPACK's getf2)
                inv = jnp.where(ok, 1.0 / jnp.where(ok, piv, 1.0), 0.0)
                mult = jnp.where(live, col * inv, 0.0)
                e_ref[:, pl.ds(c, 1), :] = hit.astype(jnp.float32)
                l_ref[:, pl.ds(c, 1), :] = mult
                region = t_ref[:, r0:r1, :]                         # (bc,R,h)
                u = jnp.sum(jnp.where(hit, region, 0.0), axis=2, keepdims=True)
                u = jnp.where(below > s, u, 0.0)    # the columns after c only
                t_ref[:, r0:r1, :] = region - u * mult
                act = jnp.where(hit, 0, act)
                win = jnp.where(slots == t0 + c, p, win)
                return act, win

            act, win = lax.fori_loop(0, _STRIDE, step, (act, win))
        if t0 + _LANE < w:
            lax.fori_loop(0, bc, lambda b, _, t1=t0 + _LANE: _later_update(
                t_ref, e_ref, l_ref, b, t1), None)
    out_ref[...] = win


@functools.lru_cache(maxsize=None)
def _chunk_merge(bc: int, w: int, interpret: bool):
    """The kernel on one chunk of ``bc`` blocks, ``(bc, 2w, w) -> (bc, 1, w)``:
    jitted, so a factorization traces and lowers it once per chunk size,
    however many levels call it."""
    h = 2 * w
    block, tile = h * w * 4, _LANE * h * 4
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, bc=bc, w=w),
        out_shape=jax.ShapeDtypeStruct((bc, 1, w), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bc, w, h), jnp.float32),
                        pltpu.VMEM((bc, _LANE, h), jnp.float32),
                        pltpu.VMEM((bc, _LANE, h), jnp.float32)],
        # the input, its working copy and the tile's pivots and multipliers,
        # as much again for the compiler's temporaries, and the default scope
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * bc * (2 * block + 2 * tile) + (16 << 20)),
        interpret=interpret))


def merge_winners(v2: jax.Array) -> jax.Array:
    """The rows partial pivoting promotes in each stacked candidate block.

    ``v2``: ``(B, 2w, w)`` float32, ``w`` a multiple of 128.  Returns
    ``(B, w)`` int32: for each block the ``w`` row indices that
    ``lax.linalg.lu(v2)[2][:, :w]`` gives, in pivot order.  No factor is
    formed.  The blocks go through the kernel in chunks of a power of two,
    at most ``_CHUNK``, zero blocks filling the last.
    """
    nblk, h, w = v2.shape
    if v2.dtype != jnp.float32 or w % _LANE or h != 2 * w:
        raise ValueError(f"merge_winners takes (B, 2w, w) float32 with w a "
                         f"multiple of {_LANE}, got {v2.shape} {v2.dtype}")
    bc = min(_CHUNK, 1 << (nblk - 1).bit_length())
    steps = -(-nblk // bc)
    if steps * bc != nblk:
        v2 = jnp.pad(v2, ((0, steps * bc - nblk), (0, 0), (0, 0)))
    call = _chunk_merge(bc, w, _interpret())
    if steps == 1:
        out = call(v2)
    else:
        out = lax.map(call, v2.reshape(steps, bc, h, w))
    return out.reshape(steps * bc, w)[:nblk]
