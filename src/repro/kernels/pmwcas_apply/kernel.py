"""Pallas TPU kernel: batched deterministic MwCAS conflict resolution.

The CPU paper resolves conflicts with CAS retry loops under cache
coherence; the TPU has neither CAS nor coherence, so the adaptation
(DESIGN.md Sec. 2.2) turns one *batch* of descriptors into a wait-free,
deterministic verdict: descriptor i succeeds iff all its expected values
match and no lower-index matching descriptor claims any of its target
addresses.  The per-row expected-value check is O(BK) and stays in XLA;
the kernel does the O(B^2 K^2) pairwise address comparison, which is
VPU-shaped: [TB, TB] tiles of the (row x row) "shares an address" matrix
evaluated in VMEM, OR-reduced into a per-row "lose" flag over the j-tile
grid dimension.

Layout (chosen so every block is legal for Mosaic): the i side is
``int32[K, B, 1]`` (one address column per slot), the j side
``int32[K, 1, B]`` (one address row per slot).  Padding and non-passing
rows are encoded in the addresses themselves — ``-1`` on the i side,
``-2`` on the j side — so an equal pair is always two real addresses of
a passing blocker, and the kernel needs no masks.  Row bases come from
``pl.program_id``; the j tiles wholly above an i tile are skipped, since
only a LOWER row can beat a row.  The output is an int32 ``[B, 1]`` flag,
turned into ``bool`` outside the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.platform import pallas_interpret


def _kernel(addr_i, addr_j, lose_ref, *, TB: int, K: int):
    ti, tj = pl.program_id(0), pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        lose_ref[...] = jnp.zeros_like(lose_ref)

    @pl.when(tj <= ti)
    def _accumulate():
        def slot(ki, hit):
            a = addr_i[ki]                               # [TB, 1]
            for kj in range(K):
                hit = jnp.where(a == addr_j[kj], 1, hit)  # vs [1, TB]
            return hit

        hit = jax.lax.fori_loop(0, K, slot, jnp.zeros((TB, TB), jnp.int32))
        rows_i = ti * TB + jax.lax.broadcasted_iota(jnp.int32, (TB, TB), 0)
        rows_j = tj * TB + jax.lax.broadcasted_iota(jnp.int32, (TB, TB), 1)
        beaten = jnp.where(rows_j < rows_i, hit, 0)
        lose_ref[...] = jnp.maximum(
            lose_ref[...], jnp.max(beaten, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def pmwcas_success_pallas(addr, cur, exp, *, tb: int = 128,
                          interpret: Optional[bool] = None):
    """addr: int32[B,K] (<0 pad), cur/exp: uint32[B,K] -> bool[B].

    ``interpret=None`` lets :func:`repro.platform.pallas_interpret` decide
    (compiled on a TPU, interpreted elsewhere).  A batch wider than ``tb``
    is tiled by ``tb`` rows; Mosaic needs ``tb`` to be a multiple of 128
    there (a batch of at most ``tb`` rows is one full-array tile)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, K = addr.shape
    valid = addr >= 0
    row_pass = jnp.where(valid, cur == exp, True).all(axis=1)   # (a)
    TB = min(tb, B)
    pad = (-B) % TB
    Bp = B + pad
    # i side: every real address; j side: only passing rows block (b)
    ai = jnp.pad(jnp.where(valid, addr, -1), ((0, pad), (0, 0)),
                 constant_values=-1)
    aj = jnp.pad(jnp.where(valid & row_pass[:, None], addr, -2),
                 ((0, pad), (0, 0)), constant_values=-2)
    n = Bp // TB
    lose = pl.pallas_call(
        functools.partial(_kernel, TB=TB, K=K),
        grid=(n, n),
        in_specs=[
            pl.BlockSpec((K, TB, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((K, 1, TB), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((TB, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        interpret=interpret,
        name="pmwcas_success",
    )(ai.T[:, :, None], aj.T[:, None, :])
    return row_pass & (lose[:B, 0] == 0)
