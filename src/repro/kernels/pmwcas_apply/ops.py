"""Jit'd public op: batched MwCAS apply against a word table.

Gather + scatter stay in XLA (they are memory-layout operations XLA
already emits optimally); the Pallas kernel resolves conflicts.
``interpret=None`` leaves the kernel mode to the platform
(:func:`repro.platform.pallas_interpret`: compiled on a TPU, interpreted
elsewhere); ``use_kernel=False`` is the pure-jnp oracle, for tests that
need a reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .kernel import pmwcas_success_pallas


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def pmwcas_apply(words, addr, exp, des, *, use_kernel: bool = True,
                 interpret: Optional[bool] = None):
    """words: uint32[W]; addr int32[B,K] (<0 pad); exp/des uint32[B,K].
    Returns (new_words, success[B])."""
    cur = words[jnp.maximum(addr, 0)]
    if use_kernel:
        success = pmwcas_success_pallas(addr, cur, exp, interpret=interpret)
    else:
        success = ref.pmwcas_success(addr, cur, exp)
    valid = (addr >= 0) & success[:, None]
    flat_addr = jnp.where(valid, addr, words.shape[0]).reshape(-1)
    new = jnp.concatenate([words, jnp.zeros((1,), words.dtype)])
    new = new.at[flat_addr].set(
        jnp.where(valid.reshape(-1), des.reshape(-1), new[flat_addr]))
    return new[:-1], success


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"),
                   donate_argnums=(0,))
def pmwcas_apply_stacked(words, addr, exp, des, *, use_kernel: bool = True,
                         interpret: Optional[bool] = None):
    """S shard rounds in ONE dispatch: vmap of :func:`pmwcas_apply`.

    words: uint32[S, W] stacked shard word tables; addr int32[S, B, K]
    (<0 pad); exp/des uint32[S, B, K].  Returns
    ``(new_words[S, W], success[S, B])``.

    ``words`` is DONATED: callers pass a freshly stacked temporary (the
    per-shard tables are untouched) and XLA reuses its buffer for the
    output — the stacked service dispatch would otherwise hold two full
    copies of every shard table per wave.  Like every jitted entry
    point this retraces per shape; the service keeps the shapes it
    feeds BUCKETED (``[S, B_cap, K_pow2]``) so steady-state waves hit
    the trace cache instead of recompiling.
    """
    def one_shard(w, a, e, d):
        return pmwcas_apply(w, a, e, d, use_kernel=use_kernel,
                            interpret=interpret)

    return jax.vmap(one_shard)(words, addr, exp, des)


def reserve_slots(free_mask, requests, *, use_kernel: bool = True,
                  interpret: Optional[bool] = None):
    """KV-cache slot reservation for the serving layer: request i atomically
    claims `requests[i]` slots (a K-word MwCAS on a free-bitmap word table).

    free_mask: uint32[W] (1 = free); requests: int32[B, K] candidate slot ids
    (<0 pad).  Returns (new_mask, granted[B]).

    Semantics corner cases (asserted kernel == ref in tests):
    - duplicate slot ids within one request claim the slot once and still
      grant the request;
    - an all-padded request is vacuously granted (claims nothing);
    - overlapping requests are linearized by batch index (lower wins).
    """
    B, K = requests.shape
    exp = jnp.ones((B, K), jnp.uint32)    # expect free
    des = jnp.zeros((B, K), jnp.uint32)   # claim
    return pmwcas_apply(free_mask, requests, exp, des,
                        use_kernel=use_kernel, interpret=interpret)
