"""Pallas TPU flash-attention forward kernel.

Grid (heads, q_tiles, kv_tiles); online-softmax state (m, l, acc) lives in
VMEM scratch and persists across the kv_tiles (last, sequential) grid
dimension.  Tiles are MXU-aligned (q/kv tile 128-multiples, head_dim is
padded to 128 by ops.py when needed).  Supports causal masks, sliding
windows, gemma-style logit softcap and GQA via an index-map that maps the
flattened q-head index onto its kv head.

The jnp reference (ref.py / models.attention._sdpa_ref) is the oracle; the
kernel is validated in interpret mode across shape sweeps by
tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret

NEG_INF = -2.0 ** 20


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
            window: int, attn_cap: float, n_k: int):
    tk = pl.program_id(2)

    @pl.when(tk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # [TQ, hd]
    k = k_ref[0]                                  # [TK, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if attn_cap > 0.0:
        s = jnp.tanh(s * (1.0 / attn_cap)) * attn_cap

    qp = qpos_ref[...]                            # [TQ] float32
    kp = kpos_ref[...]                            # [TK]
    ok = jnp.broadcast_to((kp < 2.0 ** 29)[None, :], s.shape)
    if causal:
        ok &= qp[:, None] >= kp[None, :]
    if window > 0:
        ok &= (qp[:, None] - kp[None, :]) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(tk == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "attn_cap", "tq", "tk",
                     "g", "interpret"))
def flash_attention_flat(q, k, v, q_pos, k_pos, *, scale: float,
                         causal: bool, window: int, attn_cap: float,
                         g: int, tq: int = 128, tk: int = 128,
                         interpret: Optional[bool] = None):
    """q: [H, Sq, hd] (H = B*KV*G flattened), k/v: [HK, Sk, hd] with
    HK = B*KV; q head h reads kv head h // g.  ``interpret=None`` lets
    :func:`repro.platform.pallas_interpret` decide."""
    if interpret is None:
        interpret = pallas_interpret()
    H, Sq, hd = q.shape
    HK, Sk, _ = k.shape
    TQ, TK = min(tq, Sq), min(tk, Sk)
    pq, pk = (-Sq) % TQ, (-Sk) % TK
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=2.0 ** 30)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=2.0 ** 30)
    n_q, n_k = (Sq + pq) // TQ, (Sk + pk) // TK

    grid = (H, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          attn_cap=attn_cap, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TQ,), lambda h, i, j: (i,)),          # q_pos
            pl.BlockSpec((TK,), lambda h, i, j: (j,)),          # k_pos
            pl.BlockSpec((1, TQ, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, TK, hd), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, TK, hd), lambda h, i, j: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, TQ, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((TQ,), jnp.float32),       # m
            pltpu.VMEM((TQ,), jnp.float32),       # l
            pltpu.VMEM((TQ, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q_pos.astype(jnp.float32), k_pos.astype(jnp.float32), q, k, v)
    return out[:, :Sq]
