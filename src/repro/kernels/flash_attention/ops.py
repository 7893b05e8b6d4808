"""Jit wrapper exposing the kernel in the model's [B,KV,G,S,hd] layout
(the `attn_impl="pallas"` path of repro.models.attention)."""
from __future__ import annotations

from .kernel import flash_attention_flat


def flash_attention(q, k, v, q_pos, k_pos, *, causal, window, attn_cap,
                    scale, tq: int = 128, tk: int = 128):
    B, KV, G, Sq, hd = q.shape
    Sk = k.shape[2]
    qf = q.reshape(B * KV * G, Sq, hd)
    kf = k.reshape(B * KV, Sk, hd)
    vf = v.reshape(B * KV, Sk, hd)
    out = flash_attention_flat(
        qf, kf, vf, q_pos, k_pos, scale=float(scale), causal=bool(causal),
        window=int(window), attn_cap=float(attn_cap), g=G, tq=tq, tk=tk)
    return out.reshape(B, KV, G, Sq, hd)
