"""Dispatch wrapper for flash_attention: pads seq lengths to block
multiples (with masking via window/causal semantics preserved), pads d_head
to the 128-lane MXU width, interpret mode off-TPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal: bool = True,
                    window=None, block_q: int = 128, block_kv: int = 128):
    """Drop-in for models.transformer.attention (self-attention case:
    q_pos == k_pos == arange)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    bq = min(block_q, max(16, sq))
    bkv = min(block_kv, max(16, skv))
    pad_q = (-sq) % bq
    pad_kv = (-skv) % bkv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    if pad_kv and not causal:
        # bidirectional: padded keys must not attend — give them -inf via a
        # sentinel window... simplest correct: fall back to masking by
        # causal=False + explicit slice; padded KEYS only matter if real
        # queries can see them, so zero-vector keys contribute exp(s)=1
        # uniformly. Use the sentinel-dim trick instead:
        kp = jnp.concatenate([kp, jnp.zeros_like(kp[:, :, :, :1])], -1)
        kp = kp.at[:, skv:, :, -1].set(-1e4)
        qp = jnp.concatenate([qp, jnp.ones_like(qp[:, :, :, :1])], -1)
        vp = jnp.pad(vp, ((0, 0), (0, 0), (0, 0), (0, 1)))
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_kv=bkv,
                                 interpret=tuning.interpret_mode(),
                                 scale=1.0 / (d ** 0.5))
    if pad_kv and not causal:
        out = out[..., :d]
    return out[:, :sq]
