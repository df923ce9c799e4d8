"""Fused GQA decode-attention Pallas kernel (flash-decode).

EXPERIMENTS.md §Perf pairs B/C end at the cache-bandwidth floor with ~10%
useful-flops ratios — the residual is unfused masking/softmax traffic over
the [B, S, Kh, Dh] cache. This kernel streams the cache through VMEM in
seq blocks with an online softmax, so scores/probs never round-trip HBM:

  grid (B, Kh, S/bs); scratch m/l/acc persist across the seq dimension
  (innermost) and the output tile is written on the last block.

The `pos` scalar (prefetched) masks slots beyond the current decode
position, matching the rolling-buffer semantics of models/attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, block_s: int, seq_len: int):
    s_idx = pl.program_id(2)
    n_blocks = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # [G, Dh]
    k = k_ref[0, :, 0]                    # [bs, Dh]
    v = v_ref[0, :, 0]                    # [bs, Dh]
    scale = q.shape[-1] ** -0.5

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G,bs]
    # mask invalid slots (rolling buffer: all valid once pos >= S)
    pos = pos_ref[0]
    slots = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                       (1, block_s), 1)
    valid = (pos >= seq_len) | (slots <= pos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                   # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                # [G, bs]
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(s_idx == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pos: jnp.ndarray, *, block_s: int = 256,
                 interpret: bool) -> jnp.ndarray:
    """q: [B, Kh, G, Dh] (roped, one token); k/v: [B, S, Kh, Dh] cache;
    pos: scalar int32 decode position. Returns [B, Kh, G, Dh]."""
    B, Kh, G, Dh = q.shape
    S = k.shape[1]
    assert S % block_s == 0, (S, block_s)

    grid = (B, Kh, S // block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, Dh),
                         lambda b, h, s, pos: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, 1, Dh),
                         lambda b, h, s, pos: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, s, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, block_s=block_s, seq_len=S)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, Dh), q.dtype),
        interpret=interpret,
    )(pos.reshape(1), q, k, v)
