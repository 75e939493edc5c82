"""Flash attention for TPU (Pallas): blocked online-softmax attention with
causal and sliding-window masking and native GQA (no kv repetition — the
kv block index_map folds the head group).

TPU-native design (DESIGN.md §5): q/k/v tiles live in VMEM via BlockSpecs,
score tiles are (block_q × block_k) with both dims multiples of 128 so the
MXU runs dense; the softmax running max/sum and the output accumulator are
fp32 VMEM scratch carried across the innermost (k-block) grid dimension —
the HBM→VMEM streaming pattern replaces the GPU shared-memory tiling of the
original flash attention.

Layout: q (B, H, S, D); k/v (B, Hkv, T, D); out (B, H, S, D).
Validated on CPU with interpret=True against ref.mha_reference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, seq_q: int, seq_k: int,
                  q_offset: int):
    """Grid: (B, H, nq, nk); innermost nk is 'arbitrary' (sequential) and
    carries the online-softmax state in VMEM scratch."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0) \
        + q_offset
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
    mask = kpos < seq_k                                   # padding
    mask &= qpos < seq_q + q_offset
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_offset: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, T, D) with H = G·Hkv.

    ``q_offset`` shifts query positions (decode/chunked prefill): query s has
    absolute position q_offset + s; keys are at absolute positions 0..T-1.
    """
    b, h, s, d = q.shape
    _, hkv, t, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    from repro.kernels import auto_interpret
    interpret = auto_interpret(interpret)

    block_q = min(block_q, max(s, 16))
    block_k = min(block_k, max(t, 16))
    s_pad = math.ceil(s / block_q) * block_q
    t_pad = math.ceil(t / block_k) * block_k
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    nq, nk = s_pad // block_q, t_pad // block_k
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=s, seq_k=t,
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g_=g: (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g_=g: (b_, h_ // g_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=None,
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :s]
