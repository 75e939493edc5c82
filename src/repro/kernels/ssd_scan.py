"""Mamba2 SSD chunk kernel (Pallas, TPU target).

The O(Q²) intra-chunk work — the compute hot spot of SSD training/prefill —
runs per (batch, head, chunk) grid cell entirely in VMEM:

  decay   = exp(segsum(a))           (Q, Q) lower-triangular
  y_intra = (C·Bᵀ ⊙ decay·dt) · x    two MXU matmuls
  state   = (exp(cs_last − cs)·dt·x)ᵀ · B   chunk-final state contribution
  csum    = cumsum(a) within the chunk (for the inter-chunk correction)

The sequential inter-chunk recurrence (h_c = decay_c·h_{c−1} + state_c) and
the y_inter = C·h_prev·exp(cs) correction are cheap O(Q·P·N) jnp outside the
kernel (ops.py). VMEM per cell ≈ Q² + 2·Q·N + 2·Q·P + P·N floats ≈ 0.5 MB at
(Q,P,N) = (256,64,128); all matmul dims are 128-multiples (Q=256, N=128) or
the packed-lane 64 (P) — MXU-friendly. Every block's last two dims are
whole array dims, which the TPU compiler requires of blocks that are not
(8, 128) multiples.

Validated with interpret=True against ref.ssd_reference (naive per-token
recurrence).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, cs_ref, cdecay_ref):
    """Grid: (B, H, nc). Blocks: x (Q,P), dt (1,Q) row, a (1,1) per head,
    b/c (Q,N) (group-mapped in the index_map).

    Every per-chunk vector is a (1, Q) lane row, so each block's last two
    dims are whole array dims (the TPU tiling rule). The prefix sums and
    the row→column turns are triangular/identity matmuls at full f32
    precision on the MXU."""
    x = x_ref[0, 0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)             # (1, Q)
    a_h = a_ref[0].astype(jnp.float32)                   # (1, 1)
    bm = b_ref[0, 0, 0].astype(jnp.float32)              # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)              # (Q, N)
    q = x.shape[0]

    def dot(l, r, dims):
        return jax.lax.dot_general(l, r, (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = row >= col                                     # i ≥ j
    a = dt * a_h                                         # (1, Q) ≤ 0
    cs = dot(a, tri.astype(jnp.float32), ((1,), (1,)))   # (1, Q) cumsum
    cs_col = dot(tri.astype(jnp.float32), a, ((1,), (1,)))   # (Q, 1)
    dt_col = dot((row == col).astype(jnp.float32), dt, ((1,), (1,)))
    seg = cs_col - cs                                    # cs_i − cs_j
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)

    scores = dot(cm, bm, ((1,), (1,)))                   # (Q_i, Q_j)
    w = scores * decay * dt                              # × dt_j
    y = dot(w, x, ((1,), (0,)))                          # (Q, P)

    last = cs[:, q - 1:]                                 # (1, 1)
    wstate = jnp.exp(last - cs_col) * dt_col             # (Q, 1)
    state = dot(bm * wstate, x, ((0,), (0,)))            # (N, P)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0, 0] = state
    cs_ref[0, 0, 0] = cs
    cdecay_ref[0, 0, 0] = jnp.exp(last)


def ssd_chunk_pallas(xh, dt, a_h, bm, cm, *, chunk: int,
                     interpret=None) -> Tuple[jax.Array, ...]:
    """Intra-chunk SSD terms.

    xh: (B, S, H, P); dt: (B, S, H) (post-softplus); a_h: (H,) negative;
    bm/cm: (B, S, G, N). Returns (y_intra (B,S,H,P), states (B,nc,H,N,P),
    cs (B,nc,H,Q), chunk_decay (B,nc,H)).
    """
    b, s, h, p = xh.shape
    g, n = bm.shape[2], bm.shape[3]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    rep = h // g
    from repro.kernels import auto_interpret
    interpret = auto_interpret(interpret)

    # layout: (B, H, nc, Q, ...) so the grid walks contiguous blocks; the
    # per-token vectors ride as (1, Q) rows
    x_l = xh.transpose(0, 2, 1, 3).reshape(b, h, nc, q, p)
    dt_l = dt.transpose(0, 2, 1).reshape(b, h, nc, 1, q)
    a_l = a_h.reshape(h, 1, 1)
    b_l = bm.transpose(0, 2, 1, 3).reshape(b, g, nc, q, n)
    c_l = cm.transpose(0, 2, 1, 3).reshape(b, g, nc, q, n)

    def block(*tail):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda b_, h_, c_: (b_, h_, c_) + (0,) * len(tail))

    def group_block(*tail):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda b_, h_, c_: (b_, h_ // rep, c_)
                            + (0,) * len(tail))

    y, states, cs, cdecay = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(b, h, nc),
        in_specs=[
            block(q, p),
            block(1, q),
            pl.BlockSpec((1, 1, 1), lambda b_, h_, c_: (h_, 0, 0)),
            group_block(q, n),
            group_block(q, n),
        ],
        out_specs=[block(q, p), block(n, p), block(1, q), block(1, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, q, p), xh.dtype),
            jax.ShapeDtypeStruct((b, h, nc, n, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, 1, q), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x_l, dt_l, a_l, b_l, c_l)

    y_intra = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    states = states.transpose(0, 2, 1, 3, 4)             # (B, nc, H, N, P)
    cs = cs[..., 0, :].transpose(0, 2, 1, 3)             # (B, nc, H, Q)
    cdecay = cdecay[..., 0, 0].transpose(0, 2, 1)        # (B, nc, H)
    return y_intra, states, cs, cdecay
