"""Fused elastic SGD update (Pallas): Eq. (5)'s masked-renormalized mean
gradient folded into the momentum/parameter apply, over the replica-blocked
flat parameter layout of ``train.megabatch``.

The megabatched trainer computes gradients of the *sum*-form loss
(Σ_tokens w·nll), so per replica the Eq.-(5) renormalization is a scalar:
``ḡ = g_sum / Σw`` when Σw > 0, exactly 0 when every worker is preempted
(the ``core.elastic.weighted_mean`` semantics). This kernel fuses, per
(replica, parameter-block) grid cell:

    inv  = Σw > 0 ? 1/Σw : 0          # renormalize, exact-zero on Σw = 0
    v'   = μ·v + g_sum·inv            # SGD momentum (non-nesterov)
    p'   = p − lr·v'
    p,v  = running ? (p', v') : (p, v)   # idle/finished ticks are no-ops

One kernel launch updates every parameter of every replica: inputs are the
flat ``(R, P)`` parameter/momentum/gradient blocks plus per-replica scalars
``w_sum``/``running``/``lr`` (kept as (R, 1) columns that broadcast along
each replica's row). The grid walks P in ``(R, block)`` tiles: every tile
holds all replicas (a whole array dim) and a lane-aligned slice of P, the
shapes the TPU compiler accepts, and streams through VMEM.

Validated on CPU with interpret=True against ``ref.elastic_update_reference``
(see tests/test_megabatch.py); on CPU execution paths the jnp reference is
the compiled fallback (``kernels.ops.fused_elastic_update``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: elements per (R, block) tile: 1 MiB of f32 in each streamed buffer
TILE_ELEMS = 1 << 18
LANES = 128


def _update_kernel(p_ref, v_ref, g_ref, w_ref, run_ref, lr_ref,
                   p_out, v_out, *, momentum: float):
    w = w_ref[...]                                       # (R, 1)
    # exact 0 on all-preempted; the 1e-6 clamp mirrors train_step's
    # documented grad normalization (max(Σw, 1e-6)) bit-for-bit
    inv = jnp.where(w > 0, 1.0 / jnp.maximum(w, 1e-6), 0.0)
    run = run_ref[...] > 0
    lr = lr_ref[...]
    v = v_ref[...]
    p = p_ref[...]
    v_new = momentum * v + g_ref[...] * inv
    p_new = p - lr * v_new
    p_out[...] = jnp.where(run, p_new, p)
    v_out[...] = jnp.where(run, v_new, v)


@functools.partial(jax.jit, static_argnames=("momentum", "block_p",
                                             "interpret"))
def elastic_sgd_update(params: jax.Array, mom: jax.Array, grads: jax.Array,
                       w_sum: jax.Array, running: jax.Array, lr: jax.Array,
                       *, momentum: float = 0.9,
                       block_p: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       ) -> Tuple[jax.Array, jax.Array]:
    """params/mom/grads: (R, P) f32; w_sum/running/lr: (R,). Returns the
    updated (params, mom). ``grads`` are SUM-form (unnormalized) gradients;
    the Eq.-(5) division by Σw happens inside the kernel. ``block_p`` (a
    multiple of 128) overrides the tile width, which by default holds
    `TILE_ELEMS` elements."""
    from repro.kernels import auto_interpret

    r, p_dim = params.shape
    interpret = auto_interpret(interpret)
    if block_p is None:
        block_p = max(LANES, TILE_ELEMS // r // LANES * LANES)
    blk = min(block_p, p_dim)
    cols = lambda x, dt: x.astype(dt).reshape(r, 1)
    w2 = cols(w_sum, jnp.float32)
    run2 = cols(running, jnp.float32)
    lr2 = cols(lr, jnp.float32)

    tile = pl.BlockSpec((r, blk), lambda j: (0, j))
    scal = pl.BlockSpec((r, 1), lambda j: (0, 0))
    out_shape = jax.ShapeDtypeStruct(params.shape, params.dtype)
    # a ragged last tile needs no padding copy: the update is elementwise,
    # and writes past P are dropped; params/mom update in place
    return pl.pallas_call(
        functools.partial(_update_kernel, momentum=momentum),
        grid=(pl.cdiv(p_dim, blk),),
        in_specs=[tile, tile, tile, scal, scal, scal],
        out_specs=(tile, tile),
        out_shape=(out_shape, out_shape),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(params, mom, grads, w2, run2, lr2)
