"""Mamba-2 language model (arXiv:2405.21060), in plain float32 `jax.numpy`.

Each layer is x + Mamba2(RMSNorm(x)). The block follows the paper's
Section 7 and its reference code: input projections to z, x, B, C and dt;
a causal depthwise convolution of width ``d_conv`` over x, B and C, then
SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); the selective state
space y = SSD(x·dt, A·dt, B, C) + D·x, computed by the paper's minimal
chunked algorithm (its Listing 1, `ssd`); then RMSNorm(y · SiLU(z)) and the
output projection. A final RMSNorm and a linear head give the logits.

Departures, each also in the program: the input projection is five
matrices rather than one (the same map); the convolution has no bias; the
head is its own matrix (Mamba-2 ties it to the embedding).
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference.common import (Init, dense, identity, nll_sum, rms_norm,
                                    stacked)


def spec(m: Dict) -> Dict:
    d, v, s = m["d_model"], m["vocab_size"], m["ssm"]
    d_in = s["expand"] * d
    h = d_in // s["head_dim"]
    gn = s["ngroups"] * s["d_state"]
    block = {
        "wz": dense(d, d_in), "wx": dense(d, d_in),
        "wB": dense(d, gn), "wC": dense(d, gn), "wdt": dense(d, h),
        "conv_x": Init((s["d_conv"], d_in), "normal", 0.2),
        "conv_B": Init((s["d_conv"], gn), "normal", 0.2),
        "conv_C": Init((s["d_conv"], gn), "normal", 0.2),
        "A_log": Init((h,), "a_log"), "D": Init((h,), "ones"),
        "dt_bias": Init((h,), "dt_bias"), "norm_w": Init((d_in,), "ones"),
        "wo": dense(d_in, d)}
    out = {"embed": Init((v, d), "normal", 0.02),
           "layers": stacked({"ln": Init((d,), "ones"), "ssm": block},
                             m["num_layers"]),
           "ln_f": Init((d,), "ones")}
    if not m.get("tie_embeddings"):
        out["lm_head"] = dense(d, v)
    return out


def segsum(a):
    """(..., T) -> (..., T, T): out[i, j] = a[j+1] + ... + a[i] for j <= i,
    -inf above the diagonal (the paper's stable segment sum)."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (t,))        # x[i, j] = a[i]
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), x, -jnp.inf)


def ssd(x, a, b, c, block: int, q8: Callable = identity):
    """The paper's minimal SSD (Listing 1) for one sequence.
    x: (L, H, P) inputs already scaled by dt; a: (L, H) = A·dt;
    b, c: (L, G, N) with the H heads split evenly over the G groups."""
    l, h, p = x.shape
    g = b.shape[1]
    b = jnp.repeat(b, h // g, axis=1)
    c = jnp.repeat(c, h // g, axis=1)
    nc = l // block
    x, b, c = (t.reshape((nc, block) + t.shape[1:]) for t in (x, b, c))
    a = a.reshape(nc, block, h).transpose(2, 0, 1)            # (H, C, Q)
    a_cum = jnp.cumsum(a, -1)
    # 1. the output within each chunk (diagonal blocks)
    decay = jnp.exp(segsum(a))                                # (H, C, Q, Q)
    y_diag = jnp.einsum("clhn,cshn,hcls,cshp->clhp", q8(c), q8(b), decay,
                        q8(x))
    # 2. the state each chunk leaves
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)           # (H, C, Q)
    states = jnp.einsum("clhn,hcl,clhp->chpn", q8(b), decay_states, q8(x))
    # 3. the states passed between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], 0)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (1, 0)))))
    states = jnp.einsum("hzc,chpn->zhpn", decay_chunk, states)[:-1]
    # 4. the output those states give
    y_off = jnp.einsum("clhn,chpn,hcl->clhp", q8(c), q8(states),
                       jnp.exp(a_cum))
    return (y_diag + y_off).reshape(l, h, p)


def causal_conv(u, w):
    """Depthwise causal convolution. u: (L, C), w: (K, C); w[K-1] weighs
    the current position, w[K-1-i] the one i back."""
    k = w.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(padded[i:i + u.shape[0]] * w[i] for i in range(k))


def row_loss(m: Dict, q8: Callable = identity) -> Callable:
    """``loss(w, row)``: Σ next-token NLL over one row. ``row``: tokens
    (T,), labels (T,). ``q8`` rounds each matmul operand (identity for
    float32; `common.fp8` for the control)."""
    s = m["ssm"]
    eps = m["norm_eps"]
    hd = s["head_dim"]

    def mixer(p, u):
        seq = u.shape[0]
        z = q8(u) @ q8(p["wz"])
        xs = jax.nn.silu(causal_conv(q8(u) @ q8(p["wx"]), p["conv_x"]))
        b = jax.nn.silu(causal_conv(q8(u) @ q8(p["wB"]), p["conv_B"]))
        c = jax.nn.silu(causal_conv(q8(u) @ q8(p["wC"]), p["conv_C"]))
        dt = jax.nn.softplus(q8(u) @ q8(p["wdt"]) + p["dt_bias"])  # (L, H)
        a = -jnp.exp(p["A_log"])
        x = xs.reshape(seq, -1, hd)
        g = s["ngroups"]
        y = ssd(x * dt[..., None], a * dt, b.reshape(seq, g, -1),
                c.reshape(seq, g, -1), s["chunk_size"], q8)
        y = (y + p["D"][:, None] * x).reshape(seq, -1)
        y = rms_norm(y * jax.nn.silu(z), p["norm_w"], eps)
        return q8(y) @ q8(p["wo"])

    @jax.checkpoint
    def layer(x, p):
        return x + mixer(p["ssm"], rms_norm(x, p["ln"], eps)), None

    def loss(w, row):
        x = w["embed"][row["tokens"]]
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = rms_norm(x, w["ln_f"], eps)
        head = w["embed"].T if m.get("tie_embeddings") else w["lm_head"]
        return nll_sum(q8(x) @ q8(head), row["labels"])

    return loss
