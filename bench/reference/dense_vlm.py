"""Qwen2-style decoder LM with an image-patch prefix (InternVL2's language
side), in plain float32 `jax.numpy`.

Follows Qwen2 (arXiv:2407.10671): pre-norm RMSNorm blocks, grouped-query
attention with biases on q, k and v, rotary embeddings (rotate-half form),
a SwiGLU MLP, a final RMSNorm and a linear head. The layout is InternVL2's
(arXiv:2404.16821): the projected vision tokens come first, then the text,
one causal sequence; the loss is next-token over the text positions only.

Departures, each also in the program: the vision tower and its projector
are not run, the patch embeddings are inputs; the head is its own matrix
(Qwen2-0.5B ties it to the embedding); the configuration file gives the
rotary base and the norm epsilon as run.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference.common import (F32, Init, dense, identity, nll_sum,
                                    rms_norm, rope, stacked)


def spec(m: Dict) -> Dict:
    d, f, v = m["d_model"], m["d_ff"], m["vocab_size"]
    dh = m.get("head_dim") or d // m["num_heads"]
    hq, hkv = m["num_heads"] * dh, m["num_kv_heads"] * dh
    attn = {"wq": dense(d, hq), "wk": dense(d, hkv), "wv": dense(d, hkv),
            "wo": dense(hq, d)}
    if m.get("qkv_bias"):
        attn.update(bq=Init((hq,), "zeros"), bk=Init((hkv,), "zeros"),
                    bv=Init((hkv,), "zeros"))
    layer = {"ln1": Init((d,), "ones"), "ln2": Init((d,), "ones"),
             "attn": attn,
             "mlp": {"w_gate": dense(d, f), "w_up": dense(d, f),
                     "w_down": dense(f, d)}}
    out = {"embed": Init((v, d), "normal", 0.02),
           "layers": stacked(layer, m["num_layers"]),
           "ln_f": Init((d,), "ones")}
    if not m.get("tie_embeddings"):
        out["lm_head"] = dense(d, v)
    return out


def _attention(p, x, m, q8):
    s = x.shape[0]
    dh = m.get("head_dim") or m["d_model"] // m["num_heads"]
    h, hkv = m["num_heads"], m["num_kv_heads"]
    q = q8(x) @ q8(p["wq"]) + p.get("bq", 0.0)
    k = q8(x) @ q8(p["wk"]) + p.get("bk", 0.0)
    v = q8(x) @ q8(p["wv"]) + p.get("bv", 0.0)
    q = rope(q.reshape(s, h, dh), m["rope_theta"])
    k = rope(k.reshape(s, hkv, dh), m["rope_theta"])
    v = v.reshape(s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=1)            # each kv head serves
    v = jnp.repeat(v, h // hkv, axis=1)            # h/hkv query heads
    scores = jnp.einsum("qhd,khd->hqk", q8(q), q8(k)) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = jnp.einsum("hqk,khd->qhd", q8(probs), q8(v)).reshape(s, h * dh)
    return q8(ctx) @ q8(p["wo"])


def row_loss(m: Dict, q8: Callable = identity) -> Callable:
    """``loss(w, row)``: Σ next-token NLL over one row's text positions.
    ``row``: tokens (T,), labels (T,), patches (P, d). ``q8`` rounds each
    matmul operand (identity for float32; `common.fp8` for the control)."""
    eps = m["norm_eps"]

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(p["attn"], rms_norm(x, p["ln1"], eps), m, q8)
        h = rms_norm(x, p["ln2"], eps)
        mlp = p["mlp"]
        a = jax.nn.silu(q8(h) @ q8(mlp["w_gate"])) * (q8(h) @ q8(mlp["w_up"]))
        return x + q8(a) @ q8(mlp["w_down"]), None

    def loss(w, row):
        x = w["embed"][row["tokens"]]
        n_patch = 0
        if "patches" in row:
            n_patch = row["patches"].shape[0]
            x = jnp.concatenate([row["patches"].astype(F32), x], 0)
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = rms_norm(x[n_patch:], w["ln_f"], eps)
        head = w["embed"].T if m.get("tie_embeddings") else w["lm_head"]
        return nll_sum(q8(x) @ q8(head), row["labels"])

    return loss
