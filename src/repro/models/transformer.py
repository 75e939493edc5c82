"""Decoder-only transformer LM covering the dense, MoE (incl. MLA) and VLM
families. Layers run under ``jax.lax.scan`` with configurable remat so the
HLO stays one-layer-sized regardless of depth."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    ParamSpec,
    dense_spec,
    rms_norm,
    shard,
    stack_specs,
)


def mlp_defs(cfg):
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": dense_spec(d, f),
        "w_up": dense_spec(d, f),
        "w_down": dense_spec(f, d, logical=("tp", "fsdp")),
    }


def mlp_block(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, "batch", None, "tp")
    return shard(h @ p["w_down"], "batch", "residual", None)


def layer_defs(cfg):
    d = cfg.d_model
    defs = {"ln1": ParamSpec((d,), (None,), init="ones"),
            "ln2": ParamSpec((d,), (None,), init="ones")}
    if cfg.mla is not None:
        defs["mla"] = mla_mod.mla_defs(cfg)
    else:
        defs["attn"] = attn.attn_defs(cfg)
    if cfg.moe is not None:
        defs["moe"] = moe_mod.moe_defs(cfg)
    else:
        defs["mlp"] = mlp_defs(cfg)
    return defs


def decoder_layer(p, cfg, x, qpos, *, cache=None, cache_pos=None,
                  kv_src=None, kv_pos=None, causal=True):
    """Pre-norm block. Returns (x, new_cache, aux)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        a, new_cache = mla_mod.mla_block(p["mla"], cfg, h, qpos, cache=cache,
                                         cache_pos=cache_pos)
    else:
        a, new_cache = attn.attention_block(
            p["attn"], cfg, h, qpos, cache=cache, cache_pos=cache_pos,
            kv_src=kv_src, kv_pos=kv_pos, causal=causal)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        m, aux = moe_mod.moe_block(p["moe"], cfg, h)
    else:
        m, aux = mlp_block(p["mlp"], h), jnp.zeros((), jnp.float32)
    return x + m, new_cache, aux


def lm_defs(cfg):
    d, v = cfg.d_model, cfg.vocab_size
    defs = {
        "embed": ParamSpec((v, d), ("tp", None), scale=0.02),
        "layers": stack_specs(layer_defs(cfg), cfg.num_layers),
        "ln_f": ParamSpec((d,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = dense_spec(d, v)
    return defs


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return jax.checkpoint(fn, prevent_cse=False)


def scan_decoder(layers_p, cfg, x, qpos, *, caches=None, cache_pos=None,
                 kv_src=None, kv_pos=None, causal=True, remat="full"):
    """Scan the (stacked) decoder layers. Returns (x, new_caches, aux_sum)."""

    def body(x, layer_p, cache):
        return decoder_layer(layer_p, cfg, x, qpos, cache=cache,
                             cache_pos=cache_pos, kv_src=kv_src,
                             kv_pos=kv_pos, causal=causal)

    body = _remat(body, remat)

    if caches is None:
        def step(carry, layer_p):
            x, aux = carry
            x, _, a = body(x, layer_p, None)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                                   layers_p)
        return x, None, aux

    def step(carry, xs):
        x, aux = carry
        layer_p, cache = xs
        x, new_cache, a = body(x, layer_p, cache)
        return (x, aux + a), new_cache

    (x, aux), new_caches = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.float32)), (layers_p, caches))
    return x, new_caches, aux


def embed_tokens(params, cfg, tokens):
    e = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype())
    return shard(e, "batch", "residual", None)


def unembed(params, cfg, x):
    with obs.scope("repro.loss_head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(x.dtype)
        return shard(logits, "batch", None, "tp")


def lm_forward(params, cfg, tokens, *, prefix_embeds=None, remat="full"
               ) -> Tuple[jax.Array, jax.Array]:
    """Training/prefill forward. tokens: (B, S_text). ``prefix_embeds``
    (B, P, d) are precomputed frontend embeddings (VLM patches) prefixed to
    the token embeddings. Returns (logits (B, S_total, V), moe_aux)."""
    x = embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _, aux = scan_decoder(params["layers"], cfg, x, qpos, remat=remat)
    return unembed(params, cfg, x), aux


def lm_decode(params, cfg, token, caches, pos):
    """Decode (S=1) or chunked prefill (S>1) against the cache. token:
    (B, S) int32 written at positions pos..pos+S−1 (uniform across the
    batch — production per-sequence offsets are a straightforward
    extension). Returns (logits (B,S,V), new_caches)."""
    x = embed_tokens(params, cfg, token)
    b, s, _ = x.shape
    qpos = pos + jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, new_caches, _ = scan_decoder(params["layers"], cfg, x, qpos,
                                    caches=caches, cache_pos=pos, remat="none")
    return unembed(params, cfg, x), new_caches


def lm_cache_defs(cfg, batch: int, seq_len: int):
    if cfg.mla is not None:
        one = mla_mod.mla_cache_defs(cfg, batch, seq_len)
    else:
        one = attn.self_cache_defs(cfg, batch, seq_len)
    return stack_specs(one, cfg.num_layers)
