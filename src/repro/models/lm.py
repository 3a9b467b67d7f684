"""Decoder-only LM assembly for the dense / moe / ssm / hybrid / vlm families.

Uniform layers are stacked ([L, ...] leaves) and executed with
``jax.lax.scan`` so a 64-layer model lowers to a compact HLO (fast AOT
compiles for the 512-device dry-run); per-layer ``jax.checkpoint`` gives the
remat policy. Non-uniform prefixes (moonshot's ``first_k_dense`` dense
layers) live outside the scan.

Public surface (used by dist/ and launch/):
  init_params(cfg, key)                     -> params
  forward(cfg, params, batch, rng)          -> (logits_fn-ready hidden, aux)
  logits(cfg, params, hidden)               -> [B,S,V]
  init_cache(cfg, batch, max_len)           -> cache pytree
  decode_step(cfg, params, tokens, pos, cache) -> (logits [B,1,V], cache)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, key, kind: str):
    """kind: attn_mlp | attn_moe | ssm | hybrid"""
    ks = jax.random.split(key, 8)
    p = {"norm1": L.init_norm(cfg)}
    if kind in ("attn_mlp", "attn_moe", "hybrid"):
        p["attn"] = L.init_attention(cfg, ks[0])
    if kind in ("ssm", "hybrid"):
        p["ssm"] = SSM.init_ssm(cfg, ks[1])
    if kind in ("attn_mlp", "hybrid"):
        p["norm2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(cfg, ks[2])
    if kind == "attn_moe":
        p["norm2"] = L.init_norm(cfg)
        p["moe"] = MOE.init_moe(cfg, ks[3])
    return p


def _layer_apply(cfg: ModelConfig, p, x, *, kind, positions, rng, cache, shard_ctx):
    aux = jnp.float32(0.0)
    new_cache = {}
    if kind == "ssm":
        if shard_ctx is not None and cache is None:
            x = shard_ctx.constrain(x)
        h, c = SSM.ssm_apply(cfg, p["ssm"], L.norm_apply(cfg, p["norm1"], x),
                             cache=None if cache is None else cache["ssm"])
        x = x + h
        if cache is not None:
            new_cache["ssm"] = c
        return x, aux, new_cache

    if kind == "hybrid":
        if shard_ctx is not None and cache is None:
            x = shard_ctx.constrain(x)
        xin = L.norm_apply(cfg, p["norm1"], x)
        a, ca = L.attention_apply(
            cfg, p["attn"], xin, positions=positions,
            cache=None if cache is None else cache["attn"],
        )
        s, cs = SSM.ssm_apply(cfg, p["ssm"], xin,
                              cache=None if cache is None else cache["ssm"])
        x = x + 0.5 * (a + s)  # hymba: parallel attn+SSM heads, fused mean
        x = x + L.mlp_apply(cfg, p["mlp"], L.norm_apply(cfg, p["norm2"], x))
        if cache is not None:
            new_cache = {"attn": ca, "ssm": cs}
        return x, aux, new_cache

    # attn_mlp / attn_moe
    if shard_ctx is not None and cache is None:
        x = shard_ctx.constrain(x)
    a, ca = L.attention_apply(
        cfg, p["attn"], L.norm_apply(cfg, p["norm1"], x), positions=positions,
        cache=None if cache is None else cache["attn"],
    )
    x = x + a
    h = L.norm_apply(cfg, p["norm2"], x)
    if kind == "attn_moe":
        m, aux = MOE.moe_apply(cfg, p["moe"], h, rng=rng, shard_ctx=shard_ctx)
        x = x + m
    else:
        x = x + L.mlp_apply(cfg, p["mlp"], h)
    if cache is not None:
        new_cache = {"attn": ca}
    return x, aux, new_cache


def _layer_kinds(cfg: ModelConfig) -> tuple[str, str, int]:
    """(prefix_kind, main_kind, n_prefix)."""
    if cfg.family == "moe":
        return "attn_mlp", "attn_moe", cfg.first_k_dense
    if cfg.family == "ssm":
        return "ssm", "ssm", 0
    if cfg.family == "hybrid":
        return "hybrid", "hybrid", 0
    return "attn_mlp", "attn_mlp", 0  # dense, vlm


# ---------------------------------------------------------------------------
# Model init / forward
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key):
    kp, ke, kh, kl = jax.random.split(key, 4)
    prefix_kind, main_kind, n_prefix = _layer_kinds(cfg)
    n_main = cfg.n_layers - n_prefix

    params = {
        "embed": L.dense_init(ke, (cfg.vocab, cfg.d_model), L._pdtype(cfg), scale=0.02),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(kh, (cfg.d_model, cfg.vocab), L._pdtype(cfg))
    if cfg.family == "vlm":
        params["patch_proj"] = L.dense_init(
            jax.random.fold_in(kp, 7), (cfg.d_model, cfg.d_model), L._pdtype(cfg)
        )

    if n_prefix:
        params["prefix_layers"] = [
            _init_layer(cfg, jax.random.fold_in(kp, i), prefix_kind)
            for i in range(n_prefix)
        ]
    if cfg.scan_layers:
        keys = jax.random.split(kl, n_main)
        params["layers"] = jax.vmap(
            lambda k: _init_layer(cfg, k, main_kind)
        )(keys)
    else:
        params["layers"] = [
            _init_layer(cfg, jax.random.fold_in(kl, i), main_kind)
            for i in range(n_main)
        ]
    return params


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def embed_tokens(cfg: ModelConfig, params, tokens, patch_embeds=None):
    x = params["embed"][tokens].astype(L._dtype(cfg))
    if cfg.family == "vlm" and patch_embeds is not None:
        # stub frontend: first n_patches positions carry projected patch embeds
        pe = (patch_embeds.astype(L._dtype(cfg)) @ params["patch_proj"].astype(L._dtype(cfg)))
        npatch = pe.shape[1]
        x = jnp.concatenate([pe, x[:, npatch:, :]], axis=1)
    return x


def backbone(cfg: ModelConfig, params, x, *, positions, rng=None, cache=None,
             shard_ctx=None):
    """Run all layers. cache: None (train/prefill-no-cache) or pytree of
    per-layer caches. Returns (hidden, aux_loss_sum, new_cache)."""
    prefix_kind, main_kind, n_prefix = _layer_kinds(cfg)
    aux_total = jnp.float32(0.0)
    new_cache = {}

    for i in range(n_prefix):
        c_i = None if cache is None else cache["prefix"][i]
        x, aux, nc = _layer_apply(
            cfg, params["prefix_layers"][i], x, kind=prefix_kind,
            positions=positions, rng=rng, cache=c_i, shard_ctx=shard_ctx,
        )
        aux_total += aux
        if cache is not None:
            new_cache.setdefault("prefix", []).append(nc)

    n_main = cfg.n_layers - n_prefix
    if cfg.scan_layers:
        def body(carry, inp):
            xc, auxc = carry
            # barrier: stops XLA hoisting per-layer dtype converts out of the
            # loop (which would materialize an fp32 copy of the whole
            # [L, B, S, d] remat stack — measured 2× activation memory).
            xc = jax.lax.optimization_barrier(xc)
            lp, lrng, lcache = inp
            xo, aux, nc = _layer_apply(
                cfg, lp, xc, kind=main_kind, positions=positions,
                rng=lrng, cache=lcache, shard_ctx=shard_ctx,
            )
            return (xo, auxc + aux), nc

        body = _maybe_remat(cfg, body)
        rngs = (
            jax.random.split(rng, n_main)
            if rng is not None
            else jnp.zeros((n_main, 2), jnp.uint32)
        )
        lcaches = cache["layers"] if cache is not None else None
        if lcaches is None:
            (x, aux_total), _ = jax.lax.scan(
                lambda c, inp: body(c, (inp[0], inp[1], None)),
                (x, aux_total), (params["layers"], rngs),
            )
            ncs = None
        else:
            (x, aux_total), ncs = jax.lax.scan(
                body, (x, aux_total), (params["layers"], rngs, lcaches)
            )
        if cache is not None:
            new_cache["layers"] = ncs
    else:
        for i in range(n_main):
            c_i = None if cache is None else cache["layers"][i]
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            x, aux, nc = _layer_apply(
                cfg, params["layers"][i], x, kind=main_kind, positions=positions,
                rng=lrng, cache=c_i, shard_ctx=shard_ctx,
            )
            aux_total += aux
            if cache is not None:
                new_cache.setdefault("layers", []).append(nc)

    x = L.norm_apply(cfg, params["final_norm"], x)
    return x, aux_total, (new_cache if cache is not None else None)


def logits_head(cfg: ModelConfig, params, hidden):
    dt = L._dtype(cfg)
    if cfg.tie_embeddings:
        return hidden @ params["embed"].astype(dt).T
    return hidden @ params["lm_head"].astype(dt)


def forward(cfg: ModelConfig, params, tokens, *, patch_embeds=None, rng=None,
            shard_ctx=None):
    """Full training/prefill forward → (hidden [B,S,d], aux)."""
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens, patch_embeds)
    positions = jnp.arange(S)
    hidden, aux, _ = backbone(
        cfg, params, x, positions=positions, rng=rng, cache=None,
        shard_ctx=shard_ctx,
    )
    return hidden, aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _attn_cache(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.kv_quant:
        return {
            "k_q": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), jnp.int8),
            "k_s": jnp.ones((batch, max_len, cfg.n_kv_heads), jnp.bfloat16),
            "v_q": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), jnp.int8),
            "v_s": jnp.ones((batch, max_len, cfg.n_kv_heads), jnp.bfloat16),
            "len": jnp.int32(0),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), jnp.dtype(cfg.dtype)),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), jnp.dtype(cfg.dtype)),
        "len": jnp.int32(0),
    }


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind == "ssm":
        return {"ssm": SSM.init_ssm_cache(cfg, batch)}
    if kind == "hybrid":
        return {"attn": _attn_cache(cfg, batch, max_len),
                "ssm": SSM.init_ssm_cache(cfg, batch)}
    return {"attn": _attn_cache(cfg, batch, max_len)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    prefix_kind, main_kind, n_prefix = _layer_kinds(cfg)
    cache = {}
    if n_prefix:
        cache["prefix"] = [
            _layer_cache(cfg, prefix_kind, batch, max_len) for _ in range(n_prefix)
        ]
    n_main = cfg.n_layers - n_prefix
    one = _layer_cache(cfg, main_kind, batch, max_len)
    if cfg.scan_layers:
        cache["layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_main,) + a.shape), one
        )
    else:
        cache["layers"] = [
            _layer_cache(cfg, main_kind, batch, max_len) for _ in range(n_main)
        ]
    return cache


def decode_step(cfg: ModelConfig, params, tokens, pos, cache, *, rng=None,
                shard_ctx=None):
    """One decode step. tokens [B,1]; pos scalar int32 (current position).
    Returns (logits [B,1,V], new_cache)."""
    x = params["embed"][tokens].astype(L._dtype(cfg))
    positions = pos[None] if pos.ndim == 0 else pos
    hidden, _, new_cache = backbone(
        cfg, params, x, positions=positions, rng=rng, cache=cache,
        shard_ctx=shard_ctx,
    )
    return logits_head(cfg, params, hidden), new_cache
