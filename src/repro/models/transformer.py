"""Dense GQA decoder-only transformer (qwen2 / minitron / deepseek / phi3
families) plus the VLM variant (internvl2 backbone with stub vision prefix).

Layer params are stacked on a leading L axis; ``lax.scan`` keeps the HLO
compact for 95-layer dry-run compiles, ``unroll=True`` flattens for the
cost-analysis probes. The MoE model reuses this module's plumbing with its
own block functions (see :mod:`repro.models.moe`).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import stack
from repro.models.kvlayout import KVLayout
from repro.models.layers import LayerCtx, Params

# dense-KV family: the (L, B, S, HK, Dh) cache admits the block-paged
# storage discipline (PagedLayout + block tables)
PAGED_KV = True


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def layer_params(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": L.norm_params(cfg, cfg.d_model),
        "attn": L.attention_params(cfg, k1),
        "mlp_norm": L.norm_params(cfg, cfg.d_model),
        "mlp": L.mlp_params(cfg, k2),
    }


def init_params(cfg: ModelConfig, key,
                layer_params_fn: Callable = None) -> Params:
    lp = layer_params_fn or layer_params
    ke, kl = jax.random.split(key)
    lkeys = jax.random.split(kl, cfg.num_layers)
    stacked = jax.vmap(lambda k: lp(cfg, k))(lkeys)
    return {
        **L.embed_params(cfg, ke),
        "layers": stacked,
        "final_norm": L.norm_params(cfg, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Blocks (dense). MoE swaps the mlp half.
# ---------------------------------------------------------------------------


def block(ctx: LayerCtx, p: Params, x: jax.Array,
          positions: jax.Array):
    cfg = ctx.cfg
    h = L.norm(cfg, p["attn_norm"], x)
    x = x + L.attention_block(ctx, p["attn"], h, positions)
    x = ctx.shard(x, "act_resid")
    h = L.norm(cfg, p["mlp_norm"], x)
    x = x + L.mlp_block(ctx, p["mlp"], h)
    return ctx.shard(x, "act_resid"), jnp.zeros((), jnp.float32)


def decode_block(ctx: LayerCtx, p: Params, x: jax.Array, position: jax.Array,
                 cache: dict, lengths: jax.Array,
                 block_tables: Optional[jax.Array] = None,
                 decode_groups=None, layer=None):
    """One-token decode block over either KV layout.

    ``block_tables is None`` means ``cache`` is this layer's dense (B, S,
    HK, Dh) slot cache; with tables it is the whole stacked (L, NP, PS,
    HK, Dh) page pool, addressed through the (B, NB) logical→physical map
    at layer ``layer`` and updated in place. The discriminator is resolved
    at trace time — each engine layout compiles exactly one path.
    ``decode_groups`` (paged only) switches to the prefix-shared grouped
    attention path.

    The layer runs as explicit stage boundaries (ingest → attend →
    epilogue → mlp, see :mod:`repro.models.layers`); the ingest,
    epilogue and mlp seams are one dispatch each.
    """
    q, k, v = L.decode_ingest(ctx, p["attn_norm"], p["attn"], x, position)
    if block_tables is None:
        o, ck, cv = L.decode_attend(
            ctx, q, k, v, cache["k"], cache["v"], lengths
        )
        cache = {"k": ck, "v": cv}
    else:
        o, cache = L.decode_attend_paged(
            ctx, q, k, v, cache, layer, block_tables, lengths,
            decode_groups=decode_groups,
        )
    x = L.decode_epilogue(ctx, p["attn"], o, x)
    x = L.decode_mlp(ctx, p["mlp_norm"], p["mlp"], x)
    return ctx.shard(x, "act_resid"), cache


def chunk_block(ctx: LayerCtx, p: Params, x: jax.Array, cache: dict,
                lengths: jax.Array, chunk_lens: jax.Array,
                block_tables: Optional[jax.Array] = None, layer=None):
    """Chunked-prefill block (decode-shaped path) over either KV layout
    (``cache``/``layer`` as in :func:`decode_block`)."""
    cfg = ctx.cfg
    h = L.norm(cfg, p["attn_norm"], x)
    if block_tables is None:
        a, ck, cv = L.attention_chunk_block(
            ctx, p["attn"], h, cache["k"], cache["v"], lengths,
            chunk_lens
        )
        cache = {"k": ck, "v": cv}
    else:
        a, cache = L.attention_chunk_block_paged(
            ctx, p["attn"], h, cache, layer, block_tables, lengths,
            chunk_lens,
        )
    x = x + a
    h = L.norm(cfg, p["mlp_norm"], x)
    x = x + L.mlp_block(ctx, p["mlp"], h)
    return ctx.shard(x, "act_resid"), cache


def prefill_block(ctx: LayerCtx, p: Params, x: jax.Array,
                  positions: jax.Array, s_max: int):
    """Like ``block`` but also emits this layer's (padded) KV cache entry."""
    cfg = ctx.cfg
    b, s, _ = x.shape
    h = L.norm(cfg, p["attn_norm"], x)
    q, k, v = L.attention_qkv(ctx, p["attn"], h, positions)
    from repro.kernels import ops
    o = ops.attention_prefill(
        q, k, v, phi_cfg=ctx.phi_cfg, causal=True,
        sliding_window=cfg.sliding_window, plan=ctx.plan,
    )
    o = ctx.shard(o.reshape(b, s, cfg.q_dim), "act_attn_out")
    x = x + ctx.matmul(o, p["attn"]["wo"])
    h = L.norm(cfg, p["mlp_norm"], x)
    x = x + L.mlp_block(ctx, p["mlp"], h)
    pad = [(0, 0), (0, s_max - s), (0, 0), (0, 0)]
    entry = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    return ctx.shard(x, "act_resid"), entry


# ---------------------------------------------------------------------------
# Forward passes (parameterized over block fns so MoE can reuse them)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, remat: bool):
    if not remat:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def forward_hidden(
    ctx: LayerCtx, params: Params, tokens: jax.Array,
    *, prefix_embeds: Optional[jax.Array] = None,
    unroll: bool = False, remat: bool = False,
    block_fn: Callable = block,
):
    """Token (+ optional embedding prefix) -> (hidden (B,S,D), aux loss)."""
    x = L.embed(ctx, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        x = ctx.shard(x, "act_resid")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    body = _maybe_remat(
        lambda p_i, xx: block_fn(ctx, p_i, xx, positions), remat
    )
    x, aux = stack.run_stack(params["layers"], x, body, unroll=unroll)
    return L.norm(ctx.cfg, params["final_norm"], x), aux


def train_loss(
    ctx: LayerCtx, params: Params, batch: dict,
    *, unroll: bool = False, remat: bool = True,
    block_fn: Callable = block, aux_weight: float = 0.0,
) -> jax.Array:
    x, aux = forward_hidden(
        ctx, params, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        unroll=unroll, remat=remat, block_fn=block_fn,
    )
    if batch.get("prefix_embeds") is not None:
        npfx = batch["prefix_embeds"].shape[1]
        x = x[:, npfx:]
    loss = L.cross_entropy_loss(ctx, params, x, batch["labels"])
    return loss + aux_weight * aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------


def _cache_shapes(cfg: ModelConfig, layout: KVLayout, dtype=None):
    """(pool shape, pool dtype, scale shape or None) for a layout.

    Quantized paged layouts (``layout.kv_dtype`` != bf16) store code pools
    in the spec's code dtype plus per-(layer, page, kv head) f32 step
    pools as extra ``k_scale``/``v_scale`` leaves."""
    dtype = dtype or jnp.dtype(cfg.activation_dtype)
    shape = layout.kv_shape(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim)
    kv_dtype = getattr(layout, "kv_dtype", "bf16")
    if kv_dtype == "bf16":
        return shape, dtype, None
    from repro.kernels import quant
    spec = quant.spec_for(kv_dtype)
    sshape = layout.scale_shape(cfg.num_layers, cfg.num_kv_heads)
    return shape, spec.code_dtype, sshape


def init_cache(cfg: ModelConfig, layout: KVLayout, dtype=None):
    """KV storage for any :class:`~repro.models.kvlayout.KVLayout` — the
    dense (L, B, S, HK, Dh) slot cache or the block-paged (L, NP, PS, HK,
    Dh) pool (per-sequence addressing then lives in the engine's block
    tables — see :mod:`repro.serving.blockpool`). Quantized paged layouts
    add ``k_scale``/``v_scale`` step-pool leaves."""
    shape, dtype, sshape = _cache_shapes(cfg, layout, dtype)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if sshape is not None:
        cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
        cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return cache


def cache_spec(cfg: ModelConfig, layout: KVLayout, dtype=None):
    shape, dtype, sshape = _cache_shapes(cfg, layout, dtype)
    spec = {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}
    if sshape is not None:
        spec["k_scale"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
        spec["v_scale"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
    return spec


def prefill(
    ctx: LayerCtx, params: Params, tokens: jax.Array, lengths: jax.Array,
    cache: dict, *, prefix_embeds: Optional[jax.Array] = None,
    unroll: bool = False, prefill_block_fn: Callable = prefill_block,
):
    """Process the prompt, fill the KV cache, return last-token logits."""
    cfg = ctx.cfg
    x = L.embed(ctx, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    s_max = cache["k"].shape[2]

    x, entries = stack.run_stack_collect(
        params["layers"], x,
        lambda p_i, xx: prefill_block_fn(ctx, p_i, xx, positions, s_max),
        unroll=unroll,
    )
    x = L.norm(cfg, params["final_norm"], x)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].clip(0), axis=1
    )
    logits = L.lm_logits(ctx, params, last)[:, 0]
    cache = {"k": entries["k"].astype(cache["k"].dtype),
             "v": entries["v"].astype(cache["v"].dtype)}
    return logits, cache


def _run_layers(layers_params, x, cache, block_tables, block_fn, *, unroll):
    """The cached depth loop for either KV layout. The paged pool is
    carried whole and written in place (``stack.run_stack_in_place``);
    a dense slot cache goes through the scan per layer slice.
    ``block_fn(p_i, x, cache, layer)``."""
    if block_tables is not None:
        return stack.run_stack_in_place(layers_params, x, cache, block_fn,
                                        unroll=unroll)
    return stack.run_stack_cached(
        layers_params, x, cache,
        lambda p_i, xx, c_i: block_fn(p_i, xx, c_i, None), unroll=unroll)


def decode_step(
    ctx: LayerCtx, params: Params, tokens: jax.Array, cache: dict,
    lengths: jax.Array, *, block_tables: Optional[jax.Array] = None,
    decode_groups=None, positions: Optional[jax.Array] = None,
    unroll: bool = False,
    decode_block_fn: Callable = decode_block,
):
    """One decode step. tokens: (B,) -> logits (B, V_padded), new cache.

    One signature for both KV layouts: with ``block_tables=None`` the cache
    leaves are dense (L, B, S, HK, Dh) slot caches; with a (B, NB)
    logical→physical page map they are (L, NP, PS, HK, Dh) page pools (the
    scan carries the whole pool and each layer writes its new positions in
    place, see :func:`_run_layers`; the table rides in closure).
    ``decode_groups``
    rides along the same way and activates prefix-shared grouped attention
    on the paged layout.

    ``positions`` is the per-row absolute position operand (defaults to
    ``lengths``; the engine passes its device-cached copy). The stacked
    depth runs under one ``lax.scan``; ``unroll=True`` python-unrolls it
    into L traced layer bodies for the dry-run's cost probes. Both apply
    the same per-layer math to the same leading-axis slabs; unrolling
    lets XLA place bf16 roundings at other fusion boundaries, so the two
    agree to within dtype eps, not bitwise.
    """
    cfg = ctx.cfg
    x = L.embed(ctx, params, tokens[:, None])  # (B, 1, D)
    position = lengths if positions is None else positions

    x, new_cache = _run_layers(
        params["layers"], x, cache, block_tables,
        lambda p_i, xx, c, layer: decode_block_fn(
            ctx, p_i, xx, position, c, lengths, block_tables, decode_groups,
            layer=layer),
        unroll=unroll,
    )
    x = L.norm(cfg, params["final_norm"], x)
    logits = L.lm_logits(ctx, params, x)[:, 0]
    return logits, new_cache


def prefill_chunk(
    ctx: LayerCtx, params: Params, tokens: jax.Array,
    chunk_lens: jax.Array, cache: dict, lengths: jax.Array,
    *, block_tables: Optional[jax.Array] = None, unroll: bool = False,
    chunk_block_fn: Callable = chunk_block,
):
    """Process one prompt chunk for a whole (possibly ragged) batch.

    tokens: (B, C); row b consumes its first ``chunk_lens[b]`` entries at
    absolute positions ``lengths[b]..lengths[b]+chunk_lens[b]-1``; rows with
    ``chunk_lens[b] == 0`` are spectators (nothing written, outputs garbage).
    Returns per-row logits at each row's last chunk position and the updated
    cache — long prompts stream through this in fixed-size chunks, and a
    whole admission batch prefills in one call (chunked + batched prefill).
    Starting from ``lengths == 0`` this subsumes single-shot prefill.
    Like :func:`decode_step`, ``block_tables`` selects the KV layout.
    """
    cfg = ctx.cfg
    x = L.embed(ctx, params, tokens)           # (B, C, D)

    x, new_cache = _run_layers(
        params["layers"], x, cache, block_tables,
        lambda p_i, xx, c, layer: chunk_block_fn(
            ctx, p_i, xx, c, lengths, chunk_lens, block_tables, layer=layer),
        unroll=unroll,
    )
    x = L.norm(cfg, params["final_norm"], x)
    last = jnp.take_along_axis(
        x, (chunk_lens - 1)[:, None, None].clip(0), axis=1
    )
    logits = L.lm_logits(ctx, params, last)[:, 0]
    return logits, new_cache
