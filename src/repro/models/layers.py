"""Shared building blocks for the model zoo.

Everything is a pure function over explicit param pytrees (dicts of arrays):
no framework magic, scan-compatible (layer params are stacked on a leading L
axis by the model constructors), and shardable with `with_sharding_constraint`
through the rules in :mod:`repro.distributed.sharding`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, SoftmaxPhiConfig
from repro.core.plan import DEFAULT_PLAN, ExecutionPlan
from repro.kernels import ops, quant
from repro.models.kvlayout import PagedLayout

Params = dict
ShardFn = Callable[[jax.Array, str], jax.Array]  # (x, logical role) -> x


def no_shard(x: jax.Array, role: str) -> jax.Array:  # default: no constraints
    return x


@dataclasses.dataclass(frozen=True)
class LayerCtx:
    """Per-call context threaded through every layer."""

    cfg: ModelConfig
    shard: ShardFn = no_shard
    # every implementation decision — GEMM routing, softmax scheme, decode
    # block_k, fallback branches, Pallas vs. XLA backend — lives in the
    # plan (repro.core.plan); the untuned default is the XLA reference path
    plan: ExecutionPlan = DEFAULT_PLAN
    # MoE routing group count (= data-parallel shard count at scale)
    moe_groups: int = 1
    # attention combine override, set by the distributed decode path
    decode_attention_fn: Optional[Callable] = None
    # mesh + sharding rules enable the manual (shard_map) dispatch paths
    # (MoE dispatch locality, split-KV attention); None on single-host
    mesh: Optional[Any] = None
    rules: Optional[Any] = None

    @property
    def phi_cfg(self) -> SoftmaxPhiConfig:
        return self.cfg.softmax_phi

    def matmul(self, x: jax.Array, w: jax.Array) -> jax.Array:
        return ops.matmul(x, w, plan=self.plan)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"], cfg.rms_norm_eps)


def norm_params(cfg: ModelConfig, d: int) -> Params:
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), _pdt(cfg)),
                "bias": jnp.zeros((d,), _pdt(cfg))}
    return {"scale": jnp.ones((d,), _pdt(cfg))}


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _adt(cfg: ModelConfig):
    return jnp.dtype(cfg.activation_dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA, RoPE, optional sliding window)
# ---------------------------------------------------------------------------


def attention_params(cfg: ModelConfig, key) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, dt = cfg.d_model, _pdt(cfg)
    p = {
        "wq": dense_init(k1, (d, cfg.q_dim), dt),
        "wk": dense_init(k2, (d, cfg.kv_dim), dt),
        "wv": dense_init(k3, (d, cfg.kv_dim), dt),
        "wo": dense_init(k4, (cfg.q_dim, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def attention_qkv(
    ctx: LayerCtx, p: Params, x: jax.Array, positions: jax.Array,
    *, use_rope: bool = True,
):
    """Project to q, k, v. x: (B, S, D) -> q (B,S,HQ,Dh), k/v (B,S,HK,Dh)."""
    cfg = ctx.cfg
    b, s, _ = x.shape
    q = ctx.matmul(x, p["wq"])
    k = ctx.matmul(x, p["wk"])
    v = ctx.matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = ctx.shard(q, "act_qkv")
    k = ctx.shard(k, "act_kv")
    v = ctx.shard(v, "act_kv")
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(
    ctx: LayerCtx, p: Params, x: jax.Array, positions: jax.Array,
    *, causal: bool = True, use_rope: bool = True,
    kv_override: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Full-sequence (train / prefill / encoder) attention.

    ``kv_override`` feeds cross-attention (keys/values from the encoder).
    """
    cfg = ctx.cfg
    b, s, _ = x.shape
    if kv_override is None:
        q, k, v = attention_qkv(ctx, p, x, positions, use_rope=use_rope)
    else:
        q = ctx.matmul(x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
        k, v = kv_override
    o = ops.attention_prefill(
        q, k, v,
        phi_cfg=ctx.phi_cfg if cfg.has_softmax_attention else
        SoftmaxPhiConfig(enabled=False),
        causal=causal,
        sliding_window=cfg.sliding_window,
        plan=ctx.plan,
    )
    o = ctx.shard(o.reshape(b, s, cfg.q_dim), "act_attn_out")
    return ctx.matmul(o, p["wo"])


# --- decode-layer stage boundaries -----------------------------------------
#
# One decode layer is four explicit stages:
#
#   A. ingest   — norm → QKV → bias → rope          (decode_ingest)
#   B. attend   — KV scatter → decode attention      (decode_attend[_paged])
#   C. epilogue — o_proj → residual add              (decode_epilogue)
#   D. mlp      — norm → gate/up → act → down → res  (decode_mlp)
#
# The A, C and D seams dispatch through ops.decode_ingest /
# ops.oproj_residual / ops.ffn_norm (one kernel per seam on the Pallas
# backend, the bit-identical op-chain composition in ref.py on XLA);
# families the A and D seams do not cover (layernorm, non-glu MLPs)
# compose those stages from the op chain. Stage B keeps its own
# plan-governed dispatch (attention scheme/paging/quantization are
# orthogonal axes).


def decode_ingest(
    ctx: LayerCtx, norm_p: Params, p: Params, x: jax.Array,
    position: jax.Array, *, use_rope: bool = True,
):
    """Stage A: pre-attention ingest on the residual stream.

    x: (B, 1, D) un-normed; position: (B,). Returns q (B,1,HQ,Dh),
    k/v (B,1,HK,Dh). The seam claims rmsnorm models only — layernorm
    families keep the op chain (documented fallback).
    """
    cfg = ctx.cfg
    if cfg.norm == "rmsnorm":
        q, k, v = ops.decode_ingest(
            x, norm_p["scale"], p["wq"], p["wk"], p["wv"], position,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            eps=cfg.rms_norm_eps, use_rope=use_rope,
            bq=p.get("bq"), bk=p.get("bk"), bv=p.get("bv"),
            plan=ctx.plan,
        )
        return (ctx.shard(q, "act_qkv"), ctx.shard(k, "act_kv"),
                ctx.shard(v, "act_kv"))
    h = norm(cfg, norm_p, x)
    return attention_qkv(ctx, p, h, position[:, None], use_rope=use_rope)


def decode_attend(
    ctx: LayerCtx, q: jax.Array, k: jax.Array, v: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, lengths: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage B (dense layout): append this token's KV at each row's
    length, attend over the cache. Returns (o (B,1,HQ*Dh), new caches)."""
    cfg = ctx.cfg
    b = q.shape[0]
    # single-token q/k/v are tiny: replicate over `model` (the sharded
    # resource is the cache sequence — T1's split-KV layout)
    k_new = ctx.shard(k[:, 0], "act_decode_rep")
    v_new = ctx.shard(v[:, 0], "act_decode_rep")
    qd = ctx.shard(q[:, 0], "act_decode_rep")  # (B, HQ, Dh)
    # append at each sequence's own length (in place, S-sharded cache)
    cache_k = ctx.shard(_scatter_kv(cache_k, k_new, lengths),
                        "act_cache_slice")
    cache_v = ctx.shard(_scatter_kv(cache_v, v_new, lengths),
                        "act_cache_slice")
    new_len = lengths + 1
    if ctx.decode_attention_fn is not None:
        o = ctx.decode_attention_fn(qd, cache_k, cache_v, new_len)
    else:
        o = ops.attention_decode(
            qd, cache_k, cache_v, new_len,
            phi_cfg=ctx.phi_cfg if cfg.has_softmax_attention else
            SoftmaxPhiConfig(enabled=False),
            plan=ctx.plan,
            shard=ctx.shard,
        )
    o = ctx.shard(o.reshape(b, 1, cfg.q_dim), "act_attn_out")
    return o, cache_k, cache_v


def decode_epilogue(ctx: LayerCtx, p: Params, o: jax.Array,
                    resid: jax.Array) -> jax.Array:
    """Stage C: ``resid + o @ wo`` — one fused dispatch."""
    return ops.oproj_residual(o, p["wo"], resid, plan=ctx.plan)


def decode_mlp(ctx: LayerCtx, norm_p: Params, p: Params,
               x: jax.Array) -> jax.Array:
    """Stage D: the full MLP half — mlp_norm → gate/up → activation →
    down-projection → residual add.

    Two fused dispatches: ``ops.ffn_norm`` (norm pulled inside the
    gate/up pair) and ``ops.oproj_residual`` reused for
    ``x + h @ w_down`` (the same GEMM-into-residual shape as stage C).
    The seam claims rmsnorm + glu families only — others keep the op
    chain (same documented fallback as stage A).
    """
    cfg = ctx.cfg
    if cfg.norm == "rmsnorm" and cfg.activation in ("swiglu", "geglu"):
        h = ops.ffn_norm(x, norm_p["scale"], p["w_gate"], p["w_up"],
                         activation=cfg.activation,
                         eps=cfg.rms_norm_eps, plan=ctx.plan)
        h = ctx.shard(h, "act_ffn")
        return ops.oproj_residual(h, p["w_down"], x, plan=ctx.plan)
    h = norm(cfg, norm_p, x)
    return x + mlp_block(ctx, p, h)


def attention_decode_block(
    ctx: LayerCtx, p: Params, x: jax.Array, position: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, lengths: jax.Array,
    *, use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode with KV cache (split stage composition — callers
    that norm outside and own the residual, e.g. encdec, use this).

    x: (B, 1, D); cache_k/v: (B, S, HK, Dh); lengths: (B,) current lengths.
    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    q, k, v = attention_qkv(
        ctx, p, x, position[:, None], use_rope=use_rope
    )  # q: (B,1,HQ,Dh), k/v: (B,1,HK,Dh)
    o, cache_k, cache_v = decode_attend(ctx, q, k, v, cache_k, cache_v,
                                        lengths)
    return ctx.matmul(o, p["wo"]), cache_k, cache_v


def _scatter_kv(cache: jax.Array, new: jax.Array, lengths: jax.Array):
    """cache: (B, S, H, D), new: (B, H, D) — write at per-row position."""
    b = cache.shape[0]
    return cache.at[jnp.arange(b), lengths].set(new.astype(cache.dtype))


def _scatter_kv_chunk(cache: jax.Array, new: jax.Array, lengths: jax.Array,
                      chunk_lens: jax.Array) -> jax.Array:
    """cache: (B, S, H, D), new: (B, C, H, D) — row b writes its first
    ``chunk_lens[b]`` chunk entries at positions ``lengths[b] + i``; the
    rest (chunk padding / rows not prefilling this tick) are dropped via
    an out-of-bounds sentinel index."""
    b, c = new.shape[:2]
    s = cache.shape[1]
    pos = lengths[:, None] + jnp.arange(c)[None, :]
    pos = jnp.where(jnp.arange(c)[None, :] < chunk_lens[:, None], pos, s)
    return cache.at[jnp.arange(b)[:, None], pos].set(
        new.astype(cache.dtype), mode="drop")


def _paged_scatter_chunk(pool: jax.Array, new: jax.Array,
                         block_tables: jax.Array, lengths: jax.Array,
                         chunk_lens: jax.Array) -> jax.Array:
    """Scatter a chunk of new KV into the shared block pool.

    pool: (NP, PS, H, D); new: (B, C, H, D); block_tables: (B, NB).
    Logical position ``lengths[b] + i`` lands at physical page
    ``block_tables[b, pos // PS]`` offset ``pos % PS``. Entries past a row's
    ``chunk_lens`` are redirected to page NP (out of bounds) and dropped;
    unassigned block-table entries already hold the NP sentinel, so writes
    from empty slots in a partially occupied batch are dropped too.
    """
    num_pages, ps = pool.shape[0], pool.shape[1]
    b, c = new.shape[:2]
    pos = lengths[:, None] + jnp.arange(c)[None, :]
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]
    page = jnp.clip(pos // ps, 0, block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(block_tables, page, axis=1)
    phys = jnp.where(valid, phys, num_pages)
    return pool.at[phys, pos % ps].set(new.astype(pool.dtype), mode="drop")


def _layer_pages(cache: dict, layer):
    """Address layer ``layer`` of a stacked paged cache in place.

    Each leaf is viewed as one flat ``(L*NP, ...)`` page axis
    (:meth:`PagedLayout.page_view`: a bitcast, no copy, from the logical
    or the device's stored shape); page ``p`` of layer ``l`` is ``l*NP +
    p`` there. Returns the flat cache and two translations of a page
    table onto it: ``read`` clamps unassigned entries (the sentinel
    ``NP``) to the layer's last page, as the kernels do, and ``write``
    sends them past the whole pool, so a scatter drops them — a sentinel
    of layer ``l`` never lands in layer ``l+1``."""
    flat, nl, num_pages = PagedLayout.page_view(cache)
    base = layer * num_pages

    def read(t):
        return base + jnp.minimum(t, num_pages - 1)

    def write(t):
        return jnp.where(t < num_pages, base + t, nl * num_pages)

    return flat, read, write


def _paged_write(flat: dict, new_k: jax.Array, new_v: jax.Array,
                 tables: jax.Array, lengths: jax.Array,
                 chunk_lens: jax.Array) -> dict:
    """Scatter a chunk of new K/V into the flat page view (quantized
    scatter when scale pools ride along): only the written positions
    change, so the update happens in place on the donated pool."""
    if "k_scale" not in flat:
        return {"k": _paged_scatter_chunk(flat["k"], new_k, tables, lengths,
                                          chunk_lens),
                "v": _paged_scatter_chunk(flat["v"], new_v, tables, lengths,
                                          chunk_lens)}
    from repro.serving import kvquant  # deferred: serving imports models

    spec = quant.spec_for_dtype(flat["k"].dtype)
    out = {}
    for name, new in (("k", new_k), ("v", new_v)):
        out[name], out[name + "_scale"] = kvquant.scatter_chunk_quantized(
            flat[name], flat[name + "_scale"], new, tables, lengths,
            chunk_lens, spec)
    return out


def _unflatten_pages(flat: dict, cache: dict) -> dict:
    """Flat page views back to the shapes ``cache`` is stored in."""
    return {n: a.reshape(cache[n].shape) for n, a in flat.items()}


def decode_attend_paged(
    ctx: LayerCtx, q: jax.Array, k: jax.Array, v: jax.Array,
    cache: dict, layer, block_tables: jax.Array, lengths: jax.Array,
    *, decode_groups=None,
) -> tuple[jax.Array, dict]:
    """Stage B (paged layout): append this token's KV through the block
    tables, attend over the page pool. ``cache`` is the whole stacked
    paged cache (``(L, NP, PS, HK, Dh)`` pools, plus ``(L, NP, HK)`` scale
    pools when quantized) and ``layer`` this layer's index: the write and
    the kernels address the layer's pages in place (:func:`_layer_pages`).
    Empty slots write nothing — their table entries are the sentinel.
    ``decode_groups`` activates the prefix-shared grouped attention path.
    Returns (o (B,1,HQ*Dh), the updated stacked cache)."""
    cfg = ctx.cfg
    b = q.shape[0]
    flat, read, write = _layer_pages(cache, layer)
    flat = _paged_write(flat, k, v, write(block_tables), lengths,
                        jnp.ones_like(lengths))
    groups = decode_groups
    if groups is not None:
        groups = groups._replace(tables=read(groups.tables))
    o = ops.attention_decode_paged(
        q[:, 0], flat["k"], flat["v"], read(block_tables), lengths + 1,
        phi_cfg=ctx.phi_cfg if cfg.has_softmax_attention else
        SoftmaxPhiConfig(enabled=False),
        plan=ctx.plan,
        shard=ctx.shard,
        groups=groups,
        k_scale=flat.get("k_scale"), v_scale=flat.get("v_scale"),
    )
    o = ctx.shard(o.reshape(b, 1, cfg.q_dim), "act_attn_out")
    return o, _unflatten_pages(flat, cache)


def attention_chunk_block(
    ctx: LayerCtx, p: Params, x: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array,
    lengths: jax.Array, chunk_lens: jax.Array, *, use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill step: C prompt tokens append to a dense slot cache.

    x: (B, C, D); cache_k/v: (B, S, HK, Dh). Row b's tokens sit at absolute
    positions ``lengths[b] + i`` for ``i < chunk_lens[b]``; the chunk's KV is
    scattered first, then the chunk attends causally to prefix + chunk.
    """
    cfg = ctx.cfg
    b, c, _ = x.shape
    positions = lengths[:, None] + jnp.arange(c)[None, :]
    q, k, v = attention_qkv(ctx, p, x, positions, use_rope=use_rope)
    cache_k = _scatter_kv_chunk(cache_k, k, lengths, chunk_lens)
    cache_v = _scatter_kv_chunk(cache_v, v, lengths, chunk_lens)
    o = ops.attention_chunk(
        q, cache_k, cache_v, lengths,
        phi_cfg=ctx.phi_cfg if cfg.has_softmax_attention else
        SoftmaxPhiConfig(enabled=False),
        plan=ctx.plan,
    )
    o = ctx.shard(o.reshape(b, c, cfg.q_dim), "act_attn_out")
    return ctx.matmul(o, p["wo"]), cache_k, cache_v


def attention_chunk_block_paged(
    ctx: LayerCtx, p: Params, x: jax.Array, cache: dict, layer,
    block_tables: jax.Array, lengths: jax.Array, chunk_lens: jax.Array,
    *, use_rope: bool = True,
) -> tuple[jax.Array, dict]:
    """Chunked-prefill step against the block-paged pool (paged twin of
    :func:`attention_chunk_block`). ``cache``/``layer`` as in
    :func:`decode_attend_paged`: the chunk is written into the layer's
    pages in place. Quantized layouts write the chunk through the
    quantized scatter — quantization happens in the chunk epilogue, so the
    full-precision slab never lands in HBM. Returns (out, the updated
    stacked cache)."""
    cfg = ctx.cfg
    b, c, _ = x.shape
    positions = lengths[:, None] + jnp.arange(c)[None, :]
    q, k, v = attention_qkv(ctx, p, x, positions, use_rope=use_rope)
    flat, read, write = _layer_pages(cache, layer)
    flat = _paged_write(flat, k, v, write(block_tables), lengths, chunk_lens)
    o = ops.attention_chunk_paged(
        q, flat["k"], flat["v"], read(block_tables), lengths,
        phi_cfg=ctx.phi_cfg if cfg.has_softmax_attention else
        SoftmaxPhiConfig(enabled=False),
        plan=ctx.plan,
        k_scale=flat.get("k_scale"), v_scale=flat.get("v_scale"),
    )
    o = ctx.shard(o.reshape(b, c, cfg.q_dim), "act_attn_out")
    return ctx.matmul(o, p["wo"]), _unflatten_pages(flat, cache)


# ---------------------------------------------------------------------------
# Feed-forward (dense)
# ---------------------------------------------------------------------------


def mlp_params(cfg: ModelConfig, key, d_ff: int | None = None) -> Params:
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, _pdt(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, (d, f), dt),
            "w_up": dense_init(k2, (d, f), dt),
            "w_down": dense_init(k3, (f, d), dt),
        }
    return {
        "w_up": dense_init(k1, (d, f), dt),
        "w_down": dense_init(k2, (f, d), dt),
    }


def mlp_block(ctx: LayerCtx, p: Params, x: jax.Array) -> jax.Array:
    cfg = ctx.cfg
    if cfg.activation in ("swiglu", "geglu"):
        if ctx.plan.fused_ffn.fused:
            # T2 extension: single fused kernel for gate+up+epilogue —
            # the (M, F) gate/up tensors never round-trip HBM
            h = ops.fused_ffn(x, p["w_gate"], p["w_up"],
                              activation=cfg.activation, plan=ctx.plan)
            h = ctx.shard(h, "act_ffn")
        else:
            g = ctx.matmul(x, p["w_gate"])
            u = ctx.matmul(x, p["w_up"])
            g = ctx.shard(g, "act_ffn")
            u = ctx.shard(u, "act_ffn")
            act = (jax.nn.silu(g) if cfg.activation == "swiglu"
                   else jax.nn.gelu(g))
            h = act * u
    else:
        h = ctx.matmul(x, p["w_up"])
        h = ctx.shard(h, "act_ffn")
        h = jax.nn.gelu(h)
    return ctx.matmul(h, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / LM head / loss
# ---------------------------------------------------------------------------


def vocab_padded(cfg: ModelConfig, multiple: int = 256) -> int:
    v = cfg.vocab_size
    return (v + multiple - 1) // multiple * multiple


def embed_params(cfg: ModelConfig, key) -> Params:
    vp = vocab_padded(cfg)
    k1, k2 = jax.random.split(key)
    p = {"embedding": dense_init(k1, (vp, cfg.d_model), _pdt(cfg), in_axis=1)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k2, (cfg.d_model, vp), _pdt(cfg))
    return p


def embed(ctx: LayerCtx, p: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0)
    return ctx.shard(x.astype(_adt(ctx.cfg)), "act_resid")


def lm_logits(ctx: LayerCtx, p: Params, x: jax.Array) -> jax.Array:
    w = p.get("lm_head")
    if w is None:
        w = p["embedding"].T
    logits = ctx.matmul(x, w)
    return ctx.shard(logits, "act_logits")


def cross_entropy_loss(
    ctx: LayerCtx, p: Params, x: jax.Array, labels: jax.Array,
    *, seq_chunks: int = 8,
) -> jax.Array:
    """Memory-sane LM loss: the (B,S,V) logits tensor is never materialized
    at full sequence length — a *python-unrolled* loop over sequence chunks
    keeps HLO flat (counted exactly by cost_analysis; see EXPERIMENTS.md
    §Methodology) while bounding live logits to (B, S/chunks, V).
    """
    cfg = ctx.cfg
    b, s, _ = x.shape
    vp = vocab_padded(cfg)
    seq_chunks = min(seq_chunks, s)
    assert s % seq_chunks == 0
    cs = s // seq_chunks
    total = jnp.zeros((), jnp.float32)
    for i in range(seq_chunks):
        xc = jax.lax.dynamic_slice_in_dim(x, i * cs, cs, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(labels, i * cs, cs, axis=1)
        logits = lm_logits(ctx, p, xc).astype(jnp.float32)
        if vp != cfg.vocab_size:  # mask padded vocab tail
            pad_mask = jnp.arange(vp) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e9, logits)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        total = total + jnp.sum(logz - gold)
    return total / (b * s)
