"""Token-choice top-k MoE (grok-1 / dbrx families).

Dispatch strategy (1000-node posture, documented in DESIGN.md §5):
  * routing + slotting are **group-local**: tokens are reshaped to
    ``(G, T/G, D)`` where G = the data-parallel shard count, so the argsort /
    capacity bookkeeping never crosses a shard boundary (no collectives from
    routing itself).
  * expert FFN weights are stored **unfactored** ``(E, D, F)`` and sharded
    TP-style: F over ``model``, D over the fsdp(data) axes in training. Every
    shard computes its own tokens through all experts' F-slices — compute is
    perfectly balanced regardless of routing skew, and the only collectives
    are the standard TP all-reduce after the down-projection (plus FSDP
    weight gathers in training). This avoids the all-to-all latency wall at
    pod scale at the cost of weight gathers — the trade is analyzed in
    EXPERIMENTS.md §Roofline for grok/dbrx.
  * capacity: ``C = ceil(T_g*k/E * capacity_factor)`` (train; overflow
    dropped, standard token-dropping semantics) or zero-drop full capacity
    for decode.

The decode-phase expert GEMMs are *flatter* than dense ones (M_eff ≈
M·k/E) — exactly the paper's T2/T3 regime; ``core.dispatch`` carries
per-expert [K, N] entries for them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import transformer as tfm
from repro.models.layers import LayerCtx, Params


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def moe_params(cfg: ModelConfig, key) -> Params:
    assert cfg.moe is not None
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std_in, std_out = d ** -0.5, f ** -0.5

    def init(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    p = {
        "router": init(k1, (d, e), std_in).astype(jnp.float32),
        "w_up": init(k3, (e, d, f), std_in),
        "w_down": init(k4, (e, f, d), std_out),
    }
    if cfg.activation in ("swiglu", "geglu"):  # gated: 3 expert matrices
        p["w_gate"] = init(k2, (e, d, f), std_in)
    return p


def layer_params(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": L.norm_params(cfg, cfg.d_model),
        "attn": L.attention_params(cfg, k1),
        "mlp_norm": L.norm_params(cfg, cfg.d_model),
        "moe": moe_params(cfg, k2),
    }


def init_params(cfg: ModelConfig, key) -> Params:
    return tfm.init_params(cfg, key, layer_params_fn=layer_params)


# ---------------------------------------------------------------------------
# The MoE FFN
# ---------------------------------------------------------------------------


def moe_block(
    ctx: LayerCtx, p: Params, x: jax.Array,
    *, groups: int = 1, capacity_factor: float = 1.25,
    zero_drop: bool = False,
):
    """x: (B, S, D) -> (out (B,S,D), aux load-balance loss).

    When ``ctx.mesh`` is set, the dispatch/combine runs *manually* over the
    data axes (see :func:`_moe_block_manual`) — GSPMD cannot prove the
    slot gather/scatter is group-local and inserts slot-granularity
    collectives otherwise (EXPERIMENTS.md §Perf, grok train iteration 2).
    """
    cfg = ctx.cfg
    assert cfg.moe is not None
    e, k = cfg.moe.num_experts, cfg.moe.num_experts_per_tok
    b, s, d = x.shape
    t = b * s
    g = groups
    while t % g:
        g //= 2
    tg = t // g
    if zero_drop:
        cap = tg * k
    else:
        cap = int(-(-tg * k * capacity_factor // e))
        cap = max(8, -(-cap // 8) * 8)
        cap = min(cap, tg * k)
    xg = x.reshape(g, tg, d)

    if ctx.mesh is not None and ctx.rules is not None:
        manual = _moe_block_manual(ctx, p, xg, e=e, k=k, cap=cap)
        if manual is not None:
            out, aux = manual
            return ctx.shard(out.reshape(b, s, d), "act_resid"), aux

    xg = ctx.shard(xg, "act_moe_grouped")
    out, aux = _dispatch_ffn_combine(
        cfg, p, xg, e=e, k=k, cap=cap, shard=ctx.shard)
    return ctx.shard(out.reshape(b, s, d), "act_resid"), aux


def _dispatch_ffn_combine(cfg, p: Params, xg: jax.Array, *,
                          e: int, k: int, cap: int, shard):
    """Routing -> slotting -> expert FFN -> combine, on (G, Tg, D) groups.
    Pure group-local math apart from the TP einsums."""
    g, tg, d = xg.shape

    # ---- routing (f32) ----
    logits = jnp.einsum(
        "gtd,de->gte", xg.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)                  # (G,Tg,E)
    weights, idx = jax.lax.top_k(probs, k)                   # (G,Tg,k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # ---- slotting (group-local; no collectives) ----
    eflat = idx.reshape(g, tg * k)                           # (G, T*k)
    wflat = weights.reshape(g, tg * k)
    order = jnp.argsort(eflat, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(eflat, order, axis=1)
    sorted_w = jnp.take_along_axis(wflat, order, axis=1)
    sorted_tok = order // k
    counts = jnp.sum(
        jax.nn.one_hot(eflat, e, dtype=jnp.int32), axis=1
    )                                                        # (G, E)
    starts = jnp.cumsum(counts, axis=1) - counts             # exclusive
    ranks = (
        jnp.arange(tg * k)[None, :]
        - jnp.take_along_axis(starts, sorted_e, axis=1)
    )
    keep = ranks < cap
    dest = jnp.where(keep, sorted_e * cap + ranks, e * cap)  # dump slot

    def scatter_slots(dest_g, tok_g, w_g):
        slot_tok = jnp.zeros((e * cap + 1,), jnp.int32).at[dest_g].set(tok_g)
        slot_w = jnp.zeros((e * cap + 1,), jnp.float32).at[dest_g].set(w_g)
        slot_valid = jnp.zeros((e * cap + 1,), jnp.bool_).at[dest_g].set(True)
        return slot_tok[:-1], slot_w[:-1], slot_valid[:-1]

    slot_tok, slot_w, slot_valid = jax.vmap(scatter_slots)(
        dest, sorted_tok, sorted_w
    )                                                        # (G, E*cap)

    # ---- gather tokens into (G, E, cap, D) slots ----
    xs = jnp.take_along_axis(xg, slot_tok[..., None], axis=1)
    xs = xs * slot_valid[..., None].astype(xg.dtype)
    xs = xs.reshape(g, e, cap, d)
    xs = shard(xs, "act_moe_slots")

    # ---- expert FFN (TP over model axis on F) ----
    if "w_gate" in p:   # gated (swiglu/geglu): 3 expert matrices
        gate = jnp.einsum("gecd,edf->gecf", xs, p["w_gate"])
        up = jnp.einsum("gecd,edf->gecf", xs, p["w_up"])
        gate = shard(gate, "act_moe_hidden")
        up = shard(up, "act_moe_hidden")
        act = (jax.nn.silu(gate) if cfg.activation == "swiglu"
               else jax.nn.gelu(gate))
        h = act * up
    else:               # plain MLP experts (grok-style gelu): 2 matrices
        up = jnp.einsum("gecd,edf->gecf", xs, p["w_up"])
        up = shard(up, "act_moe_hidden")
        h = jax.nn.gelu(up)
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    # NOTE: no sharding constraint on y here. The down-proj contracts the
    # model-sharded F axis, so y is a *partial sum* across the model axis;
    # constraining it at slot granularity forces GSPMD to resolve (psum or
    # worse, all-gather h) over E*cap slots = k*capacity x the token count.
    # The slot->token combine below is linear, so the reduction commutes:
    # deferring the constraint to the (B, S, D) output reduces wire bytes
    # by k*capacity (dbrx: 8x) — EXPERIMENTS.md §Perf, dbrx iteration 2.

    # ---- combine back to tokens ----
    y = y.reshape(g, e * cap, d) * (
        slot_w[..., None].astype(y.dtype)
        * slot_valid[..., None].astype(y.dtype)
    )

    def combine(y_g, tok_g):
        return jnp.zeros((tg, d), y_g.dtype).at[tok_g].add(y_g)

    out = jax.vmap(combine)(y, slot_tok)

    # ---- GShard load-balance aux ----
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def _moe_block_manual(ctx: LayerCtx, p: Params, xg: jax.Array, *,
                      e: int, k: int, cap: int):
    """Dispatch locality by construction (grok/dbrx hillclimb iteration).

    The slot gather/scatter of token-choice MoE is *group-local*, but
    GSPMD cannot prove it and materializes slot-granularity collectives
    (observed: E*cap-sized all-gathers in fwd+bwd). Running the whole
    routing->dispatch->FFN->combine under a ``shard_map`` manual over the
    data axes makes cross-group traffic impossible by construction; the
    ``model`` axis stays auto, so the expert einsums keep their TP
    sharding, and the FSDP weight gather over data becomes one explicit
    tiled all-gather per weight (weights << activations).

    Returns None when shapes don't divide the data axes (falls back to
    the GSPMD path).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    cfg = ctx.cfg
    mesh, rules = ctx.mesh, ctx.rules
    g, tg, d = xg.shape
    data_axes = tuple(a for a in rules.act_batch_axes
                      if a in mesh.axis_names)
    if not data_axes:
        return None
    nshards = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                           for a in data_axes]))
    if g % nshards:
        return None
    # weights' FSDP (data) placement, from the same rules that shard them
    moe_specs = {
        name: rules.param_spec(("layers", "moe", name), p[name].shape)
        for name in p
    }

    def data_only(spec: P) -> P:
        ents = []
        for s_ in spec:
            axes = s_ if isinstance(s_, tuple) else (s_,)
            kept = tuple(a for a in axes if a in data_axes)
            ents.append(kept if kept else None)
        return P(*ents)

    w_specs = {n: data_only(s) for n, s in moe_specs.items()}

    def body(xg_l, p_l):
        # un-FSDP the weights: one explicit tiled gather per data-sharded
        # dim (the manual mirror of GSPMD's FSDP gather)
        p_full = {}
        for name, w in p_l.items():
            spec = w_specs[name]
            for dim, s_ in enumerate(spec):
                if s_ is not None:
                    w = jax.lax.all_gather(w, s_, axis=dim, tiled=True)
            p_full[name] = w
        out, aux = _dispatch_ffn_combine(
            cfg, p_full, xg_l, e=e, k=k, cap=cap,
            shard=lambda a, _role: a,
        )
        return out, jax.lax.pmean(aux, data_axes)

    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dspec), w_specs),
        out_specs=(P(dspec), P()),
        axis_names=set(data_axes),
    )
    return fn(xg, p)


# ---------------------------------------------------------------------------
# Blocks (reusing the dense attention halves)
# ---------------------------------------------------------------------------


def make_block(groups: int = 0, capacity_factor: float = 1.25):
    def block(ctx: LayerCtx, p: Params, x: jax.Array, positions: jax.Array):
        cfg = ctx.cfg
        h = L.norm(cfg, p["attn_norm"], x)
        x = x + L.attention_block(ctx, p["attn"], h, positions)
        x = ctx.shard(x, "act_resid")
        h = L.norm(cfg, p["mlp_norm"], x)
        y, aux = moe_block(
            ctx, p["moe"], h, groups=groups or ctx.moe_groups,
            capacity_factor=capacity_factor
        )
        return ctx.shard(x + y, "act_resid"), aux

    return block


def make_decode_block(groups: int = 0):
    def decode_block(ctx: LayerCtx, p: Params, x, position, cache, lengths,
                     block_tables=None, decode_groups=None, layer=None):
        # same ingest → attend → epilogue stage boundaries as the dense
        # family (repro.models.layers); only the FFN half differs — the
        # routed expert dispatch is not a fusable seam, so the MoE block
        # shares the attention-side seams and keeps its own tail
        cfg = ctx.cfg
        q, k, v = L.decode_ingest(ctx, p["attn_norm"], p["attn"], x,
                                  position)
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
        h = L.norm(cfg, p["mlp_norm"], x)
        y, _ = moe_block(ctx, p["moe"], h, groups=groups or ctx.moe_groups,
                         zero_drop=True)
        return ctx.shard(x + y, "act_resid"), cache

    return decode_block


def _moe_chunk_mlp(ctx: LayerCtx, p: Params, h, groups: int):
    """Chunk-sized MoE half: zero-drop below the group-token cap (chunks are
    decode-adjacent sizes, so this is almost always the exact path)."""
    gr = groups or ctx.moe_groups
    b, c, _ = h.shape
    small = (b * c) // max(gr, 1) <= ZERO_DROP_MAX_GROUP_TOKENS
    y, _ = moe_block(ctx, p["moe"], h, groups=gr, zero_drop=small,
                     capacity_factor=PREFILL_CAPACITY_FACTOR)
    return y


def make_chunk_block(groups: int = 0):
    def chunk_block(ctx: LayerCtx, p: Params, x, cache, lengths,
                    chunk_lens, block_tables=None, layer=None):
        cfg = ctx.cfg
        h = L.norm(cfg, p["attn_norm"], x)
        if block_tables is None:
            a, ck, cv = L.attention_chunk_block(
                ctx, p["attn"], h, cache["k"], cache["v"], lengths,
                chunk_lens,
            )
            cache = {"k": ck, "v": cv}
        else:
            a, cache = L.attention_chunk_block_paged(
                ctx, p["attn"], h, cache, layer, block_tables, lengths,
                chunk_lens,
            )
        x = x + a
        h = L.norm(cfg, p["mlp_norm"], x)
        x = ctx.shard(x + _moe_chunk_mlp(ctx, p, h, groups), "act_resid")
        return x, cache

    return chunk_block


# Zero-drop slots cost cap = tg·k *per expert* (worst-case all-to-one
# routing) — exact but E× over-allocated. Fine for decode ticks and
# single-request engine prefill (tiny tg); catastrophic for a 1M-token
# batched prefill (the dbrx prefill_32k hillclimb, EXPERIMENTS.md §Perf).
# Above this per-group token count, batched prefill switches to a bounded
# 2.0x capacity: drops need >2x average skew on a 64k-token group.
ZERO_DROP_MAX_GROUP_TOKENS = 4096
PREFILL_CAPACITY_FACTOR = 2.0


def make_prefill_block(groups: int = 0):
    def prefill_blk(ctx: LayerCtx, p: Params, x, positions, s_max):
        from repro.kernels import ops
        cfg = ctx.cfg
        b, s, _ = x.shape
        h = L.norm(cfg, p["attn_norm"], x)
        q, kk, v = L.attention_qkv(ctx, p["attn"], h, positions)
        o = ops.attention_prefill(
            q, kk, v, phi_cfg=ctx.phi_cfg, causal=True,
            sliding_window=cfg.sliding_window, plan=ctx.plan,
        )
        o = ctx.shard(o.reshape(b, s, cfg.q_dim), "act_attn_out")
        x = x + ctx.matmul(o, p["attn"]["wo"])
        h = L.norm(cfg, p["mlp_norm"], x)
        gr = groups or ctx.moe_groups
        small = (b * s) // max(gr, 1) <= ZERO_DROP_MAX_GROUP_TOKENS
        y, _ = moe_block(ctx, p["moe"], h, groups=gr,
                         zero_drop=small,
                         capacity_factor=PREFILL_CAPACITY_FACTOR)
        x = ctx.shard(x + y, "act_resid")
        pad = [(0, 0), (0, s_max - s), (0, 0), (0, 0)]
        return x, {"k": jnp.pad(kk, pad), "v": jnp.pad(v, pad)}

    return prefill_blk


# ---------------------------------------------------------------------------
# Public API (same signatures as transformer.*)
# ---------------------------------------------------------------------------


def train_loss(ctx: LayerCtx, params: Params, batch: dict, *,
               unroll: bool = False, remat: bool = True, groups: int = 0,
               capacity_factor: float = 1.25):
    aux_w = ctx.cfg.moe.router_aux_loss_coef if ctx.cfg.moe else 0.0
    return tfm.train_loss(
        ctx, params, batch, unroll=unroll, remat=remat,
        block_fn=make_block(groups=groups, capacity_factor=capacity_factor),
        aux_weight=aux_w,
    )


def prefill(ctx: LayerCtx, params: Params, tokens, lengths, cache, *,
            unroll: bool = False, groups: int = 0, **kw):
    return tfm.prefill(
        ctx, params, tokens, lengths, cache, unroll=unroll,
        prefill_block_fn=make_prefill_block(groups=groups), **kw
    )


def decode_step(ctx: LayerCtx, params: Params, tokens, cache, lengths, *,
                block_tables=None, decode_groups=None, positions=None,
                unroll: bool = False, groups: int = 0):
    return tfm.decode_step(
        ctx, params, tokens, cache, lengths, block_tables=block_tables,
        decode_groups=decode_groups, positions=positions, unroll=unroll,
        decode_block_fn=make_decode_block(groups=groups),
    )


def prefill_chunk(ctx: LayerCtx, params: Params, tokens, chunk_lens, cache,
                  lengths, *, block_tables=None, unroll: bool = False,
                  groups: int = 0):
    return tfm.prefill_chunk(
        ctx, params, tokens, chunk_lens, cache, lengths,
        block_tables=block_tables, unroll=unroll,
        chunk_block_fn=make_chunk_block(groups=groups),
    )


PAGED_KV = True
init_cache = tfm.init_cache
cache_spec = tfm.cache_spec
