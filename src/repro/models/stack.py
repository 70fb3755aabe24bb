"""Layer-stack plumbing shared by all model families.

Uniform block interfaces:
  * train/prefill-style: ``block_fn(p_i, x) -> (x, aux)`` — aux is a scalar
    (MoE load-balance loss; 0.0 for other families), accumulated across
    layers.
  * cached decode-style: ``block_fn(p_i, x, cache_i) -> (x, new_cache_i)``
    where ``cache_i`` is the per-layer slice of a stacked cache pytree.
  * in-place decode-style (the paged pool):
    ``block_fn(p_i, x, cache, layer) -> (x, cache)`` — the whole stacked
    cache rides in the carry and each layer writes its own part of it.

``unroll=False`` uses ``lax.scan`` over the stacked-L params (compact HLO —
the only while-loop in the whole program, with a known trip count);
``unroll=True`` emits a flat python loop for the cost-analysis probes.

Everything here is generic ``jax.tree`` plumbing, which is what lets the
quantized-weight representation ride through untouched: a GEMM leaf that
``models/wquant.py`` turned into a ``{"codes", "scale"}`` dict is just
two stacked leaves ``(L, K, N)`` / ``(L, N)`` to stack/unstack/scan, so
the decode scan body has the same structure whether the params are bf16
arrays or (codes, scale) pairs.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def num_layers_of(layers_params) -> int:
    return jax.tree_util.tree_leaves(layers_params)[0].shape[0]


def unstack(tree):
    """Stacked-L pytree -> list of L per-layer pytrees.

    ``a[i]`` slicing only — no copy under jit, bitwise round-trip with
    :func:`restack` (the unrolled cost probes rely on this: per-layer
    slabs must hold exactly the scanned values).
    """
    return [jax.tree.map(lambda a: a[i], tree)
            for i in range(num_layers_of(tree))]


def restack(trees):
    """List of L per-layer pytrees -> stacked-L pytree (``jnp.stack``
    per leaf). Inverse of :func:`unstack`, bitwise."""
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def run_stack(layers_params, x, block_fn: Callable, *, unroll: bool = False):
    """Returns (x, total_aux)."""
    if unroll:
        aux = jnp.zeros((), jnp.float32)
        for p_i in unstack(layers_params):
            x, a = block_fn(p_i, x)
            aux = aux + a
        return x, aux

    def layer_scan_body(carry, p_i):
        x, aux = carry
        x, a = block_fn(p_i, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        layer_scan_body, (x, jnp.zeros((), jnp.float32)), layers_params
    )
    return x, aux


def run_stack_collect(layers_params, x, block_fn: Callable,
                      *, unroll: bool = False):
    """Like run_stack but blocks return (x, per_layer_output) and the
    per-layer outputs are stacked (used by prefill to build the KV cache)."""
    if unroll:
        outs = []
        for p_i in unstack(layers_params):
            x, o = block_fn(p_i, x)
            outs.append(o)
        return x, restack(outs)

    def layer_scan_body(carry, p_i):
        x, o = block_fn(p_i, carry)
        return x, o

    return jax.lax.scan(layer_scan_body, x, layers_params)


def run_stack_cached(layers_params, x, cache, block_fn: Callable,
                     *, unroll: bool = False):
    """Returns (x, new_cache) — cache leaves have leading L axis."""
    if unroll:
        news = []
        for p_i, c_i in zip(unstack(layers_params), unstack(cache)):
            x, c_new = block_fn(p_i, x, c_i)
            news.append(c_new)
        return x, restack(news)

    def layer_scan_body(carry, xs):
        p_i, c_i = xs
        x, c_new = block_fn(p_i, carry, c_i)
        return x, c_new

    return jax.lax.scan(layer_scan_body, x, (layers_params, cache))


def run_stack_in_place(layers_params, x, cache, block_fn: Callable,
                       *, unroll: bool = False):
    """Returns (x, cache) — for a stacked cache that each layer updates in
    place: the cache is carried whole (never sliced into per-layer ``xs``
    and reassembled from ``ys``), and ``block_fn`` gets the layer index to
    address its own part. Only what the layers write changes, so a donated
    cache is updated in its own buffer. ``unroll=True`` passes the index
    as a Python int."""
    if unroll:
        for layer, p_i in enumerate(unstack(layers_params)):
            x, cache = block_fn(p_i, x, cache, layer)
        return x, cache

    def layer_scan_body(carry, xs):
        p_i, layer = xs
        return block_fn(p_i, *carry, layer), None

    layers = jnp.arange(num_layers_of(layers_params), dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(layer_scan_body, (x, cache),
                                 (layers_params, layers))
    return x, cache
