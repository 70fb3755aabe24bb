"""Random weights of a dense decoder, drawn on the device from a seed.

The leaves and their shapes follow from the published model keys alone
(``hidden_size``, ``num_hidden_layers``, ...), so the served run and the
plain reference both call :func:`draw` and get the same values; the
reference takes no array from the program.

Each leaf has a key of its own, ``fold_in(PRNGKey(seed), crc32(name))``,
and the distribution the program's own initializer uses for it: normal
scaled by ``fan_in ** -0.5`` for matrices and the embedding, ones for
norm scales, zeros for biases, stored in bfloat16. Every leaf of the
whole tree comes out of one jitted call.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def dims(model: dict) -> dict:
    """The widths the leaves are built from."""
    d, hq = model["hidden_size"], model["num_attention_heads"]
    dh = model.get("head_dim") or d // hq
    return dict(d=d, f=model["intermediate_size"],
                layers=model["num_hidden_layers"], hq=hq,
                hk=model["num_key_value_heads"], dh=dh,
                vocab=model["vocab_size"])


def leaf_specs(model: dict) -> dict:
    """``name -> (shape, kind, fan_in)`` for every weight of the model."""
    m = dims(model)
    d, f, n, q, kv = m["d"], m["f"], m["layers"], m["hq"] * m["dh"], \
        m["hk"] * m["dh"]
    specs = {
        "embedding": ((m["vocab"], d), "normal", d),
        "final_norm.scale": ((d,), "ones", 0),
        "layers.attn_norm.scale": ((n, d), "ones", 0),
        "layers.mlp_norm.scale": ((n, d), "ones", 0),
        "layers.attn.wq": ((n, d, q), "normal", d),
        "layers.attn.wk": ((n, d, kv), "normal", d),
        "layers.attn.wv": ((n, d, kv), "normal", d),
        "layers.attn.wo": ((n, q, d), "normal", q),
        "layers.mlp.w_gate": ((n, d, f), "normal", d),
        "layers.mlp.w_up": ((n, d, f), "normal", d),
        "layers.mlp.w_down": ((n, f, d), "normal", f),
    }
    if model.get("qkv_bias"):
        specs["layers.attn.bq"] = ((n, q), "zeros", 0)
        specs["layers.attn.bk"] = ((n, kv), "zeros", 0)
        specs["layers.attn.bv"] = ((n, kv), "zeros", 0)
    if not model.get("tie_word_embeddings"):
        specs["lm_head"] = ((d, m["vocab"]), "normal", d)
    return specs


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, plan):
    out = {}
    for name, shape, kind, fan_in, padded in plan:
        if kind == "ones":
            a = jnp.ones(shape, jnp.bfloat16)
        elif kind == "zeros":
            a = jnp.zeros(shape, jnp.bfloat16)
        else:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            a = (jax.random.normal(k, shape, jnp.float32)
                 * fan_in ** -0.5).astype(jnp.bfloat16)
        if padded != shape:     # vocab rows past the model's: zeros
            a = jnp.pad(a, [(0, p - s) for s, p in zip(shape, padded)])
        out[name] = a
    return out


def draw(model: dict, seed: int, padded: dict | None = None) -> dict:
    """Every leaf as ``{name: bf16 array}``, in one jitted call.

    ``padded`` maps a leaf name to a larger shape the caller stores it in
    (a vocabulary padded for tiling); the extra rows or columns are zero
    and the drawn part is the same as unpadded."""
    padded = padded or {}
    plan = tuple((name, shape, kind, fan_in,
                  tuple(padded.get(name, shape)))
                 for name, (shape, kind, fan_in) in leaf_specs(model).items())
    key = jax.random.PRNGKey(np.uint32(seed % 2**32))
    return _draw(key, plan)


def for_program(model: dict, seed: int, program_shapes) -> dict:
    """The weights in the program's own tree (``program_shapes`` is
    ``jax.eval_shape`` of its initializer). A leaf the program has and
    the model keys do not, or a shape that is not ours or ours padded,
    is an error: the benchmark would not be serving the stated model."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(program_shapes)
    names = [".".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    specs = leaf_specs(model)
    missing = sorted(set(names) - set(specs))
    extra = sorted(set(specs) - set(names))
    if missing or extra:
        raise ValueError(f"program leaves {missing} have no published "
                         f"counterpart; published leaves {extra} are not "
                         f"in the program")
    padded = {}
    for name, (_, leaf) in zip(names, flat):
        ours = specs[name][0]
        if (len(leaf.shape) != len(ours)
                or any(p < s for p, s in zip(leaf.shape, ours))
                or leaf.dtype != jnp.bfloat16):
            raise ValueError(f"{name}: program holds {leaf.shape} "
                             f"{leaf.dtype}, the model {ours} bfloat16")
        padded[name] = tuple(leaf.shape)
    got = draw(model, seed, padded)
    return jax.tree_util.tree_unflatten(treedef, [got[n] for n in names])
