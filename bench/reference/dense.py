"""Plain float32 forward pass of a dense decoder (Qwen2 / Phi-3 family).

Written from the published equations, with no kernels, cache or
batching: embedding, then per layer ``x += Attn(RMSNorm(x))`` and
``x += SwiGLU(RMSNorm(x))``, then a final RMSNorm and the output head.
Attention is causal grouped-query attention with rotary position
embedding (rotate-half form, inverse frequencies ``theta^(-2i/d)``) and
``1/sqrt(head_dim)`` scaling; Qwen2 adds biases to q, k and v. Every
product runs in float32 at ``Precision.HIGHEST``.

Weights come in bfloat16, as served, and are widened to float32 one
layer at a time, so a 3.8B model fits beside its activations.

``lower=True`` computes the same pass one precision step below what the
configurations state (bfloat16), for the correctness control: every
projection weight rounded to int8 with one step per output channel,
keys and values rounded to int8 with one step per 64-position page and
head, and every product taken on bfloat16 inputs with a float32 sum.
The embedding, the norms and the output head keep their bfloat16
values, as the program's own int8 path keeps them.
Departures from the published models: none on the traffic the
benchmark sends (Phi-3-mini's 2047-token sliding window never binds
there, see the configuration's ``assumed``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
KV_PAGE = 64          # positions per int8 step of keys and values (lower)


def _int8(x, axes):
    """``x`` rounded to int8 codes times one step per slice reduced over
    ``axes`` (step = largest magnitude / 127)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    step = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / step), -127, 127) * step


def _kv_int8(x, valid):
    """Keys or values ``(S, H, D)`` rounded per page of ``KV_PAGE``
    positions and head; padding past the sequence is left out."""
    s, h, d = x.shape
    x = jnp.where(valid[:, None, None], x, 0.0)
    pages = _int8(x.reshape(s // KV_PAGE, KV_PAGE, h, d), (1, 3))
    return pages.reshape(s, h, d)


def _einsum(lower):
    """``einsum`` at the pass's precision."""
    if lower:
        bf = jnp.bfloat16
        return lambda eq, a, b: jnp.einsum(eq, a.astype(bf), b.astype(bf),
                                           preferred_element_type=jnp.float32)
    return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(x, p, n_valid, arch):
    hq, hk, dh, theta, eps, lower = arch
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    if lower:
        f32.update({k: _int8(f32[k], (-2,)) for k in
                    ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")})
    es = _einsum(lower)

    def dot(a, b):
        return es("sk,kn->sn", a, b)

    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, f32["attn_norm"], eps)
    q, k, v = dot(h, f32["wq"]), dot(h, f32["wk"]), dot(h, f32["wv"])
    if "bq" in f32:
        q, k, v = q + f32["bq"], k + f32["bk"], v + f32["bv"]
    q = _rope(q.reshape(s, hq, dh), pos, theta)
    k = _rope(k.reshape(s, hk, dh), pos, theta)
    v = v.reshape(s, hk, dh)
    if lower:
        k, v = _kv_int8(k, pos < n_valid), _kv_int8(v, pos < n_valid)
    k = jnp.repeat(k, hq // hk, axis=1)
    v = jnp.repeat(v, hq // hk, axis=1)
    att = es("qhd,khd->hqk", q, k) / np.sqrt(dh)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    att = jax.nn.softmax(jnp.where(mask[None], att, -jnp.inf), axis=-1)
    o = es("hqk,khd->qhd", att, v).reshape(s, hq * dh)
    x = x + dot(o, f32["wo"])
    h = _rms(x, f32["mlp_norm"], eps)
    g, u = dot(h, f32["w_gate"]), dot(h, f32["w_up"])
    return x + dot(jax.nn.silu(g) * u, f32["w_down"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, head, eps, lower):
    x = _rms(x, norm, eps)
    return _einsum(lower)("sk,kn->sn", x, head.astype(jnp.float32))


def logits(model: dict, w: dict, tokens: np.ndarray, first: int,
           pad_to: int, rows: int, lower: bool = False) -> np.ndarray:
    """Float32 logits at positions ``first .. len(tokens) - 1``, one row
    each, over the model's vocabulary. ``w`` is
    :func:`bench.weights.draw`'s output for ``model``. The sequence is
    padded to ``pad_to`` positions and ``rows`` rows are read, so every
    call of a cell runs the same compiled programs. ``lower`` computes
    one precision step below the configuration's (see the module)."""
    n = len(tokens)
    s = pad_to
    if n > s or n - first > rows:
        raise ValueError(f"{n} positions / {n - first} rows exceed "
                         f"{s} / {rows}")
    toks = np.zeros(s, np.int32)
    toks[:n] = tokens
    eps = float(model["rms_norm_eps"])
    dh = model.get("head_dim") or \
        model["hidden_size"] // model["num_attention_heads"]
    arch = (model["num_attention_heads"], model["num_key_value_heads"], dh,
            float(model["rope_theta"]), eps, bool(lower))
    x = jnp.take(w["embedding"], jnp.asarray(toks), axis=0).astype(
        jnp.float32)
    names = {k.split(".")[-2] if k.endswith(".scale") else k.split(".")[-1]:
             k for k in w if k.startswith("layers.")}
    for i in range(model["num_hidden_layers"]):
        p = {short: w[full][i] for short, full in names.items()}
        x = _layer(x, p, jnp.int32(n), arch)
    head = (w["lm_head"] if "lm_head" in w else w["embedding"].T)
    xs = jax.lax.dynamic_slice(jnp.pad(x, ((0, rows), (0, 0))),
                               (first, 0), (rows, x.shape[1]))
    out = _head(xs, w["final_norm.scale"], head, eps, bool(lower))
    return np.asarray(out[:n - first])
