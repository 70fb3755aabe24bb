"""Token sampling: greedy / temperature / top-k / top-p (pure JAX;
vocab-padded logits are masked by the caller or here via ``vocab_size``).

The engine drives this with a *per-request* PRNG key
(:class:`repro.serving.request.SamplingParams` carries an optional seed),
so one request's sampling order can never perturb another's — a
precondition for preemption being output-invariant under sampling.

One call samples a whole batch: the parameters may be per-row ``(B,)``
arrays and the key one key per row, and each row then draws exactly
what a ``(1, V)`` call with that row's parameters and key draws.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _filter(logits: jax.Array, top_k: jax.Array,
            top_p: jax.Array) -> jax.Array:
    """Top-k, then nucleus (top-p), on ``(B, V)`` logits with per-row
    ``(B,)`` ``top_k`` and ``top_p``; ``top_k <= 0`` and ``top_p >= 1``
    leave a row as it is.

    Top-k cuts below the row's k-th largest logit. Nucleus keeps the
    smallest prefix of tokens (by descending probability) whose
    cumulative probability reaches ``top_p``; the highest-probability
    token always survives (the exclusive cumsum of the top token is
    0 < top_p). Both read one descending sort: cutting below a threshold
    commutes with sorting, so the nucleus reads the top-k-cut row in
    sorted order without a second sort."""
    vocab = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sorted_logits, jnp.clip(top_k - 1, 0, vocab - 1)[:, None], axis=-1)
    kth = jnp.where(top_k[:, None] > 0, kth, -jnp.inf)
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    sorted_logits = jnp.where(sorted_logits < kth, -jnp.inf, sorted_logits)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs      # exclusive cumsum
    keep = cum_before < top_p[:, None]                   # (B, V) sorted order
    # logit threshold = smallest kept logit; everything below is cut
    pth = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                  axis=-1, keepdims=True)
    return jnp.where((top_p[:, None] < 1.0) & (logits < pth),
                     -jnp.inf, logits)


def _per_row(key: jax.Array) -> bool:
    typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    return key.ndim == (1 if typed else 2)


def sample(
    logits: jax.Array,              # (B, V_padded) f32/bf16
    key,                            # one key, or (B,) keys: one per row
    *,
    temperature=0.0,                # scalar or (B,); <= 0 is greedy
    top_k=0,                        # scalar or (B,); 0 = off
    top_p=1.0,                      # scalar or (B,); 1 = off
    vocab_size: int = 0,
    draw: bool | None = None,
    filtered: bool | None = None,
) -> jax.Array:
    """Returns (B,) int32 next tokens.

    A greedy row takes the argmax of the float32, vocab-masked logits.
    ``draw`` and ``filtered`` are static: ``draw=False`` computes the
    argmax alone (every row greedy; ``key`` is not read), and
    ``filtered=False`` skips the sort (no sampled row cuts by top-k or
    top-p). Left at None they follow scalar parameters, and are on where
    a parameter is an array."""
    logits = logits.astype(jnp.float32)
    if vocab_size and vocab_size < logits.shape[-1]:
        pad = jnp.arange(logits.shape[-1]) >= vocab_size
        logits = jnp.where(pad, -jnp.inf, logits)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if draw is None:
        draw = jnp.ndim(temperature) > 0 or temperature > 0.0
    if not draw:
        return greedy
    if filtered is None:
        filtered = (jnp.ndim(top_k) > 0 or jnp.ndim(top_p) > 0
                    or top_k > 0 or top_p < 1.0)
    rows = logits.shape[:1]
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), rows)
    sampled = temperature > 0.0
    logits = logits / jnp.where(sampled, temperature, 1.0)[:, None]
    if filtered:
        logits = _filter(
            logits, jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows),
            jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), rows))
    if _per_row(key):
        drawn = jax.vmap(lambda k, row: jax.random.categorical(
            k, row[None], axis=-1)[0])(key, logits)
    else:
        drawn = jax.random.categorical(key, logits, axis=-1)
    return jnp.where(sampled, drawn.astype(jnp.int32), greedy)
