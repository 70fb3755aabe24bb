"""Batched sampling: one jitted ``sample`` over a whole logits array must
give, row by row, what a ``(1, V)`` call with that row's parameters and
key gives; and the engine, which samples a phase in one program and one
host read, must serve the tokens the per-row path served — greedy and
seeded sampled requests, through prefix sharing and a preemption — with
at most one compile per sampler variant however its batch changes."""
import functools

import jax
import numpy as np
import pytest

from repro import configs
from repro.models.api import get_model
from repro.serving.engine import Engine
from repro.serving.request import Phase, SamplingParams
from repro.serving.sampling import sample

VPAD, VOCAB = 40, 37              # logits padded past the vocabulary

# (temperature, top_k, top_p) per row: greedy, temperature only, top-k,
# top-p, both, a greedy row that names filters, top-k 1, temperature only
MIXED = [(0.0, 0, 1.0), (0.7, 0, 1.0), (1.5, 5, 1.0), (1.0, 0, 0.6),
         (2.0, 8, 0.9), (0.0, 3, 0.5), (3.0, 1, 1.0), (1.2, 0, 1.0)]
UNFILTERED = [(t, 0, 1.0) for t in (0.0, 0.5, 1.0, 2.0, 0.0, 4.0, 1.3, 0.9)]
GREEDY = [(0.0, k, p) for k, p in ((0, 1.0), (4, 1.0), (0, 0.3), (2, 0.8))
          ] * 2


@pytest.mark.parametrize("rows,draw,filtered", [
    (MIXED, None, None), (MIXED, True, True),
    (UNFILTERED, True, False), (UNFILTERED, True, True),
    (GREEDY, False, False), (GREEDY, None, None), (GREEDY, True, True),
], ids=["mixed", "mixed-on", "unfiltered-off", "unfiltered-on",
        "greedy-off", "greedy-default", "greedy-on"])
def test_batched_sample_equals_per_row(rows, draw, filtered):
    t, k, p = (np.asarray(c) for c in zip(*rows))
    batched = jax.jit(functools.partial(
        sample, vocab_size=VOCAB, draw=draw, filtered=filtered))
    for seed in range(6):
        logits = 2.0 * jax.random.normal(jax.random.PRNGKey(100 + seed),
                                         (len(rows), VPAD))
        keys = jax.random.split(jax.random.PRNGKey(seed), len(rows))
        got = np.asarray(batched(
            logits, keys, temperature=t.astype(np.float32),
            top_k=k.astype(np.int32), top_p=p.astype(np.float32)))
        want = [int(sample(logits[i:i + 1], keys[i], temperature=float(t[i]),
                           top_k=int(k[i]), top_p=float(p[i]),
                           vocab_size=VOCAB)[0]) for i in range(len(rows))]
        assert got.tolist() == want, (seed, got, want)
        assert got.max() < VOCAB


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class PerRowEngine(Engine):
    """The path the batched sampler replaced: each row through eager
    ``sample`` with its own split of ``state.key``, one host read a
    token; the key never leaves the request."""

    def _park_key(self, idx, state):
        pass

    def _sample_rows(self, rows, *, wrote_kv=True):
        events = []
        for idx, state, logits, row in rows:
            p = state.params
            state.key, sub = jax.random.split(state.key)
            tok = int(sample(logits[row:row + 1], sub,
                             temperature=p.temperature, top_k=p.top_k,
                             top_p=p.top_p,
                             vocab_size=self.cfg.vocab_size)[0])
            state.phase = Phase.RUNNING
            events.append(self._emit(idx, state, tok, wrote_kv=wrote_kv))
        return events


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    params = get_model(cfg).init_params(jax.random.PRNGKey(0))
    return cfg, params


SAMPLED = [SamplingParams(max_new_tokens=n, temperature=t, top_k=k,
                          top_p=p, seed=s)
           for n, t, k, p, s in ((14, 0.8, 0, 1.0, 7), (30, 1.0, 20, 1.0, 8),
                                 (9, 1.2, 0, 0.7, 9), (25, 0.9, 10, 0.9, 10))]


@pytest.mark.parametrize("mixed", [False, True], ids=["greedy", "mixed"])
def test_engine_tokens_and_compiles(smoke_model, mixed):
    """Three slots, five requests over one shared 32-token header and a
    pool too small for them all: the wave shares pages, a resident is
    preempted and resumes. Tokens equal the per-row path's; the sampler
    compiles once per variant it ran (greedy; sampled without filters;
    sampled with them) while the batch keeps changing."""
    cfg, params = smoke_model
    rng = np.random.default_rng(21)
    head = rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(1, cfg.vocab_size, n)
                               .astype(np.int32)])
               for n in (5, 17, 9, 3, 12)]
    greedy = [SamplingParams(max_new_tokens=n) for n in (20, 14, 30, 9, 25)]
    params_of = ([greedy[0]] + SAMPLED if mixed else greedy)
    kw = dict(num_slots=3, max_seq=96, cache_kind="paged", page_size=16,
              prefill_chunk=16, prefix_sharing=True, num_pages=6)
    outs, batches = [], set()
    for cls in (PerRowEngine, Engine):
        eng = cls(cfg, params, **kw)
        rids = [eng.submit(p, sp) for p, sp in zip(prompts, params_of)]
        while not all(eng.requests[r].finished for r in rids):
            eng.step()
            batches.add(frozenset((i, s.rid)
                                  for i, s in eng.by_slot.items()))
        preempted = [eng.requests[r] for r in rids
                     if eng.requests[r].preemptions]
        assert preempted, "pool was never under pressure"
        if mixed:
            assert any(s.params.temperature > 0 for s in preempted)
        assert eng.stats.shared_prefix_pages > 0, "nothing was shared"
        outs.append({r: eng.requests[r].tokens for r in rids})
    assert outs[0] == outs[1]
    assert len(batches) >= 6
    assert eng._sampler._cache_size() == (3 if mixed else 1)
