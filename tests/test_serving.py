"""Serving engine: request lifecycle, continuous batching, sampling,
streaming/abort, and engine-vs-prefill consistency (greedy decode must
match teacher forcing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.api import get_model
from repro.models.kvlayout import DenseLayout
from repro.serving.engine import Engine
from repro.serving.kvcache import SlotManager
from repro.serving.request import FinishReason, SamplingParams
from repro.serving.sampling import sample


def _engine(arch, **kw):
    cfg = configs.smoke(configs.get(arch))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, Engine(cfg, params, **kw)


def test_slot_manager_lifecycle():
    sm = SlotManager(2, max_seq=32)
    a = sm.try_assign(10, prompt_len=4, max_new=8)
    b = sm.try_assign(11, prompt_len=4, max_new=8)
    assert a == 0 and b == 1
    assert sm.try_assign(12, 4, 8) is None      # full
    assert list(sm.lengths()) == [4, 4]
    sm.tick(a)
    assert list(sm.lengths()) == [5, 4]
    assert sm.block_tables() is None            # dense layout: no operand
    sm.release(a)
    assert sm.try_assign(12, 4, 8) == 0          # slot reused
    with pytest.raises(ValueError):
        sm.try_assign(13, prompt_len=30, max_new=8)  # exceeds max_seq


def test_engine_continuous_batching_queueing():
    cfg, eng = _engine("qwen2-0.5b", num_slots=2, max_seq=128)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(1, 100, size=5 + i).astype(np.int32),
             SamplingParams(max_new_tokens=4)) for i in range(5)]
    out = eng.run(reqs)
    assert set(out) == set(range(5))
    assert all(len(v) == 4 for v in out.values())
    assert all(eng.finish_reason(r) is FinishReason.LENGTH for r in out)


@pytest.mark.parametrize(
    "arch", ["qwen2-0.5b",
             pytest.param("rwkv6-1.6b", marks=pytest.mark.slow),
             pytest.param("hymba-1.5b", marks=pytest.mark.slow)])
def test_engine_matches_teacher_forcing(arch):
    """Greedy engine output == argmax of prefill(prompt + prefix) at every
    step — continuous batching/ragged prompts do not change the math."""
    cfg, eng = _engine(arch, num_slots=2, max_seq=256)
    api = get_model(cfg)
    params = eng.params
    from repro.models.layers import LayerCtx
    ctx = LayerCtx(cfg=cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 23)]
    out = eng.run([(p, SamplingParams(max_new_tokens=3)) for p in prompts])
    for i, prompt in enumerate(prompts):
        toks = out[i]
        for k in range(3):
            seq = np.concatenate([prompt, np.asarray(toks[:k], np.int32)])
            # one padded teacher shape -> one jit compile for all (i, k)
            padded = np.zeros((64,), np.int32)
            padded[:len(seq)] = seq
            cache = api.init_cache(DenseLayout(1, 256))
            logits, _ = api.prefill(
                ctx, params, jnp.asarray(padded)[None],
                jnp.array([len(seq)], jnp.int32), cache)
            want = int(jnp.argmax(logits[0, :cfg.vocab_size]))
            assert want == toks[k], (arch, i, k)


def test_engine_chunked_prefill_matches_teacher_forcing():
    """Chunked + batched prefill (prompts streamed through the decode-shaped
    path in 16-token chunks, whole admission wave in one padded batch) is
    greedy-equivalent to single-shot ``api.prefill`` teacher forcing for
    ragged prompt lengths spanning 1..4 chunks."""
    cfg, eng = _engine("qwen2-0.5b", num_slots=4, max_seq=256,
                       prefill_chunk=16)
    api = get_model(cfg)
    from repro.models.layers import LayerCtx
    ctx = LayerCtx(cfg=cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 16, 23, 61)]    # below / at / across chunk edges
    out = eng.run([(p, SamplingParams(max_new_tokens=2)) for p in prompts])
    for i, prompt in enumerate(prompts):
        toks = out[i]
        for k in range(2):
            seq = np.concatenate([prompt, np.asarray(toks[:k], np.int32)])
            # one padded teacher shape -> one jit compile for all (i, k)
            padded = np.zeros((64,), np.int32)
            padded[:len(seq)] = seq
            cache = api.init_cache(DenseLayout(1, 256))
            logits, _ = api.prefill(
                ctx, eng.params, jnp.asarray(padded)[None],
                jnp.array([len(seq)], jnp.int32), cache)
            want = int(jnp.argmax(logits[0, :cfg.vocab_size]))
            assert want == toks[k], (i, k)


def test_engine_stop_token_and_finish_reason():
    """A sampled stop token ends the request with reason ``stop``; the
    token joins the output only under ``include_stop=True`` and never
    burns ``max_new_tokens`` budget; the freed slot is reused."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 50, 8).astype(np.int32)
    # find the greedy continuation, then stop on its first token that
    # differs from the first one (a repeat of toks[0] would stop at once)
    cfg, probe = _engine("qwen2-0.5b", num_slots=1, max_seq=128)
    toks = probe.run([(prompt, SamplingParams(max_new_tokens=10))])[0]
    j = next(i for i, t in enumerate(toks) if t != toks[0])
    stop = toks[j]

    _, eng = _engine("qwen2-0.5b", num_slots=1, max_seq=128)
    out = eng.run([
        (prompt, SamplingParams(max_new_tokens=10, stop_tokens=(stop,))),
        (prompt, SamplingParams(max_new_tokens=10, stop_tokens=(stop,),
                                include_stop=True)),
        (prompt, SamplingParams(max_new_tokens=10)),
    ])
    assert out[0] == toks[:j]                    # stop excluded
    assert out[1] == toks[:j + 1]                # stop included
    assert len(out[2]) == 10                     # no stop -> full budget
    assert eng.finish_reason(0) is FinishReason.STOP
    assert eng.finish_reason(1) is FinishReason.STOP
    assert eng.finish_reason(2) is FinishReason.LENGTH
    # the event stream mirrors run(): an excluded stop token never reaches
    # it (terminal event is token=None), an included one does
    for rid in out:
        streamed = [e.token for e in eng.requests[rid].events
                    if e.token is not None]
        assert streamed == out[rid], rid
    assert eng.requests[0].events[-1].token is None
    assert eng.requests[1].events[-1].token == stop


def test_engine_single_token_requests_drain_queue():
    """max_new_tokens=1 requests finish inside prefill, leaving the batch
    empty while others wait — the engine must keep admitting (not report a
    stall) until the queue drains."""
    cfg, eng = _engine("qwen2-0.5b", num_slots=1, max_seq=64)
    rng = np.random.default_rng(2)
    out = eng.run([(rng.integers(1, 100, 6).astype(np.int32),
                    SamplingParams(max_new_tokens=1)) for _ in range(3)])
    assert all(len(v) == 1 for v in out.values())
    assert all(eng.finish_reason(r) is FinishReason.LENGTH for r in out)


def test_engine_generate_streams_and_aborts():
    """generate() yields TokenEvents incrementally (final event carries
    finished + reason); abort() cancels a co-resident request mid-flight
    and frees its slot for the queue."""
    cfg, eng = _engine("qwen2-0.5b", num_slots=2, max_seq=128)
    rng = np.random.default_rng(1)
    victim = eng.submit(rng.integers(1, 100, 12).astype(np.int32),
                        SamplingParams(max_new_tokens=50))
    events = []
    for ev in eng.generate(rng.integers(1, 100, 9).astype(np.int32),
                           SamplingParams(max_new_tokens=6)):
        events.append(ev)
        if ev.index == 2:
            assert eng.abort(victim)
    assert [e.index for e in events] == list(range(6))
    assert events[-1].finished
    assert events[-1].finish_reason is FinishReason.LENGTH
    assert all(not e.finished for e in events[:-1])
    assert eng.finish_reason(victim) is FinishReason.ABORT
    vic = eng.requests[victim]
    assert 0 < vic.generated < 50
    # streamed tokens match the state's record
    stream_rid = events[0].rid
    assert [e.token for e in events] == eng.requests[stream_rid].tokens


def test_engine_per_request_seed_isolation():
    """Sampled requests own their PRNG stream: the same (prompt, seed)
    draws the same tokens no matter how its batch-mates sample."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 100, 10).astype(np.int32)
    other = rng.integers(1, 100, 14).astype(np.int32)
    sp = SamplingParams(max_new_tokens=5, temperature=0.8, top_k=20, seed=7)

    def crowd(other_sp):
        _, eng = _engine("qwen2-0.5b", num_slots=2, max_seq=128)
        return eng.run([(other, other_sp), (prompt, sp)])[1]

    a = crowd(SamplingParams(max_new_tokens=8, temperature=1.0, seed=123))
    b = crowd(SamplingParams(max_new_tokens=3, temperature=0.3, seed=999))
    assert a == b


def test_sampling_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    assert int(sample(logits, key)[0]) == 1                       # greedy
    # vocab mask: ids >= vocab_size never sampled
    toks = [int(sample(logits, jax.random.PRNGKey(i), temperature=5.0,
                       vocab_size=3)[0]) for i in range(50)]
    assert max(toks) <= 2
    # top-k=1 == greedy even at high temperature
    toks = [int(sample(logits, jax.random.PRNGKey(i), temperature=3.0,
                       top_k=1)[0]) for i in range(20)]
    assert set(toks) == {1}


def test_sampling_top_p_distribution():
    """Nucleus sampling: the kept set is exactly the smallest prefix whose
    cumulative probability reaches top_p, and the empirical distribution
    over many draws tracks the renormalized probabilities."""
    # probs 0.5 / 0.25 / 0.125 / 0.0625 / 0.0625
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.125, 0.0625, 0.0625]]))
    draws = [int(sample(logits, jax.random.PRNGKey(i), temperature=1.0,
                        top_p=0.5)[0]) for i in range(40)]
    assert set(draws) == {0}                    # nucleus = top token only
    draws = [int(sample(logits, jax.random.PRNGKey(i), temperature=1.0,
                        top_p=0.8)[0]) for i in range(400)]
    assert set(draws) <= {0, 1, 2}              # 0.5+0.25+0.125 >= 0.8
    freq0 = draws.count(0) / len(draws)
    assert 0.45 <= freq0 <= 0.70                # ~0.5/0.875 = 0.57
    # top_p -> 1 keeps everything reachable
    draws = [int(sample(logits, jax.random.PRNGKey(i), temperature=1.0,
                        top_p=1.0)[0]) for i in range(400)]
    assert set(draws) == {0, 1, 2, 3, 4}


def test_engine_respects_max_seq_budget():
    """Unservable requests are rejected at submit() — before any
    batch-mate claims a slot — for both the max_seq and the page-pool
    worst-case bounds."""
    cfg, eng = _engine("qwen2-0.5b", num_slots=1, max_seq=32)
    with pytest.raises(ValueError):
        eng.run([(np.arange(1, 30, dtype=np.int32),
                  SamplingParams(max_new_tokens=10))])
    assert not eng.requests and not eng.waiting      # nothing half-admitted
    cfg2, paged = _engine("qwen2-0.5b", num_slots=1, max_seq=512,
                          cache_kind="paged", page_size=64, num_pages=2)
    with pytest.raises(ValueError):
        paged.submit(np.arange(1, 200, dtype=np.int32),
                     SamplingParams(max_new_tokens=100))   # 5 > 2 pages


def test_engine_evicts_finished_state():
    """Long-lived servers can drop retained per-request state once
    consumed; unfinished requests must be aborted first."""
    cfg, eng = _engine("qwen2-0.5b", num_slots=1, max_seq=64)
    rng = np.random.default_rng(0)
    out = eng.run([(rng.integers(1, 100, 6).astype(np.int32),
                    SamplingParams(max_new_tokens=2)) for _ in range(2)])
    assert eng.evict(0) == out[0]
    assert 0 not in eng.requests
    waiting_rid = eng.submit(rng.integers(1, 100, 6).astype(np.int32))
    with pytest.raises(ValueError):
        eng.evict(waiting_rid)                       # not finished
    eng.abort(waiting_rid)
    assert eng.evict_finished() == 2                 # rid 1 + the aborted
    assert not eng.requests
