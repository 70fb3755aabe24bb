"""The engine's own instrumentation: profiler spans around each phase of
``Engine.step``, the ``host_syncs``, ``prefill_tokens`` and
``recomputed_tokens`` counters, the admission time stamp, and the names
of its device programs and Pallas kernels.

A trace taken around the engine must put the device's idle time down to
the host work that caused it, so each phase opens one span per step
(never one per slot), nested as the table in ``Engine.step`` states."""
import functools
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from repro import configs
from repro.core.plan import make_plan
from repro.kernels.chunk_attention import (
    paged_chunk_attention_sync,
    paged_chunk_attention_unified_max,
)
from repro.kernels.decode_attention import (
    decode_attention_sync,
    decode_attention_unified_max,
    paged_decode_attention_sync,
    paged_decode_attention_unified_max,
)
from repro.kernels.decode_fuse import (
    decode_ingest_fused,
    ffn_norm_fused,
    oproj_residual_fused,
)
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flat_gemm import flat_gemm
from repro.kernels.fused_ffn import fused_ffn_up
from repro.kernels.gemv import gemv
from repro.kernels.group_attention import (
    DecodeGroups,
    grouped_paged_decode_attention_unified_max,
)
from repro.models.api import get_model
from repro.serving.engine import Engine
from repro.serving.request import SamplingParams

SPANS = ("engine.admit", "engine.prefill", "engine.grow", "engine.decode",
         "engine.sample")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    params = get_model(cfg).init_params(jax.random.PRNGKey(0))
    return cfg, params


def _shared_prompts(cfg, lens=(5, 9, 7), seed=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
    return [np.concatenate([head, rng.integers(1, cfg.vocab_size, n)
                            .astype(np.int32)]) for n in lens]


def _paged(cfg, params, **kw):
    return Engine(cfg, params, num_slots=2, max_seq=128, cache_kind="paged",
                  page_size=16, prefill_chunk=16, prefix_sharing=True, **kw)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_steps(smoke_model, tmp_path_factory):
    """Host spans of a paged, prefix-sharing engine serving three
    requests that share a 32-token header through two slots (the third
    waits behind the full batch), each ``step()`` inside an
    ``engine.step`` span as the benchmark opens it. Returns one list of
    ``(name, start_ns, end_ns)`` per step."""
    cfg, params = smoke_model
    eng = _paged(cfg, params)
    prompts = _shared_prompts(cfg)
    eng.run([(prompts[0], SamplingParams(max_new_tokens=2))])   # compile
    tmp = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        rids = [eng.submit(p, SamplingParams(max_new_tokens=3))
                for p in prompts]
        while not all(eng.requests[r].finished for r in rids):
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.end_ns)
                           for e in line.events
                           if e.name == "engine.step" or e.name in SPANS]
    steps = sorted(e for e in events if e[0] == "engine.step")
    inner = [e for e in events if e[0] != "engine.step"]
    assert len(steps) >= 4
    out = []
    for _, s, e in steps:
        out.append(sorted((x for x in inner if s <= x[1] and x[2] <= e),
                          key=lambda x: x[1]))
    # every program span lies inside a step
    assert sum(len(x) for x in out) == len(inner)
    return out


def test_every_span_is_opened(traced_steps):
    seen = {name for step in traced_steps for name, _, _ in step}
    assert seen == set(SPANS)


def test_spans_nest_as_stated(traced_steps):
    """``engine.admit`` ends before ``engine.prefill`` starts; a sample
    span lies either inside ``engine.prefill`` (the first tokens) or
    after ``engine.decode`` (the tick's tokens); grow comes before decode;
    admit, prefill, grow and decode never overlap one another."""
    for step in traced_steps:
        by = {}
        for name, s, e in step:
            by.setdefault(name, []).append((s, e))
        outer = sorted(iv for n in SPANS[:4] for iv in by.get(n, []))
        for a, b in zip(outer, outer[1:]):
            assert a[1] <= b[0], "phase spans overlap"
        if "engine.admit" in by and "engine.prefill" in by:
            assert by["engine.admit"][0][1] <= by["engine.prefill"][0][0]
        if "engine.grow" in by and "engine.decode" in by:
            assert by["engine.grow"][0][1] <= by["engine.decode"][0][0]
        for s, e in by.get("engine.sample", []):
            in_prefill = any(ps <= s and e <= pe
                             for ps, pe in by.get("engine.prefill", []))
            after_decode = any(de <= s for _, de in by.get("engine.decode",
                                                           []))
            assert in_prefill or after_decode


def test_at_most_one_span_per_phase_per_step(traced_steps):
    """Two slots decode in most steps, yet no phase opens more than one
    span per step: one sample span for the prefill's first tokens and one
    for the tick's, never one per row."""
    for step in traced_steps:
        names = [n for n, _, _ in step]
        for span in SPANS[:4]:
            assert names.count(span) <= 1, (span, names)
        assert names.count("engine.sample") <= 2, names


# ---------------------------------------------------------------------------
# Counters and stamps
# ---------------------------------------------------------------------------


def test_host_syncs_count_sampling_reads(smoke_model):
    """One blocking device→host read per sampling phase (a wave's first
    tokens, a tick's tokens), however many rows it samples: at most two
    a step, and fewer than the tokens sampled."""
    cfg, params = smoke_model
    eng = _paged(cfg, params)
    phases = []
    sample_rows = eng._sample_rows
    eng._sample_rows = lambda rows, **kw: (phases.append(len(rows))
                                           or sample_rows(rows, **kw))
    rids = [eng.submit(p, SamplingParams(max_new_tokens=4))
            for p in _shared_prompts(cfg, seed=1)]
    while not all(eng.requests[r].finished for r in rids):
        before = eng.stats.host_syncs
        eng.step()
        assert eng.stats.host_syncs - before <= 2
    assert sum(phases) == sum(eng.requests[r].generated for r in rids) == 12
    assert eng.stats.host_syncs == len(phases) < 12


def test_host_syncs_count_tier_gathers(smoke_model):
    """Demoting the session cache host-ward reads each cache leaf back
    once per gather."""
    cfg, params = smoke_model
    eng = _paged(cfg, params, host_pages=16, session_cache=True)
    eng.run([(_shared_prompts(cfg)[0], SamplingParams(max_new_tokens=2))])
    before = eng.stats.host_syncs
    assert eng.flush_sessions() > 0
    assert (eng.stats.host_syncs - before
            == len(jax.tree.leaves(eng.cache)))


def test_prefill_counters_zero_without_prefill_or_preemption(smoke_model):
    """``prefill_tokens`` counts every prompt position prefill computes;
    ``recomputed_tokens`` stays 0 when nothing is preempted, and both
    start at 0."""
    cfg, params = smoke_model
    eng = Engine(cfg, params, num_slots=2, max_seq=128, cache_kind="paged",
                 page_size=16, prefill_chunk=16)
    assert eng.stats.prefill_tokens == eng.stats.recomputed_tokens == 0
    prompts = _shared_prompts(cfg, seed=2)
    eng.run([(p, SamplingParams(max_new_tokens=3)) for p in prompts])
    assert eng.stats.preemptions == 0
    assert eng.stats.prefill_tokens == sum(len(p) for p in prompts)
    assert eng.stats.recomputed_tokens == 0


def test_recomputed_tokens_count_readmission_prefill(smoke_model):
    """On a pool too small for both sequences' growth, a preempted
    request re-prefills its prompt and the tokens it had generated when
    it was preempted: ``recomputed_tokens`` counts exactly those chunk
    positions, and ``prefill_tokens`` every chunk position."""
    cfg, params = smoke_model
    eng = Engine(cfg, params, num_slots=2, max_seq=64, cache_kind="paged",
                 page_size=16, num_pages=4, prefill_chunk=16)
    chunked = {"all": 0, "again": 0}
    chunk = eng._chunk

    def spy(p, tokens, chunk_lens, *rest):
        cl = np.asarray(chunk_lens)
        for idx, state in eng.by_slot.items():
            chunked["all"] += int(cl[idx])
            if state.preemptions:
                chunked["again"] += int(cl[idx])
        return chunk(p, tokens, chunk_lens, *rest)

    eng._chunk = spy
    owed = []
    preempt = eng._preempt
    eng._preempt = lambda st: (owed.append(len(st.prompt) + st.generated)
                               or preempt(st))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 10)]
    eng.run([(p, SamplingParams(max_new_tokens=26)) for p in prompts])
    assert eng.stats.preemptions == len(owed) > 0
    assert eng.stats.recomputed_tokens == chunked["again"] == sum(owed)
    assert eng.stats.prefill_tokens == chunked["all"] == (
        sum(len(p) for p in prompts) + sum(owed))


def test_admit_time_between_submit_and_first_token(smoke_model):
    """``submit_time <= admit_time <= first_token_time`` on one clock; a
    request queued behind a full batch is admitted only in a later step."""
    cfg, params = smoke_model
    eng = _paged(cfg, params)
    rids = [eng.submit(p, SamplingParams(max_new_tokens=3))
            for p in _shared_prompts(cfg, seed=2)]
    eng.step()
    after_first = time.perf_counter()
    queued = eng.requests[rids[2]]
    assert queued.admit_time is None
    while not all(eng.requests[r].finished for r in rids):
        eng.step()
    for r in rids:
        st = eng.requests[r]
        assert st.submit_time <= st.admit_time <= st.first_token_time
    assert queued.admit_time > after_first


# ---------------------------------------------------------------------------
# Program and kernel names
# ---------------------------------------------------------------------------


def test_engine_programs_lower_under_their_names(smoke_model):
    cfg, params = smoke_model
    plan = make_plan(decode_group="grouped", group_threshold=1)
    eng = _paged(cfg, params, plan=plan, host_pages=16, session_cache=True)
    i32 = jnp.int32
    b, c = eng.num_slots, eng.prefill_chunk
    bt = eng.slots.block_tables()
    lengths = eng.slots.lengths_device()
    positions = eng.slots.positions_device()
    tokens = jnp.zeros((b,), i32)
    groups = DecodeGroups(
        tables=jnp.zeros((1, 2), i32), n_pages=jnp.zeros((1,), i32),
        g_prefix_len=jnp.zeros((1,), i32), num_members=jnp.zeros((1,), i32),
        member_rows=jnp.zeros((1, b), i32), gid=jnp.zeros((b,), i32),
        member=jnp.zeros((b,), i32), prefix_len=jnp.zeros((b,), i32))
    slabs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((a.shape[0], 1) + a.shape[2:],
                                       a.dtype), eng.cache)
    lowered = {
        "decode_step": eng._decode.lower(
            params, tokens, eng.cache, bt, lengths, positions),
        "grouped_decode_step": eng._decode_grouped.lower(
            params, tokens, eng.cache, bt, lengths, groups, positions),
        "prefill_chunk": eng._chunk.lower(
            params, jnp.zeros((b, c), i32), jnp.zeros((b,), i32),
            eng.cache, bt, lengths),
        "copy_page": eng._copy_page.lower(eng.cache, 0, 1),
        "promote_upload": eng._promote_upload.lower(
            eng.cache, slabs, jnp.zeros((1,), i32)),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def _pallas_names(jaxpr) -> list:
    """Names of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else [v]):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    out += _pallas_names(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    out += _pallas_names(sub)
    return out


# qwen2-0.5b widths; tracing builds the jaxpr only, nothing compiles
B, PS, NB, HQ, HK, DH, D, F, C = 8, 64, 16, 14, 2, 64, 896, 4864, 64
S = jax.ShapeDtypeStruct
BF, I32 = jnp.bfloat16, jnp.int32
POOL = S((B * NB, PS, HK, DH), BF)
PAGED = (S((B, HQ, DH), BF), POOL, POOL, S((B, NB), I32), S((B,), I32))
DENSE = (S((B, HQ, DH), BF), S((B, HK, 1024, DH), BF),
         S((B, HK, 1024, DH), BF), S((B,), I32))
CHUNK = (S((B, C, HQ, DH), BF),) + PAGED[1:]
PREFILL = (S((1, 256, HQ, DH), BF), S((1, 256, HK, DH), BF),
           S((1, 256, HK, DH), BF))
GROUPS = DecodeGroups(
    tables=S((2, 2), I32), n_pages=S((2,), I32), g_prefix_len=S((2,), I32),
    num_members=S((2,), I32), member_rows=S((2, B), I32), gid=S((B,), I32),
    member=S((B,), I32), prefix_len=S((B,), I32))


def _ingest(x, norm, wq, wk, wv, pos):
    return decode_ingest_fused(x, norm, wq, wk, wv, pos, num_heads=HQ,
                               num_kv_heads=HK, head_dim=DH, rope_theta=1e6,
                               eps=1e-6)


KERNELS = {
    "paged_decode_attention_unified_max":
        (paged_decode_attention_unified_max, PAGED),
    "paged_decode_attention_sync": (paged_decode_attention_sync, PAGED),
    "paged_chunk_attention_unified_max":
        (paged_chunk_attention_unified_max, CHUNK),
    "paged_chunk_attention_sync": (paged_chunk_attention_sync, CHUNK),
    "decode_attention_unified_max": (decode_attention_unified_max, DENSE),
    "decode_attention_sync": (decode_attention_sync, DENSE),
    "grouped_decode_prefix,grouped_decode_tail": (
        grouped_paged_decode_attention_unified_max, PAGED + (GROUPS,)),
    "flash_prefill_unified_max": (flash_prefill, PREFILL),
    "flash_prefill_sync": (
        lambda q, k, v: flash_prefill(q, k, v, unified_max=False), PREFILL),
    "decode_ingest_fused": (_ingest, (
        S((B, D), BF), S((D,), BF), S((D, HQ * DH), BF),
        S((D, HK * DH), BF), S((D, HK * DH), BF), S((B,), I32))),
    "oproj_residual_fused": (oproj_residual_fused, (
        S((B, HQ * DH), BF), S((HQ * DH, D), BF), S((B, D), BF))),
    "ffn_norm_fused": (functools.partial(ffn_norm_fused, eps=1e-6), (
        S((B, D), BF), S((D,), BF), S((D, F), BF), S((D, F), BF))),
    "gemv": (gemv, (S((B, D), BF), S((D, HQ * DH), BF))),
    "flat_gemm": (flat_gemm, (S((B, D), BF), S((D, F), BF))),
    "fused_ffn_up": (fused_ffn_up, (S((B, D), BF), S((D, F), BF),
                                    S((D, F), BF))),
}


@pytest.mark.parametrize("names", list(KERNELS))
def test_kernel_wrapper_names_its_pallas_call(names):
    fn, args = KERNELS[names]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _pallas_names(jaxpr) == names.split(",")
