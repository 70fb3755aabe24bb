"""Scheduler policies: admission ordering, preemption victims, and the
lifecycle properties the redesign promises — no starvation under FCFS,
preempted sequences eventually finish, and lazy allocation never leaks a
page (pool balance invariant). The properties run against a host-side
simulation of the engine's scheduling protocol (admission → lazy growth →
preempt-on-dry-pool → retire), driven by the hypothesis shim; the real
jitted engine is exercised end-to-end in test_blockpool's
overcommit/preemption equivalence tests."""
import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.models.api import get_model
from repro.models.kvlayout import pages_for
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.engine import Engine
from repro.serving.request import Phase, RequestState, SamplingParams
from repro.serving.scheduler import (FCFS, PageBudgetFair, Scheduler,
                                     ShortestJobFirst, get_scheduler)

settings.register_profile("fast", max_examples=15, deadline=None)
settings.load_profile("fast")


def _state(rid, prompt_len, max_new, arrival=None):
    return RequestState(
        rid=rid, prompt=np.zeros((prompt_len,), np.int32),
        params=SamplingParams(max_new_tokens=max_new),
        arrival=arrival if arrival is not None else rid,
        key=jax.random.PRNGKey(0))


def test_get_scheduler_registry():
    assert isinstance(get_scheduler("fcfs"), FCFS)
    assert isinstance(get_scheduler("sjf"), ShortestJobFirst)
    assert isinstance(get_scheduler("pagefair"), PageBudgetFair)
    inst = PageBudgetFair()
    assert get_scheduler(inst) is inst
    with pytest.raises(ValueError):
        get_scheduler("priority-lottery")


def test_policy_orderings():
    a = _state(0, prompt_len=10, max_new=20)   # oldest, mid job, small KV
    b = _state(1, prompt_len=40, max_new=2)    # shortest job, largest KV
    c = _state(2, prompt_len=5, max_new=30)    # newest, longest job
    fcfs, sjf, fair = FCFS(), ShortestJobFirst(), PageBudgetFair()
    assert [s.rid for s in fcfs.admission_order([c, a, b])] == [0, 1, 2]
    assert [s.rid for s in sjf.admission_order([c, a, b])] == [1, 0, 2]
    assert [s.rid for s in fair.admission_order([c, a, b])] == [2, 0, 1]
    # victims mirror each policy's cost signal
    assert fcfs.pick_victim([a, b, c]).rid == 2        # newest
    assert sjf.pick_victim([a, b, c]).rid == 2         # most work left
    assert fair.pick_victim([a, b, c]).rid == 1        # largest footprint
    assert fcfs.pick_victim([]) is None


# ---------------------------------------------------------------------------
# Protocol simulation: the engine's admission/growth/preempt/retire loop
# over real pool + slot-manager state, with a stub token stream — fast
# enough to property-test every policy on random workloads.
# ---------------------------------------------------------------------------


def _simulate(scheduler: Scheduler, specs, *, num_slots, num_pages,
              page_size, max_seq, max_ticks=5_000):
    pool = BlockPool(num_pages, page_size)
    mgr = PagedSlotManager(num_slots, max_seq, pool)
    states = [_state(i, p, m) for i, (p, m) in enumerate(specs)]
    waiting = list(states)
    by_slot: dict[int, RequestState] = {}
    admissions: list[int] = []               # rids in first-admission order
    ticks = 0

    def retire(idx, st):
        mgr.release(idx)
        del by_slot[idx]
        st.finish_reason = "done"
        st.phase = Phase.FINISHED

    def emit(idx, st, wrote_kv=True):
        st.tokens.append(0)
        mgr.tick(idx, wrote_kv=wrote_kv)
        if st.generated >= st.params.max_new_tokens:
            retire(idx, st)

    while (waiting or by_slot) and ticks < max_ticks:
        # admission (+"prefill": first token) in the policy's order
        for st in scheduler.admission_order(waiting):
            idx = mgr.try_assign(
                st.rid, len(st.prefill_tokens()),
                max(st.params.max_new_tokens - st.generated, 1))
            if idx is None:
                if not scheduler.allow_skip:
                    break
                continue
            if st.phase is Phase.WAITING:
                admissions.append(st.rid)
            st.phase = Phase.RUNNING
            st.slot = idx
            by_slot[idx] = st
            emit(idx, st, wrote_kv=False)
        waiting = [s for s in waiting
                   if s.slot is None and s.phase is not Phase.FINISHED]
        # decode tick: lazy growth, preempt on dry pool (victim may be the
        # growing sequence itself — mirrors Engine._grow_or_preempt, so
        # FCFS really evicts the newest arrival), one token each
        for idx, st in list(by_slot.items()):
            if by_slot.get(idx) is not st:
                continue
            while not mgr.ensure(idx, mgr.slots[idx].length + 1):
                victim = scheduler.pick_victim(list(by_slot.values()))
                assert victim is not None, "dry pool with no victim"
                assert not (victim is st and len(by_slot) == 1), \
                    "lone sequence unsatisfiable despite admission bound"
                vidx = victim.slot
                mgr.release(vidx)
                del by_slot[vidx]
                victim.phase = Phase.PREEMPTED
                victim.slot = None
                victim.preemptions += 1
                waiting.append(victim)
                if victim is st:
                    break
        for idx in sorted(by_slot):
            emit(idx, by_slot[idx])
        mgr.check()                          # cross-structure invariants
        ticks += 1
    return states, pool, admissions, ticks


def _random_workload(rng, num_pages, page_size, max_seq, n):
    specs = []
    for _ in range(n):
        p = int(rng.integers(1, max_seq // 2))
        m = int(rng.integers(1, max_seq - p + 1))
        if pages_for(p + m, page_size) > num_pages:
            m = max(num_pages * page_size - p, 1)   # keep it servable
        specs.append((p, m))
    return specs


@given(st.integers(0, 10_000))
def test_fcfs_no_starvation_and_order(seed):
    """Strict FCFS: every request finishes (bounded ticks even under an
    overcommitted pool), first admissions happen in arrival order, and
    the pool drains back to balance."""
    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([4, 8]))
    num_pages = int(rng.integers(3, 10))
    max_seq = page_size * num_pages
    specs = _random_workload(rng, num_pages, page_size, max_seq,
                             n=int(rng.integers(2, 8)))
    states, pool, admissions, ticks = _simulate(
        FCFS(), specs, num_slots=int(rng.integers(1, 4)),
        num_pages=num_pages, page_size=page_size, max_seq=max_seq)
    assert all(s.phase is Phase.FINISHED for s in states), \
        f"starved after {ticks} ticks"
    assert admissions == sorted(admissions), \
        "FCFS let a later arrival overtake the queue head"
    assert pool.free_pages == pool.num_pages     # no page leaked


@pytest.mark.parametrize("policy", ["sjf", "pagefair"])
@given(seed=st.integers(0, 10_000))
def test_preempted_sequences_eventually_finish(policy, seed):
    """Under any policy, preemption is a detour, not an exit: preempted
    requests re-admit, re-prefill, and complete; lazy growth returns every
    page to the pool."""
    rng = np.random.default_rng(seed + sum(map(ord, policy)))
    page_size = 4
    num_pages = int(rng.integers(3, 8))
    max_seq = page_size * num_pages
    specs = _random_workload(rng, num_pages, page_size, max_seq,
                             n=int(rng.integers(3, 8)))
    states, pool, _admissions, ticks = _simulate(
        get_scheduler(policy), specs, num_slots=int(rng.integers(2, 4)),
        num_pages=num_pages, page_size=page_size, max_seq=max_seq)
    assert all(s.phase is Phase.FINISHED for s in states), \
        f"{policy}: unfinished after {ticks} ticks"
    assert all(s.generated == s.params.max_new_tokens for s in states)
    assert pool.free_pages == pool.num_pages


def test_sjf_admits_short_job_first_in_real_engine():
    """Wiring check on the jitted engine: with one slot, SJF runs the
    2-token job before the 30-token job that arrived first; FCFS does the
    opposite."""
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    long_p = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
    short_p = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)

    def ticks(policy):
        eng = Engine(cfg, params, num_slots=1, max_seq=64,
                     scheduler=policy)
        eng.run([(long_p, SamplingParams(max_new_tokens=30)),
                 (short_p, SamplingParams(max_new_tokens=2))])
        return (eng.requests[0].first_token_tick,
                eng.requests[1].first_token_tick)

    f_long, f_short = ticks("fcfs")
    assert f_long < f_short                  # arrival order
    s_long, s_short = ticks("sjf")
    assert s_short < s_long                  # cost order


def test_every_policy_completes_in_real_engine_under_overcommit():
    """Each built-in policy drives the jitted engine through a ragged
    workload on a pool half the worst case: every request gets all its
    tokens, the pool drains, and the greedy tokens are the same whatever
    order the policy admitted and preempted in."""
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    params = get_model(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(5, 60, size=6)]
    num_pages = 2 * pages_for(128, 16) // 2     # half of 2 slots' worst
    outs = {}
    for policy in ("fcfs", "sjf", "pagefair"):
        eng = Engine(cfg, params, num_slots=2, max_seq=128,
                     cache_kind="paged", page_size=16, prefill_chunk=16,
                     num_pages=num_pages, scheduler=policy)
        out = eng.run([(p, SamplingParams(max_new_tokens=8))
                       for p in prompts])
        assert sorted(out) == list(range(len(prompts))), policy
        assert all(len(v) == 8 for v in out.values()), policy
        assert 0 < eng.stats.peak_pages_used <= num_pages
        assert eng.pool.used_pages == 0
        outs[policy] = out
    assert outs["fcfs"] == outs["sjf"] == outs["pagefair"]
