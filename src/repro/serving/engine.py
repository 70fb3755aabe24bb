"""Streaming continuous-batching engine: pluggable scheduling, lazy paged
KV growth, preemption, and one cache-agnostic model surface.

The serving realization of the paper's heuristic-dataflow argument
(Sec. 5): throughput comes from *adapting* to input dynamics, so the
engine's request lifecycle, memory discipline, and admission policy are
all first-class and swappable:

  * **One model surface, two KV layouts.** The engine holds a single
    :class:`~repro.models.kvlayout.KVLayout` (``DenseLayout`` slot cache
    or ``PagedLayout`` block pool) and exactly one jitted
    ``prefill_chunk``/``decode_step`` pair; the layout's optional
    block-table operand (``slots.block_tables()``, ``None`` for dense)
    selects the addressing discipline inside the model. There is no
    dense/paged code fork anywhere in the tick loop.

  * **Request lifecycle.** Each submission is a
    :class:`~repro.serving.request.RequestState` walking WAITING →
    PREFILLING → RUNNING → FINISHED``{stop,length,abort}``, with
    PREEMPTED as the detour back to the queue. Sampling knobs ride in an
    immutable :class:`~repro.serving.request.SamplingParams` (temperature
    / top-k / top-p / per-request seed / stop tokens with explicit
    ``include_stop``), and every request owns a private PRNG key — no
    request's sampling order can perturb another's.

  * **Lazy pages + preemption.** Paged admission reserves pages only for
    the tokens about to be prefilled; each decode tick grows tables
    page-by-page. When the (possibly overcommitted) pool runs dry, the
    :class:`~repro.serving.scheduler.Scheduler` picks a victim: its pages
    are freed and its state re-queued, and on re-admission the engine
    re-prefills ``prompt + generated`` — block tables make the eviction
    relocation-free, and the rebuilt KV is exactly what an uninterrupted
    run would hold, so greedy outputs are preemption-invariant.

  * **Prefix sharing + copy-on-write.** With ``prefix_sharing=True``
    (paged only), admission consults a
    :class:`~repro.serving.prefix.PrefixIndex` mapping page-aligned
    token-chunk hash chains to live pages: a request whose prompt prefix
    is already resident bumps refcounts instead of allocating, and
    prefills only the unshared suffix. Shared pages are immutable — the
    first write into one forks it (fresh page + device slab copy +
    block-table patch), a victim's release only drops refs (surviving
    sharers keep the pages), and chunk boundaries stay on the share-less
    grid, so greedy outputs are bit-identical with sharing on or off.

  * **Tiered KV + session cache.** With ``host_pages``/``session_cache``
    (paged + sharing only), the pool becomes tier 0 of a memory
    hierarchy (:mod:`repro.serving.tiers`): retiring or preempting a
    sequence *retains* its full KV pages in a tier-0 session set instead
    of freeing them, pool pressure demotes those pages host-ward (one
    bulk device→host gather per reclaim batch), and a returning
    conversation whose prefix matches a demoted span *promotes* the
    slabs back (one bulk host→device scatter before its prefill) —
    re-prefilling only what was truly evicted. Whether promoting beats
    re-prefilling is the plan-tuned ``PagedPlan.swap_threshold``
    (:func:`repro.core.dispatch.find_swap_threshold`). Demoted bytes are
    the originally computed bytes, so resumed decode stays bit-identical
    with never-preempted and re-prefilled runs.

  * **One dispatch surface.** Every kernel decision — GEMM routing,
    softmax scheme, decode ``block_k``, backend — rides in the single
    ``plan=`` operand (:class:`~repro.core.plan.ExecutionPlan`, tuned
    offline by :func:`repro.core.plan.tune`); the engine never consults
    per-op flags, and plans change which kernel runs, never the tokens.

  * **Streaming surface.** ``generate(prompt, params)`` yields
    :class:`~repro.serving.request.TokenEvent` as ticks produce them,
    ``abort(rid)`` cancels at any phase, and the classic blocking
    ``run(requests) -> dict`` is a thin loop over ``submit``/``step``.

Prefill remains chunked + batched for dense-KV families (every admitted
prompt streams through ``api.prefill_chunk`` in fixed-size chunks, the
whole admission wave in one ``(num_slots, chunk)`` call) and batched
single-shot for recurrent/ring families; decode runs the whole slot batch
every tick (continuous batching), keeping the decode-phase GEMMs at
M = num_slots — the regime the paper's T2/T3 optimize.

With a paged cache and ``plan.paged.gather_chunk == "fused"``, waves whose
prompts reach the tuned ``fused_threshold`` run the fused chunk-attention
discipline: the block-table operand is bounded to a bucketed
O(resident pages) width per step (``_chunk_tables``), the Pallas backend
reads K/V pages in place through the fused chunk kernel, and the XLA
backend's gather shrinks to the bounded width — bitwise identical to the
full gather (trailing masked pages contribute exact zeros), so greedy
outputs match across {dense, gather, fused} × {sharing on/off}.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterator, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core.plan import (DEFAULT_PLAN, FUSION_MODES, KV_DTYPES,
                             WEIGHT_DTYPES, ExecutionPlan)
from repro.kernels import quant
from repro.models import wquant
from repro.models.api import get_model
from repro.models.kvlayout import DenseLayout, KVLayout, PagedLayout, \
    pages_for, pow2_bucket
from repro.models.layers import LayerCtx
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.kvcache import SlotManager
from repro.serving.prefix import PrefixIndex
from repro.serving.tiers import TieredPool
from repro.serving.request import (FinishReason, Phase, RequestState,
                                   SamplingParams, TokenEvent)
from repro.serving.sampling import sample
from repro.serving.scheduler import Scheduler, get_scheduler

_span = jax.profiler.TraceAnnotation

PROMPT_BUCKET = 64
DEFAULT_PREFILL_CHUNK = 64
DEFAULT_PAGE_SIZE = 64

PromptLike = Union[np.ndarray, Sequence[int]]


@dataclasses.dataclass
class EngineStats:
    """Counters for the CLI summary line and the benchmark, read as
    differences between two snapshots. (Tick count lives on
    ``Engine.ticks`` — the loop's one clock.)"""

    host_syncs: int = 0              # blocking device→host reads: one
    #                                  per sampling phase (a wave's first
    #                                  tokens, a tick's tokens), one per
    #                                  leaf of a tier gather
    preemptions: int = 0
    prefill_tokens: int = 0          # positions prefill computed (every
    #                                  chunk position; the whole prompt
    #                                  in single-shot prefill)
    recomputed_tokens: int = 0       # of those, positions a preempted
    #                                  request prefilled again at
    #                                  re-admission
    peak_pages_used: int = 0
    # prefix sharing (all zero unless Engine(prefix_sharing=True))
    shared_prefix_pages: int = 0     # page mappings served by refcount
    #                                  bumps instead of fresh allocations
    saved_prefill_tokens: int = 0    # prompt positions admission skipped
    #                                  because their KV was already resident
    cow_forks: int = 0               # shared pages privatized by a write
    # grouped decode (plan.paged.decode_group == "grouped")
    grouped_requests: int = 0        # decode-row ticks served through a
    #                                  shared-prefix group
    prefix_kv_bytes_saved: int = 0   # prefix KV bytes read once per group
    #                                  instead of once per member
    # tiered KV / session cache (all zero without host_pages/session_cache)
    demoted_pages: int = 0           # pages pushed device→host(→disk)
    #                                  instead of being discarded
    promoted_pages: int = 0          # demoted pages copied back to fresh
    #                                  tier-0 pages at re-admission
    session_hits: int = 0            # admissions that re-mapped at least
    #                                  one retained session page (tier-0
    #                                  refcount bump or promotion)
    host_evicted_pages: int = 0      # pages that fell off the bottom tier
    #                                  (KV lost; those spans re-prefill)
    # quantized KV pages (zero / bf16-sized unless kv_dtype != "bf16")
    kv_page_bytes: int = 0           # one page's K+V slab across all layers
    #                                  (code pools + scale rows as stored)
    kv_bytes_decode_read: int = 0    # cumulative KV bytes decode ticks
    #                                  streamed (resident pages x slab
    #                                  bytes) — the paper's decode
    #                                  bandwidth term, at stored width
    # quantized GEMM weights (bf16-sized unless weight_dtype != "bf16")
    weight_bytes_decode_read: int = 0  # cumulative GEMM weight bytes
    #                                  decode ticks streamed: every layer's
    #                                  projection leaves once per tick,
    #                                  codes + scales at stored width
    #                                  (embedding/lm_head excluded — not
    #                                  per-layer streams)


def _page_rows(flat, nl: int, num_pages: int, pages):
    """Index of pages ``pages`` of every layer in a flat ``(L*NP, ...)``
    page view (:meth:`PagedLayout.page_view`), layer-major; entries ``>=
    NP`` point past the pool. K/V pages are addressed as (page, row) rows
    like the steps' own token writes: a whole-page scatter makes the
    compiler relay a row-major pool for it, and back."""
    idx = jnp.where(pages < num_pages,
                    jnp.arange(nl)[:, None] * num_pages + pages,
                    nl * num_pages).reshape(-1)
    return ((idx,) if flat.ndim < 4 else
            (idx[:, None], jnp.arange(flat.shape[1])))


def gather_pages(cache: dict, pages) -> dict:
    """Tier demotion: the named pages' slabs of every paged cache leaf,
    ``(layers, n, ...)`` each, whatever shape the pool is stored in."""
    flat, nl, num_pages = PagedLayout.page_view(cache)
    return {n: a[_page_rows(a, nl, num_pages, pages)].reshape(
                nl, pages.shape[0], *a.shape[1:])
            for n, a in flat.items()}


def promote_upload(cache: dict, slabs: dict, dst) -> dict:
    """Tiered promotion: write a batch of slabs (stacked to ``(layers, n,
    ...)`` per leaf) into pages ``dst`` in one donated update; entries
    ``dst >= NP`` (padding rows) are dropped, so slab batches share a pow2
    family of compiles."""
    flat, nl, num_pages = PagedLayout.page_view(cache)
    return {n: a.at[_page_rows(a, nl, num_pages, dst)].set(
                slabs[n].astype(a.dtype).reshape(-1, *a.shape[1:]),
                mode="drop").reshape(cache[n].shape)
            for n, a in flat.items()}


def copy_page(cache: dict, src, dst) -> dict:
    """COW fork: copy one page's slab across all layers to a privately
    owned destination page (a donated in-place update; ``src``/``dst``
    trace as scalars so every fork reuses one compile)."""
    return promote_upload(cache, gather_pages(cache, jnp.atleast_1d(src)),
                          jnp.atleast_1d(dst))


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        num_slots: int = 8,
        max_seq: int = 2048,
        cache_kind: str = "dense",
        page_size: int = DEFAULT_PAGE_SIZE,
        num_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = DEFAULT_PREFILL_CHUNK,
        scheduler: Union[str, Scheduler] = "fcfs",
        plan: Optional[ExecutionPlan] = None,
        kv_dtype: Optional[str] = None,
        weight_dtype: Optional[str] = None,
        decode_fusion: Optional[str] = None,
        prefix_sharing: bool = False,
        host_pages: Optional[int] = None,
        session_cache: Optional[bool] = None,
        disk_dir: Optional[str] = None,
        disk_pages: int = 0,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.api = get_model(cfg)
        self.plan = plan if plan is not None else DEFAULT_PLAN
        # decode-stage fusion granularity: explicit arg wins, else the
        # plan's tuned knob rides along untouched (same precedence as
        # kv_dtype). The override lands *in the plan* because the model
        # stages read ctx.plan.decode_fusion at trace time.
        if decode_fusion is not None:
            if decode_fusion not in FUSION_MODES:
                raise ValueError(
                    f"decode_fusion {decode_fusion!r} not in {FUSION_MODES}")
            self.plan = dataclasses.replace(
                self.plan,
                decode_fusion=dataclasses.replace(
                    self.plan.decode_fusion, granularity=decode_fusion))
        self.decode_fusion = self.plan.decode_fusion.granularity
        # GEMM weight storage precision: explicit arg wins, else the
        # plan's tuned matmul.weight_dtype rides along (same precedence
        # as kv_dtype/decode_fusion). The resolved value lands in the
        # plan before LayerCtx so describe()/downstream readers agree;
        # the kernels themselves key off the (codes, scale) leaf
        # structure, not the knob.
        if weight_dtype is None:
            weight_dtype = getattr(self.plan.matmul, "weight_dtype", "bf16")
        if weight_dtype not in WEIGHT_DTYPES:
            raise ValueError(
                f"weight_dtype {weight_dtype!r} not in {WEIGHT_DTYPES}")
        if weight_dtype == "fp8" and not quant.fp8_supported():
            raise ValueError(
                "weight_dtype='fp8' needs ml_dtypes float8_e4m3fn; "
                "use 'int8' on this runtime")
        if weight_dtype != self.plan.matmul.weight_dtype:
            self.plan = dataclasses.replace(
                self.plan,
                matmul=dataclasses.replace(self.plan.matmul,
                                           weight_dtype=weight_dtype))
        self.weight_dtype = weight_dtype
        self.ctx = LayerCtx(cfg=cfg, plan=self.plan)
        # quantize-at-load: convert each GEMM weight leaf to a
        # (codes, scale) pair once, before any trace sees the params.
        # bf16 leaves the pytree untouched (the bitwise path).
        if weight_dtype != "bf16":
            params = wquant.quantize_params(
                params, quant.spec_for(weight_dtype))
        self.params = params
        # one decode tick's GEMM weight stream, at stored width (codes +
        # scales; embedding/lm_head excluded — not per-layer streams)
        self._weight_bytes_per_tick = (
            wquant.gemm_weight_bytes(params)
            if isinstance(params, dict) else 0)
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.scheduler = get_scheduler(scheduler)
        # chunked prefill needs the chunk-append model path (dense-KV
        # families); others fall back to batched single-shot prefill.
        # prefill_chunk=None adopts the plan's tuned chunk size.
        if prefill_chunk is None:
            prefill_chunk = self.plan.paged.chunk_block
        self.prefill_chunk = (
            prefill_chunk if self.api.supports_chunked_prefill else 0)

        # KV page storage precision: explicit arg wins, else the plan's
        # tuned kv_dtype (paged engines only — a dense engine never reads
        # plan.paged). Quantized pools need the paged layout: the
        # per-(page, head) scale rows are page-pool leaves.
        if kv_dtype is None:
            kv_dtype = (getattr(self.plan.paged, "kv_dtype", "bf16")
                        if cache_kind == "paged" else "bf16")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
        if kv_dtype != "bf16":
            if cache_kind != "paged":
                raise ValueError(
                    "kv_dtype quantization stores per-page scales in the "
                    "block pool; use cache_kind='paged'")
            if kv_dtype == "fp8" and not quant.fp8_supported():
                raise ValueError(
                    "kv_dtype='fp8' needs ml_dtypes float8_e4m3fn; "
                    "use 'int8' on this runtime")
        self.kv_dtype = kv_dtype

        # tiered KV store: any of the knobs turns the hierarchy on
        tiered = (host_pages is not None or disk_pages > 0
                  or bool(session_cache))
        self.layout: KVLayout
        self.prefix: Optional[PrefixIndex] = None
        self.tiers: Optional[TieredPool] = None
        # retain finished sequences' KV in the session cache? defaults to
        # on whenever the hierarchy exists (the session cache is its
        # point); session_cache=False keeps preemption-demotion only
        self.session_cache = (tiered if session_cache is None
                              else bool(session_cache))
        if cache_kind == "dense":
            if prefix_sharing:
                raise ValueError(
                    "prefix_sharing needs refcounted pages; "
                    "use cache_kind='paged'")
            if tiered:
                raise ValueError(
                    "tiered KV (host_pages/session_cache/disk_pages) "
                    "needs cache_kind='paged'")
            self.layout = DenseLayout(num_slots, max_seq)
            self.slots: SlotManager = SlotManager(num_slots, max_seq)
            self.pool = None
        elif cache_kind == "paged":
            if not self.api.supports_paged:
                raise ValueError(
                    f"family {cfg.family!r} has no paged-KV path "
                    "(recurrent/ring state caches); use cache_kind='dense'")
            if not self.prefill_chunk:
                raise ValueError(
                    "cache_kind='paged' requires chunked prefill "
                    "(prefill_chunk > 0)")
            if prefix_sharing and page_size % self.prefill_chunk:
                # shared prefixes are page-aligned; keeping every prefill
                # chunk boundary on the same global c-grid as a share-less
                # run is what makes outputs bit-identical (fp reductions
                # split at identical positions), and that needs c | PS
                raise ValueError(
                    f"prefix_sharing requires page_size ({page_size}) to "
                    f"be a multiple of prefill_chunk ({self.prefill_chunk})")
            # default pool = same KV bytes as the dense cache; size it
            # smaller to overcommit (lazy growth then preempts on dry pool)
            pool = BlockPool(
                num_pages if num_pages is not None
                else num_slots * pages_for(max_seq, page_size),
                page_size,
            )
            self.layout = PagedLayout(pool.num_pages, page_size, kv_dtype)
            if prefix_sharing:
                self.prefix = PrefixIndex(page_size)
            if tiered:
                if self.prefix is None:
                    raise ValueError(
                        "tiered KV needs prefix_sharing=True — the "
                        "prefix index is the cross-tier map that makes "
                        "retained/demoted pages matchable")
                self.tiers = TieredPool(
                    host_pages if host_pages is not None else 0,
                    index=self.prefix,
                    disk_dir=disk_dir, disk_pages=disk_pages)
            self.slots = PagedSlotManager(num_slots, max_seq, pool,
                                          prefix_index=self.prefix,
                                          tiers=self.tiers)
            if self.tiers is not None:
                self.slots.swap_threshold = self.plan.paged.swap_threshold
                self.slots.reclaim_cb = self._reclaim_session
            self.pool = pool
        else:
            raise ValueError(f"unknown cache_kind {cache_kind!r}")
        self.cache_kind = cache_kind
        # a paged pool is allocated in the shapes the device lays out the
        # way its kernels read it (PagedLayout.device_storage); the model
        # and the page copies view any stored shape as flat pages
        # (PagedLayout.page_view)
        if cache_kind == "paged":
            self.cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                PagedLayout.device_storage(self.api.cache_spec(self.layout),
                                           jax.devices()[0]))
        else:
            self.cache = self.api.init_cache(self.layout)

        self.seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        # resident sampled requests' PRNG keys, by slot: written at
        # admission, split by the sampler, parked back into
        # ``RequestState.key`` at preemption and retirement
        self._keys = jnp.zeros((num_slots,) + self._base_key.shape,
                               self._base_key.dtype)
        self.requests: dict[int, RequestState] = {}
        self.waiting: list[RequestState] = []
        self.by_slot: dict[int, RequestState] = {}
        self.stats = EngineStats()
        self.ticks = 0
        self._next_rid = 0
        self._arrival = 0

        # the single jitted pair: the layout's block-table operand (None
        # for dense) is just another argument, so dense and paged engines
        # trace the same functions. Each program is a named function, so
        # the compiled module (and the device trace) reads
        # ``jit_decode_step``, ``jit_prefill_chunk``, ...
        def decode_step(p, t, c, bt, le, po):
            return self.api.decode_step(self.ctx, p, t, c, le,
                                        block_tables=bt, positions=po)

        def prefill_chunk(p, t, cl, c, bt, le):
            return self.api.prefill_chunk(self.ctx, p, t, cl, c, le,
                                          block_tables=bt)

        # prefix-shared grouped decode: when the tuned plan asks for it
        # (and refcounted sharing is on so groups can exist), decode ticks
        # with a qualifying group dispatch through a second program that
        # threads the DecodeGroups operand down to the attention op
        def grouped_decode_step(p, t, c, bt, le, gr, po):
            return self.api.decode_step(self.ctx, p, t, c, le,
                                        block_tables=bt, decode_groups=gr,
                                        positions=po)

        # sampling: one program per logits array of a phase. It takes each
        # slot's row of the logits (``rows``: a fixed (num_slots,) operand,
        # so decode ticks and prefill launches share one compile) and
        # draws through the module's ``sample``, looked up when traced.
        # The variant is static and follows the rows' own parameters: an
        # all-greedy batch is the argmax alone and touches no key; else a
        # sampled slot splits its key once per draw, as its private chain
        # in ``self._keys`` (unsampled slots' keys pass through).
        vocab_size = cfg.vocab_size

        def sample_slots(logits, rows, keys=None, temperature=None,
                         top_k=None, top_p=None, *, draw=False,
                         filtered=False):
            logits = logits[rows]
            if not draw:
                return sample(logits, None, vocab_size=vocab_size), keys
            pair = jax.vmap(jax.random.split)(keys)
            toks = sample(logits, pair[:, 1], temperature=temperature,
                          top_k=top_k, top_p=top_p, vocab_size=vocab_size,
                          draw=True, filtered=filtered)
            return toks, jnp.where((temperature > 0)[:, None],
                                   pair[:, 0], keys)

        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._sampler = jax.jit(sample_slots,
                                static_argnames=("draw", "filtered"))
        self._chunk = (jax.jit(prefill_chunk, donate_argnums=(3,))
                       if self.prefill_chunk else None)
        self._copy_page = (jax.jit(copy_page, donate_argnums=(0,))
                           if cache_kind == "paged" else None)
        self._promote_upload = (jax.jit(promote_upload, donate_argnums=(0,))
                                if self.tiers is not None else None)
        self._gather_pages_fn = (jax.jit(gather_pages)
                                 if self.tiers is not None else None)
        self._group_decode = (
            cache_kind == "paged" and prefix_sharing
            and self.plan.paged.decode_group == "grouped")
        self._decode_grouped = (
            jax.jit(grouped_decode_step, donate_argnums=(2,))
            if self._group_decode else None)
        # one page's K+V slab across all layers — the unit of both the
        # COW copy and the grouped-decode bytes-saved accounting
        self._kv_bytes_per_page = (
            sum(a.nbytes for a in jax.tree.leaves(self.cache))
            // self.pool.num_pages) if cache_kind == "paged" else 0
        self.stats.kv_page_bytes = self._kv_bytes_per_page
        self._prefill_cache = {}  # bucketed P -> jitted batched prefill
        # last-uploaded device copies of the small int operands the chunk
        # loop would otherwise re-upload every step (chunk_lens is usually
        # identical across a wave's steps; lengths only moves wave rows)
        self._operand_cache: dict[str, tuple[bytes, jax.Array]] = {}

    # -- public API -----------------------------------------------------------

    def submit(self, prompt: PromptLike,
               params: Optional[SamplingParams] = None,
               *, rid: Optional[int] = None) -> int:
        """Queue a request; returns its id (auto-assigned if not given).

        Unservable requests are rejected here, not mid-admission: a raise
        inside the admission wave would leave already-slotted batch-mates
        half admitted (slots claimed, no prefill).
        """
        params = params if params is not None else SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        worst = len(prompt) + params.max_new_tokens
        if worst > self.max_seq:
            raise ValueError(
                f"request needs {worst} positions > max_seq {self.max_seq}")
        if self.pool is not None and (
                pages_for(worst, self.pool.page_size) > self.pool.num_pages):
            raise ValueError(
                f"request needs {pages_for(worst, self.pool.page_size)} "
                f"pages > pool size {self.pool.num_pages} "
                f"(page_size {self.pool.page_size})")
        if rid is None:
            while self._next_rid in self.requests:
                self._next_rid += 1
            rid = self._next_rid
            self._next_rid += 1
        elif rid in self.requests:
            raise ValueError(f"request id {rid} already submitted")
        key = (jax.random.PRNGKey(params.seed) if params.seed is not None
               else jax.random.fold_in(self._base_key, rid))
        state = RequestState(
            rid=rid, prompt=prompt,
            params=params, arrival=self._arrival, key=key,
            submit_time=time.perf_counter())
        self._arrival += 1
        self.requests[rid] = state
        self.waiting.append(state)
        return rid

    def generate(self, prompt: PromptLike,
                 params: Optional[SamplingParams] = None,
                 *, rid: Optional[int] = None) -> Iterator[TokenEvent]:
        """Stream one request: submit it and yield its ``TokenEvent``s as
        engine ticks produce them (driving the shared tick loop, so
        concurrent submissions keep decoding alongside). The final event
        has ``finished=True`` and a ``finish_reason``; aborting mid-stream
        ends the iterator with an ``abort`` event."""
        rid = self.submit(prompt, params, rid=rid)
        state = self.requests[rid]
        cursor = 0
        while True:
            while cursor < len(state.events):
                ev = state.events[cursor]
                cursor += 1
                yield ev
                if ev.finished:
                    return
            if state.finished:
                return   # finished without a terminal event (defensive)
            self.step()

    def abort(self, rid: int) -> bool:
        """Cancel a request in any phase; frees its slot/pages at once.
        Returns False if unknown or already finished."""
        state = self.requests.get(rid)
        if state is None or state.finished:
            return False
        if state.slot is not None:
            self.by_slot.pop(state.slot, None)
            self.slots.release(state.slot)
        if state in self.waiting:
            self.waiting.remove(state)
        state.finish(FinishReason.ABORT)
        state.events.append(TokenEvent(
            rid, None, state.generated, finished=True,
            finish_reason=FinishReason.ABORT))
        return True

    def finish_reason(self, rid: int) -> Optional[FinishReason]:
        return self.requests[rid].finish_reason

    def evict(self, rid: int) -> list[int]:
        """Drop a *finished* request's retained state (tokens, events,
        prompt) and return its tokens. A long-lived server must call this
        (or ``evict_finished``) after consuming results — the engine keeps
        every RequestState for post-run inspection and would otherwise
        grow without bound.

        This does **not** discard the conversation's KV: with the session
        cache on, the finished sequence's pages were already retained at
        retire time (tier-0 session set, demoted host-ward under pool
        pressure), so evicting the bookkeeping record leaves the prefix
        matchable for the conversation's next turn."""
        state = self.requests[rid]
        if not state.finished:
            raise ValueError(f"request {rid} is not finished; abort() it "
                             "first to evict early")
        del self.requests[rid]
        return state.tokens

    def evict_finished(self, *, flush: bool = False) -> int:
        """Evict every finished request's bookkeeping record; returns how
        many were dropped. Their KV stays cached (see :meth:`evict`);
        ``flush=True`` additionally demotes the whole tier-0 session
        cache host-ward right now (:meth:`flush_sessions`) instead of
        waiting for pool pressure."""
        done = [r for r, s in self.requests.items() if s.finished]
        for r in done:
            del self.requests[r]
        if flush:
            self.flush_sessions()
        return len(done)

    def run(self, requests, *, max_ticks: int = 10_000
            ) -> dict[int, list[int]]:
        """Blocking batch API on top of the streaming engine.

        ``requests`` is a list of prompts or ``(prompt, SamplingParams)``
        pairs; returns ``{rid: generated tokens}`` keyed by submission
        order."""
        rids = []
        for item in requests:
            if isinstance(item, tuple):
                prompt, sp = item
            else:
                prompt, sp = item, None
            rids.append(self.submit(prompt, sp))
        start = self.ticks
        while (any(not self.requests[r].finished for r in rids)
               and self.ticks - start < max_ticks):
            self.step()
        return {r: list(self.requests[r].tokens) for r in rids}

    # -- engine tick ------------------------------------------------------------

    def step(self) -> list[TokenEvent]:
        """Admit + prefill per the scheduler's order, then one decode tick
        (growing/preempting paged sequences first). Returns this tick's
        token events.

        Each phase runs inside one profiler span (``engine.admit``,
        ``engine.prefill``, ``engine.grow``, ``engine.decode``,
        ``engine.sample``; at most one per phase per step, never one per
        slot), so a trace taken around the engine puts the device's idle
        time down to the host work that caused it. With no profiler
        running a span costs about a microsecond."""
        events = self._admit()
        if not self.by_slot:
            if self.waiting and not events:
                # no admission progress and nothing resident to free
                # resources for the queue — a true stall, not
                # back-pressure; surface it instead of spinning. (Events
                # with an empty batch = the whole admitted wave finished
                # during prefill; the freed slots admit the queue next
                # step.)
                raise RuntimeError(
                    "admission stalled: empty batch but "
                    f"{len(self.waiting)} requests cannot be admitted")
            return events
        events += self._decode_tick()
        self.ticks += 1
        return events

    # -- admission ---------------------------------------------------------------

    def _admit(self) -> list[TokenEvent]:
        """Offer slots (and prefill pages) to waiting requests in the
        scheduler's order; prefill the admitted wave in one batch, then
        sample every admitted row's first token in one pass.

        With prefix sharing, admission hands the slot manager the exact
        prefill tokens so the prefix index can map page-aligned shared
        prefixes onto live pages (refcount bumps instead of allocations).
        A request whose match includes pages *promised by an earlier
        request in this same wave* is assigned a later prefill level —
        the wave then prefills level by level, so shared pages are always
        written before any sharer computes attention over them.
        """
        if not self.waiting:
            return []
        with _span("engine.admit"):
            admitted, levels = self._assign()
        if not admitted:
            return []
        with _span("engine.prefill"):
            if self.prefill_chunk:
                rows: list[tuple] = []
                for lv in sorted(set(levels.values())):
                    rows += self._prefill_chunked(
                        [(i, s) for i, s in admitted if levels[i] == lv])
            else:
                rows = self._prefill_batched(admitted)
            return self._sample_rows(rows, wrote_kv=False)

    def _assign(self) -> tuple[list[tuple[int, RequestState]],
                               dict[int, int]]:
        """Admission bookkeeping: hand slots (and prefix matches) to
        waiting requests in the scheduler's order, then perform the page
        promotions and COW forks the wave was promised. Returns the
        admitted ``(slot, state)`` pairs and each slot's prefill level."""
        admitted: list[tuple[int, RequestState]] = []
        levels: dict[int, int] = {}
        for state in self.scheduler.admission_order(self.waiting):
            toks = state.prefill_tokens()
            idx = self.slots.try_assign(
                state.rid, len(toks),
                max(state.params.max_new_tokens - state.generated, 1),
                tokens=toks if self.prefix is not None else None)
            if idx is None:
                if not self.scheduler.allow_skip:
                    break      # head-of-line blocking (FCFS no-starvation)
                continue
            state.phase = Phase.PREFILLING
            state.slot = idx
            if state.params.temperature > 0:
                self._keys = self._keys.at[idx].set(state.key)
            if state.admit_time is None:
                state.admit_time = time.perf_counter()
            self.by_slot[idx] = state
            admitted.append((idx, state))
            levels[idx] = 0
            if self.prefix is not None:
                slot = self.slots.slots[idx]
                levels[idx] = slot.prefill_level
                # the COW-fork destination is private, not shared
                state.shared_len = slot.shared_len - (
                    self.pool.page_size if slot.pending_fork else 0)
                # refcount-bump pages only; promoted pages are fresh
                # allocations counted under promoted_pages instead
                self.stats.shared_prefix_pages += (
                    state.shared_len // self.pool.page_size
                    - len(slot.pending_promotions))
                self.stats.saved_prefill_tokens += \
                    self._chunk_start(idx, len(toks))
                if slot.session_mapped:
                    # re-mapped a retired/preempted session's retained KV
                    # (tier-0 refcount bump and/or promotion from host)
                    self.stats.session_hits += 1
                    slot.session_mapped = 0
        if not admitted:
            return admitted, levels
        self.waiting = [s for s in self.waiting if s.slot is None]
        self._note_page_pressure()
        if self.tiers is not None:
            self._apply_pending_promotions(admitted)
        if self.prefix is not None:
            self._apply_pending_forks(admitted)
        return admitted, levels

    def _apply_pending_forks(
            self, admitted: list[tuple[int, RequestState]]) -> None:
        """Perform the slab copies admission promised: a fully-covered
        prompt forked its last shared page so the final-chunk re-run (the
        write that recovers the last-token logits) lands in a private
        copy. Sources are always committed pages, so copying before any
        prefill of this wave is safe."""
        for idx, _state in admitted:
            slot = self.slots.slots[idx]
            fork = getattr(slot, "pending_fork", None)
            if fork:
                src, dst = fork
                self.cache = self._copy_page(self.cache, src, dst)
                slot.pending_fork = None
                self.stats.cow_forks += 1

    def _apply_pending_promotions(
            self, admitted: list[tuple[int, RequestState]]) -> None:
        """Perform the host→device uploads admission promised: each
        promoted prefix page's slab (popped from the tiered store at
        match time) lands in its freshly allocated tier-0 page. One
        donated scatter for the whole wave's batch, before any fork or
        prefill of this wave reads those pages. The slabs hold the
        originally computed KV bytes, so the resumed sequence's attention
        reads are bit-identical to a never-demoted run's."""
        ups: list[tuple] = []           # (slab, dst_page)
        for idx, _state in admitted:
            slot = self.slots.slots[idx]
            if slot.pending_promotions:
                ups.extend(slot.pending_promotions)
                slot.pending_promotions = []
        if not ups:
            return
        n = len(ups)
        nb = pow2_bucket(n)
        dst = np.full((nb,), self.pool.num_pages, np.int32)  # pad = OOB
        dst[:n] = [d for _slab, d in ups]
        leaves, treedef = jax.tree.flatten(self.cache)
        stacked = []
        for j in range(len(leaves)):
            rows = [slab[j] for slab, _d in ups]
            rows += [rows[0]] * (nb - n)     # dropped via sentinel dst
            stacked.append(np.stack(rows, axis=1))
        self.cache = self._promote_upload(
            self.cache, jax.tree.unflatten(treedef, stacked),
            jnp.asarray(dst))
        self.stats.promoted_pages += n

    def _chunk_start(self, idx: int, n_prefill: int) -> int:
        """First position slot ``idx``'s chunked prefill must process.

        The shared prefix is skipped, except that at least the final
        prompt token must run (its logits seed decode). The start is
        floored to the global chunk grid so every chunk boundary matches
        a share-less run exactly — identical fp-reduction splits are what
        keep greedy outputs bit-identical with sharing on vs off (the
        re-run positions rewrite byte-identical KV, into the COW fork
        when they fall inside a shared page).
        """
        start = getattr(self.slots.slots[idx], "prefill_start", 0)
        if start <= 0:
            return 0
        start = min(start, max(n_prefill - 1, 0))
        return (start // self.prefill_chunk) * self.prefill_chunk

    # -- chunked + batched prefill (dense-KV families) -------------------------

    def _upload_i32(self, name: str, arr: np.ndarray) -> jax.Array:
        """Device copy of a small int operand, re-uploaded only when its
        contents changed since the previous call under the same name —
        the chunk loop's ``chunk_lens`` is usually identical across a
        wave's steps and would otherwise round-trip every step."""
        prev = self._operand_cache.get(name)
        key = arr.tobytes()
        if prev is not None and prev[0] == key:
            return prev[1]
        dev = jnp.asarray(arr)
        self._operand_cache[name] = (key, dev)
        return dev

    def _chunk_tables(self, fused: bool, hi: int):
        """Block-table operand for one chunk step.

        In the plan's fused chunk mode the dense table is sliced to a
        power-of-two page bound covering ``hi`` (the wave's highest
        position written or read this step), so the chunk op's KV side is
        O(resident pages) instead of O(max table width): the fused Pallas
        kernel grids over exactly those pages, and the XLA gather
        materializes only them. Trailing pages carry only causally-masked
        positions, so the truncation is bitwise-neutral (spectator rows
        whose resident KV exceeds the bound produce garbage either way —
        their logits are dropped and nothing is written). Bucketing keeps
        the number of distinct compiled shapes logarithmic.
        """
        bt = self.slots.block_tables()
        if bt is None or not fused:
            return bt
        full = self.slots.max_pages_per_seq
        bound = pow2_bucket(pages_for(hi, self.pool.page_size), hi=full)
        if bound >= full:
            return bt
        return bt[:, :bound]

    def _prefill_chunked(
            self, items: list[tuple[int, RequestState]]) -> list[tuple]:
        """Stream all admitted prompts through the chunk-append path;
        returns each row's last-token logits for :meth:`_sample_rows`.

        Each step processes one ``(num_slots, chunk)`` call: admitted rows
        consume their next chunk, every other slot is a spectator
        (``chunk_lens == 0`` — nothing written). One compiled shape total
        in the dense-gather mode; the fused mode trades that for a
        log-bounded family of resident-bounded table widths
        (``_chunk_tables``) so admission stops paying O(max table width)
        KV materialization per step. Re-admitted (preempted) requests
        prefill ``prompt + generated``, rebuilding exactly the KV an
        uninterrupted run would hold — unless the prefix index still maps
        their prefix, in which case prefill starts at the first unshared
        chunk boundary (``_chunk_start``) and the shared pages are simply
        read through the block table.
        """
        c = self.prefill_chunk
        seqs = {idx: state.prefill_tokens() for idx, state in items}
        progress = {idx: self._chunk_start(idx, len(seqs[idx]))
                    for idx, _ in items}
        plens = {idx: max(len(seqs[idx]), 1) for idx, _ in items}
        self._count_prefill(
            (state, plens[idx] - progress[idx]) for idx, state in items)
        # gather-vs-fused inflection by prompt length (plan-tuned): short
        # waves keep the one-compile full-width gather
        pp = self.plan.paged
        fused = (self.pool is not None and pp.gather_chunk == "fused"
                 and max(plens.values()) >= pp.fused_threshold)
        final: dict[int, jax.Array] = {}
        n_steps = max(-(-(plens[idx] - progress[idx]) // c)
                      for idx, _ in items)
        for _ in range(n_steps):
            tokens = np.zeros((self.num_slots, c), np.int32)
            chunk_lens = np.zeros((self.num_slots,), np.int32)
            lengths = self.slots.lengths()
            hi = 0
            for idx, _state in items:
                done = progress[idx]
                cl = min(plens[idx] - done, c)
                if cl <= 0:
                    continue
                avail = min(max(len(seqs[idx]) - done, 0), cl)
                if avail:
                    tokens[idx, :avail] = seqs[idx][done:done + avail]
                chunk_lens[idx] = cl          # p=0 feeds one pad token
                lengths[idx] = done           # prefill progress, not final P
                hi = max(hi, done + cl)
            logits, self.cache = self._chunk(
                self.params, jnp.asarray(tokens),
                self._upload_i32("chunk_lens", chunk_lens),
                self.cache, self._chunk_tables(fused, hi),
                self._upload_i32("chunk_lengths", lengths))
            for idx, _state in items:
                if chunk_lens[idx]:
                    progress[idx] += int(chunk_lens[idx])
                    if progress[idx] == plens[idx]:
                        final[idx] = logits
        for idx, _state in items:
            # full prompt pages now hold real KV: flip this slot's pending
            # index entries so later arrivals (and later levels of this
            # wave) may map them
            self.slots.commit_prefix(idx, seqs[idx])
        return [(idx, state, final[idx], idx) for idx, state in items]

    def _count_prefill(self, computed) -> None:
        """Add each admitted row's prefill positions, ``(state, n)``
        pairs, to ``prefill_tokens``, and a re-admitted (preempted)
        request's to ``recomputed_tokens`` as well."""
        for state, n in computed:
            self.stats.prefill_tokens += n
            if state.preemptions:
                self.stats.recomputed_tokens += n

    # -- batched single-shot prefill (recurrent/ring families) ------------------

    def _prefill_fn(self, padded: int):
        if padded not in self._prefill_cache:
            spec = self.api.cache_spec(
                DenseLayout(self.num_slots, self.max_seq))

            def prefill(params, tokens, lengths):
                cache = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), spec)
                return self.api.prefill(
                    self.ctx, params, tokens, lengths, cache)

            self._prefill_cache[padded] = jax.jit(prefill)
        return self._prefill_cache[padded]

    def _prefill_batched(
            self, items: list[tuple[int, RequestState]]) -> list[tuple]:
        """One padded prefill call for the whole admission wave; each row's
        cache entry is inserted at its slot index afterwards. Prompts pad
        to a power-of-two bucket (min ``PROMPT_BUCKET``) so distinct tail
        lengths share a logarithmic family of compiles instead of
        re-jitting at every 64-token multiple."""
        seqs = {idx: state.prefill_tokens() for idx, state in items}
        self._count_prefill(
            (state, len(seqs[idx])) for idx, state in items)
        pmax = max(len(s) for s in seqs.values())
        # never pad past what plain 64-multiple rounding could reach
        # (pmax <= max_seq is enforced at submit)
        padded = pow2_bucket(
            pmax, lo=PROMPT_BUCKET,
            hi=-(-self.max_seq // PROMPT_BUCKET) * PROMPT_BUCKET)
        toks = np.zeros((self.num_slots, padded), np.int32)
        lens = np.zeros((self.num_slots,), np.int32)
        for row, (idx, _state) in enumerate(items):
            toks[row, :len(seqs[idx])] = seqs[idx]
            lens[row] = len(seqs[idx])
        logits, cache_new = self._prefill_fn(padded)(
            self.params, jnp.asarray(toks), jnp.asarray(lens))
        for row, (idx, _state) in enumerate(items):
            row_cache = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=1),
                cache_new)
            self.cache = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), idx, axis=1),
                self.cache, row_cache,
            )
        return [(idx, state, logits, row)
                for row, (idx, state) in enumerate(items)]

    # -- decode ----------------------------------------------------------------

    def _grow_or_preempt(self) -> None:
        """Lazy page growth (and COW forks) for every resident sequence:
        each decode tick writes one KV position, so slot ``i`` must cover
        ``length + 1`` — and if the page holding position ``length`` is
        shared (refcount > 1), it must be forked before the scatter so
        the write can never leak into a prefix other sequences read.
        When the pool is dry (growth or fork), the scheduler names a
        victim — possibly the growing sequence itself, so e.g. FCFS
        really does evict the newest arrival rather than whichever old
        resident happens to share the tick. The victim's refs are dropped
        (shared pages survive through their other owners) and its state
        goes back to the queue (relocation-free — re-admission
        re-prefills through fresh block tables, re-mapping any shared
        prefix that survived)."""
        for idx, state in list(self.by_slot.items()):
            if self.by_slot.get(idx) is not state:
                continue                      # became a victim this tick
            while True:
                length = self.slots.slots[idx].length
                forks = None
                if self.slots.ensure(idx, length + 1):
                    forks = self.slots.fork_for_write(
                        idx, length, length + 1)
                if forks is not None:
                    for src, dst in forks:
                        self.cache = self._copy_page(self.cache, src, dst)
                        self.stats.cow_forks += 1
                    break
                self._refresh_shared_lens()
                victim = self.scheduler.pick_victim(list(self.by_slot.values()))
                if victim is None or (victim is state
                                      and len(self.by_slot) == 1):
                    # admission's whole-footprint bound makes a lone
                    # sequence always satisfiable — defensive only
                    raise RuntimeError(
                        "page pool exhausted with no preemptable victim")
                self._preempt(victim)
                if victim is state:
                    break                     # evicted itself; skip growth
        self._note_page_pressure()

    def _decode_tick(self) -> list[TokenEvent]:
        with _span("engine.grow"):
            self._grow_or_preempt()
        if not self.by_slot:
            return []
        with _span("engine.decode"):
            lengths = self.slots.lengths_device()
            positions = self.slots.positions_device()
            tokens = np.zeros((self.num_slots,), np.int32)
            for idx, state in self.by_slot.items():
                tokens[idx] = state.tokens[-1]
            gplan = (self.slots.group_plan(self.plan.paged.group_threshold)
                     if self._group_decode else None)
            if gplan is not None:
                logits, self.cache = self._decode_grouped(
                    self.params, jnp.asarray(tokens), self.cache,
                    self.slots.block_tables(), lengths, gplan.operands(),
                    positions)
                self.stats.grouped_requests += gplan.n_grouped
                self.stats.prefix_kv_bytes_saved += (
                    gplan.pages_deduped * self._kv_bytes_per_page)
            else:
                logits, self.cache = self._decode(
                    self.params, jnp.asarray(tokens), self.cache,
                    self.slots.block_tables(), lengths, positions)
        if self.pool is not None:
            # decode streams every resident page once per tick, at the
            # stored width — the term kv_dtype shrinks
            pages_read = sum(len(self.slots.slots[i].pages)
                             for i in self.by_slot)
            self.stats.kv_bytes_decode_read += (
                pages_read * self._kv_bytes_per_page)
        # every decode tick streams the full per-layer GEMM weight stack
        # once, at stored width — the term weight_dtype shrinks
        self.stats.weight_bytes_decode_read += self._weight_bytes_per_tick
        return self._sample_rows(
            [(idx, state, logits, idx) for idx, state in self.by_slot.items()])

    # -- tiered store dataflow (the only tier-crossing copies) -----------------

    def _gather_pages(self, pages: list[int]) -> dict[int, tuple]:
        """Bulk device→host copy of the named pages' KV slabs: one
        bucketed gather per cache leaf for the whole batch, returning
        ``{page: slab}`` where a slab is the per-leaf tuple of
        ``(layers, page_size, kv_heads, head_dim)`` numpy arrays the
        :class:`~repro.serving.tiers.TieredPool` stores."""
        if not pages:
            return {}
        n = len(pages)
        nb = pow2_bucket(n)
        idx = np.full((nb,), pages[0], np.int32)   # pad rows discarded
        idx[:n] = pages
        idxd = jnp.asarray(idx)
        host = [np.asarray(a)
                for a in jax.tree.leaves(
                    self._gather_pages_fn(self.cache, idxd))]
        self.stats.host_syncs += len(host)
        return {p: tuple(np.ascontiguousarray(h[:, i]) for h in host)
                for i, p in enumerate(pages)}

    def _reclaim_session(self, need: int) -> bool:
        """Slot-manager callback when an allocation finds the pool dry:
        demote LRU session pages (device→host gather included) until
        ``need`` pages are free. The session cache never wins a page
        fight against live admission or growth."""
        if self.slots.session_pages() == 0:
            return False
        before = dataclasses.replace(self.tiers.stats)
        freed = self.slots.reclaim_session(max(need, 1), self._gather_pages)
        st = self.tiers.stats
        self.stats.demoted_pages += st.demoted - before.demoted
        self.stats.host_evicted_pages += st.evicted - before.evicted
        return freed >= need

    def flush_sessions(self) -> int:
        """Demote the *entire* tier-0 session cache host-ward now (one
        bulk gather), returning how many device pages were freed. The
        demand-driven path (:meth:`_reclaim_session`) makes this
        unnecessary in steady state; it exists for checkpoints and for
        benchmarks that want host-resident sessions without first
        running the pool dry."""
        if self.tiers is None or self.slots.session_pages() == 0:
            return 0
        before = dataclasses.replace(self.tiers.stats)
        freed = self.slots.reclaim_session(
            self.slots.session_pages(), self._gather_pages)
        st = self.tiers.stats
        self.stats.demoted_pages += st.demoted - before.demoted
        self.stats.host_evicted_pages += st.evicted - before.evicted
        return freed

    # -- bookkeeping -----------------------------------------------------------

    def _preempt(self, state: RequestState) -> None:
        idx = state.slot
        self.by_slot.pop(idx, None)
        self._park_key(idx, state)
        if self.tiers is not None:
            # demote, don't discard: the victim's full KV pages move to
            # the session cache (demoted host-ward only under pressure),
            # so re-admission promotes instead of re-prefilling them
            length = self.slots.slots[idx].length
            self.slots.retain_session(
                idx, state.prefill_tokens()[:length])
        else:
            self.slots.release(idx)
        state.phase = Phase.PREEMPTED
        state.slot = None
        state.shared_len = 0          # recomputed if re-admission re-maps
        state.persistable_len = 0
        state.preemptions += 1
        self.stats.preemptions += 1
        self.waiting.append(state)

    def _park_key(self, idx: int, state: RequestState) -> None:
        """Hand a sampled request's key back from its slot's row of
        ``self._keys``, so a re-admission continues its chain."""
        if state.params.temperature > 0:
            state.key = self._keys[idx]

    def _draw(self, logits: jax.Array,
              members: list[tuple[int, RequestState, int]]) -> jax.Array:
        """Launch the sampler over one logits array for the
        ``(slot, state, row)`` members; returns the ``(num_slots,)``
        tokens, still on the device (a slot's token is read only if the
        slot is a member)."""
        b = self.num_slots
        rows = np.arange(b, dtype=np.int32)
        temperature = np.zeros((b,), np.float32)
        top_k = np.zeros((b,), np.int32)
        top_p = np.ones((b,), np.float32)
        for idx, state, row in members:
            rows[idx] = row
            p = state.params
            if p.temperature > 0:
                temperature[idx], top_k[idx], top_p[idx] = (
                    p.temperature, p.top_k, p.top_p)
        rows = self._upload_i32("sample_rows", rows)
        if not temperature.any():
            return self._sampler(logits, rows)[0]
        toks, self._keys = self._sampler(
            logits, rows, self._keys, temperature, top_k, top_p, draw=True,
            filtered=bool((top_k > 0).any() or (top_p < 1.0).any()))
        return toks

    def _sample_rows(self, rows: list[tuple], *,
                     wrote_kv: bool = True) -> list[TokenEvent]:
        """Sample one token for each ``(slot, state, logits, row)``, read
        them back to the host and account each (:meth:`_emit`): the
        step's one ``engine.sample`` span per phase, one blocking host
        read per phase. Each distinct logits array (a wave's rows may come
        from several prefill launches) goes through the sampler once."""
        events = []
        with _span("engine.sample"):
            arrays: dict[int, tuple[jax.Array, list]] = {}
            for idx, state, logits, row in rows:
                arrays.setdefault(id(logits), (logits, []))[1].append(
                    (idx, state, row))
            toks = dict(zip(arrays, jax.device_get(
                [self._draw(*a) for a in arrays.values()])))
            self.stats.host_syncs += 1
            for idx, state, logits, _row in rows:
                state.phase = Phase.RUNNING
                events.append(self._emit(idx, state,
                                         int(toks[id(logits)][idx]),
                                         wrote_kv=wrote_kv))
        return events

    def _emit(self, idx: int, state: RequestState, tok: int,
              *, wrote_kv: bool = True) -> TokenEvent:
        """Account one sampled token: stop/budget checks, event record,
        slot release on finish. The stop token itself joins the output
        only when ``SamplingParams.include_stop`` asks for it, and never
        burns ``max_new_tokens`` budget."""
        p = state.params
        if state.first_token_time is None:
            state.first_token_time = time.perf_counter()
            state.first_token_tick = self.ticks
        if tok in p.stop_tokens:
            if p.include_stop:
                state.tokens.append(tok)
                self.slots.tick(idx, wrote_kv=wrote_kv)
            return self._retire(idx, state, FinishReason.STOP)
        state.tokens.append(tok)
        self.slots.tick(idx, wrote_kv=wrote_kv)
        if (state.generated >= p.max_new_tokens
                or self.slots.slots[idx].length >= self.max_seq):
            return self._retire(idx, state, FinishReason.LENGTH)
        ev = TokenEvent(state.rid, tok, state.generated - 1)
        state.events.append(ev)
        return ev

    def _retire(self, idx: int, state: RequestState,
                reason: FinishReason) -> TokenEvent:
        """Release the slot and record the terminal event. The event
        mirrors ``state.tokens`` exactly: it carries the last *kept*
        token (so a stop token excluded by ``include_stop=False`` never
        reaches the stream either), or ``token=None`` at the next index
        when the request ends without keeping one.

        With the session cache on, the finished sequence's full KV pages
        are retained (registered in the prefix index + held by the
        manager's session set) instead of freed — the conversation's next
        turn re-maps or promotes them rather than re-prefilling."""
        if self.tiers is not None and self.session_cache:
            length = self.slots.slots[idx].length
            self.slots.retain_session(
                idx, state.prefill_tokens()[:length])
        else:
            self.slots.release(idx)
        self.by_slot.pop(idx, None)
        self._park_key(idx, state)
        state.finish(reason)
        if reason is FinishReason.STOP and not state.params.include_stop:
            ev = TokenEvent(state.rid, None, state.generated,
                            finished=True, finish_reason=reason)
        else:
            ev = TokenEvent(state.rid, state.tokens[-1] if state.tokens
                            else None, max(state.generated - 1, 0),
                            finished=True, finish_reason=reason)
        state.events.append(ev)
        return ev

    def _refresh_shared_lens(self) -> None:
        """Recompute every resident's ``shared_len`` from live refcounts
        right before the scheduler ranks victims: sharing drifts after
        admission (a leader finishing makes its follower the sole owner;
        a later arrival makes a loner's pages shared), and a stale signal
        would mis-rank eviction cost — ``exclusive_len`` must mean "pages
        an eviction actually reclaims" at the moment of the decision."""
        if self.prefix is None:
            return
        ps = self.pool.page_size
        for idx, state in self.by_slot.items():
            state.shared_len = ps * sum(
                1 for p in self.slots.slots[idx].pages
                if self.pool.refcount(p) > 1)
            if self.tiers is not None:
                # with a tiered store, preemption retains every full
                # page — so the re-admission cost signal is only the
                # partial tail past the last page boundary
                state.persistable_len = (
                    self.slots.slots[idx].length // ps) * ps

    def _note_page_pressure(self) -> None:
        if self.pool is not None:
            self.stats.peak_pages_used = max(
                self.stats.peak_pages_used, self.pool.used_pages)
