"""KV-storage layouts: the one cache-surface abstraction shared by the
model API and the serving engine.

A :class:`KVLayout` describes *how per-layer KV tensors are stored and
addressed*, so every family exposes exactly one ``init_cache`` /
``cache_spec`` / ``prefill_chunk`` / ``decode_step`` surface instead of a
dense/paged fork of ``*_paged`` twins:

  * :class:`DenseLayout` — the classic slot cache: ``(L, num_slots,
    max_seq, HK, Dh)``; logical position ``p`` of slot ``s`` lives at
    physical ``(s, p)``. No indirection operand.

  * :class:`PagedLayout` — a block-paged pool: ``(L, num_pages, page_size,
    HK, Dh)`` shared by all sequences; logical position ``p`` of slot ``s``
    lives at ``(block_tables[s, p // page_size], p % page_size)``. The
    layout's *operand* is the per-tick block-table array produced by the
    slot manager (``None`` for dense) — model steps take it as an optional
    ``block_tables`` argument and select the gather/scatter discipline on
    whether it is present.

The layout objects are pure shape/addressing descriptors (hashable,
host-side); device allocation stays in the family modules, free-list
bookkeeping stays in :mod:`repro.serving.blockpool`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple, Union


def pages_for(positions: int, page_size: int) -> int:
    """Pages needed to store ``positions`` KV entries — the one definition
    of the page ceil-div, shared by the allocator, the engine's pool
    sizing, and the benchmarks."""
    return -(-max(positions, 0) // page_size)


def pow2_bucket(n: int, lo: int = 1, hi: Union[int, None] = None) -> int:
    """Round ``n`` up to a power-of-two bucket (floor ``lo``, capped at
    ``hi``) — the one rounding that keeps jit shape families logarithmic
    (the engine's resident-bounded block tables and batched-prefill
    padding) and lets the benchmarks mirror the engine's bucketing
    exactly."""
    b = lo
    while b < n:
        b *= 2
    return b if hi is None else min(b, hi)


@dataclasses.dataclass(frozen=True)
class DenseLayout:
    """Slot-dense KV storage: every slot reserves ``max_seq`` positions."""

    num_slots: int
    max_seq: int

    kind = "dense"
    is_paged = False

    def kv_shape(self, num_layers: int, kv_heads: int,
                 head_dim: int) -> Tuple[int, int, int, int, int]:
        return (num_layers, self.num_slots, self.max_seq, kv_heads, head_dim)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Block-paged KV storage: a shared pool of fixed-size pages addressed
    through per-sequence block tables.

    ``kv_dtype`` is the page storage precision (``plan.KV_DTYPES``):
    ``"bf16"`` stores full-precision pages; ``"int8"`` / ``"fp8"`` store
    quantized codes plus parallel per-(page, kv head) f32 scale pools as
    extra cache leaves (see :mod:`repro.serving.kvquant`)."""

    num_pages: int
    page_size: int
    kv_dtype: str = "bf16"

    kind = "paged"
    is_paged = True

    def kv_shape(self, num_layers: int, kv_heads: int,
                 head_dim: int) -> Tuple[int, int, int, int, int]:
        return (num_layers, self.num_pages, self.page_size, kv_heads,
                head_dim)

    def scale_shape(self, num_layers: int,
                    kv_heads: int) -> Tuple[int, int, int]:
        """Shape of one scale pool leaf (quantized layouts only)."""
        return (num_layers, self.num_pages, kv_heads)

    def pages_for(self, positions: int) -> int:
        return pages_for(positions, self.page_size)

    @staticmethod
    def device_storage(spec, device):
        """The shapes a paged cache (``spec``: its leaves' shape structs)
        is stored in on ``device``: each leaf's ``(L, NP, ...)`` shape, or
        the same with the page axis split in two, ``(L, NP/a, a, ...)``,
        for the smallest ``a`` that the device lays out row-major by
        default.

        The paged kernels read pages row-major. For a pool whose head_dim
        is not a multiple of 128 lanes a TPU's default layout puts a page
        axis of 128 or more minor-most, so a step program would relay the
        whole pool on entry and again on exit. Split, the same bytes in
        row-major order are the default layout, and the leading dims
        merge back into the page view as a bitcast: every program takes
        and returns the pool in place, in a layout that needs no
        annotation. Where no split helps, the shape stays."""
        import jax
        from jax.experimental.layout import Layout

        def row_major(shape, dtype):
            layout = device.client.get_default_layout(dtype, shape, device)
            return (Layout.from_pjrt_layout(layout).major_to_minor
                    == tuple(range(len(shape))))

        def stored(s):
            nl, num_pages, *rest = s.shape
            for a in range(1, num_pages + 1):
                split = (num_pages,) if a == 1 else (num_pages // a, a)
                shape = (nl, *split, *rest)
                if num_pages % a == 0 and row_major(shape, s.dtype):
                    return jax.ShapeDtypeStruct(shape, s.dtype)
            return s

        return jax.tree.map(stored, spec)

    @staticmethod
    def page_view(cache: dict) -> Tuple[dict, int, int]:
        """Each leaf of a stacked paged cache as one flat ``(L*NP, ...)``
        page axis, whichever shape it is stored in (the logical ``(L, NP,
        ...)`` or a :meth:`device_storage` split): the leading dims merge,
        a bitcast. Returns the views, ``L`` and ``NP``; page ``p`` of
        layer ``l`` is row ``l*NP + p``. K/V leaves keep their ``(PS, HK,
        D)`` tail, scale leaves their ``(HK,)``."""
        k = cache["k"]
        nl, num_pages = k.shape[0], math.prod(k.shape[1:-3])
        rows = nl * num_pages

        def flat(a):
            lead, n = 0, 1
            while n < rows:
                n *= a.shape[lead]
                lead += 1
            return a.reshape(rows, *a.shape[lead:])

        return {n: flat(a) for n, a in cache.items()}, nl, num_pages


KVLayout = Union[DenseLayout, PagedLayout]


def require_dense(layout: KVLayout, family: str) -> DenseLayout:
    """Families without a dense-KV cache (recurrent / ring / encdec state)
    can only host the slot layout; give them a uniform error."""
    if getattr(layout, "is_paged", False):
        raise ValueError(
            f"family {family!r} has no paged-KV path (recurrent/ring state "
            "caches); use a DenseLayout")
    return layout
