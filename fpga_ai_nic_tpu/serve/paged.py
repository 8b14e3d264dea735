"""Paged KV-cache pool + page allocator — HBM sharing for the serving plane.

`models.llama_decode.init_cache` allocates (and zero-fills) the FULL
``[B, kv_local, max_seq, hd]`` extent per layer, per K and V, up front:
a batch of short sequences pays for ``max_seq`` anyway, and no byte is
ever shared between sequences.  The serving plane replaces it with the
vLLM-style paged layout:

  - ONE preallocated pool per layer: ``[n_pages, kv_local, page_size,
    hd]`` (page 0 reserved as the null page — the write target of empty
    slots and the gather target of unallocated table entries; its
    contents are never visible through the attention mask).
  - a static-shape ``[max_reqs, max_pages_per_seq]`` int32 page table:
    sequences own arbitrary page sets, fragmentation-free, and a page
    re-assignment changes table VALUES only — the jitted decode step
    never retraces (graftlint J10).
  - recycled pages are dirty BY DESIGN: `forward_paged`'s mask makes
    paged decode bitwise-identical to the contiguous cache regardless of
    what a page held before (pinned by tests/test_serve.py), so freeing
    is O(1) list surgery with no zero-fill pass.

Byte accounting here is exact (`pool_bytes` == the sum of the actual
device array sizes, tested) because the obs gate holds the serving
artifacts to it two-sided — the same honesty rule as the wire-byte
accounting on the collectives.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..models import llama_decode
from ..models.llama import LlamaConfig

__all__ = ["NULL_PAGE", "ServeConfig", "PageAllocator", "init_pool",
           "pool_bytes", "contiguous_cache_bytes", "page_table_bytes"]

NULL_PAGE = 0


@dataclass(frozen=True)
class ServeConfig:
    """Static shape/budget knobs of the serving plane.  Everything here
    is baked into the jitted step's shapes — requests, pages and slots
    move WITHIN these bounds without retracing."""

    max_reqs: int = 8                # decode slots (R)
    page_size: int = 16              # positions per KV page
    n_pages: int = 64                # pool pages INCLUDING null page 0
    max_pages_per_seq: int = 8       # page-table width (P)
    prefill_chunk: int = 16          # tokens per prefill call (static T)
    # fault handling (chaos serving cell): watchdog bound over each
    # tick's device work; None disables detection
    step_timeout_s: Optional[float] = None
    max_retries: int = 4
    backoff_s: float = 0.01
    # corrupted-tick guard, SECOND tier: a decode/prefill tick whose
    # logits are non-finite OR exceed this magnitude is GATED
    # (IntegrityError -> replay-tier recovery) before any token reaches
    # a stream.  Healthy logits are O(10); a NaN'd or scale-corrupted KV
    # pool lands far past this.  This tier is a magnitude guard ONLY —
    # it is provably blind to finite wrong-value damage (a flipped
    # mantissa bit in a KV page yields wrong-but-normal-magnitude
    # logits).  That class is owned by the FIRST tier, the exact
    # per-page checksum ledger below (``page_integrity``); the logit
    # guard remains as the backstop for damage classes that bypass the
    # pool (activation corruption, a poisoned weight replica).  None
    # disables the magnitude half (non-finite always trips).
    logit_guard_abs: Optional[float] = 1e6
    # corrupted-tick guard, FIRST tier: exact per-page checksums over
    # the KV pool (ops.integrity.page_checksums).  Every tick's program
    # verifies its INPUT pool bit-for-bit against the ledger the
    # previous program's output recorded, and emits the new ledger —
    # so any byte of any page changed OUTSIDE the ledger-maintaining
    # programs (host corruption, a wrong-KEY write, a failed migration)
    # trips BEFORE the tick emits a token, closing the finite
    # wrong-value class the logit guard cannot see (the honest boundary
    # docs/SERVING.md carried until PR 12).  The ledger is values-only:
    # shapes/trace counts are unchanged (J10 holds either way).
    page_integrity: bool = True

    def __post_init__(self) -> None:
        if self.max_reqs < 1 or self.page_size < 1:
            raise ValueError("max_reqs and page_size must be >= 1")
        if self.logit_guard_abs is not None and self.logit_guard_abs <= 0:
            raise ValueError("logit_guard_abs must be positive (or None)")
        if self.n_pages < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is reserved)")
        if self.max_pages_per_seq < 1:
            raise ValueError("max_pages_per_seq must be >= 1")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")

    @property
    def max_seq(self) -> int:
        """Longest sequence a single page-table row can address."""
        return self.max_pages_per_seq * self.page_size

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1          # page 0 is the null page

    def pages_for(self, n_positions: int) -> int:
        """Pages needed to hold ``n_positions`` KV entries."""
        return max(0, -(-int(n_positions) // self.page_size))


class PageAllocator:
    """Free-list allocator over pool pages ``1..n_pages-1``.

    Single-threaded by contract — only the engine loop allocates (the
    cross-thread surfaces are RequestQueue/ServeStats).  Freed pages are
    recycled LIFO and handed out dirty; `forward_paged`'s mask-parity
    makes that safe (module docstring)."""

    def __init__(self, n_pages: int) -> None:
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is reserved)")
        self.n_pages = int(n_pages)
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))
        self.in_use = 0
        self.peak_in_use = 0

    @property
    def free(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """n pages, or None (caller evicts and retries) — never partial."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.in_use += n
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pages

    def free_pages(self, pages: List[int]) -> None:
        for p in pages:
            if not 1 <= p < self.n_pages:
                raise ValueError(f"page {p} outside pool (1..{self.n_pages - 1})")
        self._free.extend(pages)
        self.in_use -= len(pages)
        if self.in_use < 0 or len(self._free) > self.n_pages - 1:
            raise RuntimeError("page double-free detected")


def init_pool(cfg: LlamaConfig, scfg: ServeConfig, *, tp_size: int = 1,
              dtype: Optional[str] = None) -> List[Dict[str, jax.Array]]:
    """Per-layer paged K/V pools ``[n_pages, kv_local, page_size, hd]``,
    zero-filled once at engine construction — the ONLY full-pool
    zero-fill the serving plane ever performs."""
    kv_local = llama_decode.kv_local_heads(cfg, tp_size)
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (scfg.n_pages, kv_local, scfg.page_size, cfg.head_dim)
    return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for _ in range(cfg.n_layers)]


def pool_bytes(cfg: LlamaConfig, scfg: ServeConfig, *, tp_size: int = 1,
               dtype: Optional[str] = None) -> int:
    """Exact bytes of the paged pool (all layers, K and V)."""
    kv_local = llama_decode.kv_local_heads(cfg, tp_size)
    dt = jnp.dtype(dtype or cfg.dtype)
    per_layer = 2 * scfg.n_pages * kv_local * scfg.page_size \
        * cfg.head_dim * dt.itemsize
    return cfg.n_layers * per_layer


def contiguous_cache_bytes(cfg: LlamaConfig, batch: int, max_seq: int, *,
                           tp_size: int = 1,
                           dtype: Optional[str] = None) -> int:
    """Exact bytes `init_cache` would allocate for the same concurrency —
    the HBM cost the paged pool is measured against."""
    kv_local = llama_decode.kv_local_heads(cfg, tp_size)
    dt = jnp.dtype(dtype or cfg.dtype)
    return cfg.n_layers * 2 * batch * kv_local * max_seq \
        * cfg.head_dim * dt.itemsize


def page_table_bytes(scfg: ServeConfig) -> int:
    """Exact bytes of the static int32 page table."""
    return scfg.max_reqs * scfg.max_pages_per_seq * 4
