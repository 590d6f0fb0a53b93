"""Block-paged, preallocated KV cache for autoregressive serving.

Two granularities share this module:

* **Contiguous slots** (`KVCacheSpec`, the PR 7 layout): one dense
  pytree `{k, v: (layers, slots, max_len, heads, head_dim), lengths}`
  — every admitted sequence owns a `max_len` stripe whether it uses 3
  positions or 300. Kept as the parity/bench twin the paged layout is
  measured against.

* **Paged pool** (`PagedKVCacheSpec`, PagedAttention — Kwon SOSP'23,
  PAPERS.md): one device-resident page pool
  `{k, v: (layers, num_pages, page_size, heads, head_dim)}` plus a
  HOST-side block table per slot mapping slot-local page index ->
  pool page id. Allocation is page-granular (`PagePool`): an admitted
  sequence takes ceil(tokens / page_size) pages and a recycled slot
  returns PAGES, not a `max_len` stripe — allocated HBM scales with
  live tokens, which is the whole PagedAttention claim
  (`kv_cache_bytes` / `pages_in_use` are the accounting seam the
  structural tests and bench.py assert against). Pages are refcounted
  so the prefix cache (`PrefixCache`) can share immutable prompt pages
  between slots; a write into a shared page copies it first
  (copy-on-write, engine-side).

* **Latent pool** (`PagedKVCacheSpec.latent_dim` > 0): the same pages,
  block tables, refcounts and prefix cache over a pool whose row is ONE
  vector a token (a latent-attention mixer's compressed row and its
  shared rotary key, already rotated): `{"latent": {layer: (num_pages,
  page_size, latent_width)}}`, one array a layer, no heads, no separate
  values. The row is the array's minor axis and is STORED padded with
  zeros to whole lane tiles (`latent_width`: 576 values are 4.5 tiles
  of 128 and are stored as 640): the TPU keeps an array whose minor
  axis is no multiple of 128 with another axis on the lanes, and every
  step then re-lays the whole pool out on its way in and out (two
  copies of 0.3 GB a layer a step, compiled for a v5e). `page_bytes`
  and `latent_pool_bytes` count what is STORED, a ninth more than the
  values. One array a layer, not a stacked one, so that a step scatters
  its rows into a layer's pool in place and never copies the stack.

* **State pool** (`StatePoolSpec`): beside the pages, for layers that
  keep no keys and values but arrays of CONSTANT size per sequence (a
  state-space layer's recurrent state and its convolution's last
  inputs): `{"state": {layer: {name: (slots, ...)}}}` in the same
  cache tree the steps donate, one row per slot, no pages, no host
  bookkeeping. A slot that is recycled is not cleared: the step that
  ingests a prompt's first chunk starts from zeros instead.

A SLOT remains the unit of admission (Orca's iteration-level
scheduling): each active request owns one slot for its lifetime and
eviction is a host-side free-list operation (`SlotAllocator`) — the
device buffers are never resized or compacted, so the compiled shapes
never churn.

Within a slot, axes follow the repo's (B, T, H, Dh) attention
convention (`ops/attention.py`) so the cache feeds
`dot_product_attention` / the SP online-softmax without transposes.

Three mesh layouts, chosen to match the TRAINING engine whose params
are being served (`cache_pspecs` / `paged_pspecs`):

  replicated — every device holds the full cache (single-chip or pure
               data-parallel serving).
  tp         — heads sharded over 'model', the Megatron axis: the
               head-sharded q/k/v a column-parallel qkv projection
               produces attend against their local head shard
               (`parallel/tensor_parallel.py` layouts).
  sp         — positions sharded over 'seq': each shard owns a
               contiguous slice of every position range (the max_len
               axis for contiguous slots, the page_size axis for the
               paged pool), decode combines per-shard partial
               attention with the same online-softmax recurrence
               `ops/ring_attention.py` uses.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LAYOUTS = ("replicated", "tp", "sp")
LANES = 128  # the TPU's minor tile


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Static shape of the preallocated cache (one per ServingEngine)."""

    num_layers: int
    num_slots: int
    max_len: int
    num_heads: int
    head_dim: int
    dtype: Any = jnp.float32

    def validate(self, layout: str, mesh: Optional[Mesh]) -> None:
        """Fail at construction (not at trace time) when the cache
        cannot be laid out on the mesh."""
        if layout not in LAYOUTS:
            raise ValueError(
                f"layout must be one of {LAYOUTS}, got {layout!r}"
            )
        if layout == "replicated":
            return
        if mesh is None:
            raise ValueError(f"layout {layout!r} needs a mesh")
        if layout == "tp":
            s = mesh.shape["model"]
            if self.num_heads % s:
                raise ValueError(
                    f"tp cache shards heads over 'model': num_heads "
                    f"{self.num_heads} not divisible by {s} shards"
                )
        if layout == "sp":
            s = mesh.shape["seq"]
            if self.max_len % s:
                raise ValueError(
                    f"sp cache shards positions over 'seq': max_len "
                    f"{self.max_len} not divisible by {s} shards"
                )


def cache_pspecs(layout: str) -> dict:
    """PartitionSpec pytree for one cache (see module docstring)."""
    if layout == "tp":
        kv = P(None, None, None, "model", None)
    elif layout == "sp":
        kv = P(None, None, "seq", None, None)
    else:
        kv = P()
    return {"k": kv, "v": kv, "lengths": P()}


def cache_shardings(mesh: Mesh, layout: str) -> dict:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        cache_pspecs(layout),
        is_leaf=lambda x: isinstance(x, P),
    )


def init_cache(spec: KVCacheSpec) -> dict:
    """Zero-filled cache pytree; place with `cache_shardings`."""
    kv_shape = (
        spec.num_layers, spec.num_slots, spec.max_len,
        spec.num_heads, spec.head_dim,
    )
    return {
        "k": jnp.zeros(kv_shape, spec.dtype),
        "v": jnp.zeros(kv_shape, spec.dtype),
        "lengths": jnp.zeros((spec.num_slots,), jnp.int32),
    }


class SlotAllocator:
    """Host-side free-list over the cache's slot axis.

    Admission takes the lowest free slot (deterministic traces),
    eviction returns it; the device-side buffers are untouched — a
    recycled slot's stale K/V beyond the new request's positions stays
    masked by the per-slot length until overwritten.

    `bytes_per_slot` is the accounting seam: for the CONTIGUOUS layout
    every live slot pins a full `max_len` stripe of K/V whether the
    sequence uses 3 positions or 300, so `kv_cache_bytes` here is
    `live_slots * bytes_per_slot` — the number the paged pool's
    token-proportional `PagePool.kv_cache_bytes` is measured against
    (the PagedAttention waste claim, asserted from the bookkeeping in
    tests/test_serving_paged.py and reported by bench.py)."""

    def __init__(self, num_slots: int, *, bytes_per_slot: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self.bytes_per_slot = int(bytes_per_slot)
        self._free: List[int] = list(range(num_slots))
        self._live: set = set()

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def live_slots(self) -> int:
        return len(self._live)

    @property
    def kv_cache_bytes(self) -> int:
        """Bytes the LIVE slots pin: the contiguous layout charges a
        whole `max_len` stripe per admission, independent of how many
        positions actually hold K/V."""
        return len(self._live) * self.bytes_per_slot

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"all {self.num_slots} cache slots are live; evict "
                "(finish) a sequence before admitting another"
            )
        slot = min(self._free)
        self._free.remove(slot)
        self._live.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not live")
        self._live.remove(slot)
        self._free.append(slot)


# ----------------------------------------------------------- paged pool


@dataclasses.dataclass(frozen=True)
class PagedKVCacheSpec:
    """Static shape of the preallocated PAGE POOL (one per paged
    ServingEngine). `num_pages` bounds total live tokens at
    `num_pages * page_size` across ALL slots — the pool may be sized
    well under `num_slots * max_len` because allocation is
    page-granular and ragged batches only pin what they use."""

    num_layers: int
    num_slots: int
    max_len: int
    page_size: int
    num_pages: int
    num_heads: int
    head_dim: int
    dtype: Any = jnp.float32
    # A page's rows are (page_size, num_heads * head_dim) instead of
    # (page_size, num_heads, head_dim). The TPU tiles an array's two
    # minor axes, so a pool of ONE cached head of 128 laid out
    # (..., 1, 128) would pad its unit axis to a whole tile of rows, 16
    # times its bytes in bfloat16. The paged attention classes reshape
    # a gathered view to heads either way. Not with the tp layout,
    # which shards the head axis.
    fold_heads: bool = False
    # > 0: a LATENT pool (module docstring): a page's rows are
    # (page_size, latent_width), `latent_dim` values and zeros up to
    # whole lane tiles, one pool a layer and no value pool; `num_heads`
    # and `head_dim` are not read.
    latent_dim: int = 0
    # A page's rows are (num_heads, page_size, head_dim): one head's
    # page is one tile, which a decode kernel reads whole, and a layer's
    # pool reshapes to one head's pages without a copy
    # (`serving/decode.py`'s `heads_major_pages` and its recorders).
    heads_major: bool = False

    @property
    def latent_width(self) -> int:
        """Values a latent row is stored as: `latent_dim` rounded up to
        the TPU's 128 lanes."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def page_shape(self) -> Tuple[int, ...]:
        """One page of one layer's keys (or values, or latent rows)."""
        if self.latent_dim:
            return (self.page_size, self.latent_width)
        if self.heads_major:
            return (self.num_heads, self.page_size, self.head_dim)
        if self.fold_heads:
            return (self.page_size, self.num_heads * self.head_dim)
        return (self.page_size, self.num_heads, self.head_dim)

    @property
    def pages_per_slot(self) -> int:
        """Block-table width: pages covering one slot's max_len."""
        return -(-self.max_len // self.page_size)

    @property
    def page_bytes(self) -> int:
        """K AND V bytes (or the latent rows', AS STORED: a row takes
        `latent_width` values, a ninth more than its 576 at the
        published widths) one pool page pins across all layers."""
        if self.latent_dim:
            return (
                self.num_layers * self.page_size * self.latent_width
                * jnp.dtype(self.dtype).itemsize
            )
        return (
            2 * self.num_layers * self.page_size * self.num_heads
            * self.head_dim * jnp.dtype(self.dtype).itemsize
        )

    def validate(self, layout: str, mesh: Optional[Mesh]) -> None:
        if layout not in LAYOUTS:
            raise ValueError(
                f"layout must be one of {LAYOUTS}, got {layout!r}"
            )
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}"
            )
        if self.max_len % self.page_size:
            raise ValueError(
                f"page_size {self.page_size} must divide max_len "
                f"{self.max_len} (the block table covers whole pages)"
            )
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold even one "
                f"full-length sequence ({self.pages_per_slot} pages "
                f"of {self.page_size})"
            )
        if layout == "replicated":
            return
        if self.latent_dim:
            raise ValueError(
                f"a latent pool has no head axis to shard: layout "
                f"{layout!r} is not built for it"
            )
        if mesh is None:
            raise ValueError(f"layout {layout!r} needs a mesh")
        if layout == "tp":
            s = mesh.shape["model"]
            if self.fold_heads or self.num_heads % s:
                raise ValueError(
                    f"tp cache shards heads over 'model': num_heads "
                    f"{self.num_heads} not divisible by {s} shards"
                    + (" (fold_heads leaves no head axis)"
                       if self.fold_heads else "")
                )
        if layout == "sp":
            s = mesh.shape["seq"]
            if self.page_size % s:
                raise ValueError(
                    f"sp shards each page's positions over 'seq': "
                    f"page_size {self.page_size} not divisible by "
                    f"{s} shards"
                )


def paged_pspecs(layout: str) -> dict:
    """PartitionSpec pytree for the page pool
    (L, num_pages, page_size, H, Dh): heads over 'model' for tp, the
    WITHIN-page position axis over 'seq' for sp (every shard owns a
    contiguous slice of every page, so block-table gathers stay
    local)."""
    if layout == "tp":
        kv = P(None, None, None, "model", None)
    elif layout == "sp":
        kv = P(None, None, "seq", None, None)
    else:
        kv = P()
    return {"k": kv, "v": kv}


def paged_shardings(mesh: Mesh, layout: str) -> dict:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        paged_pspecs(layout),
        is_leaf=lambda x: isinstance(x, P),
    )


def init_paged_cache(spec: PagedKVCacheSpec) -> dict:
    """Zero-filled page pool; place with `paged_shardings`. Unlike the
    contiguous cache, `lengths` is NOT device state — the host loop
    owns every slot's position (it owns the block table anyway), so
    positions ride in as a step argument."""
    if spec.latent_dim:
        return {"latent": {
            str(i): jnp.zeros((spec.num_pages, *spec.page_shape),
                              spec.dtype)
            for i in range(spec.num_layers)
        }}
    kv_shape = (spec.num_layers, spec.num_pages, *spec.page_shape)
    return {
        "k": jnp.zeros(kv_shape, spec.dtype),
        "v": jnp.zeros(kv_shape, spec.dtype),
    }


@dataclasses.dataclass(frozen=True)
class StatePoolSpec:
    """Static shape of the STATE POOL (module docstring): for each
    layer that keeps state, by its index in the model, the arrays of
    one slot as (name, shape, dtype)."""

    num_slots: int
    layers: Tuple[Tuple[int, Tuple[Tuple[str, Tuple[int, ...], Any], ...]],
                  ...]

    @property
    def slot_bytes(self) -> int:
        """Bytes one slot's state pins across all layers."""
        return sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for _, arrays in self.layers for _, shape, dtype in arrays
        )

    @property
    def pool_bytes(self) -> int:
        return self.num_slots * self.slot_bytes


def init_state_pool(spec: StatePoolSpec) -> dict:
    """Zero-filled {layer: {name: (slots, *shape)}}, the `"state"`
    entry of the cache tree."""
    return {
        str(layer): {
            name: jnp.zeros((spec.num_slots, *shape), dtype)
            for name, shape, dtype in arrays
        }
        for layer, arrays in spec.layers
    }


class PagePool:
    """Host-side page allocator with refcounts.

    Allocation takes the lowest free page (deterministic traces);
    `incref`/`decref` support prefix sharing — a page frees only when
    its LAST reference drops. `pages_in_use`/`kv_cache_bytes` are the
    accounting seam: paged allocation must scale with live tokens
    (ceil per live sequence), never with `slots * max_len`."""

    def __init__(self, num_pages: int, page_bytes: int = 0):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        self.page_bytes = int(page_bytes)
        # a heap: the lowest free page in log time, on the path of
        # every chunk's launch
        self._free: List[int] = list(range(num_pages))
        self._refs: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def kv_cache_bytes(self) -> int:
        return self.pages_in_use * self.page_bytes

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"page pool exhausted: all {self.num_pages} KV pages "
                "are live — size the pool larger (--kv-pages) or admit "
                "fewer concurrent sequences"
            )
        page = heapq.heappop(self._free)
        self._refs[page] = 1
        return page

    def incref(self, page: int) -> None:
        if page not in self._refs:
            raise ValueError(f"page {page} is not live")
        self._refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True when the page was freed."""
        n = self._refs.get(page)
        if n is None:
            raise ValueError(f"page {page} is not live")
        if n > 1:
            self._refs[page] = n - 1
            return False
        del self._refs[page]
        heapq.heappush(self._free, page)
        return True


class PrefixCache:
    """Host-side map from token prefixes to immutable shared pool
    pages (prompt/prefix caching — the millions-of-users shape where
    a repeated system prompt dominates prefill).

    Keys are CHAINED digests over the full token prefix (page j's key
    = blake2b(key_{j-1} || page j's int32 bytes) — the page content
    depends on every earlier token, so reuse requires an exact
    whole-prefix match, and the rolling chain prices a lookup at O(n)
    total instead of re-serializing O(n^2/page) prefix bytes per
    request). A prompt whose length is not page-aligned additionally
    registers a whole-prompt entry for its last PARTIAL page; a
    borrower of that page copies it before writing (copy-on-write,
    engine-side — the cache itself never mutates device state).

    Every cached entry holds one pool reference of its own, so pages
    outlive the slot that produced them; `release_unused` drops
    cache-only entries (refcount 1) in LRU order when the pool runs
    dry."""

    def __init__(self, pool: PagePool, page_size: int):
        self.pool = pool
        self.page_size = page_size
        # key -> page id, in LRU order (move_to_end on every match).
        self._map: "OrderedDict[bytes, int]" = OrderedDict()
        # key -> the keys chained directly off it: match() breaks at
        # the first missing key, so an entry whose PARENT is evicted
        # can never match again — eviction cascades down this map so
        # orphans neither pin pool references nor inflate `evictable`.
        self._children: Dict[bytes, List[bytes]] = {}
        self.hits = 0       # requests that reused >= 1 cached page
        self.misses = 0     # requests that matched nothing
        self.tokens_reused = 0

    def __len__(self) -> int:
        return len(self._map)

    @staticmethod
    def _chain(prev: bytes, tokens: np.ndarray) -> bytes:
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    def _keys(self, prompt: np.ndarray):
        """(key, tokens-covered) per cacheable span of `prompt`, in
        prefix order: one per full page, then the whole-prompt partial
        entry when the length is not page-aligned. THE one place the
        chain rule lives — match and register can never disagree on a
        key."""
        ps = self.page_size
        key = b""
        out: List[Tuple[bytes, int]] = []
        for j in range(len(prompt) // ps):
            key = self._chain(key, prompt[j * ps:(j + 1) * ps])
            out.append((key, (j + 1) * ps))
        if len(prompt) % ps:
            out.append((
                self._chain(key, prompt[len(prompt) // ps * ps:]),
                len(prompt),
            ))
        return out

    def match(self, prompt: np.ndarray) -> Tuple[List[int], int]:
        """Longest cached prefix of `prompt`: ([page ids], tokens
        covered). Matched pages are incref'd FOR THE CALLER (the slot
        now shares them); spans match greedily from page 0 — the
        partial whole-prompt entry can only extend a fully matched
        run of full pages (its key chains through theirs)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pages: List[int] = []
        covered = 0
        for key, n_tokens in self._keys(prompt):
            pid = self._map.get(key)
            if pid is None:
                break
            self._map.move_to_end(key)
            pages.append(pid)
            covered = n_tokens
        for pid in pages:
            self.pool.incref(pid)
        if pages:
            self.hits += 1
            self.tokens_reused += covered
        else:
            self.misses += 1
        return pages, covered

    def register(self, prompt: np.ndarray, page_ids: List[int]) -> None:
        """Publish a freshly ingested prompt's pages: one entry per
        full page plus the whole-prompt partial entry when the length
        is not page-aligned. Existing entries win (first writer keeps
        ownership); each NEW entry takes its own pool reference."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        parent = b""
        for page_idx, (key, _n) in enumerate(self._keys(prompt)):
            if page_idx >= len(page_ids):
                break
            if key not in self._map:
                pid = page_ids[page_idx]
                self.pool.incref(pid)
                self._map[key] = pid
                self._map.move_to_end(key)
                self._children.setdefault(parent, set()).add(key)
            parent = key

    def _evict(self, key: bytes) -> int:
        """Drop one entry AND its whole extension subtree (module
        docstring: a child is unmatchable once its parent is gone);
        returns pages actually freed (a page a live slot still borrows
        loses only the cache's reference)."""
        pid = self._map.pop(key, None)
        if pid is None:
            return 0
        freed = 1 if self.pool.decref(pid) else 0
        for child in self._children.pop(key, ()):
            freed += self._evict(child)
        return freed

    def release_unused(self, want: int) -> int:
        """Free up to `want` pages by dropping cache entries whose page
        no slot references (pool refcount 1 — the cache's own ref), in
        LRU order, each with its extension subtree. Returns how many
        pages were actually freed."""
        freed = 0
        for key in list(self._map):
            if freed >= want:
                break
            if key not in self._map:
                continue  # already gone with an evicted ancestor
            if self.pool.refcount(self._map[key]) == 1:
                freed += self._evict(key)
        return freed

    @property
    def evictable(self) -> int:
        """Pages only the cache still references (admission headroom)."""
        return sum(
            1 for pid in self._map.values()
            if self.pool.refcount(pid) == 1
        )


def copy_page(cache: dict, src, dst) -> dict:
    """Device-side page copy (the copy-on-write kernel): duplicate pool
    page `src` into `dst` across every layer of both K and V, or of
    the latent pool. The engine jits this once with the cache donated,
    so a COW costs one tiny in-place scatter, not a pool copy."""
    out = {
        **cache,
        **{name: cache[name].at[:, dst].set(cache[name][:, src])
           for name in ("k", "v") if name in cache},
    }
    if "latent" in cache:
        out["latent"] = {
            layer: pool.at[dst].set(pool[src])
            for layer, pool in cache["latent"].items()
        }
    return out


class PagedCacheHost:
    """Host half of the paged cache: the block tables, page-granular
    alloc/free, prefix sharing, and copy-on-write. Owns every invariant
    the compiled steps assume:

    * a slot's write position is always backed by an allocated page
      (`ensure_writable` before each decode/pseudo-decode write);
    * a write page is always PRIVATE — a shared page (prefix cache, or
      a borrowed partial page) is copied first, so distinct live slots
      never scatter into the same pool page;
    * a freed slot returns pages, not a max_len stripe (`release`),
      and shared pages survive via their remaining references.
    """

    def __init__(self, spec: PagedKVCacheSpec, *,
                 prefix_cache: bool = False, copy_fn=None):
        self.spec = spec
        self.pool = PagePool(spec.num_pages, spec.page_bytes)
        self.block_tables = np.full(
            (spec.num_slots, spec.pages_per_slot), -1, np.int32
        )
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.pool, spec.page_size)
            if prefix_cache else None
        )
        self._copy = copy_fn
        self.cow_copies = 0
        self.pages_in_use_peak = 0
        # Worst-case page commitment per admitted slot (`reserve`):
        # admission headroom is judged against every admitted-but-not-
        # yet-allocated page, so two concurrently ingesting slots can
        # never be promised the same free pages and a sequence, once
        # admitted, always completes (its decode growth and potential
        # COW swaps are inside its commitment).
        self._commit: Dict[int, int] = {}
        # Device mirror of block_tables, rebuilt lazily: steady-state
        # decode mutates the table only at page boundaries / COW /
        # admission, so most iterations reuse the cached upload
        # (every block_tables write below invalidates it).
        self._dev_table = None

    # ------------------------------------------------------ bookkeeping

    def device_table(self):
        if self._dev_table is None:
            self._dev_table = jnp.asarray(self.block_tables)
        return self._dev_table

    def device_row(self, slot: int):
        """One slot's block-table row — the per-slot steps (prefill,
        chunk ingest) take only their own row: a copy of the host
        table's, which the step's call uploads with its launch. (Not
        sliced from the device mirror: a step that ingests has just
        allocated pages, which invalidates the mirror, so a slice
        costs a whole-table upload and a gather program on the device
        ahead of the step.)"""
        return self.block_tables[slot].copy()

    def _note_peak(self) -> None:
        self.pages_in_use_peak = max(
            self.pages_in_use_peak, self.pool.pages_in_use
        )

    def _pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.spec.page_size)

    def _outstanding(self) -> int:
        """Pages promised to admitted slots but not yet allocated:
        each slot's commitment minus the PRIVATE pages it already
        holds (a shared entry — prefix-matched or cache-registered —
        still counts as owed, because a write into it copy-on-writes
        into a fresh page)."""
        total = 0
        for slot, commit in self._commit.items():
            row = self.block_tables[slot]
            private = sum(
                1 for pid in row[row >= 0].tolist()
                if self.pool.refcount(pid) == 1
            )
            total += max(0, commit - private)
        return total

    def can_hold(self, n_tokens: int) -> bool:
        """Admission headroom: enough free (or cache-evictable) pages
        for a whole `n_tokens` sequence — prompt AND its generation
        budget, assuming none of it prefix-matches — AFTER honoring
        every already-admitted slot's outstanding commitment. A
        request admitted under this check (and reserved via `reserve`)
        can always allocate its way to completion; the alternative is
        overcommit, where mid-ingest exhaustion would abort work the
        scheduler already accepted."""
        headroom = self.pool.free_pages + (
            self.prefix.evictable if self.prefix else 0
        ) - self._outstanding()
        return headroom >= self._pages_for(n_tokens)

    def reserve(self, slot: int, n_tokens: int) -> None:
        """Commit the slot's worst-case page need (call at admission,
        with the same token count `can_hold` approved)."""
        self._commit[slot] = self._pages_for(n_tokens)

    def _alloc_page(self) -> int:
        try:
            page = self.pool.alloc()
        except RuntimeError:
            if self.prefix is None or not self.prefix.release_unused(1):
                raise
            page = self.pool.alloc()
        self._note_peak()
        return page

    # ------------------------------------------------------- lifecycle

    def ensure_pages(self, slot: int, n_tokens: int) -> None:
        """Allocate so the slot's pages cover positions [0, n_tokens)
        (prefix-matched entries are already in place and kept)."""
        for j in range(self._pages_for(n_tokens)):
            if self.block_tables[slot, j] < 0:
                self.block_tables[slot, j] = self._alloc_page()
                self._dev_table = None

    def ensure_writable(self, cache: dict, slot: int,
                        position: int) -> dict:
        """Back `position` with a PRIVATE page before a device write:
        allocate if unmapped, copy-on-write if shared. Returns the
        (possibly updated) device cache."""
        j = position // self.spec.page_size
        pid = int(self.block_tables[slot, j])
        if pid < 0:
            self.block_tables[slot, j] = self._alloc_page()
            self._dev_table = None
            return cache
        if self.pool.refcount(pid) > 1:
            fresh = self._alloc_page()
            cache = self._copy(cache, jnp.int32(pid), jnp.int32(fresh))
            self.pool.decref(pid)
            self.block_tables[slot, j] = fresh
            self._dev_table = None
            self.cow_copies += 1
        return cache

    def attach_prefix(self, slot: int, prompt) -> int:
        """Install the longest cached prefix into the slot's block
        table; returns tokens covered (0 when the cache is off or
        missed)."""
        if self.prefix is None:
            return 0
        pages, covered = self.prefix.match(prompt)
        for j, pid in enumerate(pages):
            self.block_tables[slot, j] = pid
        if pages:
            self._dev_table = None
        self._note_peak()
        return covered

    def register_prefix(self, slot: int, prompt) -> None:
        if self.prefix is None:
            return
        n = self._pages_for(len(np.asarray(prompt).reshape(-1)))
        ids = [int(p) for p in self.block_tables[slot, :n]]
        if all(p >= 0 for p in ids):
            self.prefix.register(prompt, ids)

    def truncate(self, slot: int, n_tokens: int) -> None:
        """Roll a slot back so it holds only its first `n_tokens`
        positions: pages wholly beyond the kept span return to the pool
        (shared pages just drop this slot's reference). THE speculative
        rollback primitive — a rejected draft suffix is a block-table
        edit plus refcount decrements, never a KV copy. Stale K/V
        inside the kept final page's tail stays masked by the slot's
        position until overwritten, the same discipline recycled slots
        rely on."""
        keep = self._pages_for(n_tokens)
        for j in range(keep, self.spec.pages_per_slot):
            pid = int(self.block_tables[slot, j])
            if pid >= 0:
                self.pool.decref(pid)
                self.block_tables[slot, j] = -1
                self._dev_table = None

    def release(self, slot: int) -> None:
        """Recycle a slot: PAGES return to the pool (minus surviving
        shared references) — never a max_len stripe — and its
        commitment clears."""
        for j, pid in enumerate(self.block_tables[slot]):
            if pid >= 0:
                self.pool.decref(int(pid))
        self.block_tables[slot] = -1
        self._dev_table = None
        self._commit.pop(slot, None)


__all__ = [
    "KVCacheSpec",
    "LAYOUTS",
    "PagePool",
    "PagedCacheHost",
    "PagedKVCacheSpec",
    "PrefixCache",
    "SlotAllocator",
    "StatePoolSpec",
    "copy_page",
    "cache_pspecs",
    "cache_shardings",
    "init_cache",
    "init_paged_cache",
    "init_state_pool",
    "paged_pspecs",
    "paged_shardings",
]
