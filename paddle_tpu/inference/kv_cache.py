"""Block-allocated paged KV cache (ISSUE 6) — the serving-side memory
manager.

Training caches (``GPTForCausalLM.make_caches``) preallocate one dense
``(batch, heads, max_len, head_dim)`` buffer per sequence, so a 32-way
decode batch of mostly-short sequences wastes most of its HBM on padding.
The paged design (PAPERS.md: *Ragged Paged Attention*, the TPU-native
paged-KV layout) carves the cache into fixed-size **blocks** shared by
every sequence: a sequence owns a *block table* (list of block ids), the
attention kernel follows the table, and memory waste is bounded by one
partial block per sequence.  That is what lets the continuous-batching
scheduler (``inference/scheduler.py``) admit by a real byte budget and
preempt by freeing a table.

Three layers in this module:

- :class:`BlockAllocator` — host-side free-list over ``num_blocks`` block
  ids: ``alloc / free / defrag`` plus occupancy accounting.  Pure python,
  no device traffic; the scheduler calls it every step.
- :class:`PagedLayerCache` — the **device-side** view one decoder layer
  sees inside a jitted step: its page arrays, ``(num_blocks,
  block_size) + per-token shape`` each, plus the batch's
  ``block_tables`` / ``seq_lens`` / ``slot_mapping`` int32 arrays.  It
  is a registered pytree, so it flows through ``jax.jit`` with fixed
  structure — the decode step never retraces on cache state.
- :class:`PagedKVCache` — the whole-model container: per-layer page
  arrays + the allocator + per-sequence tables, with the array-building
  helpers the engine uses to assemble fixed-shape step inputs.

What a token keeps in a layer is the MODEL's to declare (``layout``,
from its ``kv_cache_layout()``): a multi-head model keeps two arrays of
``(heads, head_dim)``, keys and values, rounded up to whole tiles where
the decode kernel reads them (``paged_attention.page_token_shape``: the
writes fill the rest with zeros); a latent-attention model one row
shared by all heads (ISSUE 28).  Allocator, tables and slot arithmetic
are the same for every layout.

Page layout: token-major, ``pages[block, offset, ...]``.  One token's
slab is the minor tile (a ``(16, 128)`` bf16 slab is exactly one 4 KB
TPU tile) and a block is ``block_size`` of them, contiguous.  That is the layout XLA's scatter of new tokens wants, and
one the decode kernel accepts (its block's two minor dims are the
array's), so a step program that is handed the pool **donated** writes
it in place: no relayout before the scatter, none after it, no second
pool.  With heads ahead of the offset (the layout until PR 27) every
step copied every page array twice.  The layout is private to this
module and ``paged_attention``: models write through
:meth:`PagedLayerCache.write`.

Ownership: the serving step consumes the page arrays it is given, so
:class:`PagedKVCache` holds the only live handles and takes the step's
outputs through :meth:`PagedKVCache.update_pages` as soon as the call
returns.  :meth:`PagedKVCache.pages_lost` says whether a failed call ate
them, :meth:`PagedKVCache.reset_pages` starts over with a zeroed pool.

Slots: a flat slot id addresses one token row, ``slot = block_table[pos
// bs] * bs + pos % bs``, i.e. ``pages[slot // bs, slot % bs]``.
``slot_pad`` (== ``num_slots``, whose block id is out of bounds) marks
padded positions — page writes use ``mode="drop"`` so padding never
lands.
"""
from __future__ import annotations

import functools
import itertools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as _tree_util

from ..framework.errors import enforce

__all__ = ["KV_BLOCK_SIZE_ENV", "default_kv_block_size", "BlockAllocator",
           "PagedLayerCache", "PagedKVCache"]

KV_BLOCK_SIZE_ENV = "PTPU_KV_BLOCK_SIZE"


def default_kv_block_size() -> int:
    return int(os.environ.get(KV_BLOCK_SIZE_ENV, "16"))


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size KV blocks.

    All-or-nothing ``alloc``: a request that cannot be fully satisfied
    takes nothing (the scheduler preempts and retries instead of holding
    partial grants across steps — partial holds deadlock a full pool).
    Blocks are handed out lowest-id-first so a freshly started engine
    stays dense without defrag.
    """

    def __init__(self, num_blocks: int, block_size: int):
        enforce(num_blocks > 0 and block_size > 0,
                f"bad pool shape: {num_blocks} blocks x {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._live: set = set()
        # eviction accounting (ISSUE 15): every grant and return counted
        # for the whole pool lifetime — `total_allocs - total_frees ==
        # num_used` is the invariant the leak-freedom drills pin after
        # any interleaving of finish/cancel/deadline/preempt/quarantine
        self.total_allocs = 0
        self.total_frees = 0
        self.high_water = 0

    # -- accounting --------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def occupancy(self) -> float:
        return self.num_used / self.num_blocks

    def blocks_for_tokens(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` cache entries."""
        return -(-max(0, int(num_tokens)) // self.block_size)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    # -- alloc / free ------------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` blocks, or None (and take nothing) when the pool
        cannot satisfy the whole request."""
        if n < 0 or len(self._free) < n:
            return None
        got = [self._free.pop() for _ in range(n)]
        self._live.update(got)
        self.total_allocs += len(got)
        self.high_water = max(self.high_water, self.num_used)
        return got

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            enforce(b in self._live, f"double/foreign free of block {b}")
            self._live.discard(b)
            self._free.append(b)
        self.total_frees += len(blocks)
        # keep lowest-id-first hand-out after churn
        self._free.sort(reverse=True)

    def stats(self) -> Dict[str, object]:
        """Lifetime accounting snapshot; ``balanced`` is the
        leak-freedom invariant (allocs minus frees equals live)."""
        return {"num_blocks": self.num_blocks,
                "num_used": self.num_used,
                "num_free": self.num_free,
                "total_allocs": self.total_allocs,
                "total_frees": self.total_frees,
                "high_water": self.high_water,
                "balanced": (self.total_allocs - self.total_frees
                             == self.num_used)}

    # -- defrag ------------------------------------------------------------
    def defrag(self, tables: Dict[object, List[int]]
               ) -> Optional[np.ndarray]:
        """Compact live blocks to ids ``[0, num_used)``.

        ``tables`` maps owner -> block-id list covering every live block;
        tables are renumbered **in place**.  Returns ``perm`` with
        ``perm[new_id] = old_id`` (length ``num_blocks``) for permuting
        the device page arrays, or None when already compact (no device
        traffic needed).  With fixed-size blocks there is no external
        fragmentation — defrag exists to re-densify the pool after heavy
        churn so long-lived pools keep locality (and so snapshots of the
        used prefix stay small).
        """
        live = sorted(self._live)
        referenced = sorted({b for t in tables.values() for b in t})
        enforce(referenced == live,
                f"defrag: tables cover {referenced} but live={live}")
        if live == list(range(len(live))):
            return None
        mapping = {old: new for new, old in enumerate(live)}
        for t in tables.values():
            t[:] = [mapping[b] for b in t]
        spare = [b for b in range(self.num_blocks) if b not in mapping]
        perm = np.empty(self.num_blocks, np.int64)
        for old, new in mapping.items():
            perm[new] = old
        perm[len(live):] = spare
        self._live = set(range(len(live)))
        self._free = list(range(self.num_blocks - 1, len(live) - 1, -1))
        return perm


class PagedLayerCache:
    """One decoder layer's jit-visible paged-cache view.

    ``pages``: this layer's page arrays, ``(num_blocks, block_size) +
    per-token shape`` each, in the order the model declared them
    (``k_pages`` / ``v_pages`` name the two of a keys-and-values layout).
    ``block_tables``: ``(batch, max_blocks_per_seq)`` int32 block ids
    (padded rows/entries are 0 — masked out by ``seq_lens``).
    ``seq_lens``: ``(batch,)`` int32 context length *including* the
    tokens written by this call (0 = padding row).
    ``slot_mapping``: ``(batch, chunk)`` int32 flat write slot per new
    token; ``num_slots`` (its block is out of bounds) marks padding.

    Registered as a pytree with ``block_size`` as static aux data, so a
    jitted step sees the arrays as traced leaves but the page geometry
    as a compile-time constant (the attention kernel's grid needs it).
    """

    __slots__ = ("pages", "block_tables", "seq_lens", "slot_mapping",
                 "block_size")

    def __init__(self, pages, block_tables, seq_lens, slot_mapping,
                 block_size: int):
        self.pages = tuple(pages)
        self.block_tables = block_tables
        self.seq_lens = seq_lens
        self.slot_mapping = slot_mapping
        self.block_size = int(block_size)

    def replace(self, **kw) -> "PagedLayerCache":
        fields = {s: getattr(self, s) for s in self.__slots__}
        fields.update(kw)
        return PagedLayerCache(**fields)

    @property
    def k_pages(self):
        return self.pages[0]

    @property
    def v_pages(self):
        return self.pages[1]

    def write(self, *new) -> "PagedLayerCache":
        """Scatter this call's ``(batch * chunk,) + per-token shape``
        arrays, one per page array, into the pages at ``slot_mapping``;
        padded slots are out of bounds and dropped.  An array narrower
        than its pages' per-token shape (a pool kept in whole tiles) is
        filled up with zeros."""
        enforce(len(new) == len(self.pages),
                f"{len(new)} arrays for {len(self.pages)} page arrays")
        slots = self.slot_mapping.reshape(-1)
        blk, off = slots // self.block_size, slots % self.block_size

        def fit(x, p):
            x = x.astype(p.dtype)
            if x.shape[1:] == p.shape[2:]:
                return x
            return jnp.pad(x, [(0, 0)] + [
                (0, n - m) for n, m in zip(p.shape[2:], x.shape[1:])])
        return self.replace(pages=tuple(
            p.at[blk, off].set(fit(x, p), mode="drop")
            for p, x in zip(self.pages, new)))


def _plc_flatten(c: PagedLayerCache):
    return ((c.pages, c.block_tables, c.seq_lens, c.slot_mapping),
            c.block_size)


def _plc_unflatten(block_size, children):
    return PagedLayerCache(*children, block_size=block_size)


_tree_util.register_pytree_node(PagedLayerCache, _plc_flatten,
                                _plc_unflatten)


@functools.partial(jax.jit, donate_argnums=0)
def _zero_blocks(pages, block_ids):
    """Zeros into ``block_ids`` of every page array, in place (the pool
    is donated); ids out of bounds are dropped."""
    return [tuple(a.at[block_ids].set(0, mode="drop") for a in layer)
            for layer in pages]


class PagedKVCache:
    """Whole-model paged KV store: per-layer page arrays + the allocator
    + per-sequence block tables.

    The engine owns one of these; the scheduler talks to ``allocator``
    and the per-sequence helpers; the jitted step consumes :attr:`pages`
    (donated: the handles are dead once it is called) and hands the
    updated page arrays back through :meth:`update_pages`.
    """

    def __init__(self, layout: Sequence[Sequence[Sequence[int]]],
                 num_blocks: int, block_size: Optional[int] = None,
                 dtype=jnp.float32):
        """``layout``: per layer, the per-token shape of each of its page
        arrays, as the model's ``kv_cache_layout()`` declares them (keys
        and values of 2 heads of 4: ``[((2, 4), (2, 4))]``)."""
        block_size = (default_kv_block_size() if block_size is None
                      else int(block_size))
        self.layout = [tuple(tuple(int(d) for d in shape) for shape in layer)
                       for layer in layout]
        enforce(self.layout and all(self.layout), "empty cache layout")
        self.num_layers = len(self.layout)
        self._arity = [len(layer) for layer in self.layout]
        self.num_arrays = sum(self._arity)
        # values a token keeps in each page array, in the pool's order
        self._token_sizes = [int(np.prod(shape)) for layer in self.layout
                             for shape in layer]
        self.block_size = block_size
        self.num_blocks = int(num_blocks)
        self.num_slots = self.num_blocks * block_size
        self.slot_pad = self.num_slots          # OOB sentinel, mode="drop"
        self.dtype = jnp.dtype(dtype)
        self.allocator = BlockAllocator(self.num_blocks, block_size)
        self._tables: Dict[object, List[int]] = {}
        self.reset_pages()

    # -- the page arrays ---------------------------------------------------
    @property
    def pages(self) -> List[Tuple[jnp.ndarray, ...]]:
        """Per layer its page arrays, each ``(num_blocks, block_size) +
        per-token shape``: the step program's donated argument."""
        return self._pages

    def update_pages(self, pages: Sequence[Tuple]) -> None:
        """Take the page arrays a step program returned in place of the
        ones it consumed."""
        enforce([len(layer) for layer in pages] == self._arity,
                f"page arrays handed back do not match the layout of "
                f"{self.num_layers} layers")
        self._pages = [tuple(layer) for layer in pages]

    def reset_pages(self) -> None:
        """A zeroed pool.  The old handles, if any are left, are let go
        first, so the pool is never held twice."""
        self._pages: List[Tuple[jnp.ndarray, ...]] = []
        lead = (self.num_blocks, self.block_size)
        self._pages = [tuple(jnp.zeros(lead + shape, self.dtype)
                             for shape in layer) for layer in self.layout]

    def _live_handles(self) -> List[jnp.ndarray]:
        return [a for layer in self._pages for a in layer
                if not a.is_deleted()]

    def pages_lost(self) -> bool:
        """True when a page handle is dead: a step program consumed the
        pool and did not hand one back."""
        return len(self._live_handles()) < self.num_arrays

    def drop_pages(self) -> None:
        """Delete every page handle: what they hold cannot be trusted (the
        outputs of a program that failed on the device)."""
        for a in self._live_handles():
            a.delete()

    def pool_bytes(self) -> int:
        """Device bytes behind the live page handles: one pool.  (From
        the shapes: ``Array.nbytes`` costs 2 us a call, and this is read
        in every step.)"""
        arrays = itertools.chain.from_iterable(self._pages)
        return self.num_slots * self.dtype.itemsize * sum(
            n for a, n in zip(arrays, self._token_sizes)
            if not a.is_deleted())

    def bytes_per_token(self) -> int:
        """What one cached token takes over all layers, padding of the
        declared shapes included."""
        return self.dtype.itemsize * sum(self._token_sizes)

    def scrub_seq(self, seq_id) -> None:
        """Zero ``seq_id``'s blocks in every layer (one small donated
        program): a quarantined sequence may have left non-finite K/V
        behind, and the decode kernel's ``p * v`` turns a masked ``0 *
        NaN`` into NaN for the block's next owner."""
        table = self._tables.get(seq_id)
        if not table:
            return
        # padded to a power of two with an out-of-bounds id, so tables of
        # any length share a handful of programs
        width = 1 << (len(table) - 1).bit_length()
        ids = np.full((width,), self.num_blocks, np.int32)
        ids[:len(table)] = table
        self._pages = _zero_blocks(self._pages, jnp.asarray(ids))

    # -- per-sequence table management ------------------------------------
    def table(self, seq_id) -> List[int]:
        return self._tables.get(seq_id, [])

    def live_seqs(self) -> List[object]:
        return list(self._tables)

    def ensure_capacity(self, seq_id, num_tokens: int) -> bool:
        """Grow ``seq_id``'s table to cover ``num_tokens`` cache slots;
        False (nothing taken) when the pool cannot supply the growth."""
        table = self._tables.setdefault(seq_id, [])
        need = self.allocator.blocks_for_tokens(num_tokens) - len(table)
        if need <= 0:
            return True
        got = self.allocator.alloc(need)
        if got is None:
            if not table:
                del self._tables[seq_id]
            return False
        table.extend(got)
        return True

    def free_seq(self, seq_id) -> None:
        table = self._tables.pop(seq_id, None)
        if table:
            self.allocator.free(table)

    def slot(self, seq_id, pos: int) -> int:
        """Flat page slot of cache position ``pos`` for ``seq_id``."""
        table = self._tables[seq_id]
        block = pos // self.block_size
        enforce(0 <= block < len(table),
                f"pos {pos} outside {seq_id}'s {len(table)}-block table")
        return table[block] * self.block_size + pos % self.block_size

    def occupancy(self) -> float:
        return self.allocator.occupancy()

    def leak_report(self) -> Dict[str, object]:
        """Eviction-accounting view (ISSUE 15): allocator lifetime
        counters plus the table-coverage cross-check.  A nonzero
        ``leaked_blocks`` means some blocks are marked used but no
        sequence's table covers them — exactly the state a missed
        eviction path (cancel/deadline/quarantine) would leave."""
        report = self.allocator.stats()
        tabled = sum(len(t) for t in self._tables.values())
        report["live_seqs"] = len(self._tables)
        report["tabled_blocks"] = tabled
        report["leaked_blocks"] = int(report["num_used"]) - tabled
        return report

    # -- fixed-shape step inputs ------------------------------------------
    def table_array(self, seq_ids: Sequence[object],
                    max_blocks: int) -> np.ndarray:
        """``(len(seq_ids), max_blocks)`` int32 block-table matrix; rows
        of absent/short tables are 0-padded (masked by seq_lens)."""
        out = np.zeros((len(seq_ids), max_blocks), np.int32)
        for i, sid in enumerate(seq_ids):
            t = self._tables.get(sid, [])
            enforce(len(t) <= max_blocks,
                    f"{sid}: {len(t)} blocks > table width {max_blocks}")
            out[i, :len(t)] = t
        return out

    def slot_array(self, seq_ids: Sequence[object],
                   starts: Sequence[int], chunk: int) -> np.ndarray:
        """``(len(seq_ids), chunk)`` write-slot matrix for tokens at
        positions ``starts[i] .. starts[i]+chunk-1``; positions past the
        sequence's table get the OOB pad sentinel."""
        out = np.full((len(seq_ids), chunk), self.slot_pad, np.int32)
        for i, (sid, start) in enumerate(zip(seq_ids, starts)):
            if start < 0:        # padding row
                continue
            table = self._tables.get(sid, [])
            cap = len(table) * self.block_size
            for j in range(chunk):
                pos = start + j
                if pos < cap:
                    out[i, j] = (table[pos // self.block_size]
                                 * self.block_size
                                 + pos % self.block_size)
        return out

    def layer_caches(self, block_tables: np.ndarray, seq_lens: np.ndarray,
                     slot_mapping: np.ndarray) -> List[PagedLayerCache]:
        """The per-layer views a model's ``serving_step`` takes, over the
        live pages (the engine's step program builds the same views
        inside its trace)."""
        bt = jnp.asarray(block_tables, jnp.int32)
        sl = jnp.asarray(seq_lens, jnp.int32)
        sm = jnp.asarray(slot_mapping, jnp.int32)
        return [PagedLayerCache(layer, bt, sl, sm,
                                block_size=self.block_size)
                for layer in self._pages]

    # -- defrag ------------------------------------------------------------
    def defrag(self) -> bool:
        """Compact the pool (see :meth:`BlockAllocator.defrag`) and
        permute the device page arrays to match.  Returns True when a
        permutation was applied."""
        perm = self.allocator.defrag(self._tables)
        if perm is None:
            return False
        idx = jnp.asarray(perm)
        # a layer at a time, so at most one layer's pages are held twice
        for i, layer in enumerate(self._pages):
            self._pages[i] = tuple(jnp.take(a, idx, axis=0) for a in layer)
        return True
