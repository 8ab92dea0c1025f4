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

A layer is of a **kind** (ISSUE 35): ``full`` (a query may attend every
cached token: the layer's entry in the layout is the tuple of its page
arrays' shapes) or ``window`` (:class:`WindowLayer`: a query attends the
last ``window`` tokens only).  Each kind has a pool of its own: an
allocator, a block table a sequence, and page arrays of its own number of
blocks.  The window kind holds, for each sequence, only the blocks a later
query can still attend: growing a sequence past a block's reach frees it,
a prefill writes a prompt's last blocks only, and the table the step is
handed is a ring of ``window_table_width`` entries (block ``b`` of the
sequence at column ``b % width``).  A layout of one kind is one pool, one
table, one slot matrix: what it was before kinds.  While the engine plans a
unit ahead of a landing (ISSUE 36) the blocks let go behind a window are
HELD, not freed: the unit in flight may fault and be replayed, and its
replay attends them.  They are freed when that unit has landed
(:meth:`PagedKVCache.release_held`) or go back to the front of their tables
when it has not (:meth:`PagedKVCache.restore_held`).

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
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as _tree_util

from ..framework.errors import enforce

__all__ = ["KV_BLOCK_SIZE_ENV", "default_kv_block_size", "BlockAllocator",
           "PagedLayerCache", "PagedKVCache", "WindowLayer", "layout_kinds",
           "window_table_width", "FULL", "WINDOW"]

FULL = "full"
WINDOW = "window"

KV_BLOCK_SIZE_ENV = "PTPU_KV_BLOCK_SIZE"


def default_kv_block_size() -> int:
    return int(os.environ.get(KV_BLOCK_SIZE_ENV, "16"))


class WindowLayer(NamedTuple):
    """A layer's entry in ``kv_cache_layout()`` whose queries attend the
    last ``window`` cached tokens only (the query's own among them):
    ``shapes`` as a full layer's entry gives them."""
    shapes: Tuple[Tuple[int, ...], ...]
    window: int


def layout_kinds(layout) -> Dict[str, Optional[int]]:
    """The kinds of layer a layout holds, in the order they first appear,
    each with its reach (None: everything)."""
    kinds: Dict[str, Optional[int]] = {}
    for layer in layout:
        if isinstance(layer, WindowLayer):
            enforce(layer.window >= 1 and kinds.get(WINDOW, layer.window)
                    == layer.window, "window layers of one reach, >= 1")
            kinds[WINDOW] = int(layer.window)
        else:
            kinds[FULL] = None
    return kinds


def window_table_width(window: int, block_size: int) -> int:
    """Blocks that ``window`` consecutive tokens can lie in: the width of
    a window layer's ring of a table."""
    return (window + block_size - 2) // block_size + 1


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


class _Pool:
    """One kind's share of the cache: its allocator, a table a sequence
    and, for the window kind, where each table starts.  ``tables[seq]``
    are the blocks the sequence holds, in the order of its tokens;
    ``first[seq]`` is the block of the sequence (``position //
    block_size``) that the table's first entry holds: always 0 for the
    full kind."""

    def __init__(self, window: Optional[int], num_blocks: int,
                 block_size: int, layers: List[int]):
        self.window, self.layers = window, layers
        self.block_size = block_size
        self.num_blocks = int(num_blocks)
        self.num_slots = self.slot_pad = self.num_blocks * block_size
        self.allocator = BlockAllocator(self.num_blocks, block_size)
        self.tables: Dict[object, List[int]] = {}
        self.first: Dict[object, int] = {}
        self.freed_behind = 0
        # (sequence, blocks) let go behind a window while a unit that may
        # have to be replayed was in flight, oldest first
        self.held: List[Tuple[object, List[int]]] = []
        self.table_width = (None if window is None
                            else window_table_width(window, block_size))

    def span(self, num_tokens: int) -> Tuple[int, int]:
        """``[lo, hi)``: the blocks of a sequence that hold what a query
        at ``num_tokens - 1`` (and any later one) can attend."""
        n = max(0, int(num_tokens))
        hi = -(-n // self.block_size)
        if self.window is None:
            return 0, hi
        return max(0, n - self.window) // self.block_size, hi

    def settle(self, seq_id, num_tokens: int, hold: bool = False) -> int:
        """Free the blocks no query at or after ``num_tokens - 1`` can
        attend (``hold``: take them off the table and keep them back until
        :meth:`release_held` or :meth:`restore_held`); returns how many
        more the sequence needs to cover ``num_tokens``."""
        lo, hi = self.span(num_tokens)
        table = self.tables.get(seq_id)
        if not table:
            return hi - lo
        first = self.first[seq_id]
        if lo > first:
            drop = min(len(table), lo - first)
            if hold:
                self.held.append((seq_id, table[:drop]))
            else:
                self.allocator.free(table[:drop])
                self.freed_behind += drop
            del table[:drop]
            first = self.first[seq_id] = first + drop
            if not table:
                return hi - lo
        return hi - first - len(table)

    def release_held(self) -> None:
        """The unit that might have needed them has landed."""
        for _, blocks in self.held:
            self.allocator.free(blocks)
            self.freed_behind += len(blocks)
        self.held.clear()

    def restore_held(self) -> None:
        """That unit is to be replayed: the blocks go back to the front of
        their tables (newest first, as they came off), and what a table
        then holds beyond the ring's width, grown for the unit that is
        dropped, is freed.  A sequence that is gone frees its own."""
        for seq_id, blocks in reversed(self.held):
            table = self.tables.get(seq_id)
            if table is None:
                self.allocator.free(blocks)
                continue
            table[:0] = blocks
            self.first[seq_id] -= len(blocks)
            if len(table) > self.table_width:
                self.allocator.free(table[self.table_width:])
                del table[self.table_width:]
        self.held.clear()

    def grow(self, seq_id, num_tokens: int, need: int) -> None:
        table = self.tables.setdefault(seq_id, [])
        if not table:
            self.first[seq_id] = self.span(num_tokens)[0]
        if need > 0:
            table.extend(self.allocator.alloc(need))

    def free_seq(self, seq_id) -> None:
        table = self.tables.pop(seq_id, None)
        self.first.pop(seq_id, None)
        if table:
            self.allocator.free(table)

    def report(self) -> Dict[str, object]:
        report = self.allocator.stats()
        tabled = (sum(len(t) for t in self.tables.values())
                  + sum(len(b) for _, b in self.held))
        report["live_seqs"] = len(self.tables)
        report["tabled_blocks"] = tabled
        report["leaked_blocks"] = int(report["num_used"]) - tabled
        return report

    # -- a step's arrays -----------------------------------------------------
    def table_array(self, seq_ids, max_blocks: int) -> np.ndarray:
        width = max_blocks if self.window is None else self.table_width
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, sid in enumerate(seq_ids):
            t = self.tables.get(sid)
            if not t:
                continue
            enforce(len(t) <= width,
                    f"{sid}: {len(t)} blocks > table width {width}")
            if self.window is None:
                out[i, :len(t)] = t
            else:                  # a ring: block b at column b % width
                at = self.first[sid]
                for b in t:
                    out[i, at % width] = b
                    at += 1
        return out

    def slot_array(self, seq_ids, starts, chunk: int) -> np.ndarray:
        bs = self.block_size
        out = np.full((len(seq_ids), chunk), self.slot_pad, np.int32)
        for i, (sid, start) in enumerate(zip(seq_ids, starts)):
            table = self.tables.get(sid)
            if start < 0 or not table:        # padding row
                continue
            first = self.first[sid]
            if chunk == 1:                    # a decode row: one position
                block = start // bs - first
                if 0 <= block < len(table):
                    out[i, 0] = table[block] * bs + start % bs
                continue
            pos = start + np.arange(chunk)
            held = (pos >= first * bs) & (pos < (first + len(table)) * bs)
            pos = pos[held]
            out[i, held] = (np.asarray(table, np.int64)[pos // bs - first]
                            * bs + pos % bs)
        return out


class PagedKVCache:
    """Whole-model paged KV store: per-layer page arrays and, for each
    kind of layer, the allocator + per-sequence block tables.

    The engine owns one of these; the scheduler talks to ``allocator``
    and the per-sequence helpers; the jitted step consumes :attr:`pages`
    (donated: the handles are dead once it is called) and hands the
    updated page arrays back through :meth:`update_pages`.
    """

    def __init__(self, layout: Sequence, num_blocks: Union[int, Mapping],
                 block_size: Optional[int] = None, dtype=jnp.float32):
        """``layout``: per layer, the per-token shape of each of its page
        arrays, as the model's ``kv_cache_layout()`` declares them (keys
        and values of 2 heads of 4: ``[((2, 4), (2, 4))]``), a window
        layer's inside a :class:`WindowLayer`.  ``num_blocks``: the
        pool's blocks, of a layout of several kinds a mapping from each
        kind to its pool's."""
        block_size = (default_kv_block_size() if block_size is None
                      else int(block_size))
        layout = list(layout)
        self.kinds = layout_kinds(layout)
        self.layer_kinds = [WINDOW if isinstance(layer, WindowLayer)
                            else FULL for layer in layout]
        self.layout = [tuple(tuple(int(d) for d in shape) for shape in (
            layer.shapes if isinstance(layer, WindowLayer) else layer))
            for layer in layout]
        enforce(self.layout and all(self.layout), "empty cache layout")
        self.num_layers = len(self.layout)
        self._arity = [len(layer) for layer in self.layout]
        self.num_arrays = sum(self._arity)
        # values a token keeps in each page array, in the pool's order
        self._token_sizes = [int(np.prod(shape)) for layer in self.layout
                             for shape in layer]
        self.block_size = block_size
        if not isinstance(num_blocks, Mapping):
            enforce(len(self.kinds) == 1,
                    f"a layout of the kinds {list(self.kinds)} needs a "
                    f"number of blocks for each")
            num_blocks = {kind: num_blocks for kind in self.kinds}
        enforce(set(num_blocks) == set(self.kinds),
                f"blocks for {sorted(num_blocks)}, layers of "
                f"{sorted(self.kinds)}")
        self.pools: Dict[str, _Pool] = {
            kind: _Pool(window, num_blocks[kind], block_size,
                        [i for i, k in enumerate(self.layer_kinds)
                         if k == kind])
            for kind, window in self.kinds.items()}
        # the first kind's pool answers for "the" pool where one is asked
        # for: all there is of a layout of one kind
        self._pool_list = list(self.pools.values())
        self._main = self._pool_list[0]
        # the slots behind each page array, in the pool's order
        self._array_slots = [self.pools[kind].num_slots
                             for kind, n in zip(self.layer_kinds, self._arity)
                             for _ in range(n)]
        self.num_blocks = self._main.num_blocks
        self.num_slots = self._main.num_slots
        self.slot_pad = self.num_slots          # OOB sentinel, mode="drop"
        self.dtype = jnp.dtype(dtype)
        self.reset_pages()

    @property
    def allocator(self) -> BlockAllocator:
        return self._main.allocator

    def _pool(self, kind: Optional[str]) -> _Pool:
        return self._main if kind is None else self.pools[kind]

    @property
    def _tables(self) -> Dict[object, List[int]]:
        return self._main.tables

    # -- the page arrays ---------------------------------------------------
    @property
    def pages(self) -> List[Tuple[jnp.ndarray, ...]]:
        """Per layer its page arrays, each ``(its kind's blocks,
        block_size) + per-token shape``: the step program's donated
        argument."""
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
        self._pages = [
            tuple(jnp.zeros((self.pools[kind].num_blocks, self.block_size)
                            + shape, self.dtype) for shape in layer)
            for layer, kind in zip(self.layout, self.layer_kinds)]

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
        """Device bytes behind the live page handles: one pool a kind.
        (From the shapes: ``Array.nbytes`` costs 2 us a call, and this is
        read in every step.)"""
        arrays = itertools.chain.from_iterable(self._pages)
        return self.dtype.itemsize * sum(
            n * m for a, n, m in zip(arrays, self._token_sizes,
                                     self._array_slots)
            if not a.is_deleted())

    def bytes_per_token(self) -> int:
        """What one cached token takes over all layers, padding of the
        declared shapes included."""
        return self.dtype.itemsize * sum(self._token_sizes)

    def block_bytes(self, kind: str) -> int:
        """What one block of ``kind``'s pool takes over its layers."""
        return self.dtype.itemsize * self.block_size * sum(
            int(np.prod(shape)) for i in self.pools[kind].layers
            for shape in self.layout[i])

    def scrub_seq(self, seq_id) -> None:
        """Zero ``seq_id``'s blocks in every layer (one small donated
        program a kind): a quarantined sequence may have left non-finite
        K/V behind, and the decode kernel's ``p * v`` turns a masked ``0 *
        NaN`` into NaN for the block's next owner."""
        for pool in self.pools.values():
            table = pool.tables.get(seq_id)
            if not table:
                continue
            # padded to a power of two with an out-of-bounds id, so tables
            # of any length share a handful of programs
            width = 1 << (len(table) - 1).bit_length()
            ids = np.full((width,), pool.num_blocks, np.int32)
            ids[:len(table)] = table
            zeroed = _zero_blocks([self._pages[i] for i in pool.layers],
                                  jnp.asarray(ids))
            for i, layer in zip(pool.layers, zeroed):
                self._pages[i] = layer

    # -- per-sequence table management ------------------------------------
    def table(self, seq_id, kind: Optional[str] = None) -> List[int]:
        return self._pool(kind).tables.get(seq_id, [])

    def live_seqs(self) -> List[object]:
        return list(self._main.tables)

    def ensure_capacity(self, seq_id, num_tokens: int,
                        hold: bool = False) -> bool:
        """Grow ``seq_id``'s tables to cover ``num_tokens`` cache slots,
        letting go first of the window blocks that no query from there on
        can attend (``hold``: a unit in flight may still be replayed over
        them, so they are kept back, see :meth:`release_held`); False
        (nothing taken) when a pool cannot supply the growth."""
        pools = self._pool_list
        if len(pools) == 1 and self._main.window is None:
            # a decode step's usual case in a cache of one kind: the table
            # covers the token already (256 rows ask every step)
            table = self._main.tables.get(seq_id)
            if table is not None and \
                    num_tokens <= len(table) * self.block_size:
                return True
        need = [pool.settle(seq_id, num_tokens, hold) for pool in pools]
        for pool, n in zip(pools, need):
            if n > 0 and not pool.allocator.can_alloc(n):
                return False
        for pool, n in zip(pools, need):
            if n > 0 or seq_id not in pool.tables:
                pool.grow(seq_id, num_tokens, n)
        return True

    def release_held(self) -> None:
        """Free the window blocks held back while a unit was planned ahead
        of a landing: the unit in flight then has landed."""
        for pool in self._pool_list:
            if pool.held:
                pool.release_held()

    def restore_held(self) -> None:
        """Give them back to their sequences instead: the unit in flight
        then faulted, and its replay attends them."""
        for pool in self._pool_list:
            if pool.held:
                pool.restore_held()

    def holds(self, num_tokens: int) -> bool:
        """Whether the pools, empty, could hold a sequence of
        ``num_tokens``."""
        return all(hi - lo <= pool.num_blocks for pool in self.pools.values()
                   for lo, hi in [pool.span(num_tokens)])

    def free_seq(self, seq_id) -> None:
        for pool in self.pools.values():
            pool.free_seq(seq_id)

    def slot(self, seq_id, pos: int, kind: Optional[str] = None) -> int:
        """Flat page slot of cache position ``pos`` for ``seq_id``."""
        pool = self._pool(kind)
        table = pool.tables[seq_id]
        block = pos // self.block_size - pool.first.get(seq_id, 0)
        enforce(0 <= block < len(table),
                f"pos {pos} outside {seq_id}'s {len(table)}-block table")
        return table[block] * self.block_size + pos % self.block_size

    def occupancy(self) -> float:
        return max(p.allocator.occupancy() for p in self.pools.values())

    def blocks_used(self) -> int:
        return sum(p.allocator.num_used for p in self.pools.values())

    def leak_report(self) -> Dict[str, object]:
        """Eviction-accounting view (ISSUE 15): allocator lifetime
        counters plus the table-coverage cross-check.  A nonzero
        ``leaked_blocks`` means some blocks are marked used but no
        sequence's table covers them — exactly the state a missed
        eviction path (cancel/deadline/quarantine) would leave.  Of
        several kinds the sums (``high_water``: of the pools' own), and
        each pool's own report under ``pools``."""
        reports = {kind: pool.report() for kind, pool in self.pools.items()}
        if len(reports) == 1:
            return next(iter(reports.values()))
        total = {k: sum(int(r[k]) for r in reports.values())
                 for k in next(iter(reports.values()))
                 if k not in ("balanced", "live_seqs")}
        total["balanced"] = all(r["balanced"] for r in reports.values())
        total["live_seqs"] = max(r["live_seqs"] for r in reports.values())
        total["pools"] = reports
        return total

    # -- fixed-shape step inputs ------------------------------------------
    def table_array(self, seq_ids: Sequence[object], max_blocks: int,
                    kind: Optional[str] = None) -> np.ndarray:
        """``(len(seq_ids), max_blocks)`` int32 block-table matrix; rows
        of absent/short tables are 0-padded (masked by seq_lens).  Of the
        window kind ``(len(seq_ids), its table width)``, block ``b`` of a
        sequence at column ``b % width``."""
        return self._pool(kind).table_array(seq_ids, max_blocks)

    def slot_array(self, seq_ids: Sequence[object],
                   starts: Sequence[int], chunk: int,
                   kind: Optional[str] = None) -> np.ndarray:
        """``(len(seq_ids), chunk)`` write-slot matrix for tokens at
        positions ``starts[i] .. starts[i]+chunk-1``; positions outside
        the sequence's table (past it, or of the window kind behind it)
        get the OOB pad sentinel."""
        return self._pool(kind).slot_array(seq_ids, starts, chunk)

    def step_tables(self, seq_ids, max_blocks: int) -> List[np.ndarray]:
        """:meth:`table_array` of each kind, in the kinds' order."""
        return [p.table_array(seq_ids, max_blocks)
                for p in self.pools.values()]

    def step_slots(self, seq_ids, starts, chunk: int) -> List[np.ndarray]:
        """:meth:`slot_array` of each kind, in the kinds' order."""
        return [p.slot_array(seq_ids, starts, chunk)
                for p in self.pools.values()]

    def table_widths(self, max_blocks: int) -> Tuple[int, ...]:
        return tuple(max_blocks if p.window is None else p.table_width
                     for p in self.pools.values())

    def layer_caches(self, block_tables, seq_lens: np.ndarray,
                     slot_mapping) -> List[PagedLayerCache]:
        """The per-layer views a model's ``serving_step`` takes, over the
        live pages (the engine's step program builds the same views
        inside its trace).  ``block_tables`` and ``slot_mapping``: one
        array, or of several kinds one a kind in the kinds' order."""
        if len(self.pools) == 1 and not isinstance(block_tables,
                                                   (list, tuple)):
            block_tables, slot_mapping = [block_tables], [slot_mapping]
        sl = jnp.asarray(seq_lens, jnp.int32)
        by_kind = {kind: (jnp.asarray(bt, jnp.int32),
                          jnp.asarray(sm, jnp.int32))
                   for kind, bt, sm in zip(self.pools, block_tables,
                                           slot_mapping)}
        return [PagedLayerCache(layer, by_kind[kind][0], sl,
                                by_kind[kind][1], block_size=self.block_size)
                for layer, kind in zip(self._pages, self.layer_kinds)]

    # -- defrag ------------------------------------------------------------
    def defrag(self) -> bool:
        """Compact each pool (see :meth:`BlockAllocator.defrag`) and
        permute its layers' page arrays to match.  Returns True when a
        permutation was applied."""
        moved = False
        for pool in self.pools.values():
            perm = pool.allocator.defrag(pool.tables)
            if perm is None:
                continue
            moved = True
            idx = jnp.asarray(perm)
            # a layer at a time, so at most one layer's pages are held twice
            for i in pool.layers:
                self._pages[i] = tuple(jnp.take(a, idx, axis=0)
                                       for a in self._pages[i])
        return moved
