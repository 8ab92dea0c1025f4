"""Serving subsystem (ISSUE 6): paged-KV cache invariants, scheduler
policy under a tight block budget, ragged-vs-dense numerics, the compile
contract, the slow-consumer fault drill, and the legacy facade routing.
The engine's own classes run once a model family: a GPT and a
DeepSeek-V2 (a latent pool, an expert layer, aux outputs a step)."""
import importlib
import re
import time

import numpy as np
import pytest
from serving_families import (VOCAB, dense_continuation,  # noqa: F401
                              dense_forward, family, tiny_model)

import jax.numpy as jnp

from paddle_tpu.inference import (BlockAllocator, Config, PagedKVCache,
                                  ServingEngine, create_predictor)
from paddle_tpu.inference.engine import (pack_step_inputs,
                                         unpack_step_inputs)
from paddle_tpu.inference.paged_attention import (_pages_per_wave,
                                                  paged_attention_pallas,
                                                  paged_attention_reference)
from paddle_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                            SequenceState, prefill_bucket)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability.compilation import CompileTracker
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.serving


def assert_no_block_aliasing(cache: PagedKVCache):
    for kind in cache.pools:
        seen = {}
        for sid in cache.live_seqs():
            for b in cache.table(sid, kind):
                assert b not in seen, \
                    f"{kind} block {b} aliased by {sid} and {seen[b]}"
                seen[b] = sid


# a cache of one kind of layer (every model until ISSUE 35), and one with a
# window layer beside the full one: a pool, an allocator and a table a kind
KINDS = ["one_kind", "two_kinds"]
WINDOW = 6


def kinds_layout(kinds, shapes, layers=1):
    from paddle_tpu.inference.kv_cache import WindowLayer
    return [shapes] * layers + (
        [WindowLayer(shapes, WINDOW)] if kinds == "two_kinds" else [])


def kinds_blocks(kinds, blocks, window_blocks=8):
    return (blocks if kinds == "one_kind"
            else {"full": blocks, "window": window_blocks})


# ---------------------------------------------------------------------------
# KV block allocator
# ---------------------------------------------------------------------------
class TestBlockAllocator:
    def test_alloc_free_reuse(self):
        a = BlockAllocator(4, block_size=8)
        g1 = a.alloc(3)
        assert sorted(g1) == [0, 1, 2] and a.num_free == 1
        assert a.alloc(2) is None          # all-or-nothing
        assert a.num_free == 1             # the failed alloc took nothing
        a.free(g1[:2])
        g2 = a.alloc(3)
        assert g2 is not None and a.num_free == 0
        assert set(g2).isdisjoint({g1[2]})
        assert a.occupancy() == 1.0

    def test_double_free_rejected(self):
        a = BlockAllocator(2, block_size=4)
        g = a.alloc(1)
        a.free(g)
        with pytest.raises(Exception):
            a.free(g)

    def test_blocks_for_tokens(self):
        a = BlockAllocator(8, block_size=4)
        assert [a.blocks_for_tokens(n) for n in (0, 1, 4, 5, 8)] \
            == [0, 1, 1, 2, 2]

    def test_defrag_compacts_and_renumbers(self):
        a = BlockAllocator(8, block_size=4)
        t1 = a.alloc(2)
        t2 = a.alloc(2)
        t3 = a.alloc(2)
        a.free(t1)
        a.free(t3)
        tables = {"s2": list(t2)}
        perm = a.defrag(tables)
        assert perm is not None
        # live blocks now occupy the lowest ids and tables were rewritten
        assert sorted(tables["s2"]) == [0, 1]
        assert a.num_used == 2
        # perm maps new -> old for the page permutation
        assert [perm[n] for n in tables["s2"]] == t2 or \
            sorted(perm[:2].tolist()) == sorted(t2)
        # fresh allocs continue from the compacted prefix
        assert sorted(a.alloc(6)) == [2, 3, 4, 5, 6, 7]


# ---------------------------------------------------------------------------
# PagedKVCache
# ---------------------------------------------------------------------------
class TestPagedKVCache:
    @pytest.fixture(autouse=True, params=KINDS)
    def _kinds(self, request):
        self.kinds = request.param

    def make(self, blocks=6, bs=4, layers=1, window_blocks=8):
        return PagedKVCache(
            kinds_layout(self.kinds, ((2, 4), (2, 4)), layers),
            num_blocks=kinds_blocks(self.kinds, blocks, window_blocks),
            block_size=bs)

    def step_arrays(self, c, sids, starts, chunk, width):
        """A step's tables and slots as the engine hands them on: an
        array each, or of two kinds a list of them."""
        if self.kinds == "one_kind":
            return (c.table_array(sids, width),
                    c.slot_array(sids, starts, chunk))
        return c.step_tables(sids, width), c.step_slots(sids, starts, chunk)

    def test_capacity_growth_and_slots(self):
        c = self.make()
        assert c.ensure_capacity("a", 5)       # 2 blocks
        assert len(c.table("a")) == 2
        assert c.ensure_capacity("a", 8)       # still 2
        assert len(c.table("a")) == 2
        assert c.ensure_capacity("a", 9)       # grows to 3
        t = c.table("a")
        assert c.slot("a", 0) == t[0] * 4
        assert c.slot("a", 6) == t[1] * 4 + 2
        if self.kinds == "two_kinds":
            # of 9 tokens a window of 6 reaches 3..8: blocks 0, 1 and 2
            w = list(c.table("a", "window"))
            assert len(w) == 3
            assert c.slot("a", 6, "window") == w[1] * 4 + 2
            assert c.ensure_capacity("a", 10)  # reaches 4..9: block 0 goes
            assert c.table("a", "window") == w[1:]
            with pytest.raises(Exception, match="outside"):
                c.slot("a", 3, "window")
            assert len(c.table("a")) == 3      # the full kind keeps all
        c.free_seq("a")
        assert c.allocator.num_used == 0 and c.blocks_used() == 0

    def test_no_aliasing_across_live_seqs(self):
        c = self.make(blocks=8)
        for sid, n in (("a", 9), ("b", 5), ("c", 12)):
            assert c.ensure_capacity(sid, n)
        assert_no_block_aliasing(c)
        c.free_seq("b")
        assert c.ensure_capacity("d", 8)
        assert_no_block_aliasing(c)

    def test_oom_takes_nothing(self):
        c = self.make(blocks=2)
        assert c.ensure_capacity("a", 8)       # both blocks
        used = c.blocks_used()
        assert not c.ensure_capacity("b", 5)   # needs 2, has 0
        assert c.table("b") == [] and "b" not in c.live_seqs()
        assert c.allocator.num_used == 2 and c.blocks_used() == used
        if self.kinds == "two_kinds":
            assert c.table("b", "window") == []

    def test_growth_past_the_window_frees_exactly_the_blocks_behind_it(
            self):
        """A sequence grown a token at a time (a decode step each) to 40
        tokens: of the window kind it holds, at every length, exactly the
        blocks that hold positions ``n - window .. n - 1``, never more
        than the ring's width; of the full kind every block."""
        if self.kinds == "one_kind":
            c = self.make(blocks=10)
            for n in range(1, 41):
                assert c.ensure_capacity("a", n)
                assert len(c.table("a")) == -(-n // 4)
            assert c.leak_report()["total_frees"] == 0
            return
        from paddle_tpu.inference.kv_cache import window_table_width
        c = self.make(blocks=10, window_blocks=3)
        width = window_table_width(WINDOW, 4)
        assert width == 3 == c.table_widths(99)[1]
        pool, freed = c.pools["window"], []
        for n in range(1, 41):
            before = list(c.table("a", "window"))
            assert c.ensure_capacity("a", n)
            held = c.table("a", "window")
            lo, hi = max(0, n - WINDOW) // 4, -(-n // 4)
            assert pool.first["a"] == lo and len(held) == hi - lo <= width
            freed += [b for b in before if b not in held]
            assert len(c.table("a")) == hi
            # the step's table is a ring: block b at column b % width
            ring = c.table_array(["a"], 99, "window")
            assert ring.shape == (1, width)
            for j, b in enumerate(range(lo, hi)):
                assert ring[0, b % width] == held[j]
            # slots of the positions the window holds, pad behind them
            slots = c.slot_array(["a"], [0], n, "window")[0]
            assert (slots[:lo * 4] == pool.slot_pad).all()
            assert slots[lo * 4:n].tolist() == [
                held[p // 4 - lo] * 4 + p % 4 for p in range(lo * 4, n)]
        assert len(freed) == pool.freed_behind == 40 // 4 - 2
        assert pool.allocator.num_used == 2 and pool.allocator.high_water \
            <= width

    def test_a_prefill_takes_only_a_prompts_last_window_blocks(self):
        c = self.make(blocks=10, window_blocks=3)
        assert c.ensure_capacity("a", 27)         # a prompt of 27 tokens
        assert len(c.table("a")) == 7
        if self.kinds == "two_kinds":
            # a window of 6 reaches 21..26: blocks 5 and 6
            assert len(c.table("a", "window")) == 2
            assert c.pools["window"].first["a"] == 5
            slots = c.slot_array(["a"], [0], 32, "window")[0]
            assert (slots[:20] == c.pools["window"].slot_pad).all()
            assert (slots[20:28] < c.pools["window"].num_slots).all()
            assert (slots[28:] == c.pools["window"].slot_pad).all()

    def test_free_scrub_and_leak_report_account_for_every_pool(self):
        c = self.make(blocks=6, window_blocks=4)
        c.ensure_capacity("a", 11)
        c.ensure_capacity("b", 3)
        c.update_pages([tuple(jnp.ones_like(a) for a in layer)
                        for layer in c.pages])
        report = c.leak_report()
        per_pool = report.get("pools", {"full": report})
        assert report["leaked_blocks"] == 0 and report["balanced"]
        assert report["num_used"] == c.blocks_used() == sum(
            r["tabled_blocks"] for r in per_pool.values())
        assert set(per_pool) == set(c.pools)
        held = {kind: list(c.table("a", kind)) for kind in c.pools}
        c.scrub_seq("a")
        for layer, kind in zip(c.pages, c.layer_kinds):
            for a in layer:
                a = np.asarray(a)
                assert not a[held[kind]].any()          # a's blocks zeroed
                assert a[c.table("b", kind)].all()      # b's untouched
        c.free_seq("a")
        c.free_seq("b")
        report = c.leak_report()
        assert report["num_used"] == 0 and report["balanced"]
        assert report["total_allocs"] == report["total_frees"] > 0
        assert c.pool_bytes() == sum(
            np.asarray(a).nbytes for layer in c.pages for a in layer)

    def test_defrag_preserves_page_data(self):
        c = self.make(blocks=6, bs=4)
        c.ensure_capacity("a", 8)
        c.ensure_capacity("b", 8)
        # write a recognizable value into b's first slot of every kind,
        # the way a model does: through the layer view's write()
        tables, _ = self.step_arrays(c, ["b"], [0], 1, 2)
        slots = [np.asarray([[c.slot("b", 4, kind)]], np.int32)
                 for kind in c.pools]
        layers = c.layer_caches(
            tables, np.ones((1,), np.int32),
            slots[0] if self.kinds == "one_kind" else slots)
        kv = jnp.full((1, 2, 4), 7.5)
        c.update_pages([layer.write(kv, kv).pages for layer in layers])
        c.free_seq("a")
        assert c.defrag() is True
        # b's tables were renumbered to the compact prefix; its data moved
        for i, kind in enumerate(c.layer_kinds):
            assert sorted(c.table("b", kind)) == [0, 1]
            blk, off = divmod(c.slot("b", 4, kind), c.block_size)
            k_pages = np.asarray(c._pages[i][0])
            assert k_pages.shape == (c.pools[kind].num_blocks, 4, 2, 4)
            assert (k_pages[blk, off] == 7.5).all()
            assert (k_pages != 0).sum() == 2 * 4      # exactly that one row
        # idempotent when already compact
        assert c.defrag() is False

    def test_write_lands_token_major_and_drops_padding(self):
        c = self.make(layers=2)
        c.ensure_capacity("a", 6)
        tables, slots = self.step_arrays(c, ["a"], [3], 4, 2)
        every = [slots] if self.kinds == "one_kind" else slots
        slots = every[0]                             # positions 3..6
        assert slots[0, 3] != c.slot_pad
        for a, pool in zip(every, c.pools.values()):
            a[0, 3] = pool.slot_pad                  # a padded position
        layers = c.layer_caches(tables, np.asarray([6], np.int32),
                                slots if self.kinds == "one_kind"
                                else every)
        assert len(layers) == 2 + (self.kinds == "two_kinds")
        k = jnp.arange(4 * 2 * 4, dtype=jnp.float32).reshape(4, 2, 4) + 1
        new = layers[1].write(k, -k)
        assert new.k_pages.shape == (6, 4, 2, 4)
        kp, vp = np.asarray(new.k_pages), np.asarray(new.v_pages)
        for j in range(3):
            blk, off = divmod(int(slots[0, j]), 4)
            np.testing.assert_array_equal(kp[blk, off], np.asarray(k[j]))
            np.testing.assert_array_equal(vp[blk, off], -np.asarray(k[j]))
        # the padded token went nowhere
        assert (kp != 0).sum() == 3 * 2 * 4
        # layer 0's view shares the step's tables but has its own pages
        assert layers[0].block_tables is layers[1].block_tables
        assert not np.asarray(layers[0].k_pages).any()

    def test_scrub_seq_zeroes_only_that_table(self):
        c = self.make(blocks=8, bs=4)
        c.ensure_capacity("a", 12)                   # 3 blocks: pads to 4
        c.ensure_capacity("b", 4)
        ones = jnp.ones((8, 4, 2, 4))
        c.update_pages([(ones + 0, ones * jnp.nan)     # a buffer each
                        for _ in range(c.num_layers)])
        c.scrub_seq("a")
        c.scrub_seq("nobody")                        # no table: no-op
        for layer, kind in zip(c.pages, c.layer_kinds):
            kp, vp = (np.asarray(a) for a in layer)
            for b in range(8):
                if b in c.table("a", kind):
                    assert not kp[b].any() and not vp[b].any()
                else:
                    assert (kp[b] == 1).all() and np.isnan(vp[b]).all()

    def test_pool_handles_lost_and_reset(self):
        c = self.make()
        assert not c.pages_lost()
        window = (2 * 8 * 4 * 2 * 4 * 4 if self.kinds == "two_kinds"
                  else 0)                            # its own 8 blocks
        assert c.pool_bytes() == 2 * 6 * 4 * 2 * 4 * 4 + window
        c.pages[0][1].delete()
        assert c.pages_lost()
        # live handles only
        assert c.pool_bytes() == 6 * 4 * 2 * 4 * 4 + window
        c.reset_pages()
        assert not c.pages_lost()
        assert not np.asarray(c.pages[0][0]).any()
        c.drop_pages()
        assert c.pages_lost() and c.pool_bytes() == 0


# ---------------------------------------------------------------------------
# Ragged paged attention numerics
# ---------------------------------------------------------------------------
def _in_whole_tiles(pages):
    """Pages as the pool keeps them on the chip (``page_token_shape``):
    the token slab in whole bf16 tiles, zeros in the added heads and
    dims."""
    h, d = pages.shape[2:]
    return jnp.asarray(np.pad(pages, [(0, 0), (0, 0), (0, -h % 16),
                                      (0, -d % 128)]), jnp.bfloat16)


class TestPagedAttention:
    def test_pallas_matches_reference_incl_empty_rows(self):
        rng = np.random.RandomState(0)
        B, H, D, bs, nb, T = 4, 2, 8, 4, 12, 5
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
        kp = jnp.asarray(rng.randn(nb, bs, H, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(nb, bs, H, D).astype(np.float32))
        tbl = jnp.asarray(rng.randint(0, nb, (B, T)), jnp.int32)
        lens = jnp.asarray([7, 0, 20, 1], jnp.int32)
        ref = paged_attention_reference(q, kp, vp, tbl, lens, bs)
        pal = paged_attention_pallas(q, kp, vp, tbl, lens, bs,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                                   atol=1e-5)
        assert float(jnp.max(jnp.abs(ref[1]))) == 0.0   # len-0 row

    @pytest.mark.parametrize("h,d,dtype,tol", [
        (12, 64, jnp.float32, 1e-5), (16, 128, jnp.float32, 1e-5),
        (12, 64, jnp.bfloat16, 2.0 ** -6), (16, 128, jnp.bfloat16, 2.0 ** -6)])
    def test_pallas_matches_reference_at_the_hardware_tests_lengths(
            self, h, d, dtype, tol):
        # tests/test_tpu_hw.py's case in interpret mode: an empty row, one
        # token, a non-multiple of the block, an exact multiple, a full
        # table; GPT-125M and GPT-1.3B head shapes
        bs, nb, T = 16, 24, 12
        lens = jnp.asarray([0, 1, 37, 64, T * bs, 100], jnp.int32)
        B = lens.shape[0]
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, h, d), dtype)
        kp = jnp.asarray(rng.randn(nb, bs, h, d), dtype)
        vp = jnp.asarray(rng.randn(nb, bs, h, d), dtype)
        tbl = jnp.asarray(rng.randint(0, nb, (B, T)), jnp.int32)
        ref = paged_attention_reference(q, kp, vp, tbl, lens, bs).astype(
            jnp.float32)
        out = paged_attention_pallas(q, kp, vp, tbl, lens, bs,
                                     interpret=True).astype(jnp.float32)
        assert bool(jnp.isfinite(out).all())
        assert float(jnp.max(jnp.abs(out - ref))) <= tol
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0      # the empty row

    @pytest.mark.parametrize("h,d,bs,poison", [
        (h, d, bs, False) for h, d in ((12, 64), (16, 128))
        for bs in (4, 8, 16, 32)] + [(12, 64, 4, True), (16, 128, 16, True)])
    def test_pallas_walks_live_pages_only(self, h, d, bs, poison):
        """ISSUE 29: a row's loop ends at its own length.  Lengths 0, 1,
        a page, a wave, a wave and a token, something ragged and the
        whole table, empty rows between live ones, a table whose width
        is no multiple of the wave.  Poisoned: every page that no live
        table entry names is NaN and the dead part of each table holds
        an id outside the pool, so an entry past a row's length is
        neither read nor dereferenced."""
        # the pool keeps both head shapes as 16 x 128 bf16 tiles, as on
        # the chip: 128 tokens a wave
        wave = _pages_per_wave(bs, 16, 128, jnp.bfloat16, 10 ** 6)
        assert wave * bs == 128
        T = 2 * wave + 3
        lens = np.asarray([0, 1, bs, 0, wave * bs, wave * bs + 1, 0,
                           2 * wave * bs - bs // 2, T * bs, 0], np.int32)
        B = lens.shape[0]
        used = -(-lens // bs)
        nb = int(used.sum()) + 5
        rng = np.random.RandomState(bs + h)
        q = jnp.asarray(rng.randn(B, h, d), jnp.bfloat16)
        kp = rng.randn(nb, bs, h, d).astype(np.float32)
        vp = rng.randn(nb, bs, h, d).astype(np.float32)
        tbl = np.zeros((B, T), np.int32)         # what the engine pads with
        ids = rng.permutation(nb)[:used.sum()]   # every live entry its own
        for b in range(B):
            tbl[b, :used[b]], ids = ids[:used[b]], ids[used[b]:]
        ref = paged_attention_reference(
            q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
            jnp.asarray(tbl), jnp.asarray(lens), bs).astype(jnp.float32)
        if poison:
            named = np.zeros(nb, bool)
            for b in range(B):
                named[tbl[b, :used[b]]] = True
                tbl[b, used[b]:] = nb + 1000
            kp[~named] = np.nan
            vp[~named] = np.nan
        out = paged_attention_pallas(
            q, _in_whole_tiles(kp), _in_whole_tiles(vp),
            jnp.asarray(tbl), jnp.asarray(lens), bs,
            interpret=True).astype(jnp.float32)
        assert out.shape == q.shape
        assert bool(jnp.isfinite(out).all())
        assert float(jnp.max(jnp.abs(out - ref))) <= 2.0 ** -6
        assert not np.asarray(out)[lens == 0].any()     # the empty rows

    @pytest.mark.parametrize("h,d,bs", [(16, 128, 16), (12, 64, 4)])
    def test_pallas_keeps_a_rows_nan_to_itself(self, h, d, bs):
        """A row whose own keys and values went non-finite this step (they
        are written to its pages before attention) returns NaN and no
        other row does: a shorter row's partial wave leaves the rest of
        the VMEM buffer as the long row's waves filled it, and the tail
        of a row's last page is its last owner's.  Both are masked, keys
        by the score's select and values before ``p @ v``."""
        wave = _pages_per_wave(bs, 16, 128, jnp.bfloat16, 10 ** 6)
        T = 3 * wave
        # the poisoned row fills both halves of the double buffer; after
        # it come one token, a page and a token, nothing, a wave and a token
        lens = np.asarray([5, 3 * wave * bs, 1, bs + 1, 0, wave * bs + 1],
                          np.int32)
        sick = 1
        B = lens.shape[0]
        used = -(-lens // bs)
        nb = int(used.sum())
        rng = np.random.RandomState(h)
        q = jnp.asarray(rng.randn(B, h, d), jnp.bfloat16)
        kp = rng.randn(nb, bs, h, d).astype(np.float32)
        vp = rng.randn(nb, bs, h, d).astype(np.float32)
        tbl = np.zeros((B, T), np.int32)
        ids = rng.permutation(nb)
        for b in range(B):
            tbl[b, :used[b]], ids = ids[:used[b]], ids[used[b]:]
        args = (jnp.asarray(tbl), jnp.asarray(lens), bs)
        ref = paged_attention_reference(
            q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
            *args).astype(jnp.float32)
        kp[tbl[sick, :used[sick]]] = np.nan
        vp[tbl[sick, :used[sick]]] = np.nan
        for b in range(B):                   # what a page's last owner left
            if b != sick and lens[b] % bs:
                kp[tbl[b, used[b] - 1], lens[b] % bs:] = np.nan
                vp[tbl[b, used[b] - 1], lens[b] % bs:] = np.nan
        out = np.asarray(paged_attention_pallas(
            q, _in_whole_tiles(kp), _in_whole_tiles(vp),
            *args, interpret=True).astype(jnp.float32))
        well = np.arange(B) != sick
        assert np.isnan(out[sick]).all()
        assert np.isfinite(out[well]).all()
        assert np.abs(out[well] - np.asarray(ref)[well]).max() <= 2.0 ** -6

    @pytest.mark.parametrize("q_dtype,page_dtype,tol", [
        (jnp.float32, jnp.bfloat16, 1e-4), (jnp.bfloat16, jnp.float32,
                                            2.0 ** -6)])
    def test_pallas_takes_a_query_of_another_type_than_the_pages(
            self, q_dtype, page_dtype, tol):
        # the products are those of the stored values in f32, as the
        # reference's, whatever the two types are
        rng = np.random.RandomState(3)
        B, H, D, bs, nb, T = 3, 16, 128, 16, 12, 4
        q = jnp.asarray(rng.randn(B, H, D), q_dtype)
        kp = jnp.asarray(rng.randn(nb, bs, H, D), page_dtype)
        vp = jnp.asarray(rng.randn(nb, bs, H, D), page_dtype)
        tbl = jnp.asarray(rng.randint(0, nb, (B, T)), jnp.int32)
        lens = jnp.asarray([T * bs, 0, 19], jnp.int32)
        ref = paged_attention_reference(q, kp, vp, tbl, lens, bs)
        out = paged_attention_pallas(q, kp, vp, tbl, lens, bs, interpret=True)
        assert out.dtype == ref.dtype == q.dtype
        assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                     - ref.astype(jnp.float32)))) <= tol

    @pytest.mark.parametrize("route,compiled,dtype,want", [
        ("pallas", True, "bfloat16", (16, 128)),
        ("pallas", True, "float32", (16, 128)),
        ("pallas", False, "bfloat16", (12, 64)),    # interpret mode
        ("reference", True, "bfloat16", (12, 64)),
        ("", False, "bfloat16", (12, 64))])         # a CPU's own choice
    def test_pages_are_whole_tiles_where_the_kernel_runs_compiled(
            self, monkeypatch, route, compiled, dtype, want):
        pa = importlib.import_module("paddle_tpu.inference.paged_attention")
        monkeypatch.setenv(pa.PAGED_KERNEL_ENV, route)
        monkeypatch.setattr(pa, "_interpret", lambda: not compiled)
        assert pa.page_token_shape(12, 64, dtype) == want
        assert pa.page_token_shape(16, 128, dtype) == (16, 128)
        cfg = GPTConfig(vocab_size=32, hidden_size=12 * 64, num_layers=2,
                        num_heads=12, max_position_embeddings=32, dtype=dtype)
        assert (GPTForCausalLM.kv_cache_layout(type("M", (), {"config": cfg}))
                == [(want, want)] * 2)

    def test_compiled_kernel_refuses_pages_that_are_not_whole_tiles(self):
        q = jnp.zeros((2, 12, 64), jnp.bfloat16)
        pages = jnp.zeros((3, 4, 12, 64), jnp.bfloat16)
        with pytest.raises(Exception, match="whole tiles"):
            paged_attention_pallas(q, pages, pages,
                                   jnp.zeros((2, 2), jnp.int32),
                                   jnp.ones((2,), jnp.int32), 4,
                                   interpret=False)

    def test_engine_on_a_pool_of_whole_tiles_says_the_same(self, monkeypatch):
        """What the chip's engine does at a head shape that is not whole
        tiles: the pool is wider than the model's heads, writes fill the
        rest with zeros, attention widens ``q`` and cuts the output."""
        pa = importlib.import_module("paddle_tpu.inference.paged_attention")
        prompts = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10]]
        model = tiny_model()

        def tokens():
            eng = ServingEngine(model, max_seqs=4, kv_block_size=4,
                                registry=MetricsRegistry())
            return eng, eng.generate(prompts, max_new_tokens=6)
        _, plain = tokens()
        monkeypatch.setattr(pa, "page_token_shape", pa._whole_tiles)
        eng, tiled = tokens()
        assert eng.cache.pages[0][0].shape[2:] == (8, 128)
        assert tiled == plain
        assert not np.asarray(eng.cache.pages[0][0])[:, :, 2:].any()
        assert not np.asarray(eng.cache.pages[0][1])[:, :, :, 16:].any()

    def test_head_major_pages_are_refused(self):
        q = jnp.zeros((1, 2, 8))
        old = jnp.zeros((3, 2, 4, 8))          # (blocks, heads, bs, dim)
        with pytest.raises(Exception, match="pages"):
            paged_attention_reference(q, old, old,
                                      jnp.zeros((1, 2), jnp.int32),
                                      jnp.ones((1,), jnp.int32), 4)

    def test_reference_matches_dense_gather(self):
        rng = np.random.RandomState(1)
        H, D, bs, nb = 3, 16, 4, 8
        q = jnp.asarray(rng.randn(1, H, D).astype(np.float32))
        kp = jnp.asarray(rng.randn(nb, bs, H, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(nb, bs, H, D).astype(np.float32))
        tbl = jnp.asarray([[5, 2, 7, 0]], jnp.int32)
        ln = 11
        out = paged_attention_reference(q, kp, vp, tbl,
                                        jnp.asarray([ln], jnp.int32), bs)
        # (T, bs, H, D) blocks in table order -> (T*bs, H, D) token rows
        gather = lambda p: np.asarray(p)[np.asarray(tbl[0])].reshape(  # noqa: E731
            -1, H, D)[:ln]
        k, v = gather(kp), gather(vp)
        s = np.einsum("hd,lhd->hl", np.asarray(q[0]), k) * D ** -0.5
        p = np.exp(s - s.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        o = np.einsum("hl,lhd->hd", p, v)
        np.testing.assert_allclose(np.asarray(out[0]), o, atol=1e-5)


# ---------------------------------------------------------------------------
# Scheduler policy (pure host logic against a real cache)
# ---------------------------------------------------------------------------
class TestScheduler:
    @pytest.fixture(autouse=True, params=KINDS)
    def _kinds(self, request):
        self.kinds = request.param

    def make(self, blocks=4, bs=4, max_seqs=3, max_len=16,
             window_blocks=12):
        cache = PagedKVCache(
            kinds_layout(self.kinds, ((1, 4), (1, 4))),
            num_blocks=kinds_blocks(self.kinds, blocks, window_blocks),
            block_size=bs)
        return cache, ContinuousBatchingScheduler(cache, max_seqs, max_len)

    @staticmethod
    def seq(rid, prompt_len=4, max_new=4):
        return SequenceState(request_id=rid,
                             prompt=list(range(1, prompt_len + 1)),
                             max_new_tokens=max_new)

    def test_admission_is_block_budgeted(self):
        cache, sch = self.make(blocks=2, bs=4, max_len=8)
        a = self.seq("a", prompt_len=5, max_new=3)   # needs both blocks
        b = self.seq("b", prompt_len=4, max_new=4)
        sch.submit(a)
        sch.submit(b)
        plan = sch.schedule()
        assert plan.kind == "prefill" and plan.seqs[0].request_id == "a"
        sch.mark_prefilled(a)
        a.output.append(9)
        a.pending = 9
        # "b" cannot be admitted while "a" holds the pool
        plan2 = sch.schedule()
        assert plan2.kind == "decode"
        assert [s.request_id for s in plan2.seqs] == ["a"]
        # finishing "a" frees the pool; "b" admits next step
        sch.complete(a, "eos")
        plan3 = sch.schedule()
        assert plan3.kind == "prefill" and plan3.seqs[0].request_id == "b"

    def test_preempt_newest_on_oom_and_requeue_front(self):
        cache, sch = self.make(blocks=3, bs=2, max_seqs=3, max_len=6)
        a, b = self.seq("a", 3, 3), self.seq("b", 2, 4)
        for s in (a, b):
            sch.submit(s)
        p = sch.schedule()                 # prefill a: 2 blocks, 1 free
        assert p.kind == "prefill" and p.seqs[0].request_id == "a"
        sch.mark_prefilled(a)
        a.output.append(5)
        a.pending = 5
        p = sch.schedule()                 # prefill b: 1 block, 0 free
        assert p.kind == "prefill" and p.seqs[0].request_id == "b"
        sch.mark_prefilled(b)
        b.output.append(6)
        b.pending = 6
        # decode: a grows into its 2nd block's spare slot; b needs a 2nd
        # block for position 2 and the pool is dry -> the NEWEST running
        # sequence (b itself) is preempted, a (the oldest) survives
        p = sch.schedule()
        assert p.kind == "decode"
        assert [s.request_id for s in p.seqs] == ["a"]
        assert [s.request_id for s in p.preempted] == ["b"]
        assert b.state == "preempted" and b.computed_len == 0
        # preempted work requeues at the FRONT, ahead of new arrivals
        c = self.seq("c", 2, 2)
        sch.submit(c)
        assert sch.waiting[0].request_id == "b"
        # b's blocks all returned; its recompute context keeps the
        # already-streamed token out (pending's KV is written on replay)
        assert b.context() == b.prompt
        assert_no_block_aliasing(cache)
        # a finishing frees space; b re-admits before c
        sch.complete(a, "eos")
        p = sch.schedule()
        assert p.kind == "prefill" and p.seqs[0].request_id == "b"

    @pytest.mark.parametrize("short", ["full", "window"])
    def test_admission_refuses_when_either_pool_is_short(self, short):
        """Two prompts of 8 tokens in blocks of 4: each needs 2 blocks of
        the full kind and (a window of 6 reaches 2..7) 2 of the window
        kind.  Whichever pool holds 3, the second is not admitted, takes
        nothing from the other pool, and gets in when the first is
        done."""
        if self.kinds == "one_kind" and short == "window":
            pytest.skip("a cache of one kind has no window pool")
        cache, sch = self.make(blocks=3 if short == "full" else 8, bs=4,
                               window_blocks=3 if short == "window" else 8)
        a, b = self.seq("a", 8, 2), self.seq("b", 8, 2)
        sch.submit(a)
        sch.submit(b)
        assert sch.schedule().seqs == [a]
        sch.mark_prefilled(a)
        a.output.append(9)
        a.pending = 9
        used = cache.blocks_used()
        plan = sch.schedule()                  # b does not fit: a decodes
        assert plan.kind == "decode" and plan.seqs == [a]
        assert b.state == "waiting" and "b" not in cache.live_seqs()
        assert all(not p.tables.get("b") for p in cache.pools.values())
        # a's next block, of each kind
        assert cache.blocks_used() == used + len(cache.pools)
        sch.complete(a, "eos")
        assert cache.blocks_used() == 0
        assert sch.schedule().seqs == [b]
        assert all(len(p.tables["b"]) == 2 for p in cache.pools.values())

    def test_preemption_returns_every_kind_of_block(self):
        cache, sch = self.make(blocks=4, bs=4, window_blocks=8, max_len=16)
        a, b = self.seq("a", 8, 6), self.seq("b", 7, 6)
        for q in (a, b):
            sch.submit(q)
            assert sch.schedule().seqs == [q]
            sch.mark_prefilled(q)
            q.output.append(1)
            q.pending = 1
        assert cache.allocator.num_free == 0
        plan = sch.schedule()       # a needs a 3rd full block: b gives way
        assert plan.kind == "decode" and plan.seqs == [a]
        assert plan.preempted == [b] and b.computed_len == 0
        for pool in cache.pools.values():
            assert "b" not in pool.tables and "b" not in pool.first
            assert pool.allocator.num_used == len(pool.tables["a"])
        report = cache.leak_report()
        assert report["leaked_blocks"] == 0 and report["balanced"]
        assert sch.waiting[0] is b
        assert_no_block_aliasing(cache)

    def test_prefill_bucket_shapes(self):
        assert prefill_bucket(1, 64) == 8
        assert prefill_bucket(8, 64) == 8
        assert prefill_bucket(9, 64) == 16
        assert prefill_bucket(33, 64) == 64
        assert prefill_bucket(60, 64) == 64

    def test_submit_rejects_impossible_requests(self):
        cache, sch = self.make(blocks=2, bs=2, max_len=16)
        with pytest.raises(Exception):
            sch.submit(self.seq("x", prompt_len=10, max_new=10))  # > max_len
        with pytest.raises(Exception):
            # fits max_len but can never fit the whole pool
            sch.submit(self.seq("y", prompt_len=6, max_new=2))


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestServingEngine:
    def test_ragged_decode_matches_dense_logits(self):
        model = tiny_model()
        prompts = [[1, 2, 3, 4, 5], [7, 8], [9, 10, 11, 12, 13, 14, 15]]
        max_new = 5
        dense = [dense_continuation(model, p, max_new) for p in prompts]
        eng = ServingEngine(model, max_seqs=4, kv_block_size=4,
                            capture_logits=True, registry=MetricsRegistry())
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        eng.run(max_steps=200)
        for rid, p, want in zip(rids, prompts, dense):
            r = eng.collect(rid)
            assert r["tokens"] == want, (p, r["tokens"], want)
            # logits through the paged path == dense no-cache forward
            full = p + r["tokens"]
            ref = dense_forward(model)(full)
            for i, row in enumerate(r["logits"]):
                np.testing.assert_allclose(
                    row, ref[len(p) - 1 + i], atol=1e-4)

    def test_tight_pool_preempts_but_stays_exact(self):
        model = tiny_model()
        prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9], [10, 11, 12, 13, 14]]
        max_new = 6
        dense = [dense_continuation(model, p, max_new) for p in prompts]
        reg = MetricsRegistry()
        # pool far too small for 4 concurrent sequences
        eng = ServingEngine(model, max_seqs=4, kv_block_size=4,
                            num_kv_blocks=5, registry=reg)
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        while eng.has_work():
            eng.step()
            assert_no_block_aliasing(eng.cache)
        assert eng.sched.preemptions > 0
        for rid, want in zip(rids, dense):
            assert eng.collect(rid)["tokens"] == want
        # every block returned to the pool
        assert eng.cache.allocator.num_used == 0
        assert reg.counter("serve.preemptions").value > 0

    def test_one_compile_per_bucket_no_storms(self):
        model = tiny_model()
        tracker = CompileTracker(registry=MetricsRegistry())
        import paddle_tpu.observability.compilation as comp
        eng = ServingEngine(model, max_seqs=3, kv_block_size=4,
                            registry=MetricsRegistry())
        # route this engine's track_jit through a private tracker
        orig = comp.get_tracker
        comp.get_tracker = lambda: tracker
        try:
            prompts = [[1, 2], [3, 4, 5, 6, 7, 8, 9], [1, 2, 3],
                       [4, 5, 6, 7, 8, 9, 10, 11, 12]]
            eng.generate(prompts, max_new_tokens=4)
        finally:
            comp.get_tracker = orig
        names = [f for f in tracker.functions() if f.startswith("serve")]
        assert "serve_decode" in names
        assert "serve_prefill_b8" in names
        assert "serve_prefill_b16" in names
        for fn in names:
            st = tracker.stats(fn)
            assert st["traces"] == 1, (fn, st)      # one compile per shape
            assert st["retraces"] == 0 and st["storms"] == 0, (fn, st)
            assert st["walks"] == 1, (fn, st)       # ... and one walk
        assert tracker.stats("serve_decode")["calls"] > 1

    def test_eos_stops_early_and_frees(self):
        model = tiny_model()
        eng = ServingEngine(model, max_seqs=2, kv_block_size=4,
                            registry=MetricsRegistry())
        # pick the model's own first greedy token as "eos" so it fires
        probe = dense_continuation(model, [1, 2, 3], 1)[0]
        rid = eng.submit([1, 2, 3], max_new_tokens=8, eos_token_id=probe)
        out = eng.collect(rid, max_steps=50)
        assert out["finish_reason"] == "eos"
        assert out["tokens"][-1] == probe and len(out["tokens"]) < 8
        assert eng.cache.allocator.num_used == 0

    @pytest.mark.faults
    def test_slow_consumer_does_not_stall_the_batch(self):
        model = tiny_model()
        eng = ServingEngine(model, max_seqs=4, kv_block_size=4,
                            registry=MetricsRegistry())
        # warm the compiles so the timed window measures scheduling only
        eng.generate([[1, 2]], max_new_tokens=2)
        delay, max_new = 0.15, 6
        got = {"slow": [], "fast": []}
        slow_cb = faults.slow_call(
            lambda rid, tok, fin: got["slow"].append(tok), delay)
        fast_cb = lambda rid, tok, fin: got["fast"].append(tok)  # noqa: E731
        t0 = time.monotonic()
        eng.submit([1, 2, 3], max_new_tokens=max_new, on_token=slow_cb)
        r_fast = eng.submit([4, 5, 6], max_new_tokens=max_new,
                            on_token=fast_cb)
        eng.run(max_steps=100)
        elapsed = time.monotonic() - t0
        # the batch finished without serializing behind the slow consumer:
        # its callbacks alone would take max_new * delay seconds
        assert elapsed < max_new * delay * 0.8, elapsed
        assert len(eng.collect(r_fast)["tokens"]) == max_new
        assert eng.drain_callbacks(timeout=max_new * delay * 3 + 5)
        assert len(got["slow"]) == max_new
        assert len(got["fast"]) == max_new

    def test_status_pages_and_load_shed(self):
        model = tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, max_seqs=2, kv_block_size=4,
                            shed_queue_depth=1, registry=reg)
        from paddle_tpu.observability.monitor import StatusServer
        srv = StatusServer(registry=reg, engine=eng)
        for _ in range(2):
            eng.submit([1, 2, 3], max_new_tokens=3)
        for _ in range(3):
            eng.step()
        sz = srv.statusz()
        serving = sz["serving"]
        assert serving["ttft_ms"]["count"] >= 1
        assert serving["ttft_ms"]["p50"] > 0
        assert serving["kv_occupancy"] > 0
        code, _state = srv.healthz()
        assert code == 200
        # flood past the shed threshold -> 503
        for _ in range(4):
            eng.submit([1, 2], max_new_tokens=2)
        code, state = srv.healthz()
        assert code == 503 and state.startswith("load-shed")
        eng.run(max_steps=300)
        code, _ = srv.healthz()
        assert code == 200


# ---------------------------------------------------------------------------
# The step program updates the pool in place (ISSUE 27)
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestPoolInPlace:
    def engine(self, **kw):
        kw.setdefault("registry", MetricsRegistry())
        return ServingEngine(tiny_model(), max_seqs=4, kv_block_size=4,
                             **kw)

    @pytest.mark.parametrize("rows,chunk", [(4, 1), (1, 8)],
                             ids=["decode", "prefill_b8"])
    def test_every_page_array_aliases_its_input(self, family, rows,
                                                chunk):
        import jax
        eng = self.engine()
        pool = eng.cache.pool_bytes()
        packed = pack_step_inputs(
            np.zeros((rows, chunk)), np.zeros((rows,)), 0,
            np.zeros((rows, eng.sched.max_blocks_per_seq)),
            np.ones((rows,)), np.zeros((rows, chunk)))
        compiled = eng._build_step_fn().lower(
            eng._params, packed, eng.cache.pages, jax.random.PRNGKey(0),
            eng._no_prev, rows=rows, chunk=chunk).compile()
        header = compiled.as_text().split("input_output_alias={", 1)[1]
        header = header.split("entry_computation_layout", 1)[0]
        aliased = [int(p) for p in re.findall(r"\((\d+), \{\}", header)]
        flat, _ = jax.tree_util.tree_flatten(eng._params)
        # params, the packed inputs; the key and the previous program's
        # tokens come after the pages
        first = len(flat) + 1
        n = sum(len(layer) for layer in eng.cache.pages)
        assert n == {"gpt": 2, "deepseek_v2": 1}[family] * 2    # layers
        assert sorted(aliased) == list(range(first, first + n))
        assert compiled.memory_analysis().alias_size_in_bytes == pool

    def test_a_step_consumes_the_old_handles_and_holds_one_pool(self):
        eng = self.engine()
        pool = eng.cache.pool_bytes()
        eng.submit([1, 2, 3], max_new_tokens=4)
        # every call launches a unit (the first two: a prefill, then
        # decodes), and whatever is launched consumes the handles
        for _ in range(3):
            old = [a for kv in eng.cache.pages for a in kv]
            eng.step()
            assert all(a.is_deleted() for a in old)
            assert not eng.cache.pages_lost()
            snap = eng._reg().snapshot()
            assert snap["serve.kv_pool_bytes"]["value"] == pool
        assert eng.stats()["resilience"]["pool_rebuilds"] == 0

    def test_statusz_shows_the_pool_and_its_rebuilds(self):
        import json
        from urllib.request import urlopen
        eng = self.engine()
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run(max_steps=20)
        srv = eng.start_status_server(port=0, host="127.0.0.1")
        try:
            with urlopen(f"http://127.0.0.1:{srv.port}/statusz",
                         timeout=10) as r:
                serving = json.loads(r.read())["serving"]
        finally:
            eng.stop()
        assert serving["kv_pool_bytes"] == eng.cache.pool_bytes()
        assert serving["resilience"]["pool_rebuilds"] == 0


# ---------------------------------------------------------------------------
# Spans inside engine.step (ISSUE 26)
# ---------------------------------------------------------------------------
PHASES = {"reap", "schedule", "tables", "h2d", "dispatch", "device_wait",
          "logits_copy", "guard", "accept", "gauges"}


@pytest.mark.usefixtures("family")
class TestStepSpans:
    @pytest.fixture()
    def warm(self):
        """A warm two-row engine (tables 8 wide) whose fault seam
        sleeps, so that a step is long beside what its spans cost."""
        from paddle_tpu.observability import tracing
        reg = MetricsRegistry()
        eng = ServingEngine(
            tiny_model(), max_seqs=2, kv_block_size=4, registry=reg,
            step_fault=lambda *a: time.sleep(0.05))
        eng.generate([[1, 2, 3]], max_new_tokens=3)      # compiles both
        tracing.reset_tracing()
        return eng, reg, tracing

    @staticmethod
    def step_spans(tracing, step):
        spans = [s for s in tracing.spans_between(0.0, float("inf"))
                 if s[3].get("step") == step]
        root, = [s for s in spans if s[0] == "engine.step"]
        kids = [s for s in spans if s[0].startswith("engine.step/")]
        return root, kids

    @pytest.mark.parametrize("kind", ["prefill", "decode"])
    def test_a_step_is_its_phases_and_nothing_else(self, warm, kind):
        eng, reg, tracing = warm
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.step()                                       # the prefill
        if kind == "decode":
            eng.step()
        step = eng.steps - 1
        root, kids = self.step_spans(tracing, step)
        assert {k[0].split("/", 1)[1] for k in kids} == PHASES
        attrs = root[3]
        # the root's attributes are the LANDED unit's, and the kind of
        # the unit launched ahead of that landing
        assert attrs["kind"] == kind and attrs["rows"] == 1
        assert attrs["bucket"] == (8 if kind == "prefill" else 0)
        assert attrs["ahead_kind"] == "decode"
        # children lie inside the root, in the order the step runs them:
        # the launch of the next unit, then the landing of the one in
        # flight; the first call of a busy stretch launches both
        assert all(root[1] <= k[1] and k[2] <= root[2] for k in kids)
        order = [k[0].split("/", 1)[1]
                 for k in sorted(kids, key=lambda k: k[1])]
        launch = ["schedule", "tables", "h2d", "dispatch"]
        assert order == (["reap"] + launch * (2 if kind == "prefill" else 1)
                         + ["device_wait", "logits_copy", "guard", "accept",
                            "accept", "gauges"])
        # the phases' self times make up the step within 2%
        total = root[2] - root[1]
        covered = sum(k[2] - k[1] for k in kids)
        assert total >= 0.05
        assert 0.98 * total <= covered <= total

    def test_stats_phases_and_byte_counters(self, family, warm):
        eng, reg, tracing = warm
        h2d, d2h = (reg.counter("serve.h2d_bytes"),
                    reg.counter("serve.d2h_bytes"))
        h0, d0 = h2d.value, d2h.value
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        assert eng.run() == 3               # one prefill, two decode steps
        phases = eng.stats()["phases"]
        assert set(phases) == PHASES
        tree = tracing.span_tree_totals()
        for name, row in phases.items():
            assert row == tree["engine.step/" + name]
            # one dispatch a unit; the first call plans two units and the
            # last finds none to plan
            want = {"accept": 6, "schedule": 4}.get(name, 3)
            assert row["count"] == want, name
            assert 0 <= row["self_ms"] <= row["total_ms"]
        root = tree["engine.step"]
        assert root["self_ms"] <= 0.02 * root["total_ms"]
        assert phases["guard"]["total_ms"] >= 3 * 50 * 0.99
        # int32 everywhere: ids, positions, last index, tables (8 blocks a
        # sequence), lengths, slots, where each row's id comes from, the
        # step's number
        i32 = 4
        prefill = i32 * (8 + 1 + 1 + 8 + 1 + 8 + 1 + 1)  # bucket 8, 1 row
        decode = i32 * (2 + 2 + 1 + 2 * 8 + 2 + 2 + 2 + 1)   # 2 slots
        assert h2d.value - h0 == prefill + 2 * decode
        # next tokens (int32), a finite flag a row and, since this engine's
        # fault seam is set, float32 logits over the vocabulary; with them
        # a step's counts, where the model books any (DeepSeek-V2:
        # ``moe_load`` of 1 expert layer x 4 held experts, ``moe_dropped``)
        row = 4 + 1 + 4 * VOCAB[family]
        counts = {"gpt": 0, "deepseek_v2": i32 * (1 * 4 + 1)}[family]
        assert d2h.value - d0 == row + 2 * 2 * row + 3 * counts

    def test_paged_block_counters_and_span_attributes(self, warm):
        """ISSUE 29: the share of a decode step's block tables that is
        live.  Tables are 8 wide (32 positions / 4), 2 rows launched."""
        eng, reg, tracing = warm
        live, table = (reg.counter("serve.paged_blocks_live"),
                       reg.counter("serve.paged_blocks_table"))
        l0, t0 = live.value, table.value
        before = dict(eng.stats()["paged_blocks"])
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=5)
        eng.submit([6, 7], max_new_tokens=2)
        steps0 = eng.steps
        eng.run()
        want_live = want_table = decodes = 0
        for step in range(steps0, eng.steps):
            attrs = self.step_spans(tracing, step)[0][3]
            if attrs["kind"] != "decode":
                assert "kv_blocks_live" not in attrs
                continue
            decodes += 1
            assert attrs["kv_blocks_table"] == 2 * 8
            assert 1 <= attrs["kv_blocks_live"] <= 2 * 3
            want_live += attrs["kv_blocks_live"]
            want_table += attrs["kv_blocks_table"]
        # row one decodes at lengths 6..9 (2, 2, 2, 3 pages of 4), row two
        # at length 3 (1 page): its second token is its last
        assert decodes == 4 and want_live == 2 + 2 + 2 + 3 + 1
        assert live.value - l0 == want_live
        assert table.value - t0 == want_table == decodes * 2 * 8
        after = eng.stats()["paged_blocks"]
        assert after["live"] - before["live"] == want_live
        assert after["table"] - before["table"] == want_table

    def test_a_quarantined_step_names_its_bisection(self):
        from paddle_tpu.observability import tracing
        inj = faults.poison_request(1, mode="raise", kinds=("decode",))
        eng = ServingEngine(tiny_model(), max_seqs=4, kv_block_size=4,
                            registry=MetricsRegistry(), step_fault=inj)
        tracing.reset_tracing()
        for p in ([1, 2, 3], [4, 5], [6, 7, 8]):
            eng.submit(p, max_new_tokens=3)
        eng.run(max_steps=50)
        assert eng.stats()["resilience"]["poisoned"] == 1
        tree = tracing.span_tree_totals()
        assert tree["engine.step/quarantine"]["count"] == 1
        # the probes' own phases nest under it, apart from the step's
        assert tree["engine.step/quarantine/dispatch"]["count"] >= 1
        assert "quarantine" in eng.stats()["phases"]
        assert "recover" not in eng.stats()["phases"]


# ---------------------------------------------------------------------------
# What crosses the host-device boundary in a step (ISSUE 31)
# ---------------------------------------------------------------------------
def _fetch_all(engine, kind, request_ids, logits):
    """An identity fault hook: an engine whose seam is set fetches every
    step's logits, as every engine did before ISSUE 31: the oracle."""
    return None


# what a DeepSeek-V2 step's counts take (``moe_load`` of 1 expert layer x
# 4 held experts, ``moe_dropped``, int32); a GPT books none
COUNTS_BYTES = {"gpt": 0, "deepseek_v2": 4 * (1 * 4 + 1)}


@pytest.mark.usefixtures("family")
class TestWhatCrossesTheBoundary:
    PROMPTS = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11, 12, 13, 14], [15])

    def engine(self, **kw):
        kw.setdefault("registry", MetricsRegistry())
        return ServingEngine(tiny_model(), max_seqs=4, kv_block_size=4,
                             **kw)

    def test_a_plain_step_moves_tokens_and_flags_and_no_logits(
            self, family):
        from paddle_tpu.observability import tracing
        eng = self.engine()
        reg = eng._reg()
        tracing.reset_tracing()
        for p in self.PROMPTS:
            eng.submit(p, max_new_tokens=5)
        steps = eng.run()
        prefills = reg.counter("serve.prefills").value
        decodes = reg.counter("serve.decode_steps").value
        assert prefills == 4 and prefills + decodes == steps
        # a token (int32) and a flag a row launched, and the counts:
        # nothing that grows with the vocabulary
        want = (prefills * (1 * 5 + COUNTS_BYTES[family])
                + decodes * (4 * 5 + COUNTS_BYTES[family]))
        assert reg.counter("serve.d2h_bytes").value == want
        assert reg.counter("serve.logits_fetch_steps").value == 0
        assert eng.stats()["logits_fetch_steps"] == 0
        roots = [s for s in tracing.spans_between(0.0, float("inf"))
                 if s[0] == "engine.step"]
        assert len(roots) == steps
        assert all(s[3]["logits_fetched"] is False for s in roots)

    def test_a_captured_row_gets_its_logits_and_nobody_elses_cross(self):
        oracle = self.engine(step_fault=_fetch_all, capture_logits=True)
        want = [oracle.submit(p, max_new_tokens=n)
                for p, n in zip(self.PROMPTS[:2], (3, 6))]
        oracle.run()
        eng = self.engine()
        eng.capture_logits = True           # for this request only
        captured = eng.submit(self.PROMPTS[0], max_new_tokens=3)
        eng.capture_logits = False
        plain = eng.submit(self.PROMPTS[1], max_new_tokens=6)
        steps = eng.run()
        got, ref = eng.collect(captured), oracle.collect(want[0])
        assert got["tokens"] == ref["tokens"]
        assert len(got["logits"]) == len(ref["logits"]) == 3
        for a, b in zip(got["logits"], ref["logits"]):
            assert a.dtype == np.float32 and np.array_equal(a, b)
        other = eng.collect(plain)
        assert "logits" not in other
        assert other["tokens"] == oracle.collect(want[1])["tokens"]
        # the captured request's prefill and its two decode steps, which
        # the other request shared; the other's own five steps fetch none
        assert steps == 2 + 5
        assert eng.stats()["logits_fetch_steps"] == 3
        reg = eng._reg()
        assert reg.counter("serve.logits_fetch_steps").value == 3
        assert oracle.stats()["logits_fetch_steps"] == oracle.steps

    @pytest.mark.parametrize("route", ["page", "hook"])
    def test_the_guard_names_a_nonfinite_row(self, route):
        """The guard reads the flags the step computed.  ``page``: the
        NaN arises on the device (one request's cached page), nothing is
        fetched and nothing bisected.  ``hook``: the seam overwrites the
        row on the host, and the guard reads what it handed back."""
        from paddle_tpu.observability import tracing
        clean = self.engine()
        want = clean.generate(self.PROMPTS, max_new_tokens=6)
        hook = (faults.poison_request(1, mode="nan", kinds=("decode",))
                if route == "hook" else None)
        eng = self.engine(nan_guard=True, step_fault=hook)
        tracing.reset_tracing()
        rids = [eng.submit(p, max_new_tokens=6) for p in self.PROMPTS]
        for _ in rids:
            eng.step()                       # the four prefills
        if route == "page":
            block = eng.cache.table(rids[1])[0]
            pages = [list(layer) for layer in eng.cache.pages]
            pages[0][0] = pages[0][0].at[block].set(np.nan)
            eng.cache.update_pages(pages)
        eng.run()
        assert list(eng.quarantined) == [rids[1]]
        assert "nonfinite" in eng.quarantined[rids[1]]["error"]
        assert eng.collect(rids[1])["finish_reason"] == "poisoned"
        for i in (0, 2, 3):
            assert eng.collect(rids[i])["tokens"] == want[i]
        tree = tracing.span_tree_totals()
        assert tree["engine.step/quarantine"]["count"] == 1
        assert "engine.step/quarantine/dispatch" not in tree    # no probe
        # with the seam set every executed step program fetches: each
        # step, and the replay of the faulted one on its survivors
        fetched = eng.stats()["logits_fetch_steps"]
        assert fetched == (0 if route == "page" else eng.steps + 1)
        assert eng.cache.allocator.num_used == 0

    def test_two_kinds_pack_a_table_and_a_slot_matrix_each(self):
        rng = np.random.default_rng(1)
        rows, chunk, widths = 3, 5, (7, 2)
        draw = lambda *shape: rng.integers(0, 99, shape).astype(np.int32)
        tables = [draw(rows, w) for w in widths]
        slots = [draw(rows, chunk) for _ in widths]
        parts = [draw(rows, chunk), draw(rows), draw(), tables, draw(rows),
                 slots, draw(rows), draw()]
        packed = pack_step_inputs(*parts[:6], src=parts[6], step=parts[7])
        flat = [a for x in parts for a in (x if isinstance(x, list) else [x])]
        assert packed.nbytes == sum(a.nbytes for a in flat)
        assert np.array_equal(packed, np.concatenate(
            [a.reshape(-1) for a in flat]))
        out = unpack_step_inputs(packed, rows, chunk, widths)
        for got, want in zip(out, parts):
            if isinstance(want, list):
                assert len(got) == len(want)
                for g, w in zip(got, want):
                    assert np.array_equal(g, w)
            else:
                assert np.array_equal(got, want)
        with pytest.raises(Exception, match="packed step inputs"):
            unpack_step_inputs(packed, rows, chunk, (7, 3))

    @pytest.mark.parametrize("rows,chunk", [(4, 1), (1, 16)],
                             ids=["decode", "prefill_b16"])
    def test_a_one_kind_layout_packs_byte_for_byte_what_the_parent_did(
            self, rows, chunk):
        """The tables, the slots and the packed buffer of a model whose
        layers are all of one kind, against the loops of the cache before
        kinds (copied here from PR 33's ``kv_cache.py``)."""
        eng = self.engine()
        cache, bs = eng.cache, eng.cache.block_size
        assert list(cache.pools) == ["full"] and cache.table_widths(9) == (9,)
        sids = [f"s{i}" for i in range(rows)]
        lens = ([3, 9, 0, 14] if chunk == 1 else [chunk - 3])
        for sid, n in zip(sids, lens):
            assert cache.ensure_capacity(sid, n)
        cache.free_seq(sids[0])             # churn: ids out of order
        assert cache.ensure_capacity(sids[0], lens[0] + 5)
        starts = [n - 1 for n in lens] if chunk == 1 else [0]
        if chunk == 1:
            starts[2] = -1                  # a padding row
        width = eng.sched.max_blocks_per_seq
        want_tables = np.zeros((rows, width), np.int32)
        want_slots = np.full((rows, chunk), cache.num_blocks * bs, np.int32)
        for i, (sid, start) in enumerate(zip(sids, starts)):
            t = cache._tables.get(sid, [])
            want_tables[i, :len(t)] = t
            if start < 0:
                continue
            for j in range(chunk):
                pos = start + j
                if pos < len(t) * bs:
                    want_slots[i, j] = t[pos // bs] * bs + pos % bs
        (tables,), (slots,) = eng._tables_and_slots(sids, starts, chunk)
        assert tables.dtype == slots.dtype == np.int32
        assert tables.tobytes() == want_tables.tobytes()
        assert slots.tobytes() == want_slots.tobytes()
        ids = np.arange(rows * chunk, dtype=np.int32).reshape(rows, chunk)
        positions = np.asarray(starts, np.int32).clip(0)
        seq_lens = np.asarray(lens, np.int32)
        packed = pack_step_inputs(ids, positions, 0, [tables], seq_lens,
                                  [slots], step=5)
        # ... and, since ISSUE 36, a word a row that says where its id
        # comes from (-1: this buffer), ahead of the step's number
        want = np.concatenate([
            np.asarray(a, np.int32).reshape(-1)
            for a in (ids, positions, 0, want_tables, seq_lens, want_slots,
                      np.full((rows,), -1), 5)])
        assert packed.tobytes() == want.tobytes()
        assert cache.pool_bytes() == sum(
            a.nbytes for layer in cache.pages for a in layer)
        assert not [n for n in eng._reg().snapshot()
                    if n.startswith("serve.kv_full")]    # nothing a kind

    def test_the_packed_inputs_unpack_to_what_went_in(self):
        rng = np.random.default_rng(0)
        rows, chunk, width = 3, 5, 7
        parts = [rng.integers(0, 99, shape).astype(np.int32)
                 for shape in ((rows, chunk), (rows,), (), (rows, width),
                               (rows,), (rows, chunk), (rows,), ())]
        packed = pack_step_inputs(*parts[:6], src=parts[6], step=parts[7])
        assert packed.dtype == np.int32 and packed.ndim == 1
        assert packed.nbytes == sum(a.nbytes for a in parts)
        for got, want in zip(unpack_step_inputs(packed, rows, chunk), parts):
            assert got.shape == want.shape and np.array_equal(got, want)
        with pytest.raises(Exception, match="packed step inputs"):
            unpack_step_inputs(packed[:-1], rows, chunk)

    @pytest.mark.parametrize("rows,chunk", [(4, 1), (1, 8), (1, 16),
                                            (1, 32)],
                             ids=["decode", "prefill_b8", "prefill_b16",
                                  "prefill_b32"])
    def test_a_packed_step_is_the_unpacked_step(self, rows, chunk):
        """The step program against a reference that takes the six
        arrays apart, as the program did before ISSUE 31: the same
        tokens, finite flags that agree with the logits."""
        import jax
        from paddle_tpu.inference.kv_cache import PagedLayerCache
        eng = self.engine()
        model, bs = eng.model, eng.cache.block_size
        rng = np.random.default_rng(chunk)
        eng.cache.update_pages([
            tuple(jnp.asarray(rng.normal(size=a.shape) * 0.3, a.dtype)
                  for a in layer) for layer in eng.cache.pages])
        sids = [f"s{i}" for i in range(rows)]
        if chunk == 1:                       # rows that hold 3..6 tokens
            lens = np.arange(3, 3 + rows, dtype=np.int32)
            starts, last = list(lens - 1), 0
            positions = lens - 1
        else:                                # a prompt that fills its bucket
            lens = np.asarray([chunk - 2], np.int32)
            starts, last = [0], chunk - 3
            positions = np.zeros((1,), np.int32)
        for sid, n in zip(sids, lens):
            assert eng.cache.ensure_capacity(sid, int(n))
        ids = rng.integers(0, 30, (rows, chunk)).astype(np.int32)
        tables = eng.cache.table_array(sids, eng.sched.max_blocks_per_seq)
        slots = eng.cache.slot_array(sids, starts, chunk)

        @jax.jit
        def reference(params, ids, positions, last, pages, tables, lens,
                      slots):
            caches = [PagedLayerCache(layer, tables, lens, slots,
                                      block_size=bs) for layer in pages]
            logits = model.apply(params, ids, caches, positions, last,
                                 method="serving_step")[0]
            return jnp.argmax(logits, axis=-1), logits

        want, want_logits = reference(
            eng._params, ids, positions, np.asarray(last, np.int32),
            eng.cache.pages, tables, lens, slots)
        nxt, finite, logits, pages, _, carry = eng._build_step_fn()(
            eng._params,
            pack_step_inputs(ids, positions, last, tables, lens, slots),
            eng.cache.pages, jax.random.PRNGKey(0), eng._no_prev,
            rows=rows, chunk=chunk)
        eng.cache.update_pages(pages)
        # the tokens as the program launched next takes them
        assert carry.shape == (eng.max_seqs,) and carry.dtype == jnp.int32
        assert np.asarray(carry)[:rows].tolist() == np.asarray(nxt).tolist()
        assert np.asarray(nxt).tolist() == np.asarray(want).tolist()
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits, np.float32),
                                   atol=1e-5)
        assert finite.dtype == jnp.bool_ and finite.shape == (rows,)
        assert np.asarray(finite).all()

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_generate_is_token_for_token_what_the_fetching_engine_gives(
            self, temperature):
        """Same seed, same traffic (a pool tight enough to preempt):
        keeping the logits on the device changes no token, greedy or
        sampled."""
        kw = dict(temperature=temperature, seed=11, num_kv_blocks=7)
        oracle = self.engine(step_fault=_fetch_all, **kw)
        eng = self.engine(**kw)
        want = oracle.generate(self.PROMPTS, max_new_tokens=7)
        got = eng.generate(self.PROMPTS, max_new_tokens=7)
        assert got == want and all(len(t) == 7 for t in got)
        assert eng.sched.preemptions == oracle.sched.preemptions > 0
        assert eng.stats()["logits_fetch_steps"] == 0


# ---------------------------------------------------------------------------
# The sampling key is data of the step program (ISSUE 33)
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestTheKeyIsData:
    PROMPTS = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11, 12, 13, 14], [15])

    def engine(self, **kw):
        kw.setdefault("registry", MetricsRegistry())
        return ServingEngine(tiny_model(), max_seqs=4, kv_block_size=4,
                             **kw)

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    def test_n_steps_are_n_dispatches_of_the_step_program_alone(
            self, monkeypatch, temperature):
        """A warm engine's step opens one ``dispatch`` span, calls one
        tracked program, puts one buffer and asks ``jax.random`` for
        nothing on the host: no device program beside the step's own."""
        import jax
        import paddle_tpu.observability.compilation as comp
        from paddle_tpu.observability import tracing
        eng = self.engine(temperature=temperature, seed=5)
        eng.generate(self.PROMPTS, max_new_tokens=3)     # every program
        tracker = CompileTracker(registry=MetricsRegistry())
        monkeypatch.setattr(comp, "get_tracker", lambda: tracker)
        host_calls = []
        for name in ("split", "fold_in", "PRNGKey", "key", "categorical"):
            monkeypatch.setattr(
                jax.random, name,
                lambda *a, _n=name, **kw: host_calls.append(_n))
        puts = []
        real_put = jax.device_put
        monkeypatch.setattr(jax, "device_put",
                            lambda x, *a, **kw: (puts.append(x.shape),
                                                 real_put(x, *a, **kw))[1])
        tracing.reset_tracing()
        for prompt in self.PROMPTS:
            eng.submit(prompt, max_new_tokens=4)
        steps = eng.run()
        assert steps == 4 + 3
        assert eng.stats()["phases"]["dispatch"]["count"] == steps
        calls = {f: tracker.stats(f) for f in tracker.functions()}
        assert sum(st["calls"] for st in calls.values()) == steps
        # the private tracker meets each program warm: its first call is
        # walked, the cache did not grow, and that is every walk there is
        assert calls["serve_decode"]["calls"] == 3
        assert all(st["walks"] == 1 and st["retraces"] == 0
                   for st in calls.values())
        assert host_calls == [] and len(puts) == steps

    def test_one_seed_one_stream_and_another_seed_another(self):
        streams = [self.engine(temperature=0.8, seed=seed).generate(
            self.PROMPTS, max_new_tokens=8) for seed in (3, 3, 4)]
        assert all(len(t) == 8 for s in streams for t in s)
        assert streams[0] == streams[1]
        assert streams[0] != streams[2]
        greedy = self.engine().generate(self.PROMPTS, max_new_tokens=8)
        assert streams[0] != greedy            # it does sample

    def test_a_replayed_sampled_step_draws_what_it_would_have(self):
        """A decode step that faults is bisected and replayed on its
        survivors under the step's own number: the rows that stay where
        they were sample what the un-faulted run samples, then and after
        (the culprit was the last row, so nobody moved)."""
        kw = dict(temperature=0.8, seed=9)
        clean = self.engine(**kw)
        want = clean.generate(self.PROMPTS, max_new_tokens=6)
        inj = faults.poison_request(3, mode="raise", kinds=("decode",))
        eng = self.engine(step_fault=inj, **kw)
        rids = [eng.submit(p, max_new_tokens=6) for p in self.PROMPTS]
        steps = eng.run()
        assert inj.fired > 1                   # the probes met it again
        assert list(eng.quarantined) == [rids[3]]
        assert steps == clean.steps            # the replay is no new step
        for rid, tokens in zip(rids[:3], want):
            assert eng.collect(rid)["tokens"] == tokens
        assert want != self.engine(temperature=0.0).generate(
            self.PROMPTS, max_new_tokens=6)

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    def test_the_step_number_is_the_last_packed_word(self, temperature):
        """A sampling program draws from (the engine's key, the word); a
        greedy program reads neither."""
        import jax
        eng = self.engine(temperature=temperature)
        fn = eng._build_step_fn()
        parts = (np.arange(4).reshape(4, 1), np.zeros((4,)), 0,
                 np.zeros((4, 8)), np.ones((4,)), np.zeros((4, 1)))
        assert pack_step_inputs(*parts, step=77)[-1] == 77
        assert pack_step_inputs(*parts)[-1] == 0

        def tokens(key, step):
            nxt, _, _, pages, _, _ = fn(
                eng._params, pack_step_inputs(*parts, step=step),
                eng.cache.pages, key, eng._no_prev, rows=4, chunk=1)
            eng.cache.update_pages(pages)
            return np.asarray(nxt).tolist()
        key, other = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
        draws = [tokens(key, 5), tokens(key, 5), tokens(key, 6),
                 tokens(other, 5)]
        assert draws[0] == draws[1]
        if temperature > 0:
            assert draws[0] != draws[2] and draws[0] != draws[3]
        else:
            assert draws[0] == draws[2] == draws[3]


# ---------------------------------------------------------------------------
# Legacy facade routing
# ---------------------------------------------------------------------------
class TestLegacyFacadeRouting:
    def test_enable_continuous_batching_routes_to_engine(self):
        model = tiny_model()
        cfg = Config()
        cfg.enable_continuous_batching(max_seqs=4, kv_block_size=4)
        cfg.set_decoder_model(model, max_new_tokens=4, eos_token_id=None,
                              pad_token_id=0)
        pred = create_predictor(cfg)
        assert type(pred).__name__ == "EnginePredictor"
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8]]
        width = max(len(p) for p in prompts)
        ids = np.zeros((2, width), np.int64)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = p
        # reference call shapes: named input handle -> run -> output handle
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.copy_from_cpu(ids)
        pred.run()
        out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
        assert out.shape[0] == 2
        for i, p in enumerate(prompts):
            want = p + dense_continuation(model, p, 4)
            assert out[i, :len(want)].tolist() == want

    def test_plain_config_still_builds_plain_predictor(self, tmp_path):
        cfg = Config(str(tmp_path))
        assert not cfg.continuous_batching_enabled()
        with pytest.raises(Exception):
            # CB enabled without a decoder model is an explicit error
            cfg2 = Config()
            cfg2.enable_continuous_batching()
            create_predictor(cfg2)
