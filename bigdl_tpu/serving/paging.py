"""The decode engine's cache managers and the host-side page bookkeeping.

A cache manager owns the *layout* of the K/V state: it builds the cache
and the programs that depend on the layout (tick, slot write, verify),
supplies the extra argument those programs take (the block table), and
answers the scheduler's questions about room (``reserve`` / ``release``
/ ``pages_for`` / ``pages_free``), bytes (``resident_bytes``, the
HbmLedger lane) and what a trace should see (``span_args``).  The
scheduler's page *policy* (serving/decode.py: oldest first, evict
younger, pause) runs against this interface and never asks which
layout it has: on :class:`DenseCache` ``reserve`` is always true.

The compiled tick only ever sees a block table (an (S, M) int32 device
argument) and the page pool (docs/decoding.md §Paged KV cache;
ops/paged_kv.py for the array ops).  Everything stateful — the free
list, which slot owns which physical page — lives in
:class:`PageAllocator`, in plain Python, under the engine loop's single
thread.

Two extents (docs/decoding.md §Two extents): a model whose layers say
how many rows of a slot they keep (``decode_extents()``: all, or the
last ``window``) gets a second pool and table for its window layers
(:class:`BandAllocator`), in which the pages behind a slot's band go
back to the free list as the slot grows; the tick then takes the two
tables stacked (2, S, M).  A model that says nothing keeps the one pool
and the (S, M) table.

Fixed blocks (docs/decoding.md §Fixed blocks): a leaf that a layer
declares as a :class:`~bigdl_tpu.ops.paged_kv.Block` (the state of a
state-space layer) is one block a slot beside the pages.  It takes no
page and no table; the slot write copies a prefilled row's block into
its slot, which is the block's admission, and a slot holds it from its
first ``reserve`` to its ``release`` (``state_blocks_held``).
"""
from __future__ import annotations

from collections import deque
from typing import Callable, List, Optional

import numpy as np

from bigdl_tpu.ops import paged_kv
from bigdl_tpu.serving import decode_programs


def default_num_pages(slots: int, max_len: int, page_size: int) -> int:
    """Worst-case pool (every slot at max_len) + the trash page — the
    conservative default; callers shrink it to trade HBM for eviction
    risk (bench's paged arm runs 2x slots on the dense arm's budget)."""
    per_slot = -(-max_len // page_size)
    return slots * per_slot + 1


class OutOfPagesError(RuntimeError):
    """The pool has no free page and no evictable donor."""


class PageAllocator:
    """Free-list allocator over physical pages 1..P-1 (0 is the trash
    page, ops/paged_kv.py).  ``table`` is the live (S, M) block table
    handed to every tick; unmapped entries stay 0 so stray reads and
    redirected writes land on trash."""

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 max_len: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.pages_per_slot = -(-self.max_len // self.page_size)
        self.table = np.zeros((self.slots, self.pages_per_slot),
                              np.int32)
        self._free: deque = deque(range(1, self.num_pages))
        self._owned: List[List[int]] = [[] for _ in range(self.slots)]

    # ------------------------------------------------------------ stats
    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    def owned(self, slot: int) -> int:
        return len(self._owned[slot])

    # ------------------------------------------------------- allocation
    def pages_for(self, tokens: int) -> int:
        """How many pages hold ``tokens`` of one slot."""
        return min(-(-max(tokens, 0) // self.page_size),
                   self.pages_per_slot)

    def needed(self, slot: int, tokens: int) -> int:
        """How many new pages ``slot`` needs to hold ``tokens``."""
        return max(0, self.pages_for(tokens) - len(self._owned[slot]))

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s mapping to cover ``tokens`` logical tokens.
        Returns False (mapping unchanged) when the free list is short —
        the engine then evicts a donor slot and retries."""
        need = self.needed(slot, tokens)
        if need > len(self._free):
            return False
        own = self._owned[slot]
        for _ in range(need):
            phys = self._free.popleft()
            self.table[slot, len(own)] = phys
            own.append(phys)
        return True

    def release(self, slot: int):
        """Free every page ``slot`` owns (retirement / eviction)."""
        self._free.extend(self._owned[slot])
        self._owned[slot] = []
        self.table[slot, :] = 0


class BandAllocator(PageAllocator):
    """The window layers' pages: a slot keeps the pages that hold its
    last ``window + step - 1`` rows (the band of every query the next
    round can make: ``step`` tokens a round) and no other.  Logical
    pages keep their absolute index, so a row's position is its
    position in both extents; the entries behind the band are unmapped
    (0, the trash page, which no query inside the band reads).  The pool
    is sized for every slot at its fullest, so ``ensure`` never runs
    short."""

    def __init__(self, page_size: int, slots: int, max_len: int,
                 window: int, step: int):
        self.window, self.step = int(window), int(step)
        # the band's rows, not aligned to a page: one page more
        self.pages_per_band = min(
            -(-max_len // page_size),
            -(-(self.window + self.step - 1) // page_size) + 1)
        super().__init__(slots * self.pages_per_band + 1, page_size,
                         slots, max_len)
        self._first = [0] * self.slots    # first logical page mapped

    def first_row(self, tokens: int) -> int:
        """The first row a round that leaves ``tokens`` rows reads."""
        return max(tokens - self.step - self.window + 1, 0)

    def ensure(self, slot: int, tokens: int) -> bool:
        first = self.first_row(tokens) // self.page_size
        own = self._owned[slot]
        if not own:
            self._first[slot] = first
        drop = min(first - self._first[slot], len(own))
        if drop > 0:        # the pages behind the band go back
            lo = self._first[slot]
            self._free.extend(own[:drop])
            del own[:drop]
            self.table[slot, lo:lo + drop] = 0
            self._first[slot] = first if not own else lo + drop
        for logical in range(self._first[slot] + len(own),
                             self.pages_for(tokens)):
            phys = self._free.popleft()
            self.table[slot, logical] = phys
            own.append(phys)
        return True


class DenseCache:
    """One ``max_len`` row per slot, reserved whole: nothing to budget,
    so every request for room is granted."""

    resident_name = "decode_kv_cache"
    pages_in_use = 0
    pages_free = 0

    def __init__(self, slots: int, max_len: int):
        self.slots = int(slots)
        self.max_len = int(max_len)
        self._bytes = 0

    # ----------------------------------------------- cache and programs
    def init_cache(self, model, dtype):
        import jax

        cache = model.init_cache(self.slots, self.max_len, dtype)
        self._bytes = sum(leaf.size * leaf.dtype.itemsize
                          for leaf in jax.tree_util.tree_leaves(cache))
        return cache

    def build_tick(self, model):
        return decode_programs.build_sampling_tick(model)

    def build_write(self):
        return decode_programs.build_write_slot()

    def build_verify(self, model, k: int):
        return decode_programs.build_spec_verify(model, k)

    def tick_extra(self) -> tuple:
        """What the tick and the verify take after the cache."""
        return ()

    def write_extra(self, slot: int) -> tuple:
        """What the slot write takes after the cache."""
        return ()

    # ------------------------------------------------------------- room
    def check_servable(self, tokens: int):
        """Raise when a request of ``tokens`` could never be held."""

    def pages_for(self, tokens: int) -> int:
        return 0

    def owned(self, slot: int) -> int:
        return 0

    def reserve(self, slot: int, tokens: int) -> bool:
        return True

    def release(self, slot: int):
        pass

    # ---------------------------------------------------------- readouts
    def resident_bytes(self) -> int:
        """The fixed worst-case reservation."""
        return self._bytes

    def span_args(self) -> Optional[dict]:
        return None


class PagedCache(PageAllocator):
    """The page pool of ops/paged_kv.py: a slot holds the pages its
    tokens need and retirement hands them back.  ``gauge`` is told the
    pages in use whenever they change."""

    resident_name = "decode_kv_pages"

    def __init__(self, slots: int, max_len: int, page_size: int,
                 num_pages: int, kv_dtype=None,
                 gauge: Callable[..., None] = lambda n, band=0: None,
                 step: int = 1):
        super().__init__(num_pages, page_size, slots, max_len)
        self.kv_dtype = kv_dtype
        self.step = int(step)   # tokens a slot may write in one round
        self.page_bytes = 0
        # the window layers' extent, where the model declares one
        self.band: Optional[BandAllocator] = None
        self.band_page_bytes = 0
        self._windows: dict = {}
        # the leaves each layer keeps one block a slot of, where it has
        # any, their bytes a slot, and the slots that hold theirs
        self._blocks: dict = {}
        self.block_bytes = 0
        self._block_slots: set = set()
        self._gauge = gauge

    # ----------------------------------------------- cache and programs
    def init_cache(self, model, dtype):
        extents = getattr(model, "decode_extents", dict)()
        self._windows = {lk: w for lk, w in extents.items() if w}
        kw = {}
        if self._windows:
            window, = set(self._windows.values())   # one band a model
            self.band = BandAllocator(self.page_size, self.slots,
                                      self.max_len, window, self.step)
            kw["window_pages"] = self.band.num_pages
        cache = model.init_paged_cache(self.num_pages, self.page_size,
                                       self.slots, dtype,
                                       kv_dtype=self.kv_dtype, **kw)
        self._blocks = {
            lk: [name for name, spec in leaves.items()
                 if paged_kv.is_block(spec)]
            for lk, leaves in getattr(model, "decode_state", dict)().items()}
        self._blocks = {lk: n for lk, n in self._blocks.items() if n}
        # bytes one physical page costs across every layer's pool of
        # its extent (K + V + scales), and one slot's blocks
        leaf_bytes = lambda leaf: int(np.prod(leaf.shape[1:])) \
            * leaf.dtype.itemsize
        page_bytes = lambda lks: sum(
            leaf_bytes(leaf) for lk in lks
            for name, leaf in cache[lk].items()
            if name != "length" and name not in self._blocks.get(lk, ()))
        self.block_bytes = sum(leaf_bytes(cache[lk][name])
                               for lk, names in self._blocks.items()
                               for name in names)
        self.page_bytes = page_bytes(lk for lk in cache
                                     if lk not in self._windows)
        self.band_page_bytes = page_bytes(self._windows)
        return cache

    def build_tick(self, model):
        return decode_programs.build_paged_tick(model)

    def build_write(self):
        return decode_programs.build_paged_write_slot(
            self._windows,
            self.band.pages_per_band if self.band else 0, self._blocks)

    def build_verify(self, model, k: int):
        return decode_programs.build_spec_verify(model, k, paged=True)

    def _tables(self):
        """(S, M), or the two extents' tables stacked (2, S, M)."""
        return self.table if self.band is None \
            else np.stack([self.table, self.band.table])

    def tick_extra(self) -> tuple:
        """The block table, a plain device argument each call (values
        change, shape never)."""
        return (self._tables(),)

    def write_extra(self, slot: int) -> tuple:
        return (self._tables()[..., slot, :],)

    # ------------------------------------------------------------- room
    def check_servable(self, tokens: int):
        pages = self.pages_for(tokens)
        if pages > self.num_pages - 1:
            raise OutOfPagesError(
                f"request needs {pages} pages at its longest but the "
                f"pool only has {self.num_pages - 1} usable pages of "
                f"{self.page_size} tokens")

    def reserve(self, slot: int, tokens: int) -> bool:
        """Grow ``slot`` to hold ``tokens``; False (nothing changed)
        when the free list is short."""
        held = self._held()
        if not self.ensure(slot, tokens):
            return False
        if self.band is not None:   # never short: sized for every slot
            self.band.ensure(slot, tokens)
        if self._blocks:
            self._block_slots.add(slot)
        if self._held() != held:
            self._gauge(*self._held())
        return True

    def release(self, slot: int):
        super().release(slot)
        if self.band is not None:
            self.band.release(slot)
        self._block_slots.discard(slot)
        self._gauge(*self._held())

    def _held(self) -> tuple:
        """Pages in use: ``(full extent,)`` or ``(full, window)``."""
        return (self.pages_in_use,) if self.band is None \
            else (self.pages_in_use, self.band.pages_in_use)

    # ---------------------------------------------------------- readouts
    def resident_bytes(self) -> int:
        """Bytes of the pages actually held, both extents', and of the
        blocks of the slots held — the readout that retirement frees
        memory."""
        return self.pages_in_use * self.page_bytes + (
            self.band.pages_in_use * self.band_page_bytes
            if self.band else 0) + len(self._block_slots) * self.block_bytes

    def span_args(self) -> Optional[dict]:
        """``loop/tick_dispatch``'s counters: the share of the ``S * M``
        extent this tick's attention has to read at a full layer
        (``pages_held``), where window layers keep their own, at one of
        those (``window_pages_held``) and, where layers keep a block a
        slot, the slots whose block is live (``state_blocks_held``: the
        blocks this tick's state-space layers read and write)."""
        args = {"pages_held": self.pages_in_use}
        if self.band is not None:
            args["window_pages_held"] = self.band.pages_in_use
        if self._blocks:
            args["state_blocks_held"] = len(self._block_slots)
        return args
