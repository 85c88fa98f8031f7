"""Block-paged KV cache — the PagedAttention memory model (SOSP '23).

Generative serving cannot pre-reserve ``max_slots × max_context`` of KV
memory per sequence: real prompts/outputs vary by two orders of magnitude
and the reserved-but-unused tail is the memory that would have held more
concurrent sequences. The vLLM answer, reproduced here:

* KV storage is ONE device array of fixed-size **pages**
  ``(layers, sides, num_pages + 1, page_size, row_width)`` allocated once
  at server start — decode steps never reallocate device memory and their
  jit signature never changes (the compile-once property
  ``tests/test_serving.py`` asserts through the RecompileLedger). This is
  the ONE layout, and its geometry is the MODEL's (``models/served.py``
  ``CacheRows``): with two ``sides`` a token's K (or V) of one layer is a row
  of ``heads * head_dim`` numbers, the projection's own output, heads merged;
  with one side a token leaves ONE latent row an attention sub-layer (MLA:
  the normalised latent, the rotated shared key, dead lanes up to whole
  128-lane tiles). At GPT-2's
  widths a page ``(16, 768)`` fills the TPU's ``(8, 128)`` float32 and
  ``(16, 128)`` bfloat16 tiles exactly, so the device keeps the pool
  row-major, scatters update it in place and the paged kernel reads pages
  where they lie; with heads and head size as two minor dimensions
  ``(12, 64)`` the device padded every page 2.67-fold and copied the whole
  pool into another layout and back in every program (PERF.md, PR 26).
  Every serving program takes and returns this array; none slices a layer
  out of it (``tests/test_tpu_compile.py`` holds the compiled ``decode`` and
  ``write_prompt`` to that).
* Each sequence owns an ordered list of pages recorded in a **page table**
  row ``(max_slots, max_pages_per_seq)``; logical token position ``t`` lives
  at ``(page_table[slot, t // page_size], t % page_size)``.
* A host-side **free list** hands out pages at admit/growth and takes them
  back at evict — allocation is O(1) list ops between decode iterations,
  never device work.

Since the radix prefix cache (``serving/prefix.py``, docs/SERVING.md
§ Radix prefix cache) pages are **refcounted**: a page may be mapped into
several slots' page-table rows at once (shared system-prompt KV) and/or
pinned by the prefix tree, so "owned by exactly one slot" became "held by
``refcount`` holders"; a page returns to the free list only when the last
holder releases it. Writes into shared pages are forbidden by construction
— the engine's admission path **copies** a partially-filled tail page
before a slot may write into it (:meth:`cow_page`, the copy-on-write rule).

The LAST page (index ``num_pages``) is the **trash page**: inactive slots'
decode writes and unallocated page-table entries point at it, so the fully
vectorized decode step needs no scatter masking — garbage lands where
nothing ever reads it (attention masks positions ``>= seq_len``).

Invariants (exercised by tests/test_serving.py + tests/test_prefix.py):
  * every page is either in the free list XOR has ``refcount >= 1``;
  * ``len(free) + |{p : refcount(p) > 0}| == num_pages`` at all times;
  * ``refcount(p) == (#slot rows mapping p) + (#prefix-tree refs on p)``;
  * a freed slot's page-table row points wholly at the trash page.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import faults, observe


class PagedKVCache:
    """Fixed-pool paged KV storage + refcounted free-list allocator
    (host-side bookkeeping, device-side ``kv`` array threaded through the
    jitted decode step functionally)."""

    def __init__(self, *, layers: int, row_width: int, sides: int = 2,
                 page_size: int = 16, num_pages: int = 64,
                 max_slots: int = 4, max_pages_per_seq: int = 8,
                 dtype=jnp.float32):
        if page_size <= 0 or num_pages <= 0:
            raise ValueError("page_size and num_pages must be positive")
        self.layers = int(layers)
        self.sides = int(sides)
        self.row_width = int(row_width)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_slots = int(max_slots)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.trash_page = self.num_pages
        # +1: the trash page — see module docstring
        self._kv_shape = (self.layers, self.sides, self.num_pages + 1,
                          self.page_size, self.row_width)
        self._kv_dtype = dtype
        self.kv = jnp.zeros(self._kv_shape, self._kv_dtype)
        self.free: List[int] = list(range(self.num_pages))
        self.refcount: List[int] = [0] * self.num_pages
        self.page_table = np.full((self.max_slots, self.max_pages_per_seq),
                                  self.trash_page, np.int32)
        self.seq_lens = np.zeros((self.max_slots,), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(self.max_slots)]
        self._copy_fn = None

    # ----------------------------------------------------------- accounting
    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` tokens."""
        return -(-int(n_tokens) // self.page_size)

    @property
    def free_pages(self) -> int:
        return len(self.free)

    def used_pages(self) -> int:
        return sum(len(o) for o in self.owned)

    def max_context(self) -> int:
        """Longest sequence one slot can hold."""
        return self.max_pages_per_seq * self.page_size

    # ------------------------------------------------------------- refcounts
    def alloc_page(self) -> Optional[int]:
        """Pop a page off the free list with ``refcount == 1``. None when
        the pool is exhausted (callers translate to their oom arm)."""
        if not self.free:
            return None
        page = self.free.pop()
        self.refcount[page] = 1
        return page

    def retain(self, page: int) -> None:
        """Add one reference to a LIVE page (a prefix-tree insert, or a
        slot mapping a shared page). Retaining a free page is a bug — it
        would hand the same page to two unrelated holders."""
        if self.refcount[page] <= 0:
            raise AssertionError(
                f"retain of page {page} with refcount "
                f"{self.refcount[page]} (page is on the free list)")
        self.refcount[page] += 1

    def release(self, page: int) -> None:
        """Drop one reference; the page returns to the free list only at
        refcount zero — the exactly-once property under sharing."""
        if self.refcount[page] <= 0:
            raise AssertionError(
                f"release of page {page} with refcount "
                f"{self.refcount[page]} (double free)")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self.free.append(page)

    def map_shared(self, slot: int, page: int) -> None:
        """Map an already-live page (a prefix-cache hit) into ``slot``'s
        next page-table position, taking a reference. The slot must never
        WRITE into a shared page — the engine CoWs the partial tail first."""
        self.retain(page)
        idx = len(self.owned[slot])
        self.owned[slot].append(page)
        self.page_table[slot, idx] = page

    def cow_page(self, slot: int, src: int) -> Optional[int]:
        """Copy-on-write: allocate a fresh page, device-copy ``src`` into
        it, and map it into ``slot``'s next page-table position. Returns
        the new page id, or None when the pool is exhausted (the caller
        unwinds the admission). The copy is ONE jitted device op whose
        signature depends only on the kv geometry — compile once."""
        dst = self.alloc_page()
        if dst is None:
            return None
        idx = len(self.owned[slot])
        self.owned[slot].append(dst)
        self.page_table[slot, idx] = dst
        if self._copy_fn is None:
            self._copy_fn = self._build_copy()
        observe.note_jit_signature(
            self._copy_fn, graph="serving", key="copy_page",
            signature=observe.signature_of(shape=self._kv_shape))
        self.kv = self._copy_fn(self.kv, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))
        return dst

    def _build_copy(self):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def copy_page(kv_pages, src, dst):
            return kv_pages.at[:, :, dst].set(kv_pages[:, :, src])

        return copy_page

    # ----------------------------------------------------------- allocation
    def ensure_capacity(self, slot: int, n_tokens: int) -> str:
        """Grow ``slot``'s page list to cover ``n_tokens`` tokens.

        Returns ``"ok"`` on success, ``"overflow"`` when the sequence would
        exceed its page-table row (evict: the sequence is at max context),
        ``"oom"`` when the free list is exhausted (evict: pool pressure).
        Partial growth never happens — the slot's pages are untouched on
        either failure."""
        need = self.pages_for(n_tokens)
        have = len(self.owned[slot])
        if need <= have:
            return "ok"
        if faults.should_fire("page_oom"):
            # injected pool pressure: report exhaustion WITHOUT touching
            # the slot's pages — identical contract to the real oom arm
            return "oom"
        if need > self.max_pages_per_seq:
            return "overflow"
        if need - have > len(self.free):
            return "oom"
        for i in range(have, need):
            page = self.alloc_page()
            self.owned[slot].append(page)
            self.page_table[slot, i] = page
        return "ok"

    def free_slot(self, slot: int) -> int:
        """Release ``slot``'s references; reset its row to the trash page.
        Under sharing a page only returns to the free list when its LAST
        holder (another slot, or the prefix tree) releases it — each
        holder releases exactly once, so a page can never enter the free
        list twice. Returns the number of page references released."""
        released = len(self.owned[slot])
        for page in self.owned[slot]:
            self.release(page)
        self.owned[slot] = []
        self.page_table[slot, :] = self.trash_page
        self.seq_lens[slot] = 0
        return released

    def decode_args(self) -> tuple:
        """What a decode launch takes of the host's tables (copies: they
        change in place while the launch is in flight)."""
        return (self.page_table.copy(), self.seq_lens.copy())

    def write_args(self, slot: int, prompt_len: int) -> tuple:
        """Where an admission's write puts the prefill's rows."""
        return (self.page_table[slot].copy(), np.int32(prompt_len))

    def reset_kv(self) -> None:
        """Reallocate the device page pool (supervised crash recovery): a
        decode step that died mid-call may have consumed the DONATED kv
        buffer, leaving ``self.kv`` pointing at deleted device memory.
        Shape and dtype are unchanged, so the engine's cached jit
        signatures stay valid — recovery never recompiles. Host-side page
        accounting is untouched; the caller frees/retries slots (and drops
        the prefix tree — its cached KV died with the buffer)."""
        self.kv = jnp.zeros(self._kv_shape, self._kv_dtype)

    def check_invariants(self, tree_refs=None) -> None:
        """Allocator soundness (test hook), refcount era: partition
        property (free XOR refcount >= 1, jointly covering the pool),
        table/owned agreement, and — when the prefix tree's per-page
        reference counts are passed as ``tree_refs`` — exact refcount
        accounting: rc(p) == slot holders + tree holders. Raises
        AssertionError on violation."""
        live = [p for p in range(self.num_pages) if self.refcount[p] > 0]
        assert sorted(self.free + live) == list(range(self.num_pages)), (
            f"page pool corrupt: free={sorted(self.free)} "
            f"live={live} owned={self.owned}")
        holders = {}
        for slot, pages in enumerate(self.owned):
            row = self.page_table[slot]
            assert list(row[:len(pages)]) == pages, (
                f"slot {slot} page-table row {row} disagrees with owned "
                f"{pages}")
            assert all(int(p) == self.trash_page
                       for p in row[len(pages):]), (
                f"slot {slot} has stale table entries past its pages: {row}")
            assert self.seq_lens[slot] <= len(pages) * self.page_size
            for p in pages:
                holders[p] = holders.get(p, 0) + 1
        for p in range(self.num_pages):
            assert self.refcount[p] >= holders.get(p, 0), (
                f"page {p}: refcount {self.refcount[p]} below its "
                f"{holders.get(p, 0)} slot holders")
        if tree_refs is not None:
            for p in range(self.num_pages):
                want = holders.get(p, 0) + int(tree_refs.get(p, 0))
                assert self.refcount[p] == want, (
                    f"page {p}: refcount {self.refcount[p]} != "
                    f"{holders.get(p, 0)} slot holders + "
                    f"{tree_refs.get(p, 0)} tree refs")


class SlotStatePool:
    """The cache of a model whose state is a fixed size a SLOT
    (``models/served.py`` ``SlotState``: a recurrence, not attention over a
    context): a dict of device arrays, each ``(max_slots,) + shape`` in the
    dtype the MODEL names, allocated once, donated through every serving
    program and updated where it lies. There are no pages: a slot IS where
    its state lies, so admission needs a slot and nothing else, and a
    sequence's only limit is ``page_size * max_pages_per_seq`` positions,
    the same two constructor arguments that bound a paged sequence.

    A freed slot's state is dead: nothing reads it (a decode step leaves an
    inactive slot's state as it is and drops its output), and the next
    admission's write replaces every value of it, which is the reset.

    The host-side surface is what the engine asks of ``PagedKVCache``:
    ``kv`` (the pool), ``seq_lens``, ``ensure_capacity``, ``free_slot``,
    ``reset_kv``, ``check_invariants``, ``max_context``; the page counts
    read 0 (nothing to allocate, nothing to run out of).

    Invariants: ``kv`` holds exactly the geometry's arrays at
    ``(max_slots,) + shape`` in their dtypes; ``0 <= seq_lens[slot] <=
    max_context()``; a freed slot's length is 0."""

    # no page is allocated, shared or written: what the engine reads of a
    # paged pool's accounting
    num_pages = 0
    free_pages = 0

    def __init__(self, *, state, page_size: int = 16, max_slots: int = 4,
                 max_pages_per_seq: int = 8):
        if page_size <= 0 or max_pages_per_seq <= 0 or max_slots <= 0:
            raise ValueError("page_size, max_pages_per_seq and max_slots "
                             "must be positive")
        if not state.arrays:
            raise ValueError("a slot's state needs at least one array")
        self.arrays = {name: (tuple(int(n) for n in shape), jnp.dtype(dtype))
                       for name, (shape, dtype) in state.arrays.items()}
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.seq_lens = np.zeros((self.max_slots,), np.int32)
        self.reset_kv()

    def pages_for(self, n_tokens: int) -> int:
        """A sequence of any length takes no page."""
        return 0

    def max_context(self) -> int:
        """Longest sequence one slot may hold: the paged pool's arithmetic
        from the same arguments (no page is allocated for it)."""
        return self.max_pages_per_seq * self.page_size

    def ensure_capacity(self, slot: int, n_tokens: int) -> str:
        """``"ok"``, or ``"overflow"`` past :meth:`max_context`; a state
        does not grow, so there is no ``"oom"`` but the injected one."""
        if faults.should_fire("page_oom"):
            return "oom"
        return "overflow" if n_tokens > self.max_context() else "ok"

    def free_slot(self, slot: int) -> int:
        """The slot's state is dead from here (see the class docstring).
        Returns 0: no page reference to release."""
        self.seq_lens[slot] = 0
        return 0

    def reset_kv(self) -> None:
        """Reallocate the pool (at start, and after a crash that may have
        consumed the DONATED buffers): same shapes and dtypes, so the
        engine's compiled programs stay valid."""
        self.kv = {name: jnp.zeros((self.max_slots,) + shape, dtype)
                   for name, (shape, dtype) in self.arrays.items()}

    def decode_args(self) -> tuple:
        """What a decode launch takes of the host's tables (a copy: they
        change in place while the launch is in flight)."""
        return (self.seq_lens.copy(),)

    def write_args(self, slot: int, prompt_len: int) -> tuple:
        """Where an admission's write puts the prefill's state."""
        return (np.int32(slot),)

    def check_invariants(self, tree_refs=None) -> None:
        assert tree_refs is None, "a slot-state pool shares no pages"
        assert set(self.kv) == set(self.arrays), (
            f"pool holds {sorted(self.kv)}, the geometry names "
            f"{sorted(self.arrays)}")
        for name, (shape, dtype) in self.arrays.items():
            got = self.kv[name]
            assert got.shape == (self.max_slots,) + shape and \
                got.dtype == dtype, (
                    f"pool array {name!r} is {got.dtype}{got.shape}, the "
                    f"geometry says {dtype}{(self.max_slots,) + shape}")
        assert np.all((self.seq_lens >= 0)
                      & (self.seq_lens <= self.max_context())), (
            f"sequence lengths {self.seq_lens} outside "
            f"[0, {self.max_context()}]")
