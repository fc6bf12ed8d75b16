"""Ragged-batch state management for continuous batching.

TPU-native analog of the reference FastGen ragged layer
(``inference/v2/ragged/``): ``BlockedAllocator`` (blocked_allocator.py:11),
``DSSequenceDescriptor`` (sequence_descriptor.py), ``DSStateManager``
(ragged_manager.py:19), and ``RaggedBatchWrapper`` (ragged_wrapper.py).

All of this is host-side bookkeeping (numpy, no device work): the device sees
only the dense arrays a ``RaggedBatch`` assembles — padded token/position
matrices plus per-sequence block tables into the paged KV pool. Static shape
buckets keep XLA recompiles rare; the pad rows' writes index past the pool's
last page and are dropped (see ``paged.py``).

Because this layer sits on the serving hot path (one assembly per dispatched
step), everything here is O(1)-per-item and vectorized:

  - ``BlockedAllocator`` is a preallocated int32 free *stack* plus a boolean
    free bitmap — allocate/free are numpy slice copies, no Python-level
    per-block work (the reference's torch-tensor free list, same idea).
  - ``SequenceDescriptor`` carries its block table as a preallocated numpy
    row, so copying it into the batch's ``block_tables`` is one memcpy.
  - ``BatchStaging`` keeps one set of pinned staging buffers per
    (rows, chunk) bucket, reused across steps — steady-state assembly does
    zero allocation and writes tokens/positions with vectorized masked
    scatters instead of per-token Python loops.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.inference.cache import PlainLayout


class BlockedAllocator:
    """O(1)-per-block free-list allocator for KV-cache blocks (reference
    ``BlockedAllocator`` inference/v2/ragged/blocked_allocator.py:11).

    A list free stack plus a ``bytearray`` free bitmap: C-level slice
    pops/extends move whole batches, the bitmap gives ~40ns double-free
    detection per block, and no numpy call overhead rides the small-alloc
    path (a decode step allocates a handful of blocks; numpy's per-call
    fixed cost would dominate it). ``allocate`` returns an int32 ndarray so
    downstream block-table writes stay vectorized. ``free`` validates the
    whole batch before mutating — a bad call leaves the allocator unchanged.

    Ref-counted sharing (prefix cache, ISSUE 12): every allocated block
    carries a reference count (``allocate`` sets it to 1). ``share`` adds a
    holder, ``release`` drops one and returns the block to the free stack at
    zero. ``free`` keeps the strict single-owner contract: freeing a block
    another holder still references raises. All four ops validate the whole
    batch before mutating and roll back on error — a bad call leaves the
    bitmap, the refcounts, and the stack unchanged (invariant:
    ``_refs[b] == 0  <=>  _state[b] == 1`` i.e. free).
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"need at least 1 block, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free_stack: List[int] = list(range(num_blocks - 1, -1, -1))
        self._state = bytearray(b"\x01" * num_blocks)  # 1 = free
        self._refs: List[int] = [0] * num_blocks  # holders per block

    @property
    def free_blocks(self) -> int:
        return len(self._free_stack)

    def refcount(self, block: int) -> int:
        """Holders of ``block`` (0 = free)."""
        return self._refs[block]

    def allocate(self, n: int) -> np.ndarray:
        stack = self._free_stack
        if n > len(stack):
            raise RuntimeError(f"cannot allocate {n} blocks ({len(stack)} free)")
        if n == 0:
            return np.empty((0,), np.int32)
        out = stack[-n:]
        del stack[-n:]
        state = self._state
        refs = self._refs
        for b in out:
            state[b] = 0
            refs[b] = 1
        return np.asarray(out, dtype=np.int32)

    def free(self, blocks: Sequence[int]) -> None:
        """Strict single-owner free: every block must have exactly one holder.
        Freeing a shared block (refcount > 1) raises — use ``release`` for
        refcounted holders."""
        lst = blocks.tolist() if isinstance(blocks, np.ndarray) else list(blocks)
        if not lst:
            return
        state = self._state
        refs = self._refs
        num = self.num_blocks
        i = 0
        try:
            for i, b in enumerate(lst):
                if b < 0 or b >= num or state[b]:  # bitmap catches in-call dupes too
                    raise ValueError(f"bad free of block {b}")
                if refs[b] != 1:
                    raise ValueError(
                        f"free of shared block {b} (refcount {refs[b]}); "
                        "holders must release, not free")
                state[b] = 1
                refs[b] = 0
        except ValueError:
            for b in lst[:i]:  # roll back: a bad call leaves state unchanged
                state[b] = 0
                refs[b] = 1
            raise
        self._free_stack.extend(lst)

    def share(self, blocks: Sequence[int]) -> None:
        """Add one holder to each allocated block (batch-validated: a bad id
        or a free block anywhere in the call leaves every refcount
        unchanged)."""
        lst = blocks.tolist() if isinstance(blocks, np.ndarray) else list(blocks)
        if not lst:
            return
        refs = self._refs
        num = self.num_blocks
        i = 0
        try:
            for i, b in enumerate(lst):
                if b < 0 or b >= num or refs[b] < 1:
                    raise ValueError(f"share of unallocated block {b}")
                refs[b] += 1
        except ValueError:
            for b in lst[:i]:
                refs[b] -= 1
            raise

    def release(self, blocks: Sequence[int]) -> int:
        """Drop one holder from each block; blocks reaching zero holders
        return to the free stack. Releasing a free block (double release)
        raises, with full rollback. Returns how many blocks became free."""
        lst = blocks.tolist() if isinstance(blocks, np.ndarray) else list(blocks)
        if not lst:
            return 0
        state = self._state
        refs = self._refs
        num = self.num_blocks
        freed: List[int] = []
        i = 0
        try:
            for i, b in enumerate(lst):
                if b < 0 or b >= num or refs[b] < 1:
                    raise ValueError(f"double release of block {b}")
                refs[b] -= 1
                if refs[b] == 0:
                    state[b] = 1
                    freed.append(b)
        except ValueError:
            for b in lst[:i]:  # roll back refcounts AND the bitmap
                if refs[b] == 0:
                    state[b] = 0
                refs[b] += 1
            raise
        self._free_stack.extend(freed)
        return len(freed)


@dataclasses.dataclass
class SequenceDescriptor:
    """Per-sequence tracking (reference ``DSSequenceDescriptor``).

    The block table is a preallocated int32 row, the layout's whole width
    (``cache.PlainLayout``, ``WindowLayout`` or ``RingLayout``): its live pages
    are the first ``n_summary`` columns and ``n_window`` columns from
    ``summary_cols`` on, and ``n_blocks`` is their sum. A row without a window
    or a ring has the first group alone, a block of positions a column.
    """

    uid: int
    layout: Any
    _table: np.ndarray
    seen_tokens: int = 0
    n_blocks: int = 0
    n_summary: int = 0
    n_window: int = 0
    # a model with recurrent state: the sequence's slot of the state pool
    # (``cache.StatePool``), which is also its ROW in every program it is fed to
    slot: Optional[int] = None

    @property
    def blocks(self) -> np.ndarray:
        """Live block ids (a view where the first group holds them all — do not mutate; else a copy)."""
        if not self.n_window:
            return self._table[: self.n_summary]
        first = self.layout.summary_cols
        return np.concatenate([self._table[: self.n_summary], self._table[first: first + self.n_window]])

    def table_into(self, row: np.ndarray) -> None:
        """The block table as the device reads it, into ``row`` (zeros: no dead column holds a page)."""
        first = self.layout.summary_cols
        row[: self.n_summary] = self._table[: self.n_summary]
        row[first: first + self.n_window] = self._table[first: first + self.n_window]

    def hold(self, fresh: np.ndarray, summary: int) -> None:
        """Take ``fresh`` pages: the first group's up to ``summary`` columns, the rest the second's."""
        first = self.layout.summary_cols
        more = max(summary - self.n_summary, 0)
        self._table[self.n_summary: self.n_summary + more] = fresh[:more]
        self._table[first + self.n_window: first + self.n_window + len(fresh) - more] = fresh[more:]
        self.n_summary += more
        self.n_window += len(fresh) - more
        self.n_blocks = self.n_summary + self.n_window

    def pages_behind(self, keep: int) -> np.ndarray:
        """Give up the second group's pages past the first ``keep``."""
        first = self.layout.summary_cols
        gone = self._table[first + keep: first + self.n_window].copy()
        self._table[first + keep: first + self.n_window] = 0
        self.n_window = min(self.n_window, keep)
        self.n_blocks = self.n_summary + self.n_window
        return gone

    def append_blocks(self, new: np.ndarray) -> None:
        """Pages somebody else filled, in position order (a prefix hit's, a migration's): the first group's."""
        self.hold(new, self.n_summary + len(new))


class StateManager:
    """uid -> sequence state + block accounting (reference ``DSStateManager``
    inference/v2/ragged/ragged_manager.py:19): ONE path whatever the model
    caches. The layout (``cache.CachePlan.layout``; none given: a block of
    positions a column, ``max_blocks_per_seq`` or the pool's worth) says how
    many pages of each class a row holds, and there is an allocator a class:
    ``allocators[0]`` the one every model has (the prefix cache's and a
    migration's), ``allocators[1]`` a ``RingLayout``'s ring, ``ring_blocks`` pages."""

    def __init__(self, num_blocks: int, block_size: int, max_seqs: int = 256, max_blocks_per_seq: Optional[int] = None,
                 layout: Any = None, state_slots: Optional[int] = None, ring_blocks: Optional[int] = None):
        self.layout = layout or PlainLayout(block_size, (max_blocks_per_seq or num_blocks) * block_size)
        sizes = (num_blocks, ring_blocks)[: max(self.layout.classes) + 1]
        self.allocators = tuple(BlockedAllocator(n) for n in sizes)
        self.block_size = block_size
        self.max_seqs = max_seqs
        self._seqs: Dict[int, SequenceDescriptor] = {}
        # Recurrent state (``cache.StatePool``): a sequence takes the LOWEST free
        # slot with its descriptor and gives it back at its flush, finished or
        # preempted. Its slot is its row in every program, so the lowest keeps
        # the programs' row buckets small. None: the model keeps no such state.
        self.state_slots = state_slots
        self._free_slots: Optional[List[int]] = None if state_slots is None else list(range(state_slots))

    @property
    def allocator(self) -> BlockedAllocator:
        """The first class's: the pages every model has."""
        return self.allocators[0]

    @property
    def n_active(self) -> int:
        return len(self._seqs)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def utilization(self) -> float:
        """Fraction of the KV pool's blocks currently allocated (the
        ``serving/kv_pool_utilization`` gauge)."""
        total = self.allocator.num_blocks
        return (total - self.allocator.free_blocks) / total

    def get(self, uid: int) -> Optional[SequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create(self, uid: int) -> SequenceDescriptor:
        if uid not in self._seqs:
            if len(self._seqs) >= self.max_seqs:
                raise RuntimeError(f"max_seqs={self.max_seqs} active sequences reached")
            slot = None
            if self._free_slots is not None:
                if not self._free_slots:
                    raise RuntimeError(f"no free state slot: all {self.state_slots} hold a sequence")
                slot = heapq.heappop(self._free_slots)
            self._seqs[uid] = SequenceDescriptor(uid, self.layout, np.zeros((self.layout.width,), np.int32), slot=slot)
        return self._seqs[uid]

    @property
    def state_slots_in_use(self) -> int:
        return 0 if self._free_slots is None else self.state_slots - len(self._free_slots)

    def rows_of(self, uids: Sequence[int], row_bucket: int) -> Tuple[np.ndarray, int]:
        """(the program's row of each of ``uids``, the rows the program needs,
        a multiple of ``row_bucket``). A sequence with a state slot is fed in
        the row of that number, so that a state-space layer updates ONE slice of
        the state pool; any other takes the rows in the order given."""
        at = np.arange(len(uids)) if self._free_slots is None else np.fromiter(
            (self._seqs[u].slot for u in uids), dtype=np.int64, count=len(uids))
        return at, _round_up(int(at.max(initial=-1)) + 1, row_bucket)

    def can_schedule(self, uids: Sequence[int], token_counts: Sequence[int]) -> bool:
        """Admission check (reference ``InferenceEngineV2.can_schedule`` :184). The serving loop asks it of a
        growing list at every admission, so a row costs a dict lookup, one ``pages()`` and a few compares."""
        need_s = need_w = fresh = 0  # pages short in each group of columns
        seqs, pages, capacity = self._seqs, self.layout.pages, self.layout.capacity
        for uid, n in zip(uids, token_counts):
            seq = seqs.get(uid)
            if seq is None:
                fresh += 1
                seen = have_s = have_w = 0
            else:
                seen, have_s, have_w = seq.seen_tokens, seq.n_summary, seq.n_window
            if seen + n > capacity:
                return False  # sequence would exceed engine max_seq_len
            want_s, want_w = pages(seen, n)
            if want_s > have_s:
                need_s += want_s - have_s
            if want_w > have_w:
                need_w += want_w - have_w
        need = [0] * len(self.allocators)
        need[self.layout.classes[0]] += need_s
        need[self.layout.classes[1]] += need_w
        if len(self._seqs) + fresh > self.max_seqs:
            return False
        if self._free_slots is not None and fresh > len(self._free_slots):
            return False  # pages for it, and no state slot
        return all(n <= a.free_blocks for n, a in zip(need, self.allocators))

    def extend(self, uid: int, new_tokens: int) -> SequenceDescriptor:
        """Ensure blocks exist for ``new_tokens`` more tokens of ``uid``, each group's from its own class."""
        seq = self.get_or_create(uid)
        want_s, want_w = self.layout.pages(seq.seen_tokens, new_tokens)
        first, second = self.layout.classes
        if want_s > seq.n_summary:  # (``hold`` lays fresh pages over the columns of the group that is short)
            seq.hold(self.allocators[first].allocate(want_s - seq.n_summary), want_s)
        if want_w > seq.n_window:
            seq.hold(self.allocators[second].allocate(want_w - seq.n_window), seq.n_summary)
        return seq

    def advance(self, uid: int, tokens: int) -> int:
        """``uid`` has been fed ``tokens`` more. Returns how many windows that closed (``WindowLayout``), and
        gives their allocator back the pages behind the last one."""
        seq = self._seqs[uid]
        before = seq.seen_tokens
        seq.seen_tokens = before + tokens
        closed, keep = self.layout.closed(before, seq.seen_tokens)
        if closed and keep < seq.n_window:
            self.allocators[self.layout.classes[1]].release(seq.pages_behind(keep))
        return closed

    def flush(self, uid: int) -> None:
        """Release a finished sequence (reference ``flush_uid`` engine_v2.py).
        Refcount-aware: blocks the prefix cache still holds stay allocated
        (the sequence drops its reference); exclusively-owned blocks return
        to the free stack — identical to ``free`` when nothing is shared.
        Each group's pages go back to their own class's free list."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            return
        first = self.layout.summary_cols
        for cls, pages in zip(self.layout.classes, (seq._table[: seq.n_summary],
                                                    seq._table[first: first + seq.n_window])):
            self.allocators[cls].release(pages)
        if seq.slot is not None:
            heapq.heappush(self._free_slots, seq.slot)  # as it stands: the next sequence starts from zeros


# --------------------------------------------------------------- prefix cache
@dataclasses.dataclass
class PrefixHit:
    """Result of a prefix-cache lookup against a prompt.

    ``blocks`` are FULL cached blocks covering ``len(blocks) * block_size``
    leading tokens (already position-aligned: chain keys start at position
    0, so a hit is only possible for identically positioned content).
    ``cow_block``/``cow_len`` describe an optional partial hit one block
    deeper: a cached block whose first ``cow_len`` tokens match the prompt's
    next tokens — reusable via copy-on-write at the first divergent token.
    """

    blocks: List[int]
    cow_block: Optional[int] = None
    cow_len: int = 0

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)


@dataclasses.dataclass
class _PrefixEntry:
    key: bytes
    block: int
    tokens: np.ndarray  # the block_size token ids this block's KV encodes
    parent: bytes  # chain key of the preceding prefix ('' for block 0)
    content_hash: Optional[str] = None  # blake2b over the quantized pool bytes


class PrefixCache:
    """Content-addressed KV-block reuse over the paged pool (ROADMAP #1b).

    Host-side index: chain-hash of position-aligned token blocks -> pool
    block id, with the allocator's refcounts making shared blocks safe
    (the cache itself holds one reference per entry; sequences reusing a
    block hold their own). Each entry additionally records a blake2b digest
    of the block's *quantized pool bytes* (values + scale pages together —
    exactly the PR-10 layout) at insert time: the cached artifact IS the
    quantized bytes attention reads, so a hit is never re-quantized and the
    digest pins that sharing/COW/eviction never corrupted the stored bytes
    (asserted by the correctness tests and the nightly smoke).

    LRU eviction: entries release their block reference in LRU order when
    ``capacity_blocks`` is exceeded or the engine needs blocks back
    (``evict_one`` under admission pressure). Releasing while a live
    sequence still references the block only drops the cache's hold — the
    block returns to the free stack at refcount zero.
    """

    def __init__(self, allocator: BlockedAllocator, block_size: int,
                 capacity_blocks: Optional[int] = None):
        from collections import OrderedDict

        self.allocator = allocator
        self.block_size = block_size
        self.capacity_blocks = capacity_blocks
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        self._children: Dict[bytes, List[bytes]] = {}
        # accounting for serving/prefix_* metrics
        self.lookups = 0
        self.hits = 0  # lookups that reused >= 1 token
        self.hit_tokens = 0  # tokens served from cache (incl. COW prefixes)
        self.insertions = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _chain_key(parent: bytes, tokens: np.ndarray) -> bytes:
        import hashlib

        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    # ----------------------------------------------------------------- lookup
    def match(self, tokens: np.ndarray) -> PrefixHit:
        """Longest cached prefix of ``tokens``, full blocks first, then an
        optional COW partial block. Reuse is capped at ``len(tokens) - 1``
        so at least one token remains to prefill (the step that samples the
        first new token needs a non-empty row)."""
        bs = self.block_size
        usable = max(len(tokens) - 1, 0)
        key = b""
        blocks: List[int] = []
        pos = 0
        while pos + bs <= usable:
            k = self._chain_key(key, tokens[pos: pos + bs])
            e = self._entries.get(k)
            if e is None:
                break
            self._entries.move_to_end(k)  # LRU touch
            blocks.append(e.block)
            key = k
            pos += bs
        # partial hit one block deeper: longest common prefix against any
        # cached child of the matched chain -> COW at the divergent token
        cow_block, cow_len, cow_key = None, 0, None
        rest = np.asarray(tokens[pos:usable], np.int32)
        if len(rest) > 0:
            for ck in self._children.get(key, ()):
                e = self._entries.get(ck)
                if e is None:
                    continue
                n = min(len(rest), bs)
                lcp = int((e.tokens[:n] == rest[:n]).cumprod().sum())
                if lcp > cow_len and lcp < bs:
                    cow_block, cow_len, cow_key = e.block, lcp, ck
            if cow_key is not None:
                self._entries.move_to_end(cow_key)
        return PrefixHit(blocks=blocks, cow_block=cow_block, cow_len=cow_len)

    def record(self, hit: Optional[PrefixHit]) -> None:
        """Count one ADMISSION's lookup outcome. Deliberately separate from
        ``match``: admission may re-probe the same stalled request every
        scheduling round while the pool is full — counting at match time
        would let one stalled request skew ``serving/prefix_hit_rate`` by
        its retry count."""
        self.lookups += 1
        if hit is not None and (hit.blocks or hit.cow_len):
            self.hits += 1
            self.hit_tokens += len(hit.blocks) * self.block_size + hit.cow_len

    @property
    def hit_rate(self) -> float:
        """Fraction of admissions that reused at least one cached token."""
        return self.hits / self.lookups if self.lookups else 0.0

    # ----------------------------------------------------------------- insert
    def insert(self, tokens: np.ndarray, blocks: Sequence[int],
               hasher=None) -> int:
        """Index the FULL blocks of ``tokens`` (``blocks[i]`` holds tokens
        ``[i*bs, (i+1)*bs)``). Already-cached prefixes are skipped; each new
        entry takes one ``share`` reference on its block and records
        ``hasher(block_id)`` (the quantized-bytes digest) when a hasher is
        given — called only for NEW entries, so re-inserting a warm prefix
        costs no device fetch. Returns the number of entries added."""
        bs = self.block_size
        n_full = min(len(tokens) // bs, len(blocks))
        key = b""
        added = 0
        for i in range(n_full):
            chunk = np.asarray(tokens[i * bs: (i + 1) * bs], np.int32)
            k = self._chain_key(key, chunk)
            if k not in self._entries:
                if self.capacity_blocks is not None:
                    while (len(self._entries) >= self.capacity_blocks
                           and self.evict_one()):
                        pass
                    if len(self._entries) >= self.capacity_blocks:
                        break
                self.allocator.share([int(blocks[i])])
                self._entries[k] = _PrefixEntry(
                    key=k, block=int(blocks[i]), tokens=chunk.copy(),
                    parent=key,
                    content_hash=hasher(int(blocks[i])) if hasher else None)
                self._children.setdefault(key, []).append(k)
                self.insertions += 1
                added += 1
            else:
                self._entries.move_to_end(k)
            key = k
        return added

    # --------------------------------------------------------------- eviction
    def evict_one(self) -> bool:
        """Release the LRU entry's block reference. Returns False when
        empty."""
        if not self._entries:
            return False
        key, e = next(iter(self._entries.items()))
        del self._entries[key]
        sibs = self._children.get(e.parent)
        if sibs is not None:
            try:
                sibs.remove(key)
            except ValueError:
                pass
            if not sibs:
                del self._children[e.parent]
        self.allocator.release([e.block])
        self.evictions += 1
        return True

    def clear(self) -> None:
        while self.evict_one():
            pass


@dataclasses.dataclass
class RaggedBatch:
    """Dense view of one scheduling step (reference ``RaggedBatchWrapper``).

    Rows are sequences; pad rows have ``new_lens == 0``. ``tokens`` is
    right-padded to the chunk bucket; ``block_tables`` is padded with 0 (pad
    slots never read: masked by position; never written: pad writes index out
    of the pool and drop).

    When assembled through a ``BatchStaging``, the arrays are views into that
    staging pool and are overwritten by the next assembly of the same
    (rows, chunk) bucket — consume (i.e. ``jnp.asarray``) before rebuilding.
    """

    uids: List[int]
    tokens: np.ndarray  # [N, C] int32
    positions: np.ndarray  # [N, C] int32 (global position of each new token)
    new_lens: np.ndarray  # [N] int32
    block_tables: np.ndarray  # [N, P] int32
    seen: np.ndarray  # [N] int32 (tokens already in cache, before this step)
    # the rows that hold ``uids``, in their order: the first ones, or each
    # sequence's state slot (``StateManager.rows_of``)
    at: Any = None

    @property
    def n_rows(self) -> int:
        return self.tokens.shape[0]


class BatchStaging:
    """Reusable per-(rows, chunk)-bucket staging buffers for batch assembly.

    One set of host arrays per bucket, zeroed and refilled in place each step
    — the device copy (``jnp.asarray`` at dispatch) is the only per-step
    allocation left. ``allocations``/``reuses`` are exposed so tests and the
    serving benchmark can assert steady-state reuse.
    """

    def __init__(self, max_pages: int):
        self.max_pages = max_pages
        self._bufs: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
        self._dirty_rows: Dict[Tuple[int, int], int] = {}
        self.allocations = 0
        self.reuses = 0

    def acquire(self, rows: int, chunk: int) -> Dict[str, np.ndarray]:
        key = (rows, chunk)
        b = self._bufs.get(key)
        if b is None:
            b = {
                "tokens": np.zeros((rows, chunk), np.int32),
                "positions": np.zeros((rows, chunk), np.int32),
                "new_lens": np.zeros((rows,), np.int32),
                "block_tables": np.zeros((rows, self.max_pages), np.int32),
                "seen": np.zeros((rows,), np.int32),
            }
            self._bufs[key] = b
            self.allocations += 1
        else:
            self.reuses += 1
            d = self._dirty_rows.get(key, rows)
            for a in b.values():  # zero only the rows the previous step touched
                a[:d] = 0
        return b

    def mark_dirty(self, rows: int, chunk: int, used_rows: int) -> None:
        self._dirty_rows[(rows, chunk)] = used_rows


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def build_ragged_batch(
    manager: StateManager,
    uids: Sequence[int],
    token_lists: Sequence[np.ndarray],
    max_pages: int,
    row_bucket: int = 8,
    chunk_bucket: int = 8,
    staging: Optional[BatchStaging] = None,
) -> RaggedBatch:
    """Allocate blocks and assemble the dense step arrays.

    Caller must have checked ``can_schedule`` and pass distinct uids; this
    raises if blocks run out. With ``staging``, the returned arrays are the
    staging pool's buffers (zero allocation in steady state); without, fresh
    arrays are allocated.
    """
    n = len(uids)
    assert n == len(token_lists) and n > 0
    lens = np.fromiter((len(t) for t in token_lists), dtype=np.int64, count=n)
    chunk = _round_up(max(int(lens.max()), 1), chunk_bucket)
    if manager.layout.one_token_program and lens.max() <= 1:
        chunk = 1
    seqs = [manager.get_or_create(uid) for uid in uids]
    at, rows = slice(0, n), _round_up(n, row_bucket)
    if manager.state_slots is not None:
        at, rows = manager.rows_of(uids, row_bucket)  # a sequence's row is its state slot
    used = n if isinstance(at, slice) else int(at.max()) + 1

    staging = staging or BatchStaging(max_pages)  # (none given: fresh arrays)
    if staging.max_pages != max_pages:
        raise ValueError(f"staging max_pages={staging.max_pages} != requested {max_pages}")
    buf = staging.acquire(rows, chunk)
    tokens, positions = buf["tokens"], buf["positions"]
    new_lens, block_tables, seen = buf["new_lens"], buf["block_tables"], buf["seen"]
    staging.mark_dirty(rows, chunk, used)

    seen_v = np.fromiter((s.seen_tokens for s in seqs), dtype=np.int32, count=n)
    over = seen_v.astype(np.int64) + lens > manager.layout.capacity
    if over.any():
        i = int(np.argmax(over))
        raise RuntimeError(
            f"uid {uids[i]}: {int(seen_v[i] + lens[i])} tokens exceeds engine "
            f"max_seq_len={manager.layout.max_seq_len}")
    manager.layout.check_fed(uids, lens, seen_v)
    for uid, length in zip(uids, lens):  # the layout says how many pages of each class a row holds
        manager.extend(uid, int(length))

    # --- vectorized fills (no per-token Python loops)
    new_lens[at] = lens
    seen[at] = seen_v
    if int(lens.max()) == 1:
        # decode fast path: one token per row, position == seen (a
        # zero-length row stays a pad: new_lens==0 masks it device-side)
        positions[at, 0] = seen_v
        tokens[at, 0] = np.fromiter(
            (t[0] if len(t) else 0 for t in token_lists), dtype=np.int64, count=n)
    else:
        col = np.arange(chunk)
        valid = col[None, :] < lens[:, None]  # [n, chunk]
        positions[at] = np.where(valid, seen_v[:, None] + col[None, :], 0)
        # row-major boolean scatter == concatenation order of the ragged lists
        fed = np.zeros((n, chunk), np.int32)
        fed[valid] = np.concatenate([np.asarray(t, np.int32) for t in token_lists])
        tokens[at] = fed
    for i, s in zip(range(n) if isinstance(at, slice) else at, seqs):
        s.table_into(block_tables[i])

    return RaggedBatch(uids=list(uids), tokens=tokens, positions=positions, new_lens=new_lens,
                       block_tables=block_tables, seen=seen, at=at)
