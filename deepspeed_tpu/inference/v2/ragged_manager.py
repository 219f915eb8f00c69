"""Ragged batch state management.

Reference: `inference/v2/ragged/ragged_manager.py:19` (`DSStateManager`) +
`sequence_descriptor.py` — tracks every live sequence's KV block lease and
token progress, and hands the engine per-step batch descriptors.

The scheduling policy implemented by the engine on top of this state is the
FastGen "Dynamic SplitFuse" (blogs/deepspeed-fastgen): long prompts are
split into fixed-size chunks so every engine step does a bounded amount of
work, and token generation continues every step.  TPU adaptation: the
per-step shapes are fixed (chunk size, max concurrent sequences), so the
whole serving loop runs in a few compiled programs (bucketed
prefill-chunks, decode).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from .blocked_allocator import BlockedAllocator, KindCounts

__all__ = ["SequenceDescriptor", "DSStateManager", "KIND_NAMES"]

# the kinds of a two-kind cache, in the order their counts are given
KIND_NAMES = ("global", "window")


@dataclass
class SequenceDescriptor:
    """Reference: sequence_descriptor.py — per-sequence tracked state."""
    uid: int
    prompt: np.ndarray                       # full prompt token ids
    seen_tokens: int = 0                     # tokens already in the KV cache
    blocks: List[int] = field(default_factory=list)
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # prompt tokens covered by a shared KV prefix at create time
    # (serving/prefix_cache.py): positions [0, prefix_covered) live in
    # read-only shared blocks and are never re-prefilled or re-written;
    # prefill starts at this offset.  0 = no shared prefix (all of
    # today's behavior).
    prefix_covered: int = 0
    # a two-kind cache's window-kind lease: table entry -> block, for the
    # entries the sequence still holds (`blocks` is the global kind's)
    window_blocks: Dict[int, int] = field(default_factory=dict)
    # the slot of per-sequence recurrent state the sequence holds from
    # `create` to `flush` (-1: the model keeps none)
    state_slot: int = -1

    @property
    def in_prefill(self) -> bool:
        return self.seen_tokens < len(self.prompt)


class DSStateManager:
    """Owns the allocator + live sequences; builds step descriptors.

    `window` = (tokens, window-kind blocks) makes the cache TWO-KIND:
    `num_blocks` then counts the global kind (`allocator`,
    `SequenceDescriptor.blocks`: every position of a sequence), and a
    second free list (`window_allocator`, `window_blocks`) serves the
    window layers, of which a sequence holds only the blocks that still
    have a key inside the window of some query to come: at most
    `window_row_blocks` = `ceil(window / block_size) + 1` between two
    steps, which is what admission reserves a row (`blocks_needed`).
    Through a step that prefills it, a row also holds what the chunk
    READS, so more: `chunk_room` cuts a chunk to what the pool can lease
    beyond the rows' steady shares.  `ensure_capacity` leases both kinds,
    hands a window-kind block back once it lies wholly behind the window,
    and a row's table is `[2, max_blocks_per_seq]` with -1 at the window
    kind's dead entries.

    `state_slots` > 0 adds a THIRD kind of state: slots of fixed-size
    per-sequence recurrent state (a state-space mixer's), one a live
    sequence, leased at `create` and handed back at `flush` through a free
    list of their own (`free_state_slots`).  A slot is not cleared when it
    changes hands: a fresh prompt's scan starts from zeros and overwrites
    it (`ssm_ops`)."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, max_seqs: int, window=None,
                 state_slots: int = 0):
        self.allocator = BlockedAllocator(num_blocks)
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_seqs = max_seqs
        self.seqs: Dict[int, SequenceDescriptor] = {}
        self.window = window[0] if window else 0
        self.window_allocator = (BlockedAllocator(window[1]) if window
                                 else None)
        self.window_row_blocks = -(-self.window // block_size) + 1
        # window-kind blocks handed back because they fell behind the
        # window (flushes not counted), and those of them a step's account
        # has reported (`hybrid_ops.step_account`)
        self.window_released = self.window_reported = 0
        self.state_slots = state_slots
        self._free_state_slots = list(range(state_slots - 1, -1, -1))

    # -- lifecycle -------------------------------------------------------
    def create(self, uid: int, prompt_tokens,
               prefix=None) -> SequenceDescriptor:
        """Track a new sequence.  `prefix` is an optional matched KV
        prefix `(block_ids, covered_tokens)` from the radix prefix cache
        (serving/prefix_cache.py): the sequence attaches those shared
        read-only blocks, starts prefill at position `covered_tokens`,
        and only the uncovered suffix is ever computed.  The caller must
        already hold a reference on each shared block (PrefixCache.
        acquire does); flush releases it with everything else."""
        if uid in self.seqs:
            raise ValueError(f"uid {uid} already tracked")
        if len(self.seqs) >= self.max_seqs:
            raise RuntimeError(
                f"too many concurrent sequences (max_seqs={self.max_seqs})")
        if self.state_slots and not self._free_state_slots:
            raise RuntimeError(
                f"no free recurrent-state slot (all {self.state_slots} "
                f"held by live sequences)")
        d = SequenceDescriptor(uid=uid,
                               prompt=np.asarray(prompt_tokens, np.int32))  # dstpu: noqa[DST001] prompt tokens arrive as host arrays per the engine contract
        if prefix is not None:
            blocks, covered = prefix
            if covered % self.block_size:
                raise ValueError(
                    f"prefix covered={covered} is not block-aligned "
                    f"(block_size {self.block_size}): only whole blocks "
                    f"can be shared read-only")
            if len(blocks) * self.block_size != covered:
                raise ValueError(
                    f"prefix has {len(blocks)} blocks for covered="
                    f"{covered} tokens (block_size {self.block_size})")
            if covered >= len(d.prompt):
                raise ValueError(
                    f"prefix covers {covered} of a {len(d.prompt)}-token "
                    f"prompt: at least the last prompt token must prefill "
                    f"so the sequence produces first-token logits")
            d.blocks = list(blocks)
            d.seen_tokens = covered
            d.prefix_covered = covered
        if self.state_slots:
            d.state_slot = self._free_state_slots.pop()
        self.seqs[uid] = d
        return d

    def flush(self, uid: int) -> None:
        """Release the sequence's lease on its blocks (reference: state
        manager flush).  With per-block refcounts this is decref-to-zero:
        private blocks return to the free list, shared prefix blocks
        stay allocated for their remaining owners (the cache, other
        matching sequences)."""
        d = self.seqs.pop(uid)
        if d.blocks:
            self.allocator.free(d.blocks)
        if d.window_blocks:
            self.window_allocator.free(d.window_blocks.values())
            d.window_blocks.clear()
        if d.state_slot >= 0:
            self._free_state_slots.append(d.state_slot)
            d.state_slot = -1

    def ensure_capacity(self, d: SequenceDescriptor, upto_tokens: int,
                        first_query: int = None) -> None:
        """Lease blocks so positions [0, upto_tokens) fit.  In a two-kind
        cache the step's first query stands at `first_query` (default: the
        last position, a decode step): window-kind blocks wholly behind
        ITS window go back first, then the entries from the window of the
        next query to come (at `upto_tokens`) on are leased.  Entries in
        between (a chunk longer than the window) stay dead: no later query
        sees their keys."""
        need = -(-upto_tokens // self.block_size)  # ceil
        if need > self.max_blocks_per_seq:
            raise RuntimeError(
                f"sequence {d.uid} needs {need} blocks > max_blocks_per_seq "
                f"{self.max_blocks_per_seq}")
        grow = max(need - len(d.blocks), 0)
        if self.window:
            q0 = upto_tokens - 1 if first_query is None else first_query
            self.release_behind(d, q0)
            new = self._window_entries(d, upto_tokens)
            if grow > self.allocator.free_blocks:
                self.allocator.allocate(grow)     # raises: nothing leased
            d.window_blocks.update(
                zip(new, self.window_allocator.allocate(len(new))))
        if grow:
            d.blocks.extend(self.allocator.allocate(grow))

    def _window_entries(self, d: SequenceDescriptor, upto: int) -> List[int]:
        """The window-kind table entries `d` lacks for positions up to
        `upto`: those of the window of the next query to come."""
        first = max(0, upto - self.window) // self.block_size
        return [j for j in range(first, -(-upto // self.block_size))
                if j not in d.window_blocks]

    def chunk_room(self, d: SequenceDescriptor, start: int, n: int) -> int:
        """Of a prompt chunk [start, start + n) of `d`, the tokens the
        cache can take now: all `n`, or fewer where the window kind is
        short (0 to `n`, cut at a block's edge).  A row may always reach
        its steady share (`window_row_blocks`, which admission reserved
        it); through a chunk it holds the blocks the chunk reads beside
        those it writes, and that excess comes out of what the pool has
        free AFTER every other row's steady share, so no step of theirs
        ever finds the pool empty.  The blocks behind the window of the
        row's first query of this step go back first."""
        if not self.window:
            return n
        self.release_behind(d, d.seen_tokens)
        held, share = len(d.window_blocks), self.window_row_blocks
        if held + len(self._window_entries(d, start + n)) <= share:
            return n
        spare = self.window_allocator.free_blocks - sum(
            max(0, share - len(r.window_blocks)) for r in self.seqs.values())
        room = max(share - held, 0) + max(spare, 0)
        bs = self.block_size
        # the chunk's last position, from its end back by blocks
        for end in [start + n] + list(range((start + n - 1) // bs * bs,
                                            start, -bs)):
            if len(self._window_entries(d, end)) <= room:
                return end - start
        return 0

    def release_behind(self, d: SequenceDescriptor, query: int) -> None:
        """Hand back the window-kind blocks no query at position `query`
        or later can see (every key at or before `query - window`)."""
        first = max(0, query - self.window + 1) // self.block_size
        dead = [j for j in d.window_blocks if j < first]
        if dead:
            self.window_allocator.free(d.window_blocks.pop(j) for j in dead)
            self.window_released += len(dead)

    # -- two kinds, counted -------------------------------------------------
    def blocks_needed(self, tokens: int):
        """Blocks a sequence of `tokens` tokens holds at most: an int, or
        one count a kind (`KIND_NAMES`)."""
        n = -(-tokens // self.block_size)
        if not self.window:
            return n
        return KindCounts((n, min(n, self.window_row_blocks)))

    def blocks_leased(self, d: SequenceDescriptor):
        if not self.window:
            return len(d.blocks)
        return KindCounts((len(d.blocks), len(d.window_blocks)))

    @property
    def free_blocks(self):
        if not self.window:
            return self.allocator.free_blocks
        return KindCounts((self.allocator.free_blocks,
                           self.window_allocator.free_blocks))

    @property
    def free_state_slots(self) -> int:
        """Recurrent-state slots no live sequence holds (0 where the model
        keeps no such state: ask `state_slots` first)."""
        return len(self._free_state_slots)

    # -- block conservation audit ----------------------------------------
    def audit(self, cache_blocks=()) -> Dict[str, int]:
        """Verify block conservation: free + live + shared-refcounted
        blocks == num_blocks, and every allocated block's refcount equals
        the owners that can be named — one per live sequence holding it
        plus one if the prefix cache holds it (`cache_blocks`).  Raises
        RuntimeError naming the discrepancy (a leak or a refcount bug);
        returns a summary dict when clean."""
        alloc = self.allocator
        expected = [0] * alloc.num_blocks
        for b in cache_blocks:
            if not 0 <= b < alloc.num_blocks:
                raise RuntimeError(f"prefix cache holds bad block id {b}")
            if expected[b]:
                raise RuntimeError(
                    f"prefix cache holds block {b} more than once")
            expected[b] += 1
        live = set()
        for d in self.seqs.values():
            for b in d.blocks:
                expected[b] += 1
                live.add(b)
        refs = alloc.refcounts()
        bad = [(b, refs[b], expected[b]) for b in range(alloc.num_blocks)
               if refs[b] != expected[b]]
        if bad:
            leaked = [b for b, got, want in bad if got > want]
            raise RuntimeError(
                f"block conservation violated: {len(bad)} blocks with "
                f"refcount != named owners (block, refcount, expected): "
                f"{bad[:8]}{'...' if len(bad) > 8 else ''}; "
                f"{len(leaked)} leaked (refcount above every nameable "
                f"owner)")
        allocated = sum(1 for r in refs if r > 0)
        if alloc.free_blocks + allocated != alloc.num_blocks:
            raise RuntimeError(
                f"free list ({alloc.free_blocks}) + allocated "
                f"({allocated}) != num_blocks ({alloc.num_blocks})")
        cached = set(cache_blocks)
        out = {
            "free": alloc.free_blocks,
            "live": len(live - cached),
            "shared": len(live & cached),
            "cached": len(cached),
            "total": alloc.num_blocks,
        }
        if self.window:
            out.update(self._audit_window())
        if self.state_slots:
            out.update(self._audit_state_slots())
        return out

    def _audit_state_slots(self) -> Dict[str, int]:
        """The slots' conservation: every slot is free or held by exactly
        one live sequence, and every live sequence holds one."""
        held = [d.state_slot for d in self.seqs.values()]
        free = self._free_state_slots
        if (sorted(held + free) != list(range(self.state_slots))
                or len(held) != len(self.seqs)):
            raise RuntimeError(
                f"recurrent-state slot conservation violated: "
                f"{len(self.seqs)} live sequences hold {sorted(held)}, free "
                f"{sorted(free)}, of {self.state_slots} slots")
        return {"state_slots_free": len(free), "state_slots_live": len(held),
                "state_slots_total": self.state_slots}

    def _audit_window(self) -> Dict[str, int]:
        """The window kind's conservation: every allocated block is held by
        exactly one live sequence, under one table entry."""
        alloc = self.window_allocator
        owners = [0] * alloc.num_blocks
        for d in self.seqs.values():
            for b in d.window_blocks.values():
                owners[b] += 1
        refs = alloc.refcounts()
        bad = [(b, refs[b], owners[b]) for b in range(alloc.num_blocks)
               if refs[b] != owners[b]]
        if bad or alloc.free_blocks + sum(owners) != alloc.num_blocks:
            raise RuntimeError(
                f"window-kind block conservation violated: (block, "
                f"refcount, holders) {bad[:8]}; free {alloc.free_blocks} + "
                f"held {sum(owners)} of {alloc.num_blocks}")
        return {"window_free": alloc.free_blocks,
                "window_live": sum(owners), "window_total": alloc.num_blocks}

    # -- step descriptor construction ------------------------------------
    @property
    def table_shape(self) -> tuple:
        """A row's block table: `[MB]`, or `[2, MB]` (global, window)."""
        return ((2,) if self.window else ()) + (self.max_blocks_per_seq,)

    def block_table(self, d: SequenceDescriptor) -> np.ndarray:
        if self.window:
            t = np.full(self.table_shape, -1, np.int32)
            t[0, :len(d.blocks)] = d.blocks
            if d.window_blocks:
                t[1, list(d.window_blocks)] = list(d.window_blocks.values())
            return t
        t = np.zeros((self.max_blocks_per_seq,), np.int32)
        t[:len(d.blocks)] = d.blocks
        return t

    def decode_batch(self) -> List[SequenceDescriptor]:
        return [d for d in self.seqs.values()
                if not d.in_prefill and not d.done]
