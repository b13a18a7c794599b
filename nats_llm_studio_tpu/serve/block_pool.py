"""Host-side refcounted allocator for the paged KV block pool.

The device side of paged KV is a single pair of arrays shaped
``[n_blocks, n_layers, n_kv_heads, block_tokens, head_dim]`` (plus int8
scale leaves under KVQ).  This module owns the *host* bookkeeping for
those blocks: a refcount per block id, a free list, and the shared/CoW
counters the metrics endpoint exports.

Design points (vLLM PagedAttention + RadixAttention sharing):

- Block id 0 is the **null block**: permanently referenced, never
  allocated, used to pad device block tables.  Padded gathers read junk
  from it and padded scatters write junk into it; both are masked out by
  the causal attention mask, so its contents never reach a logit.
- A live slot holds one reference per block in its table; the prefix
  cache holds its own reference per cached block.  Sharing a prefix is a
  refcount bump, never a copy.  ``refs > 1`` means the block is shared
  and must be copy-on-write'd before an in-place write.
- ``epoch`` guards against stale frees: when the batcher rebuilds the
  device pool after a poisoned dispatch it calls :meth:`reset`, which
  bumps the epoch; deferred frees from the old pool (e.g. pinned
  prefix-cache nodes released later) carry the old epoch and are
  ignored instead of corrupting the fresh refcounts.

Thread safety: the batcher owner thread does alloc/free/CoW, while the
registry event loop and the metrics scrape thread read stats and may
trigger prefix-cache eviction — hence the lock.
"""

from __future__ import annotations

import threading

__all__ = ["BlockPool", "StatePool"]


class BlockPool:
    """Refcounts for a fixed population of KV blocks; id 0 is the null block."""

    def __init__(self, n_blocks: int, block_tokens: int):
        if n_blocks < 2:
            raise ValueError(f"pool needs >= 2 blocks (null + 1), got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self._lock = threading.Lock()
        self.epoch = 0
        self.cow_copies = 0
        # high-water mark of live (non-free) blocks since the last reset:
        # the pressure signal the tiering bench reads to prove a working
        # set really exceeded the pool, not just the prefix budget
        self.peak_live = 0
        self._refs = [0] * self.n_blocks
        self._refs[0] = 1  # the null block is never allocatable
        self._free = list(range(self.n_blocks - 1, 0, -1))  # pop() -> low ids first

    # -- allocation ----------------------------------------------------------

    def alloc(self, k: int) -> list[int] | None:
        """Take ``k`` fresh blocks (refcount 1 each), or None if short."""
        with self._lock:
            if k > len(self._free):
                return None
            ids = [self._free.pop() for _ in range(k)]
            for i in ids:
                self._refs[i] = 1
            live = self.n_blocks - 1 - len(self._free)
            if live > self.peak_live:
                self.peak_live = live
            return ids

    def incref(self, ids) -> None:
        with self._lock:
            for i in ids:
                if self._refs[i] <= 0:
                    raise RuntimeError(f"incref of free block {i}")
                self._refs[i] += 1

    def decref(self, ids, epoch: int | None = None) -> None:
        """Drop one reference per id; freed blocks rejoin the free list.

        ``epoch`` (when given) must match the pool's current epoch or the
        call is a no-op — that is how deferred frees from a pre-reset pool
        are discarded safely.
        """
        with self._lock:
            if epoch is not None and epoch != self.epoch:
                return
            for i in ids:
                if i == 0:
                    continue  # the null block never dies
                r = self._refs[i] - 1
                if r < 0:
                    raise RuntimeError(f"double free of block {i}")
                self._refs[i] = r
                if r == 0:
                    self._free.append(i)

    def refcount(self, i: int) -> int:
        with self._lock:
            return self._refs[i]

    def reset(self) -> None:
        """Forget everything (the device pool was rebuilt); bump the epoch."""
        with self._lock:
            self.epoch += 1
            self.cow_copies = 0
            self.peak_live = 0
            self._refs = [0] * self.n_blocks
            self._refs[0] = 1
            self._free = list(range(self.n_blocks - 1, 0, -1))

    # -- introspection -------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def stats(self) -> dict:
        with self._lock:
            shared = sum(1 for r in self._refs[1:] if r > 1)
            live = sum(1 for r in self._refs[1:] if r > 0)
            return {
                "blocks_total": self.n_blocks - 1,  # null block excluded
                "blocks_free": len(self._free),
                "blocks_live": live,
                "blocks_shared": shared,
                "blocks_peak_live": self.peak_live,
                "block_tokens": self.block_tokens,
                "cow_copies": self.cow_copies,
                "epoch": self.epoch,
            }


class StatePool:
    """The books of the per-slot state pool beside the block pool.

    A family with state-space layers keeps a recurrent state a slot
    (models/ssm_hybrid.py): device arrays indexed by SLOT, not by a block
    table, so there is nothing to allocate: slot i's state is row i, written
    whole by the slot's admit and dead when the slot finishes (no copy, no
    free list). What is kept here is what admission and the metrics page
    need: how many slots there are, what one costs, how many hold a request.
    """

    def __init__(self, slots: int, bytes_per_slot: int, live_fn):
        self.slots = int(slots)
        self.bytes_per_slot = int(bytes_per_slot)
        self._live_fn = live_fn

    @property
    def nbytes(self) -> int:
        return self.slots * self.bytes_per_slot

    def stats(self) -> dict:
        return {"slots_total": self.slots, "slots_live": int(self._live_fn()),
                "bytes": self.nbytes}
