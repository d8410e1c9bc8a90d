"""Fixed-size KV slot pool: the host-side allocator behind continuous batching.

The device holds ONE persistent cache (allocated once — see ``scheduler.py``):
``num_slots`` private rows shaped [num_slots, cache_len] per layer. This
module tracks which slots are live, what request occupies each, and the
per-slot layout the decode step needs:

- ``base``: the first decode write offset — the prompt bucket the row was
  PREFILLED at (its admission batch's max bucket). Decode step t writes its
  KV at slot ``base + emitted`` (the engine's per-row ``write_offsets``
  machinery from the speculative-decoding PR, promoted to the serving path)
- ``real_len``: real (non-pad) prompt tokens — RoPE/learned positions
  continue from here, exactly as a batch-1 ``DecodeEngine.generate`` would
- ``emitted``: generated tokens so far (incl. a stopping EOS)

Free slots form an explicit free list (lowest id first, deterministic);
``release`` returns the slot and marks it for device-side invalidation —
the scheduler zeroes the row's ``key_valid``/``lengths`` before the next
decode step, so a recycled slot can never attend to its previous tenant's
keys even transiently.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

from fairness_llm_tpu.serving.request import Request


@dataclasses.dataclass
class SlotState:
    request: Request
    base: int  # first decode write offset (see module docstring)
    real_len: int  # real prompt tokens (position origin for decode)
    emitted: int = 0  # generated tokens so far
    tokens: List[int] = dataclasses.field(default_factory=list)
    # The prompt's ids as prefilled: real token t sits at slot
    # ``base - real_len + t``, which is what lets a later row with the same
    # leading ids copy them (``LiveRowPrefixes``). None: never a donor, as
    # every row of a model that keeps a fixed state per row: its state
    # stands at the row's own last token, which is nothing a shorter
    # shared prefix could be copied from.
    ids: Optional[np.ndarray] = None
    # Provenance of the prompt's leading positions: the row that computed
    # them (``root``, a number no other admission gets) and how many of
    # them this row holds bit for bit as that row computed them
    # (``root_shared``): a row that copied nothing holds its whole prompt
    # as its own root; a rider holds what it copied, no more than its donor
    # held. Two rows of one root hold the fewer of their two counts alike
    # (``scheduler._decode_plan``).
    root: int = -1
    root_shared: int = 0


class LiveRowPrefixes:
    """The live rows' prompt ids side by side, so that a new row finds the
    resident row sharing its longest prefix in one comparison. Built once
    per admission batch, before the batch claims its slots: a row it writes
    is never in it."""

    def __init__(self, pool: "SlotPool"):
        self.slots = [slot for slot in pool.live_slots()
                      if pool.get(slot).ids is not None]
        rows = [pool.get(slot).ids for slot in self.slots]
        width = max(map(len, rows), default=0)
        self.ids = np.full((len(rows), width), -1, np.int64)  # -1: no token
        for i, ids in enumerate(rows):
            self.ids[i, :len(ids)] = ids

    def by_shared(self, ids: np.ndarray) -> List[Tuple[int, int]]:
        """(slot, shared ids) of every live row that shares the first id
        with ``ids``, most shared first, the lower slot first among
        equals."""
        if not self.slots:
            return []
        n = min(len(ids), self.ids.shape[1])
        differ = self.ids[:, :n] != np.asarray(ids[:n])[None, :]
        shared = np.where(differ.any(axis=1), differ.argmax(axis=1), n)
        return [(self.slots[i], int(shared[i]))
                for i in np.argsort(-shared, kind="stable") if shared[i]]


class SlotPool:
    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots))
        heapq.heapify(self._free)
        self._live: Dict[int, SlotState] = {}
        # Slots released since the last invalidation flush; the scheduler
        # zeroes their device rows (key_valid/lengths) and clears this.
        # A dict used as an ordered set: membership/removal are O(1) in
        # ``alloc`` (the old list paid an O(n) ``remove`` per recycled
        # slot) while iteration keeps insertion order, so the flush stays
        # deterministic. Exposed as a list property for readers.
        self._pending_invalidation: Dict[int, None] = {}

    def __len__(self) -> int:
        return len(self._live)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> int:
        return len(self._live)

    @property
    def pending_invalidation(self) -> List[int]:
        """Released-not-yet-invalidated slots, in release order (a read-only
        view; mutation goes through alloc/release/take_invalidations)."""
        return list(self._pending_invalidation)

    def live_slots(self) -> List[int]:
        return sorted(self._live)

    def get(self, slot: int) -> SlotState:
        return self._live[slot]

    def alloc(self, state: SlotState) -> Optional[int]:
        """Claim the lowest free slot for ``state``; None when the pool is
        full (the request stays queued — admission backpressure)."""
        if not self._free:
            return None
        slot = heapq.heappop(self._free)
        self._live[slot] = state
        # A reallocated slot must NOT keep a deferred invalidation: prefill
        # fully re-initializes the row ([0, P) overwritten, [P:) key_valid
        # cleared), and a flush landing AFTER that prefill would wipe the
        # new tenant's prompt (caught by the recycled-slot parity test).
        self._pending_invalidation.pop(slot, None)
        return slot

    def release(self, slot: int) -> SlotState:
        """Free ``slot`` and queue it for device-side invalidation. Raises
        KeyError for a slot that isn't live (double-release is a bug, not a
        no-op — silent tolerance would mask allocator corruption)."""
        state = self._live.pop(slot)
        heapq.heappush(self._free, slot)
        self._pending_invalidation[slot] = None
        return state

    def take_invalidations(self) -> List[int]:
        out = list(self._pending_invalidation)
        self._pending_invalidation.clear()
        return out
