"""Continuous batching scheduler: one FIFO admission queue.

Fixed-slot batching (the KV cache is a static (B, S) arena under jit):
requests occupy slots; a finished request frees its slot immediately and a
queued request is admitted on the next step with a per-slot prefill.
Admission control rejects requests longer than the arena.

Queued requests wait in one queue and are admitted in submission order —
never reordered, property-tested in ``tests/test_serve_batching.py``.
Token-level callers (``ServeEngine`` in token mode) pass their arena
write cursor to :meth:`ContinuousBatcher.admit`: a request that no longer
fits the remaining arena blocks admission head-of-line (no skipping —
that would starve long requests) until the engine opens a fresh arena
generation. Pure bookkeeping, unit-tested without a model.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass


@dataclass
class Slot:
    request_id: int | None = None
    pos: int = 0                  # tokens generated so far (incl. prompt)
    max_pos: int = 0              # stop position
    active: bool = False
    start: int = 0                # arena write cursor at admission


@dataclass
class QueuedRequest:
    """One queued submission: identity and lengths."""
    request_id: int
    prompt_len: int
    max_new_tokens: int


class ContinuousBatcher:
    """Slot scheduler for continuous batching (see module docstring).

    Bookkeeping only — owns no model or cache. ``submit`` enqueues (or
    rejects oversize), ``admit`` fills free slots from the queue,
    ``step``/``advance`` move slots forward and free finished ones — the
    only place a slot is freed. ``finished``/``rejected`` are append-only
    audit logs (``finished`` in completion order)."""

    def __init__(self, n_slots: int, max_seq: int):
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.slots = [Slot() for _ in range(n_slots)]
        self.finished: list[int] = []
        self.rejected: list[int] = []
        self._queue: deque[QueuedRequest] = deque()

    # -- intake --------------------------------------------------------------

    def submit(self, request_id: int, prompt_len: int,
               max_new_tokens: int) -> bool:
        """Enqueue a request; False (and logged in ``rejected``) if it
        cannot ever fit the arena."""
        if prompt_len + max_new_tokens > self.max_seq:
            self.rejected.append(request_id)
            return False
        self._queue.append(QueuedRequest(request_id, prompt_len,
                                         max_new_tokens))
        return True

    # -- admission -----------------------------------------------------------

    def _next(self, arena_pos: int) -> QueuedRequest | None:
        """Pop the head of the queue, head-of-line: if it does not fit the
        remaining arena, nothing is admitted (no skipping)."""
        if not self._queue:
            return None
        head = self._queue[0]
        if arena_pos + head.prompt_len + head.max_new_tokens > self.max_seq:
            return None
        return self._queue.popleft()

    def admit(self, arena_pos: int = 0) -> list[tuple[int, int, int]]:
        """Fill free slots from the queue.

        ``arena_pos`` is the caller's arena write cursor (token-level
        engines); a request needing more arena than remains blocks
        head-of-line. Cohort callers leave it 0 (whole arena free).
        Returns [(slot_idx, request_id, prompt_len)] needing prefill."""
        admitted = []
        for i, s in enumerate(self.slots):
            if s.active:
                continue
            nxt = self._next(arena_pos)
            if nxt is None:
                break
            self.slots[i] = Slot(request_id=nxt.request_id,
                                 pos=nxt.prompt_len,
                                 max_pos=nxt.prompt_len + nxt.max_new_tokens,
                                 active=True, start=arena_pos)
            admitted.append((i, nxt.request_id, nxt.prompt_len))
        return admitted

    # -- progress ------------------------------------------------------------

    def advance(self, slot_idx: int) -> int | None:
        """Advance one slot by one token; frees the slot and returns the
        request id when it finishes (else None). Engines call this per
        slot per generated token — slots still being fed their prompt
        are simply not advanced that step."""
        s = self.slots[slot_idx]
        if not s.active:
            return None
        s.pos += 1
        if s.pos >= s.max_pos:
            rid = s.request_id
            self.finished.append(rid)
            s.active = False
            s.request_id = None
            return rid
        return None

    def step(self) -> list[int]:
        """Advance every active slot one token (lock-step view); returns
        freed request ids."""
        freed = []
        for i, s in enumerate(self.slots):
            if s.active:
                rid = self.advance(i)
                if rid is not None:
                    freed.append(rid)
        return freed

    # -- introspection -------------------------------------------------------

    @property
    def queue(self) -> list[QueuedRequest]:
        """Queued requests in submission order (read-only snapshot)."""
        return list(self._queue)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_slots(self) -> int:
        return sum(1 for s in self.slots if s.active)

    def done(self) -> bool:
        return self.queue_depth == 0 and self.active_slots == 0
