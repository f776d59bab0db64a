"""Admission queue + slot grants for continuous batching.

The scheduler is deliberately host-only and device-free: it owns the
FIFO queue, enforces admission control (bounded queue depth,
prompt-fits-in-capacity) and decides WHICH queued requests get a slot
this step: iteration-level scheduling (Orca; PAPERS.md) — every step,
any free slot is immediately refilled from the queue. Retirements and
admissions interleave with decode, so slots never idle while work is
queued.

Under the serving engine's stall-free mode, ``grant`` additionally
enforces a per-step prefill TOKEN BUDGET (Sarathi-style): admission
stops charging new prompts once the step's prefill work — bucketed
whole-prompt admissions plus at most one in-flight chunk — would exceed
the budget, so a burst of arrivals can no longer stall live decode
slots behind an unbounded prefill wave.
"""

from __future__ import annotations

import collections
from typing import Deque, List, Optional, Tuple

from .request import RejectReason, Request, RequestState


class FIFOScheduler:
    """Bounded FIFO admission queue that refills free slots every step."""

    def __init__(self, num_slots: int, max_queue_depth: int = 64,
                 capacity: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None, page_headroom: int = 0):
        self.num_slots = num_slots
        self.max_queue_depth = max_queue_depth
        self.capacity = capacity
        # paged-KV admission accounting: with a PagedKVPool the real
        # admission currency is PAGES, not rows — ``capacity`` alone
        # would accept a request the page pool can never hold under
        # oversubscription. ``page_headroom`` is extra columns charged
        # per request (speculative verify's k-past-the-index writes).
        self.page_size = int(page_size) if page_size is not None else None
        self.num_pages = int(num_pages) if num_pages is not None else None
        self.page_headroom = int(page_headroom)
        self.queue: Deque[Request] = collections.deque()

    def page_footprint(self, req: Request) -> Optional[int]:
        """Worst-case page count ``req`` could ever need (seed + its
        remaining generation budget + headroom), or None when the pool
        is not paged."""
        if self.page_size is None:
            return None
        cols = (req.seed_len + req.max_new_tokens - len(req.output_tokens)
                + self.page_headroom)
        return -(-cols // self.page_size)

    @property
    def pending(self) -> int:
        return len(self.queue)

    def head(self) -> Optional[Request]:
        """The request the next ``grant`` would pop first, or None.

        The engine peeks at this (never at ``queue[0]`` directly) for
        starvation/pressure decisions, so subclasses with a different
        grant order (priority scheduling) redefine "head" in one place.
        """
        return self.queue[0] if self.queue else None

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> Tuple[bool, Optional[RejectReason]]:
        """Admission control. Returns ``(accepted, reject_reason)``;
        accepted requests join the FIFO queue. Capacity is checked
        against the request's FULL footprint (seed + remaining budget) so
        a preempted request that could never finish is refused rather
        than admitted to die at the length cap."""
        if self.capacity is not None and \
                req.seed_len + req.max_new_tokens - len(req.output_tokens) \
                > self.capacity:
            return False, RejectReason.PROMPT_TOO_LONG
        if self.num_pages is not None:
            # page-denominated footprint check: even with the WHOLE pool
            # free (every other request preempted and the prefix cache
            # fully evicted) this request could never seat its worst
            # case — reject now, not at an unseatable queue head
            fp = self.page_footprint(req)
            if fp is not None and fp > self.num_pages:
                return False, RejectReason.PROMPT_TOO_LONG
        if len(self.queue) >= self.max_queue_depth:
            return False, RejectReason.QUEUE_FULL
        req.state = RequestState.QUEUED
        self.queue.append(req)
        return True, None

    def requeue_front(self, reqs: List[Request]) -> None:
        """Put granted-but-never-admitted (or manually preempted)
        requests back at the HEAD of the queue, preserving their
        RELATIVE order: after ``requeue_front([a, b])`` the queue pops
        ``a`` then ``b`` then whatever was already waiting. The reversed
        ``appendleft`` walk is what makes that hold — appendleft-ing in
        forward order would reverse the batch, a FIFO inversion that
        reorders same-step aborted grants on re-admission (pinned by a
        regression test). Bypasses admission control — these requests
        already passed it."""
        for r in reversed(reqs):
            r.state = RequestState.QUEUED
            self.queue.appendleft(r)

    def requeue_back(self, reqs: List[Request]) -> None:
        """Requeue at the TAIL — the automatic pressure-preemption path.
        A pressure victim must NOT go to the head: the very next grant
        would hand it back its own freed slot (a swap loop that preempts
        forever and generates nothing). Sending it behind the arrivals
        that caused the pressure yields round-robin time-slicing
        instead. Bypasses admission control, like ``requeue_front``."""
        for r in reqs:
            r.state = RequestState.QUEUED
            self.queue.append(r)

    def expire(self, now: float) -> List[Request]:
        """Remove and return queued requests whose deadline has passed —
        a request that expired while WAITING should not cost a slot and
        a prefill before being retired. The engine stamps these
        ``finish_reason="deadline"`` through the normal retire path."""
        expired = [r for r in self.queue if r.expired(now)]
        if expired:
            self.queue = collections.deque(
                r for r in self.queue if not r.expired(now))
        return expired

    def grant(self, free_slots: int,
              token_budget: Optional[int] = None,
              cost=None, spent: int = 0,
              page_budget: Optional[int] = None,
              page_cost=None) -> List[Request]:
        """Pop the requests that may take a slot this step.

        With ``token_budget``/``cost`` (the stall-free admission policy),
        each pop is charged ``cost(req)`` prefill tokens against the
        budget and the FIFO head blocks further grants when it no longer
        fits — per-step prefill work is bounded by tokens, not by how
        many slots happen to be free. ``spent`` is prefill work the
        caller already committed this step (an in-flight chunk).

        HEAD-LIVENESS GUARANTEE (pinned by regression tests, relied on
        by the priority scheduler): when NOTHING has been spent or
        granted yet this step, the head is granted even if its cost
        alone exceeds the budget (bounded overshoot beats a permanently
        stuck queue). Consequence: on any step where a slot is free and
        no prefill work was already committed, the next-to-pop request
        makes progress — no token budget, however small, can livelock
        the queue. ``PriorityScheduler`` preserves exactly this property
        for its highest-ranked waiter, which is how the lowest class
        still makes progress when higher classes are idle: it IS the
        highest-ranked waiter then.

        With ``page_budget``/``page_cost`` (paged KV), each pop is also
        charged ``page_cost(req)`` fresh pages (its uncached prefix).
        The page budget is STRICT — no liveness overshoot: over-granting
        pages doesn't slow the step down, it makes seating raise
        PagePoolExhausted and abort the whole step. Starvation is the
        engine's job, not an overshoot's: pressure preemption frees
        victims' pages, and the submit-time footprint check guarantees
        the head fits an otherwise-empty pool."""
        granted: List[Request] = []
        remaining = None if token_budget is None else token_budget - spent
        pages_left = page_budget
        while self.queue and len(granted) < free_slots:
            pc = 0
            if pages_left is not None:
                pc = page_cost(self.queue[0]) if page_cost is not None else 0
                if pc > pages_left:
                    break
            if remaining is not None:
                c = cost(self.queue[0]) if cost is not None else 0
                if c > remaining and (granted or spent > 0):
                    break
                remaining -= c
            if pages_left is not None:
                pages_left -= pc
            granted.append(self.queue.popleft())
        return granted
