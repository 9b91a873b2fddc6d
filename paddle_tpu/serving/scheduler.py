"""Slot-based continuous-batching scheduler.

The engine runs ONE jitted fixed-shape step over ``max_slots`` rows;
requests come and go by flipping per-slot masks (position ``-1`` means
"empty slot"), never by changing array shapes — so the step compiles
exactly once for the lifetime of the engine.

This module is pure bookkeeping (no jax): it decides *which* request
occupies *which* slot, when a waiting request is admitted (FCFS, gated
on block availability through :class:`BlockManager.can_allocate`), how
prompt prefill is cut into chunks that fill a step's token budget beside
its decode rows, and who gets preempted (evict-and-recompute: youngest
running request releases its pages and re-queues with ``prompt +
generated`` as its new prompt) when the pool runs dry mid-decode.  The
engine launches a step before it has read the last one's tokens, so a
request also counts the token it has in flight (``Request.in_flight``):
positions, the page of the next write and the rows still to launch are
taken from ``generated`` plus that count.  Keeping it
array-free lets the property tests drive thousands of randomized
admit/cancel/preempt/finish sequences without touching a device.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
from typing import Deque, Dict, List, Optional, Tuple

from .block_manager import BlockManager

__all__ = ["Request", "Scheduler", "PrefillChunk",
           "WAITING", "PREFILL", "RUNNING", "HANDOFF", "FINISHED",
           "CANCELLED"]

# request lifecycle states; preemption maps RUNNING/PREFILL -> WAITING.
# HANDOFF is the disaggregated-prefill terminal-on-this-engine state: the
# prompt KV is resident and the first token sampled, but decode happens
# on ANOTHER engine after the cluster layer exports the pages.
WAITING = "waiting"
PREFILL = "prefill"
RUNNING = "running"
HANDOFF = "handoff"
FINISHED = "finished"
CANCELLED = "cancelled"

_rid_counter = itertools.count()


@dataclasses.dataclass
class Request:
    """One inference request moving through the engine."""
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    deadline: Optional[float] = None      # absolute time.monotonic()
    arrival: float = 0.0
    rid: int = dataclasses.field(
        default_factory=lambda: next(_rid_counter))
    state: str = WAITING
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    num_cached: int = 0          # prompt tokens restored from prefix cache
    prefilled: int = 0           # prompt tokens whose KV is resident
    generated: List[int] = dataclasses.field(default_factory=list)
    remaining: int = 0           # tokens still to emit (set on first add)
    preemptions: int = 0
    first_token_at: Optional[float] = None
    finish_reason: Optional[str] = None
    handoff: bool = False        # disagg: stop after prefill + 1st token
    handoff_token: Optional[int] = None  # the sampled 1st token
    # tokens of this request that a launched step is computing and the
    # host has not read yet (0 or 1): the engine launches step n+1 while
    # step n runs, so positions, pages and the tokens left to launch are
    # counted from ``generated`` PLUS this
    in_flight: int = 0
    # observability.request_log.RequestTimeline, attached by the engine
    # ONLY when telemetry is enabled — None keeps the scheduler's hot
    # paths at one attribute read on the disabled path, and the
    # scheduler stays clock-free (the timeline owns its clock)
    timeline: Optional[object] = None

    def __post_init__(self) -> None:
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens <= 0:
            raise ValueError("max_new_tokens must be > 0")
        self.remaining = self.max_new_tokens

    # position of the NEXT KV write during decode: the last generated
    # token (read by the host or still in flight) sits at
    # total_len() - 1
    def decode_pos(self) -> int:
        return self.total_len() - 1

    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated) + self.in_flight


@dataclasses.dataclass
class PrefillChunk:
    """One chunk of prompt tokens to run this step (at most one per
    scheduler step, interleaved with decode)."""
    req: Request
    start: int                   # first prompt index in the chunk
    tokens: List[int]
    last: bool                   # completes the prompt -> sample token


class Scheduler:
    """FCFS continuous-batching scheduler over a fixed slot grid."""

    def __init__(self, manager: BlockManager, max_slots: int,
                 max_seq_len: int):
        if max_slots <= 0:
            raise ValueError("max_slots must be > 0")
        self.manager = manager
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.waiting: Deque[Request] = collections.deque()
        self.slots: Dict[int, Request] = {}
        self._free_slots: List[int] = list(range(max_slots))[::-1]
        self.preemptions = 0

    # ------------------------------------------------------------ intake
    def add(self, req: Request) -> None:
        if req.total_len() + req.remaining > self.max_seq_len:
            raise ValueError(
                "request needs %d positions, engine max_seq_len is %d"
                % (req.total_len() + req.remaining, self.max_seq_len))
        req.state = WAITING
        self.waiting.append(req)

    def cancel(self, req: Request, reason: str = "cancelled") -> None:
        """Remove a request wherever it is and release its resources."""
        if req.state in (FINISHED, CANCELLED):
            return
        if req.state == WAITING:
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
        else:
            self._release(req)
        req.state = CANCELLED
        req.finish_reason = reason

    def finish(self, req: Request, reason: str) -> None:
        """Normal completion: publish full prompt blocks to the prefix
        cache, then drop this request's references."""
        self.manager.register_prefix(req.prompt, req.blocks)
        self._release(req)
        req.state = FINISHED
        req.finish_reason = reason

    def _release(self, req: Request) -> None:
        if req.blocks:
            self.manager.free(req.blocks)
            req.blocks = []
        if req.slot >= 0:
            del self.slots[req.slot]
            self._free_slots.append(req.slot)
            req.slot = -1

    # -------------------------------------------------------- scheduling
    def running(self) -> List[Request]:
        return [r for r in self.slots.values() if r.state == RUNNING]

    def decode_rows(self) -> List[Request]:
        """RUNNING requests that still have a token to launch: one whose
        last allowed token is in flight is not packed again, so an end
        by length costs no wasted row."""
        return [r for r in self.slots.values()
                if r.state == RUNNING and r.remaining > r.in_flight]

    def num_active(self) -> int:
        return len(self.slots)

    def admit(self) -> List[Request]:
        """FCFS admission: pop waiting requests into free slots while
        the pool can cover their prompt (+1 decode block) above the
        watermark.  Head-of-line blocking is intentional — skipping
        ahead would starve long prompts."""
        admitted: List[Request] = []
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            cached_blocks, cached = self.manager.match_prefix(req.prompt)
            need_total = self.manager.blocks_for_tokens(
                len(req.prompt) + 1)
            need_new = need_total - len(cached_blocks)
            if not self.manager.can_allocate(need_new):
                self.manager.free(cached_blocks)   # undo the match refs
                break
            self.waiting.popleft()
            req.blocks = cached_blocks + self.manager.allocate(need_new)
            req.num_cached = cached
            req.prefilled = cached
            req.slot = self._free_slots.pop()
            self.slots[req.slot] = req
            req.state = PREFILL
            if req.timeline is not None:
                req.timeline.mark_admitted()
            admitted.append(req)
        return admitted

    def place_running(self, req: Request, blocks: List[int]) -> None:
        """Seat an externally-prefilled request (disaggregated handoff)
        straight into a decode slot: its KV pages were imported by the
        engine, so it skips WAITING/PREFILL entirely."""
        if not self._free_slots:
            raise RuntimeError("no free slot for adopted request")
        req.blocks = list(blocks)
        req.prefilled = len(req.prompt)
        req.slot = self._free_slots.pop()
        self.slots[req.slot] = req
        req.state = RUNNING

    def next_prefills(self, token_budget: int,
                      max_chunk: Optional[int] = None) -> List[PrefillChunk]:
        """Ragged-step prefill packing: oldest-first PREFILL slots each
        take as many prompt tokens as still fit ``token_budget``.  The
        budget bounds per-step latency globally, so there is no
        per-request chunk cap unless the caller gives one (``max_chunk``:
        a windowed layer's ring holds that many of a row's tokens a step)
        — several short prompts can finish their whole prefill in one
        step, riding alongside the decode rows."""
        chunks: List[PrefillChunk] = []
        left = int(token_budget)
        cands = sorted((r for r in self.slots.values()
                        if r.state == PREFILL),
                       key=lambda r: r.arrival)
        for req in cands:
            if left <= 0:
                break
            start = req.prefilled
            n = min(left, len(req.prompt) - start)
            if max_chunk is not None:
                n = min(n, max_chunk)
            if n <= 0:
                continue
            chunks.append(PrefillChunk(req, start,
                                       req.prompt[start:start + n],
                                       last=start + n == len(req.prompt)))
            left -= n
        return chunks

    def ensure_decode_blocks(self) -> List[Request]:
        """Before a decode step, make sure every RUNNING request owns
        the page its next KV write lands in; preempt
        (evict-and-recompute) youngest-first when the pool is dry.
        Returns the list of preempted requests. Preemption folds
        ``generated`` into the prompt, so the engine calls this only
        when no victim can have a token in flight (``decode_pages_short``
        says beforehand whether it would preempt)."""
        preempted: List[Request] = []
        for req in sorted(self.decode_rows(), key=lambda r: r.arrival):
            if req.state != RUNNING:     # already preempted this pass
                continue
            need_block = req.decode_pos() // self.manager.block_size
            while need_block >= len(req.blocks):
                if self.manager.num_free() > 0:
                    req.blocks.extend(self.manager.allocate(1))
                    continue
                victim = self._pick_victim(exclude=req)
                if victim is None:
                    victim = req          # nobody younger: evict self
                self._preempt(victim)
                preempted.append(victim)
                if victim is req:
                    break
        return preempted

    def decode_pages_short(self) -> bool:
        """Whether :meth:`ensure_decode_blocks` would have to preempt:
        the decode rows' next writes need more new pages than the pool
        has free."""
        bs = self.manager.block_size
        need = sum(max(0, r.decode_pos() // bs + 1 - len(r.blocks))
                   for r in self.decode_rows())
        return need > self.manager.num_free()

    def _pick_victim(self, exclude: Request) -> Optional[Request]:
        cands = [r for r in self.slots.values()
                 if r is not exclude and r.state in (RUNNING, PREFILL)]
        if not cands:
            return None
        return max(cands, key=lambda r: r.arrival)   # youngest

    def _preempt(self, req: Request) -> None:
        """Evict-and-recompute: fold generated tokens into the prompt,
        release pages + slot, and re-queue at the FCFS position its
        arrival time dictates (front of line among waiting)."""
        assert not req.in_flight, "preempted with a token in flight"
        self._release(req)
        req.prompt = req.prompt + req.generated
        req.generated = []
        req.prefilled = 0
        req.num_cached = 0
        req.preemptions += 1
        self.preemptions += 1
        if req.timeline is not None:
            req.timeline.mark_preempted()
        req.state = WAITING
        # keep the waiting deque sorted by arrival (FCFS overall)
        idx = 0
        for idx, w in enumerate(self.waiting):      # noqa: B007
            if w.arrival > req.arrival:
                break
        else:
            idx = len(self.waiting)
        self.waiting.insert(idx, req)

    # ------------------------------------------------------------ checks
    def assert_consistent(self) -> None:
        """Slot grid and block refs line up (property-test hook)."""
        assert len(self.slots) + len(self._free_slots) == self.max_slots
        assert set(self.slots) | set(self._free_slots) == \
            set(range(self.max_slots))
        for s, r in self.slots.items():
            assert r.slot == s
            assert r.state in (PREFILL, RUNNING, HANDOFF)
        for r in self.waiting:
            assert r.state == WAITING
            assert not r.blocks and r.slot == -1
