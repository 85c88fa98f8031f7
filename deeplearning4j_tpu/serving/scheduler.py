"""Slot-based continuous-batching scheduler — iteration-level scheduling.

Orca (OSDI '22) named the policy this module implements: schedule at the
**decode-iteration** boundary, not the request boundary. A fixed bank of
``max_slots`` slots rides one compiled decode step; between iterations the
scheduler (a) retires slots whose sequence finished (EOS / token budget) or
must leave (page-table overflow, page-pool exhaustion), returning their
pages to the free list, and (b) admits queued requests into the freed slots
— so a 5-token reply never holds its slot hostage for a 500-token
neighbour's lifetime, which is what fixed-window batching
(``ParallelInference``'s request path) does for stateless inference.

The scheduler is pure host-side policy/state: no jax, no device work — the
``GenerativeEngine`` owns prefill/decode dispatch and calls in here between
iterations. Timing fields use ``time.perf_counter`` only (graftlint GL010).

Slot lifecycle::

    FREE --admit(prefill ok)--> ACTIVE --finish(eos|length)--> FREE
                                   \\--detach(last token in flight)--> FREE
                                   \\--evict(overflow|oom|stopped)--> FREE
                                   \\--expire(deadline)--> FREE
                                   \\--crash(retryable)--> PENDING (retry)
                                   \\--crash(budget spent: error)--> FREE

``GenerationResult.finish_reason`` records which arc retired the request.
The engine may read a launched step's tokens a step late (docs/SERVING.md
§ The loop): a slot state counts the tokens launched for it and not read yet
(``unlanded``), and a sequence that is complete by that count gives its slot
up at once and waits in ``leaving`` for its last token (``detach``).
``shed`` never reaches a slot: the engine's bounded-queue admission gate
completes over-capacity submissions immediately (docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Deque, Dict, List, Optional

import numpy as np

from deeplearning4j_tpu import observe

# Terminal states. The last three are the robustness tier's
# (docs/ROBUSTNESS.md): "shed" = an admission gate (the engine's bounded
# queue or the SLO frontend, serving/frontend.py) rejected the request,
# "deadline" = its per-request deadline expired (queued or mid-decode),
# "error" = a worker crash consumed its whole retry budget OR the
# frontend's circuit breaker fast-failed it. The SLO frontend consumes
# these as load signals AND produces them — one shared vocabulary, so
# ``dl4j_tpu_serving_evicted_total{reason}`` is the single place every
# terminal outcome is counted (asserted in tests/test_frontend.py).
FINISH_REASONS = ("eos", "length", "overflow", "oom", "stopped",
                  "shed", "deadline", "error")


# one running number for every request of the process: spans of one request
# (serving_queue_wait, serving_admit, serving_prefill, serving_request) carry
# it as ``request``, across supervisor retries and cluster migration
_REQUEST_IDS = itertools.count(1)


def request_id_of(request: "GenerationRequest") -> int:
    if request.request_id is None:
        request.request_id = next(_REQUEST_IDS)
    return request.request_id


def note_terminal(request: "GenerationRequest", submit_t: Optional[float],
                  reason: str, tokens: int = 0) -> None:
    """The ``serving_request`` span: one per request at its terminal state,
    submit -> terminal (zero long where the submit time is not known: shed
    at the gate). A request's span, not a thread's: an async pair."""
    now = time.perf_counter()
    rid = request_id_of(request)
    observe.tracer().async_between(
        "serving_request", now if submit_t is None else submit_t, now,
        key=rid, category="serving", request=rid, reason=reason,
        prompt_len=int(request.prompt.size), tokens=int(tokens),
        retries_used=request.retries_used)


def count_terminal(reason: str) -> None:
    """Increment the ONE terminal-outcome counter family. Every path that
    completes a request — retire, unslotted finish, fail_all/fail_pending,
    frontend sheds — funnels through here so the vocabulary cannot drift."""
    if reason not in FINISH_REASONS:
        raise ValueError(f"unknown finish reason {reason!r}")
    observe.metrics().counter(
        "dl4j_tpu_serving_evicted_total", reason=reason).inc()


@dataclasses.dataclass
class GenerationRequest:
    """One text-generation request (token-id space; tokenization is the
    caller's concern)."""

    prompt: np.ndarray               # (t,) int32 token ids
    max_new_tokens: int = 16
    temperature: float = 0.0         # <= 0 -> greedy
    top_k: int = 0                   # 0 -> disabled
    top_p: float = 1.0               # 1.0 -> disabled
    eos_token: int = -1              # -1 -> never stop on a token
    deadline_s: Optional[float] = None  # submit -> terminal budget (wall)
    max_retries: int = 1             # crash re-admissions before "error"
    retries_used: int = 0            # supervisor bookkeeping, not user-set
    # SLO-frontend fields (serving/frontend.py). ``priority`` orders the
    # pending queue (lower admits first); supervisor retries re-queue the
    # SAME request object, so class/priority/submit-time survive a crash
    # and recovery can never invert priority. ``degraded`` records that
    # the degradation ladder trimmed this request's parameters — it rides
    # into the GenerationResult so callers can see they got a degraded
    # answer.
    priority: int = 1                # 0 = most important
    slo_class: str = "standard"      # frontend class name (label value)
    degraded: bool = False           # ladder trimmed max_new_tokens/extras
    # ``spec_disabled``: the frontend's ``ClassPolicy.disable_spec``
    # degraded-mode knob turned speculative decoding off for this request
    # (shedding state frees the draft model's compute for the target);
    # the engine then decodes it non-speculatively even when spec is on.
    # Rides into the GenerationResult like ``degraded``.
    spec_disabled: bool = False
    # assigned by SlotScheduler.submit, never by the caller; the SAME
    # request object is re-queued by a supervisor retry, so it keeps its id
    request_id: Optional[int] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens <= 0:
            raise ValueError("max_new_tokens must be positive")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 disables), "
                             f"got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            # top_p == 0 would mask EVERY token and silently degenerate to
            # emitting id 0; "disable" is top_p=1.0
            raise ValueError(f"top_p must be in (0, 1] (1.0 disables), "
                             f"got {self.top_p}")
        if self.deadline_s is not None and self.deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0 (None disables), "
                             f"got {self.deadline_s}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if self.priority < 0:
            raise ValueError(f"priority must be >= 0, got {self.priority}")


@dataclasses.dataclass
class GenerationResult:
    """Completed (or evicted) generation + its latency raw material."""

    tokens: np.ndarray               # generated ids (no prompt, no eos)
    finish_reason: str
    prompt_len: int
    ttft_s: Optional[float]          # submit -> first token (perf_counter)
    intertoken_s: List[float]        # successive decode-token gaps
    slo_class: str = "standard"      # the request's admission class
    degraded: bool = False           # True: the ladder trimmed this answer
    prefix_hit_tokens: int = 0       # prompt tokens served from the radix
    #                                  prefix cache (0 = full prefill)
    # speculative-decoding accounting (docs/SERVING.md § Speculative
    # decoding): draft tokens proposed / committed for THIS request, and
    # whether the frontend's degraded-mode knob disabled speculation for
    # it. Zero/False on non-speculative requests.
    spec_proposed_tokens: int = 0
    spec_accepted_tokens: int = 0
    spec_disabled: bool = False
    # a block model's (generation by diffusion over blocks): the denoising
    # pass of its block at which each token was unmasked; None for a model
    # that decodes a token at a time
    unmask_pass: Optional[np.ndarray] = None


@dataclasses.dataclass(eq=False)
class _Slot:
    request: GenerationRequest
    future: "Future[GenerationResult]"
    submit_t: float
    prompt_len: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None
    intertoken_s: List[float] = dataclasses.field(default_factory=list)
    last_token_t: Optional[float] = None
    prefix_hit_tokens: int = 0
    spec_proposed_tokens: int = 0
    spec_accepted_tokens: int = 0
    # tokens launched for this sequence (its prefill's, a decode step's,
    # a block step's whole block) that the host has not read yet
    unlanded: int = 0
    unmask_pass: Optional[List[int]] = None


class SlotScheduler:
    """Pending queue + slot bank. Thread-safe for one engine loop plus
    submitting client threads AND the SLO frontend: every structural
    mutation of ``pending`` (append, best-pending removal, victim steal,
    drain) holds ``_plock``, because the frontend's shed-lowest-first
    steal removes items from the middle of the deque while the worker is
    index-scanning it — atomic deque ops alone no longer suffice."""

    def __init__(self, max_slots: int):
        self.max_slots = int(max_slots)
        self.pending: Deque[tuple] = deque()
        self.slots: Dict[int, _Slot] = {}
        # sequences that gave their slot up with their last token in flight
        self.leaving: List[_Slot] = []
        self._plock = threading.Lock()

    # ------------------------------------------------------------ submission
    def submit(self, request: GenerationRequest) -> "Future[GenerationResult]":
        fut: "Future[GenerationResult]" = Future()
        request_id_of(request)
        with self._plock:
            self.pending.append((request, fut, time.perf_counter()))
        return fut

    # --------------------------------------------------------------- queries
    def active_slots(self) -> List[int]:
        return sorted(self.slots)

    def free_slot_ids(self) -> List[int]:
        return [s for s in range(self.max_slots) if s not in self.slots]

    def has_work(self) -> bool:
        return bool(self.slots) or bool(self.pending) or bool(self.leaving)

    def holds(self, slot: int, st: _Slot) -> bool:
        """``st`` is still a live sequence (in ``slot``, or leaving): what a
        step launched for it may still be committed to it."""
        return self.slots.get(slot) is st or any(
            s is st for s in self.leaving)

    def occupancy(self) -> float:
        return len(self.slots) / self.max_slots if self.max_slots else 0.0

    def pending_snapshot(self) -> List[tuple]:
        """A consistent copy of the pending queue (frontend accounting)."""
        with self._plock:
            return list(self.pending)

    # --------------------------------------------------- priority admission
    def peek_best_pending(self) -> Optional[tuple]:
        """The pending item that should admit NEXT: lowest
        ``request.priority`` first, then earliest submit time (FIFO within
        a class). Returns the item without removing it — the engine
        inspects page-pool feasibility before committing."""
        with self._plock:
            best, best_key = None, None
            for i, item in enumerate(self.pending):
                key = (item[0].priority, item[2], i)
                if best_key is None or key < best_key:
                    best_key, best = key, item
            return best

    def remove_pending(self, item: tuple) -> bool:
        """Remove ``item`` (by identity) from the pending queue. Returns
        False when a concurrent actor (a frontend victim steal, a deadline
        sweep) already took it — the caller must then re-select."""
        with self._plock:
            for i, it in enumerate(self.pending):
                if it is item:
                    del self.pending[i]
                    return True
        return False

    def steal_lowest_pending(self, than_priority: int) -> Optional[tuple]:
        """Remove and return the WORST queued item strictly lower-priority
        than ``than_priority`` (highest priority number; latest submit
        breaks ties — the newest of the worst class is shed, the oldest is
        closest to service). None when nothing lower-priority is queued.
        The shed-lowest-first arm of the SLO frontend's queue bound."""
        with self._plock:
            worst, worst_key, worst_i = None, None, -1
            for i, item in enumerate(self.pending):
                if item[0].priority <= than_priority:
                    continue
                key = (item[0].priority, item[2], i)
                if worst_key is None or key > worst_key:
                    worst_key, worst, worst_i = key, item, i
            if worst is not None:
                del self.pending[worst_i]
            return worst

    # ------------------------------------------------------------- lifecycle
    def admit(self, slot: int, request: GenerationRequest,
              future: "Future[GenerationResult]", submit_t: float,
              first_token: Optional[int] = None,
              now: Optional[float] = None,
              prefix_hit_tokens: int = 0, unlanded: int = 1) -> _Slot:
        """Install a request whose prefill was launched into ``slot``. Its
        first sampled token is in flight (``unlanded``) until
        :meth:`on_first_token` lands it; given here, it lands at once. A
        block model's prefill launches no token (``unlanded=0``).
        ``prefix_hit_tokens`` records how much of the prompt the radix
        prefix cache served — it rides into the GenerationResult so
        callers and the replay bench can account hits per request."""
        st = _Slot(request=request, future=future, submit_t=submit_t,
                   prompt_len=int(request.prompt.size),
                   prefix_hit_tokens=int(prefix_hit_tokens),
                   unlanded=int(unlanded))
        self.slots[slot] = st
        if first_token is not None:
            self.on_first_token(st, first_token, now)
        return st

    def on_first_token(self, st: _Slot, token: int, now: float) -> None:
        """The prefill's token reached the host: TTFT is measured here."""
        st.unlanded -= 1
        st.tokens.append(int(token))
        st.ttft_s = now - st.submit_t
        st.last_token_t = now

    def on_decode_token(self, st: _Slot, token: int, now: float) -> None:
        """A decode step's token for ``st`` reached the host."""
        st.unlanded -= 1
        st.tokens.append(int(token))
        if st.last_token_t is not None:
            st.intertoken_s.append(now - st.last_token_t)
        st.last_token_t = now

    def on_block_tokens(self, st: _Slot, tokens: List[int],
                        passes: List[int], now: float,
                        launched: int) -> List[float]:
        """A block step's tokens for ``st`` reached the host together:
        ``launched`` were counted unlanded, ``tokens`` are what the commit
        keeps of them (cut at an ``eos`` or at ``max_new_tokens``),
        ``passes`` the pass that unmasked each. They are stamped together:
        the first is the first token (TTFT) or follows the last landed one
        by the step's gap, the rest follow it by 0. Returns the gaps it
        appended."""
        st.unlanded -= int(launched)
        if not tokens:
            return []
        if st.unmask_pass is None:
            st.unmask_pass = []
        gaps = [0.0] * len(tokens)
        if st.ttft_s is None:
            st.ttft_s = now - st.submit_t
            gaps = gaps[1:]
        else:
            gaps[0] = now - st.last_token_t
        st.tokens.extend(int(t) for t in tokens)
        st.unmask_pass.extend(int(p) for p in passes[:len(tokens)])
        st.intertoken_s.extend(gaps)
        st.last_token_t = now
        return gaps

    def on_spec_tokens(self, slot: int, tokens: List[int], now: float,
                       proposed: int, accepted: int) -> Optional[float]:
        """Commit a verify pass's tokens for ``slot`` — possibly several
        per engine step. Inter-token latency is accounted PER COMMITTED
        TOKEN (the step gap divided by the tokens it committed), not per
        step: a speculative step that lands 4 tokens in 50ms must read as
        12.5ms/token, or spec-on percentiles (and the SLO frontend's
        rolling decode estimate built on them) would overstate per-token
        latency by the acceptance factor. Returns the per-token gap (None
        on the first tokens after admission) so the engine can mirror the
        same value into the process histograms."""
        st = self.slots[slot]
        m = max(1, len(tokens))
        gap = (None if st.last_token_t is None
               else (now - st.last_token_t) / m)
        for t in tokens:
            st.tokens.append(int(t))
            if gap is not None:
                st.intertoken_s.append(gap)
        st.last_token_t = now
        st.spec_proposed_tokens += int(proposed)
        st.spec_accepted_tokens += int(accepted)
        return gap

    @staticmethod
    def finish_reason(st: _Slot) -> Optional[str]:
        """``"eos"``/``"length"`` when the tokens that have landed complete
        the sequence."""
        if st.tokens and st.tokens[-1] == st.request.eos_token:
            return "eos"
        if len(st.tokens) >= st.request.max_new_tokens:
            return "length"
        return None

    def should_finish(self, slot: int) -> Optional[str]:
        """``"eos"``/``"length"`` when the slot's sequence is complete."""
        return self.finish_reason(self.slots[slot])

    def last_in_flight(self, slot: int) -> bool:
        """The slot's sequence is complete by its count and its last token
        has not landed: nothing more is launched for it."""
        st = self.slots[slot]
        return bool(st.unlanded) and (
            len(st.tokens) + st.unlanded >= st.request.max_new_tokens)

    def detach(self, slot: int) -> _Slot:
        """Free ``slot`` for the next request while its sequence waits in
        ``leaving`` for its last token; :meth:`finish` completes it."""
        st = self.slots.pop(slot)
        self.leaving.append(st)
        return st

    def retire(self, slot: int, reason: str) -> GenerationResult:
        """Remove ``slot`` and complete its future. The caller frees the
        slot's cache pages (the scheduler never touches device state)."""
        return self.finish(self.slots.pop(slot), reason)

    def finish(self, st: _Slot, reason: str) -> GenerationResult:
        """Complete the future of a sequence that holds no slot (any more)."""
        if reason not in FINISH_REASONS:
            raise ValueError(f"unknown finish reason {reason!r}")
        self.leaving = [s for s in self.leaving if s is not st]
        toks = st.tokens
        if reason == "eos" and toks and toks[-1] == st.request.eos_token:
            toks = toks[:-1]
        passes = (None if st.unmask_pass is None
                  else np.asarray(st.unmask_pass[:len(toks)], np.int32))
        result = GenerationResult(
            tokens=np.asarray(toks, np.int32), finish_reason=reason,
            prompt_len=st.prompt_len, ttft_s=st.ttft_s,
            intertoken_s=list(st.intertoken_s),
            slo_class=st.request.slo_class, degraded=st.request.degraded,
            prefix_hit_tokens=st.prefix_hit_tokens,
            spec_proposed_tokens=st.spec_proposed_tokens,
            spec_accepted_tokens=st.spec_accepted_tokens,
            spec_disabled=st.request.spec_disabled, unmask_pass=passes)
        note_terminal(st.request, st.submit_t, reason, len(toks))
        if not st.future.done():
            # graftlife: justified(GR003): retire() only forms the result —
            # its callers (engine._retire, frontend._shed_victim) own the
            # count_terminal(reason) increment, exactly once each
            st.future.set_result(result)
        return result

    def fail_all(self, exc: Exception, reason: str = "error") -> None:
        """Engine shutdown/crash: fail every in-flight and queued future so
        blocked callers wake instead of hanging (the ParallelInference.stop
        contract). Each future actually failed here counts ONCE under
        ``dl4j_tpu_serving_evicted_total{reason}`` — exception exits share
        the terminal-reason vocabulary with result exits."""
        leaving, self.leaving = self.leaving, []
        for slot in list(self.slots):
            leaving.append(self.slots.pop(slot, None))  # None: a concurrent caller
        for st in leaving:
            if st is not None and not st.future.done():
                st.future.set_exception(exc)
                count_terminal(reason)
                note_terminal(st.request, st.submit_t, reason, len(st.tokens))
        self.fail_pending(exc, reason=reason)

    def fail_pending(self, exc: Exception, reason: str = "error") -> None:
        """Fail ONLY the queued-but-never-admitted futures. Used alone when
        a hung worker may still own the active slots (stop() timeout):
        completing those futures here would race the stuck thread."""
        drained: List[tuple] = []
        while True:
            with self._plock:
                try:
                    drained.append(self.pending.popleft())
                except IndexError:  # drained (possibly by a concurrent one)
                    break
        for req, fut, t_sub in drained:
            if not fut.done():
                fut.set_exception(exc)
                count_terminal(reason)
                note_terminal(req, t_sub, reason)
