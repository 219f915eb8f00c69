"""Request lifecycle for the serving layer.

Reference: DeepSpeed-MII's `RequestBase`/`RaggedRequestBase` lifecycle
(mii/batching/data_classes.py) — a request moves QUEUED -> PREFILL ->
DECODE -> one of {DONE, CANCELLED, TIMED_OUT}; every transition is
timestamped on the serve loop's clock so per-request SLAs (TTFT, TPOT,
end-to-end latency) are measured, not inferred.

The transition table is enforced: an illegal move raises instead of
silently corrupting scheduler bookkeeping.  Completion is exposed both
synchronously (`finished`, `output_tokens`) and through a
`threading.Event` so the threaded frontend can block in `result()`
without polling.
"""
from __future__ import annotations

import enum
import threading
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

__all__ = ["RequestState", "Request", "RequestCancelled", "RequestTimedOut",
           "RequestFailed", "RequestErrored"]


class RequestState(str, enum.Enum):
    QUEUED = "queued"          # admitted to the bounded queue, not the engine
    PREFILL = "prefill"        # occupies an engine slot, prompt in flight
    DECODE = "decode"          # produced its first token, generating
    DONE = "done"              # finished (EOS or max_new_tokens)
    CANCELLED = "cancelled"    # caller cancelled before completion
    TIMED_OUT = "timed_out"    # deadline passed before completion
    FAILED = "failed"          # serving-side error (crash containment);
    #                            the error is attached to the request


TERMINAL_STATES = frozenset(
    {RequestState.DONE, RequestState.CANCELLED, RequestState.TIMED_OUT,
     RequestState.FAILED})

_ALLOWED = {
    RequestState.QUEUED: {RequestState.PREFILL, RequestState.CANCELLED,
                          RequestState.TIMED_OUT, RequestState.FAILED},
    RequestState.PREFILL: {RequestState.DECODE, RequestState.DONE,
                           RequestState.CANCELLED, RequestState.TIMED_OUT,
                           RequestState.FAILED},
    RequestState.DECODE: {RequestState.DONE, RequestState.CANCELLED,
                          RequestState.TIMED_OUT, RequestState.FAILED},
}


class RequestFailed(RuntimeError):
    """Base: the request ended without producing a complete result."""


class RequestCancelled(RequestFailed):
    pass


class RequestTimedOut(RequestFailed):
    pass


class RequestErrored(RequestFailed):
    """The serving side failed the request (replica crash / step error);
    the causing exception rides `.__cause__` when known."""


@dataclass
class Request:
    """One generation request and its measured lifecycle."""

    uid: int
    prompt: np.ndarray                     # int32 prompt token ids
    max_new_tokens: int
    arrival_time: float                    # clock() at submit, or `due=`
    deadline: Optional[float] = None       # absolute clock() bound, or None
    priority: int = 0                      # lower admits first; FIFO within
    eos_token_id: Optional[int] = None
    temperature: float = 0.0               # 0 = greedy argmax
    top_k: int = 0                         # 0 = no truncation (stochastic
    #                                        sampling only; greedy ignores)
    # per-request sampling seed (serving/streaming.seeded_sample): with
    # a seed, every stochastic draw is a pure function of
    # (seed, token position) — a counter-based stream, so regeneration
    # after failover reproduces the tokens bit-for-bit and streamed
    # replay is verifiable.  None = the serve loop's shared RNG (the
    # pre-streaming behavior; replay of stochastic rows then diverges).
    seed: Optional[int] = None
    # multi-tenant serving (serving/tenancy): the tenant this request
    # bills to — rate limits, WFQ weight, and per-tenant telemetry key
    # on it.  "default" is the single-tenant serve loop's implicit
    # tenant, so tenancy-off traffic never carries a surprising label.
    tenant: str = "default"
    # LoRA adapter this request decodes through (AdapterPool id), or
    # None = the base model (bit-identical to single-tenant serving —
    # the parity lock)
    adapter_id: Optional[str] = None
    # output grammar (serving/structured.ResponseFormat: regex or JSON
    # schema) this request's generation is constrained to by the
    # on-device automaton, or None = unconstrained — bit-for-bit the
    # pre-structured serve loop (the parity lock).  Compiled (or cache-
    # hit) at submit; a grammar the compiler rejects never enqueues.
    response_format: Optional[object] = None

    state: RequestState = RequestState.QUEUED
    admit_time: Optional[float] = None     # QUEUED -> PREFILL
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    generated: List[int] = field(default_factory=list)
    # serving-side error that finalized this request FAILED (crash
    # containment / failover retry exhaustion); None otherwise
    error: Optional[BaseException] = field(default=None, repr=False)
    # times this request was pulled back off a dead replica and re-queued
    # by the fleet supervisor's failover (tokens regenerate from scratch
    # on the adopting replica; with streaming on, the regeneration is
    # verified against — and suppressed by — the delivered token log,
    # so consumers see each token exactly once)
    retries: int = 0
    # times this request was preempted mid-decode by the SLO-aware
    # scheduler (PreemptionConfig): its KV was swapped out (or parked
    # for recompute) and it re-admits with `generated` intact
    preemptions: int = 0
    # speculative-decoding accounting (serving/speculative.py): draft
    # tokens proposed for / accepted by this request's verify dispatches
    # (0/0 with speculation off); acceptance = accepted / drafted
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    # distributed trace (serving/tracing.py): the span tree of this
    # request's whole fleet lifecycle, attached at submit when
    # `ServingConfig.tracing` is on.  Rides the Request object, so it
    # survives drain/failover/handoff re-homing.  None = tracing off —
    # every hook below guards on it (the bit-for-bit parity state).
    trace: Optional[object] = field(default=None, repr=False)
    # incremental token delivery (serving/streaming.TokenStream): the
    # request's sequence-numbered token log + consumer seam, attached
    # at submit when `ServingConfig.streaming` is on.  Rides the
    # Request object like the trace, so the stream survives drain,
    # failover, disagg handoff, and preemption resume.  None =
    # streaming off — every hook guards on it (the parity state).
    stream: Optional[object] = field(default=None, repr=False)

    # scheduler bookkeeping: the (per-loop) arrival sequence the bounded
    # queue ordered this request by — preserved on requeue so a rolled-
    # back admission keeps its FIFO place (the no-skip-ahead
    # anti-starvation invariant)
    _arrival_seq: Optional[int] = field(default=None, repr=False)
    # weighted-fair-queueing virtual start time, stamped by
    # TenantFairScheduler.submit and PRESERVED on requeue (like
    # `_arrival_seq`): a rolled-back / preempted request re-enters at
    # its old virtual-time place, keeping per-tenant FIFO and the
    # cross-tenant fairness ordering stable under churn
    _wfq_start: Optional[float] = field(default=None, repr=False)
    # fleet-level arrival order, stamped by the disaggregated router at
    # submit: the handoff coordinator adopts prefill-finished requests
    # onto the decode pool in THIS order, so the cross-pool handoff
    # preserves FIFO within a priority class even when two prefill
    # replicas finish out of replica-id order (no-skip-ahead across
    # pools); None outside disaggregated serving
    _fleet_seq: Optional[int] = field(default=None, repr=False)

    _cancel_requested: bool = field(default=False, repr=False)
    _done_event: threading.Event = field(default_factory=threading.Event,
                                         repr=False)

    # -- lifecycle --------------------------------------------------------
    def advance(self, new_state: RequestState, now: float) -> None:
        """Move to `new_state`, stamping the transition time.  Raises on a
        transition the lifecycle does not allow (scheduler bug guard)."""
        if new_state not in _ALLOWED.get(self.state, frozenset()):
            raise RuntimeError(
                f"request {self.uid}: illegal transition "
                f"{self.state.value} -> {new_state.value}")
        old_state = self.state
        self.state = new_state
        if new_state is RequestState.PREFILL:
            self.admit_time = now
        elif new_state in TERMINAL_STATES:
            self.finish_time = now
        if self.trace is not None:
            # record BEFORE waking result() waiters: a threaded caller
            # may export the trace the moment the event sets, and must
            # see the finish entry and the closed final phase
            self.trace.on_transition(old_state, new_state, now)
        if new_state in TERMINAL_STATES:
            if self.stream is not None:
                # close the token stream BEFORE the completion event
                # sets, same ordering discipline as the trace: a waiter
                # that wakes on the event must find the stream closed
                # (its consumers unblock with the final state attached)
                self.stream.close(new_state, self.error)
            self._done_event.set()

    def cancel(self) -> None:
        """Ask the serve loop to cancel this request.  Takes effect at the
        next scheduler step (the engine batch is never mutated mid-step)."""
        self._cancel_requested = True

    def fail(self, error: Optional[BaseException], now: float) -> None:
        """Finalize FAILED with the causing error attached — crash
        containment: the serving side cannot complete this request and
        its `result()` waiters must raise instead of hang."""
        self.error = error
        self.advance(RequestState.FAILED, now)

    def reset_for_retry(self, now: Optional[float] = None) -> None:
        """Return an IN-FLIGHT request to QUEUED for failover adoption on
        another replica (the fleet supervisor's path off a dead replica).
        Generated tokens are discarded and regenerated from scratch.
        Without streaming nothing was delivered before the terminal
        state, so the retry is invisible apart from latency; with a
        token stream attached, the delivered log survives the reset and
        the regeneration is verified against it (replayed tokens
        suppressed — exactly-once delivery).  TTFT keeps the original
        arrival (the client's experienced wait).  `now` (serve clock)
        stamps the re-queue on the request's trace when one is
        attached; the reset itself is time-free."""
        if self.state not in (RequestState.PREFILL, RequestState.DECODE):
            raise RuntimeError(
                f"request {self.uid}: reset_for_retry needs an in-flight "
                f"request, got {self.state.value}")
        self.state = RequestState.QUEUED
        self.admit_time = None
        self.first_token_time = None
        self.generated = []
        if self.stream is not None:
            # the log stays authoritative; the replay-verification
            # cursor rewinds so regeneration is re-checked token by
            # token against what consumers already received
            self.stream.on_reset()
        # discarded tokens take their speculative accounting with them
        # (the adopting replica's dispatches recount from scratch)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.retries += 1
        if self.trace is not None and now is not None:
            self.trace.on_requeue(now, self.retries)

    def preempt(self, now: float) -> None:
        """Return a DECODE-state request to QUEUED for SLO-aware
        preemption, KEEPING its generated tokens: the serve loop
        re-admits it with `prompt + generated` as the effective prompt
        (KV is a pure function of tokens and positions, so either the
        swapped-out span re-attaches from the prefix cache or a
        re-prefill reproduces it bit-for-bit) and the token stream
        continues where it left off — no replay, no loss.  TTFT keeps
        its first-token stamp; the interruption shows up in TPOT, which
        is the trade preemption makes.  The direct state rebind is the
        designed-path idiom (like the disagg handoff), not a retry."""
        if self.state is not RequestState.DECODE:
            raise RuntimeError(
                f"request {self.uid}: preempt needs a DECODE-state "
                f"request, got {self.state.value}")
        self.state = RequestState.QUEUED
        self.admit_time = None
        self.preemptions += 1
        if self.stream is not None:
            self.stream.on_resume()
        if self.trace is not None:
            self.trace.on_preempt(now, self.preemptions)

    @property
    def cancel_requested(self) -> bool:
        return self._cancel_requested

    @property
    def finished(self) -> bool:
        return self.state in TERMINAL_STATES

    def mark_first_token(self, now: float) -> None:
        if self.first_token_time is None:
            self.first_token_time = now

    # -- results ----------------------------------------------------------
    @property
    def output_tokens(self) -> np.ndarray:
        return np.asarray(self.generated, np.int32)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request reaches a terminal state and return the
        generated tokens.  Raises RequestCancelled / RequestTimedOut when
        the request did not complete, TimeoutError when the wait itself
        expires (the request keeps running)."""
        if not self._done_event.wait(timeout):
            raise TimeoutError(
                f"request {self.uid} still {self.state.value} after "
                f"{timeout}s wait")
        if self.state is RequestState.CANCELLED:
            raise RequestCancelled(f"request {self.uid} was cancelled "
                                   f"({len(self.generated)} tokens produced)")
        if self.state is RequestState.TIMED_OUT:
            raise RequestTimedOut(
                f"request {self.uid} missed its deadline "
                f"({len(self.generated)}/{self.max_new_tokens} tokens)")
        if self.state is RequestState.FAILED:
            raise RequestErrored(
                f"request {self.uid} failed serving-side: "
                f"{self.error!r}") from self.error
        return self.output_tokens

    # -- measured SLAs ----------------------------------------------------
    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, queue wait included."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def tpot(self) -> Optional[float]:
        """Mean time per output token after the first."""
        if (self.first_token_time is None or self.finish_time is None
                or len(self.generated) < 2):
            return None
        return ((self.finish_time - self.first_token_time)
                / (len(self.generated) - 1))

    @property
    def e2e_latency(self) -> Optional[float]:
        if self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time
