"""Phase-span derivation: turn a request's state history into trace spans.

The serving layers already keep an exact, timestamped state history per
request (``ServingRequest.history`` / ``FleetRequest.history``) — the
tracer does not shadow it with live open/close bookkeeping on the hot
path.  Instead, when a request (or a failed-over replica attempt) ends,
its history is folded into contiguous **phase spans** here:

    queued    — QUEUED (admission queue, preemption requeue, backoff)
    prefill   — the part of PREFILL in which a step that carried a chunk of
                the request was running (``ServingRequest.carry_windows``:
                prompt + recompute-on-resume KV build); all of PREFILL for
                a request nobody noted steps for
    decode    — DECODE
    migrating — MIGRATING (paused for chunked KV export — the per-request
                migration cost of disaggregated serving)
    vision_encode — the part of PREFILL before the request's images were
                through the vision tower (``ServingRequest.encode_windows``)
    prefill_bypassed — the part of PREFILL in which a step of the engine ran
                and carried no chunk of the request (the token budget went
                to another prompt, a step was in flight when it was
                admitted; ``ServingRequest.bypass_windows``)
    prefill_wait — the part of PREFILL in which no step ran: admission and
                planning before a dispatch, delivery after it, the caller's
                loop between ticks
    pending   — fleet-level router queue time (before dispatch, between
                failover displacement and re-dispatch)

Phase spans TILE the request's lifetime exactly — consecutive history
entries share boundary timestamps — which is the property
``scripts/trace_report.py`` verifies against the recorded TTFT/TPOT
accounting (sum of phases == ttft + tpot*(n-1) == e2e for completed
requests).  ``clamp_start`` exists for resumed fleet attempts: their
``ServingRequest.arrival_ts`` is backdated to the CLIENT arrival (so
replica-side aging/deadlines stay correct), but the attempt's spans must
start at its dispatch or they would double-count the previous attempt's
time."""

from typing import List, Optional, Tuple

from ..serving.request import RequestState, ServingRequest
from .trace import Span, Tracer

__all__ = ["PHASE_OF_STATE", "FIRST_TOKEN_PARTS", "phase_intervals", "attempt_intervals", "emit_attempt_spans",
           "first_token_row"]

#: the parts of a first token's time, in the row's order: they are
#: non-negative and sum to ``first_token_ts - arrival_ts``
FIRST_TOKEN_PARTS = ("late_s", "queued_s", "carried_s", "bypassed_s", "vision_encode_s", "wait_s", "other_s")

# RequestState -> phase name; EVICTED is transient (the requeue lands at
# the same timestamp) but named so a non-zero-length eviction window —
# e.g. a future async release — would still be visible, not silently
# merged into queue time.
PHASE_OF_STATE = {
    RequestState.QUEUED: "queued",
    RequestState.PREFILL: "prefill",
    RequestState.DECODE: "decode",
    RequestState.EVICTED: "evicted",
    # host-staging window of a KV migration (serving/kvtransfer): the
    # request is paused on the source replica while its pages export — the
    # per-request migration cost the disaggregation bench accounts for
    RequestState.MIGRATING: "migrating",
    # idle session with its KV demoted to the host tier (serving/kvtier):
    # zero device pages held; ends at resume() re-enqueue
    RequestState.PARKED: "parked",
}


def _carve(intervals: List[Tuple[str, float, float]],
           windows: List[Tuple[float, float]], name: str = "promote",
           out_of: Tuple[str, ...] = ("parked", "tool_stall", "queued")
           ) -> List[Tuple[str, float, float]]:
    """Carve h2d promotion transfer windows (``ServingRequest.
    promote_windows``) out of the ``parked``/``queued`` intervals they
    overlap, as ``promote`` pieces; or, with ``name`` and ``out_of``, other
    windows out of other phases (a request's wait for the vision tower,
    ``encode_windows``, out of ``prefill`` as ``vision_encode``).  The pieces PARTITION each original
    interval (tiling preserved exactly): a resume's TTFT then splits into
    genuine queue wait vs promotion transfer instead of lumping both into
    ``queued``.  Windows never overlap other phases — the engine stalls
    admission until ``t_ready`` before stamping PREFILL."""
    if not windows:
        return intervals
    # merge overlapping/adjacent windows (seq + prefix promotes can abut)
    merged: List[List[float]] = []
    for w0, w1 in sorted(windows):
        if merged and w0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], w1)
        else:
            merged.append([w0, w1])
    out: List[Tuple[str, float, float]] = []
    for phase, t0, t1 in intervals:
        if phase not in out_of:
            out.append((phase, t0, t1))
            continue
        cur = t0
        for w0, w1 in merged:
            lo, hi = max(cur, w0), min(t1, w1)
            if hi <= lo:
                continue
            if lo > cur:
                out.append((phase, cur, lo))
            out.append((name, lo, hi))
            cur = hi
        if t1 > cur:
            out.append((phase, cur, t1))
    return out


def phase_intervals(history: List[Tuple[RequestState, float]],
                    end_ts: Optional[float] = None,
                    clamp_start: Optional[float] = None,
                    tail_phase: Optional[str] = None,
                    park_phase: str = "parked"
                    ) -> List[Tuple[str, float, float]]:
    """Fold a state history into ``(phase, t0, t1)`` intervals.

    ``end_ts`` closes the last non-terminal state (required for displaced
    attempts whose history never reached a terminal entry); terminal
    entries are points and close the walk.  Zero-length intervals are
    dropped.  ``clamp_start`` clips every interval's start (see module
    docstring).

    ``tail_phase`` relabels the OPEN tail — the stretch from the last
    recorded transition to ``end_ts`` — with a caller-supplied phase
    name.  The fleet router uses ``"fenced"`` for lease-expired/fenced
    attempts: the router credits the phases it observed up to the last
    transition it could know about, and attributes the remainder of the
    attempt window — work served outside the replica's lease, later
    discarded by the fence — to ``phase/fenced``, so transport-mode
    traces still tile [arrival, terminal] exactly
    (scripts/trace_report.py).

    ``park_phase`` relabels PARKED intervals (``ServingRequest.
    park_phase``): ``"tool_stall"`` when a session parked the request
    mid-generation awaiting a tool result — same machinery, different
    attribution (a tool stall is the AGENT's latency, an idle park the
    user's think time)."""
    out: List[Tuple[str, float, float]] = []
    for i, (state, ts) in enumerate(history):
        if state.terminal:
            break
        open_tail = i + 1 >= len(history)
        if not open_tail:
            nxt = history[i + 1][1]
        elif end_ts is not None:
            nxt = end_ts
        else:
            break  # open-ended non-terminal tail with no close time: skip
        t0 = ts if clamp_start is None else max(ts, clamp_start)
        if nxt > t0 and state in PHASE_OF_STATE:
            if open_tail and tail_phase is not None:
                phase = tail_phase
            elif state is RequestState.PARKED:
                phase = park_phase
            else:
                phase = PHASE_OF_STATE[state]
            out.append((phase, t0, nxt))
    return out


#: once the carried pieces of PREFILL have a name of their own, what is left
#: of it is the wait, and the carried pieces take the phase's name
_AFTER_CARRY = {"prefill": "prefill_wait", "prefill_carried": "prefill"}


def attempt_intervals(req: ServingRequest, end_ts: Optional[float] = None,
                      clamp_start: Optional[float] = None,
                      tail_phase: Optional[str] = None) -> List[Tuple[str, float, float]]:
    """One serving attempt's ``(phase, t0, t1)`` pieces: the state history
    folded (:func:`phase_intervals`) and carved by the windows the request
    carries: ``promote`` out of the queue, ``vision_encode`` out of PREFILL,
    then what is left of PREFILL by the steps that ran in it: ``prefill``
    stays the name of the pieces under ``carry_windows``, ``prefill_bypassed``
    are those under ``bypass_windows`` and ``prefill_wait`` is the rest.  A
    request nobody noted steps for (no ``carry_windows`` attribute) keeps
    its PREFILL in one ``prefill`` piece."""
    intervals = phase_intervals(req.history, end_ts=end_ts,
                                clamp_start=clamp_start,
                                tail_phase=tail_phase,
                                park_phase=getattr(req, "park_phase",
                                                   "parked"))
    intervals = _carve(intervals, getattr(req, "promote_windows", None) or [])
    intervals = _carve(intervals, getattr(req, "encode_windows", None) or [], "vision_encode", ("prefill", ))
    carry = getattr(req, "carry_windows", None)
    if carry is None:
        return intervals
    intervals = _carve(intervals, carry, "prefill_carried", ("prefill", ))
    intervals = _carve(intervals, getattr(req, "bypass_windows", None) or [], "prefill_bypassed", ("prefill", ))
    return [(_AFTER_CARRY.get(phase, phase), t0, t1) for phase, t0, t1 in intervals]


def first_token_row(req: ServingRequest, ran_s: float) -> dict:
    """A request's way to its first token as one row (``StepAnatomy.
    first_tokens``; docs/OBSERVABILITY.md "The way to a first token"): the
    pieces of :func:`attempt_intervals` up to ``first_token_ts`` summed into
    ``FIRST_TOKEN_PARTS``.  The queue splits at ``submit_ts`` into ``late_s``
    (the caller held the request) and ``queued_s``; ``ran_s``, the step
    seconds inside the request's PREFILL as the frontend's running sum has
    them, splits what no carrying step covered into ``bypassed_s`` (a step
    ran) and ``wait_s`` (none did), so the row needs no list of the steps
    that passed the request by.  Raw clock differences: ``StepAnatomy.
    to_doc`` rounds them."""
    first = req.first_token_ts
    submit = req.submit_ts if req.submit_ts is not None else req.arrival_ts
    parts = dict.fromkeys(FIRST_TOKEN_PARTS, 0.0)
    uncarried = 0.0
    for phase, t0, t1 in attempt_intervals(req, end_ts=first):   # the history ends here: no piece lies behind
        if phase == "queued":
            late = max(0.0, min(t1, submit) - t0)
            parts["late_s"] += late
            parts["queued_s"] += (t1 - t0) - late
        elif phase == "prefill":
            parts["carried_s"] += t1 - t0
        elif phase == "vision_encode":
            parts["vision_encode_s"] += t1 - t0
        elif phase in ("prefill_wait", "prefill_bypassed"):
            uncarried += t1 - t0
        else:
            parts["other_s"] += t1 - t0
    parts["bypassed_s"] = min(max(ran_s - parts["carried_s"], 0.0), uncarried)
    parts["wait_s"] = uncarried - parts["bypassed_s"]
    carried = req.carry_windows[-1][1] if req.carry_windows else None
    return {"uid": req.uid, "arrival_ts": req.arrival_ts, "submit_ts": submit, "admitted_ts": req.admitted_ts,
            "first_dispatch_ts": req.first_dispatch_ts, "last_carried_ts": carried, "first_token_ts": first,
            "ttft_s": first - req.arrival_ts, **parts,
            "prompt_tokens": len(req.prompt), "prefill_tokens": req.prefill_tokens,
            "prefill_steps": req.prefill_steps, "preemptions": req.preemptions}


def emit_attempt_spans(tracer: Tracer, req: ServingRequest, trace_id: int,
                       parent_id: Optional[int], track: str,
                       end_ts: Optional[float] = None,
                       clamp_start: Optional[float] = None,
                       tail_phase: Optional[str] = None) -> List[Span]:
    """Materialize one serving attempt's phase spans (children of
    ``parent_id``) plus its preemption span events.  Used by the serving
    frontend at request terminal and by the fleet router for the partial
    attempt a replica death (or lease expiry — ``tail_phase="fenced"``)
    displaced."""
    spans = []
    for phase, t0, t1 in attempt_intervals(req, end_ts=end_ts, clamp_start=clamp_start, tail_phase=tail_phase):
        spans.append(tracer.add_span(f"phase/{phase}", trace_id, t0, t1,
                                     parent_id=parent_id, track=track))
    return spans
