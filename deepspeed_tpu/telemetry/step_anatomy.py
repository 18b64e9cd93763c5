"""Per-step anatomy: where does one engine step's wall time actually go?

The ROADMAP's largest open perf item — the AOT-compiled serving step —
cannot be judged without a number for the Python step-loop tax it exists
to kill.  This module decomposes EVERY engine step into:

* named **host segments**, measured as disjoint cursor intervals on the
  recorder's clock —

    ``admit``          the serving frontend's deadline expiry and
                       admission (``_expire`` + ``_admit``) at a tick's
                       start
    ``schedule``       step planning (``SplitFuseScheduler.plan`` /
                       the serving frontend's KV-pressure preflight)
    ``draft_plan``     speculative draft planning (``_plan_drafts``)
    ``verify_plan``    verify-batch staging (history splice + ``pack``)
    ``aot_compile``    ahead-of-time ``lower().compile()`` work done
                       inside a step window (``warm_all`` invoked while
                       a step is open — deliberate warm-up, not a miss)
    ``compile_wait``   a dispatch that triggered a JIT cache miss — the
                       trace+compile ride the first call synchronously
    ``dispatch``       host-side dispatch of an already-compiled program
                       (batch packing, array staging, the jitted call's
                       enqueue)
    ``sample_accept``  host-side token fold (argmax accept loop, EOS/
                       limit checks, rollback truncation)
    ``deliver``        the serving frontend handing the step's tokens to
                       their requests (``_deliver``: stop checks,
                       finishing, flushing)
    ``overlap``        the caller's loop between two pipelined ticks,
                       run while the previous dispatch was still in
                       flight (loop tax HIDDEN under device time; in the
                       async double-buffered tick ``admit`` and
                       ``deliver`` run in flight too)
    ``bookkeeping``    everything else inside the step window (prefix-
                       cache publish, descriptor updates, the residual
                       between the last mark and step end)

* ``device_s`` — on a real clock the HOST'S WAIT at the blocking
  readback of the dispatch's outputs (the ``ds.device_wait`` range): an
  upper bound on what the device still had to do when the host got
  there, never the device's busy time, which only the profiler's device
  trace gives.  Under ``VirtualClock``/``ReplicaClockView`` it is the
  explicitly charged step cost (``charge_last_step``);

* the **host gap** — clock time between the previous step's end and this
  step's begin.  Under a serving frontend both ends lie at a ``tick()``'s
  edges, so the gap is what the CALLER did between two ticks (submitting
  requests, its own loop).  Idle waits (``note_idle``) are excluded —
  idle is absent load, not loop tax — and the following step is flagged
  ``after_idle``.

* **counts** of what the step carried, noted where the engine packs the
  batch and folds the tokens: the program's key (``step:b16:c1:b1:c128``:
  a step's row groups, rows and width each; ``multi:b16:k8``),
  ``rows_decode``/``rows_prefill`` (rows of the batch: a decoding sequence
  each, a chunk of a prefilling one each) and ``seqs_prefill`` (prefilling
  sequences: fewer than their rows where one's consecutive chunks ran ahead
  as a run in the spare rows of its group),
  ``tokens_real`` (token positions computed for a live sequence),
  ``slots`` (positions the program computed, padding included),
  ``tokens_out`` (tokens that reached a sequence),
  ``tokens_discarded`` (overshoot of the fused rung, rejected drafts),
  ``expert_rows`` (rows a layer's routed experts multiplied:
  ``tokens_real`` x experts a token; 0 for a model with no expert layer),
  ``expert_rows_kernel`` (those of them that went through the grouped
  kernel ``ds_gmm``: all of a step's that takes the sorted form on a TPU of
  one device, by ``moe/sharded_moe.takes_sorted`` asked of the step's slots
  and, where they say "dense" and the program holds both forms
  (``live_rows_sorted``), of the rows that live in it, a round's of a fused
  dispatch, as the program asks it when it runs; none of a step's
  where every expert multiplies every row, and none where the product is
  ``jax.lax.ragged_dot``; in a device trace the dense form's products lie
  under the scope ``ds_experts_dense``.  There is no ``expert_rows_held``
  (of a share's ``expert_rows``, those that fell on an expert held here): a
  step brings back one array, its tokens, and the layers' ``exp_counts``
  would be a second transfer a dispatch; a reader takes ``held / router``
  of the rows, their expectation: ``benchmark/roofline_experts.py``)
  and, under every cache geometry, ``attn_rows_visible`` (key rows a query
  could see: ring rows plus summary rows, or its whole history; summed over
  the step's token rows, one layer) and ``attn_rows_walked`` (key rows the
  paged kernel's walk covered for them: whole blocks up to the last row its
  call could see, 0 where the attention does not read through the kernel;
  ``visible / walked`` is the kernel's tightness), ``attn_decode_rows`` (rows
  of the step's groups of one query position a row, a call of the kernel,
  that went through its decode form, padding included) and
  ``attn_decode_rows_live`` (those of them that carried a token: the form
  walks these alone).  The engine notes the
  program's key and its rows before it enqueues the program and the counts
  that take a pass over the rows (``note_counts``) after, under the device's
  busy time.

* on a real clock (``PerfClock``, ``WallClock``: ``real_time = True``), two
  readings that say what held a slow step, neither a part of the tiling:
  ``cpu_s`` (``time.thread_time()`` from ``step_begin`` to ``step_end``: CPU
  the serving thread burned; ``wall_s - host_gap_s - device_s - cpu_s`` is
  time it was blocked or descheduled) and ``gc_s`` (seconds inside Python's
  collector during the step, from one ``gc.callbacks`` hook the first such
  recorder installs).  Both stay 0.0 under a virtual clock, so two runs of
  one seed still export the same bytes.

The decomposition TILES by construction: every component is a
non-negative clock difference (or an explicit charge), and

    wall_s == host_gap_s + sum(host segments) + device_s

exactly, per step.  ``scripts/step_anatomy.py`` re-verifies the tiling
from the committed per-step table within 1e-6 (exit 1 on mismatch) —
the same trust-but-re-verify stance as ``why_slow.py``'s cause tiling.

With an ``annotate`` factory (``utils/nvtx.py::profiler_range``) every
step is also written into the host plane of a running ``jax.profiler``
trace, on the device trace's clock: one ``ds.step`` range from
``step_begin`` to ``step_end`` whose metadata are the step's index, key
and counts, and one instant ``ds.mark.<segment>`` at every cursor mark
(``ds.mark.device_wait`` for the readback).  The recorder is a cursor — a
segment's name is known when it ENDS — so a reader rebuilds segment
``ds.<segment>`` as the interval from the previous mark (or the step's
begin) to the mark; what lies between the last mark and the range's end
is ``bookkeeping``.  The module itself stays free of jax.

A step that **falls behind** says so.  When a step closes, its own time
(``wall_s - host_gap_s``) is compared with the median of the last 64 closed
steps of the same program key; with at least 16 of them, a step over 4 x
that median and at least 0.25 s over it is *slow*: its row goes into
``slow_steps`` (a ring of 256) and one ``ds.slow_step`` line goes to the
program's logger, one a second at most, with the gap before it, the wait at
the readback, the two largest host segments, ``cpu_s``, ``gc_s`` and what
the step carried.  ``host_gap_s`` is printed and left out of the rule: a
caller's idle waits lie there too.

A request's **way to its first token** rides along: the serving frontend
notes a step for every prefilling sequence it carried and folds them, when
the first token is delivered, into one row of ``first_tokens`` (a ring of
4,096 beside ``steps`` and ``encodes``; :meth:`StepAnatomy.note_first_token`):
the request's timestamps and counts and the parts of its TTFT
(``telemetry.spans.FIRST_TOKEN_PARTS``: the caller held it, it queued, a step
that carried it ran, a step that passed it by ran, the vision tower, no step
ran), which sum to it.  With a factory the row is also the instant
``ds.first_token`` of the profile, and a step's ``ds.step`` range names the
prefilling requests it carried (``prefill_uids``), so one request's steps can
be followed in a trace of the chip.

Every recorder is reachable in its process: :func:`recorders` gives the
live ones, weakly held, oldest first (what the benchmark's readers of the
step records call, and what a server's debug endpoint would).

A **compile tracker** rides along: every JIT cache miss the engine
reports (``note_compile``) is tagged warm-up or — after
:meth:`mark_steady` — an *unexpected steady-state recompile*, the
regression guard the AOT roadmap item will be held to (a serving step
set that recompiles mid-measurement is not AOT).

Overhead contract: the disabled path (:data:`NULL_ANATOMY`) allocates
NOTHING per call — one attribute read + one predicate per hook, pinned
by the tracemalloc test alongside :data:`~.trace.NULL_TRACER`.
Deliberately stdlib-only (no jax import): the engine imports it at
module scope and ``scripts/step_anatomy.py`` stays standalone.
"""

import gc
import statistics
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

from ..utils.logging import logger
from .trace import PerfClock

__all__ = ["HOST_SEGMENTS", "COUNTS", "ENCODE_COUNTS", "StepAnatomy", "NullStepAnatomy", "NULL_ANATOMY",
           "StepRecord", "CompileRecord", "recorders"]

#: the closed host-segment vocabulary; every step exports all of them
#: (zero-filled) so the per-step table has one fixed shape
HOST_SEGMENTS = ("admit", "schedule", "draft_plan", "verify_plan",
                 "aot_compile", "compile_wait", "dispatch", "sample_accept",
                 "deliver", "overlap", "bookkeeping", "promote_wait", "vision_encode")

#: what a step carried; zero until the engine notes them
COUNTS = ("rows_decode", "rows_prefill", "seqs_prefill", "tokens_real", "slots", "tokens_out",
          "tokens_discarded", "expert_rows", "expert_rows_kernel", "attn_rows_visible", "attn_rows_walked",
          "ssm_rows", "window_rows_visible", "ssd_state_bytes", "mla_rows_read", "mm_tokens",
          "sparse_decode_rows_read", "lightning_state_bytes", "ring_rows_held", "ring_rows_seen",
          "full_rows_seen", "attn_decode_rows", "attn_decode_rows_live")

#: what an encode dispatch of a vision tower carried (``StepAnatomy.encodes``, beside the step records)
ENCODE_COUNTS = ("vit_images", "vit_patches_real", "vit_patches_padded", "vit_pairs", "vit_reencoded")

#: names of the instant profiler events, built once (a mark allocates no string)
_MARK_NAMES = {s: "ds.mark." + s for s in HOST_SEGMENTS + ("device_wait", )}

#: the slow-step rule: a step's own time against the median of the last
#: SLOW_HISTORY closed steps of its key, once SLOW_MIN_STEPS of them are there
SLOW_HISTORY, SLOW_MIN_STEPS = 64, 16
SLOW_FACTOR, SLOW_OVER_S = 4.0, 0.25
SLOW_RING = 256
#: rows of ``first_tokens``: a request each, whatever ``max_steps`` is
FIRST_TOKEN_RING = 4096
SLOW_LOG_EVERY_S = 1.0

_RECORDERS: List[weakref.ref] = []
#: the recorder that closed the process's newest step, held until another
#: closes one: a reader that comes once the engine has gone (the benchmark's
#: ``step_rows.window_rows``, after its run returned) finds the run's records
#: whenever Python's collector ran, not only before it
_newest: Optional["StepAnatomy"] = None


def recorders() -> list:
    """The live recorders of this process, oldest first.  Weakly held: a
    recorder goes with its engine (or whoever else made it), and its
    reference leaves the list with it; only the one that closed the newest
    step stays until another recorder closes a step."""
    return [rec for ref in list(_RECORDERS) if (rec := ref()) is not None]


_gc_total_s = 0.0   # seconds inside Python's collector since the hook went in
_gc_t0 = None


def _gc_hook(phase, info):
    global _gc_total_s, _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()  # dslint-ok(determinism): the collector runs in real time; only a recorder on a real clock reads the sum
    elif _gc_t0 is not None:
        _gc_total_s += time.perf_counter() - _gc_t0  # dslint-ok(determinism): as above
        _gc_t0 = None


class StepRecord:
    """One recorded engine step (mutable only via the recorder)."""

    __slots__ = ("index", "key", "path", "segments", "device_s", "host_gap_s",
                 "wall_s", "after_idle", "compiles", "end_ts", "cpu_s", "gc_s", "prefill_uids") + COUNTS

    def __init__(self, index: int):
        self.index = index
        self.key: Optional[str] = None       # the program: step:b16:c1:b1:c128 | multi:b16:k8 | verify:b16:w5
        self.path: Optional[str] = None      # decode|prefill|mixed|spec_verify|multi_decode
        self.segments: Dict[str, float] = {s: 0.0 for s in HOST_SEGMENTS}
        self.device_s = 0.0                  # real clock: the host's wait at the readback
        self.host_gap_s = 0.0
        self.wall_s = 0.0
        self.after_idle = False
        self.compiles = 0                    # JIT cache misses THIS step paid for
        self.end_ts = 0.0                    # recorder-clock time at step end
        self.cpu_s = 0.0                     # real clock: CPU the stepping thread burned in the step
        self.gc_s = 0.0                      # real clock: seconds inside Python's collector in the step
        self.prefill_uids: tuple = ()        # the prefilling requests it carried (the range's metadata, no column)
        self.rows_decode = self.rows_prefill = self.seqs_prefill = 0
        self.tokens_real = self.slots = 0
        self.tokens_out = self.tokens_discarded = 0
        self.expert_rows = self.expert_rows_kernel = 0
        self.attn_rows_visible = self.attn_rows_walked = 0
        self.ssm_rows = self.window_rows_visible = self.ssd_state_bytes = self.mla_rows_read = self.mm_tokens = 0
        self.sparse_decode_rows_read = self.lightning_state_bytes = 0
        self.ring_rows_held = self.ring_rows_seen = self.full_rows_seen = 0
        self.attn_decode_rows = self.attn_decode_rows_live = 0

    def host_s(self) -> float:
        return sum(self.segments.values())

    def own_s(self) -> float:
        """The step without the gap before it: host segments and the wait at the readback."""
        return self.wall_s - self.host_gap_s

    def counts(self) -> Dict[str, int]:
        return {c: getattr(self, c) for c in COUNTS}

    def to_row(self) -> dict:
        """Deterministic export row (9-dp rounding, fixed key order)."""
        return {
            "index": self.index,
            "key": self.key,
            "path": self.path,
            **self.counts(),
            "segments": {s: round(self.segments[s], 9) for s in HOST_SEGMENTS},
            "device_s": round(self.device_s, 9),
            "host_gap_s": round(self.host_gap_s, 9),
            "wall_s": round(self.wall_s, 9),
            "end_ts": round(self.end_ts, 9),
            "cpu_s": round(self.cpu_s, 9),
            "gc_s": round(self.gc_s, 9),
            "after_idle": self.after_idle,
            "compiles": self.compiles,
        }


class CompileRecord:
    """One compile event: which program key, at which step, whether it
    fired after the warm-up boundary (``steady`` = the regression), and
    whether it was a deliberate AOT ``lower().compile()`` (``aot``)
    rather than a JIT cache miss a dispatch paid for synchronously."""

    __slots__ = ("key", "step_index", "steady", "ts", "aot")

    def __init__(self, key: str, step_index: int, steady: bool, ts: float,
                 aot: bool = False):
        self.key = key
        self.step_index = step_index
        self.steady = steady
        self.ts = ts
        self.aot = aot

    def to_row(self) -> dict:
        return {"key": self.key, "step_index": self.step_index,
                "steady": self.steady, "aot": self.aot,
                "ts": round(self.ts, 9)}


class StepAnatomy:
    """Per-step anatomy recorder with a pluggable clock.

    ``clock``: any ``now() -> float`` provider (``VirtualClock``,
    ``ReplicaClockView``, ``WallClock``, :class:`~.trace.PerfClock`
    default).  ``max_steps`` bounds the per-step table (deque; evictions
    counted in ``dropped_steps``); lifetime totals keep accumulating past
    the cap, so the host-gap-fraction gauges never lie about the window
    they cover being the whole run.  ``annotate``: an optional factory of
    profiler ranges, ``annotate(name)`` giving a context manager with
    ``set_metadata(**kw)`` (``utils/nvtx.py::profiler_range``); with it
    every step and mark is also written into a running profiler trace."""

    enabled = True

    def __init__(self, clock=None, max_steps: int = 4096, annotate=None):
        if max_steps < 1:
            raise ValueError(f"max_steps must be >= 1, got {max_steps}")
        self.steps = deque(maxlen=int(max_steps))
        self.dropped_steps = 0
        #: rows of the steps the slow-step rule caught, with ``own_s`` and ``median_s``
        self.slow_steps = deque(maxlen=SLOW_RING)
        self._own_by_key: Dict[str, deque] = {}
        self._slow_logged: Optional[float] = None   # clock time of the last ds.slow_step line
        self.compiles: List[CompileRecord] = []
        self.steady_state_recompiles = 0
        #: one row a dispatch of a vision tower's program: ``ts`` (this clock),
        #: ``key`` (``vit:p4096``) and ``ENCODE_COUNTS``
        self.encodes = deque(maxlen=int(max_steps))
        #: one row a request that reached its first token under a serving
        #: frontend (``telemetry.spans.first_token_row``), raw clock differences
        self.first_tokens = deque(maxlen=FIRST_TOKEN_RING)
        #: monotonic count of CLOSED steps (deque eviction never rewinds it)
        self.total_steps = 0
        # lifetime totals (survive deque eviction; the cheap gauge inputs)
        self.total_wall_s = 0.0
        self.total_host_s = 0.0
        self.total_device_s = 0.0
        self.total_host_gap_s = 0.0
        self._steady = False
        self._last_end: Optional[float] = None
        self._after_idle = False
        self._cur: Optional[StepRecord] = None
        self._held = False      # a frontend owns the window's close
        self._gap0 = 0.0        # inter-step gap captured at step_begin
        self._t = 0.0           # segment cursor
        self._annotate = annotate
        self._range = None      # the open step's ``ds.step`` profiler range
        self._cpu0 = self._gc0 = 0.0
        self._bind(clock if clock is not None else PerfClock())
        _RECORDERS.append(weakref.ref(self, _RECORDERS.remove))

    def _bind(self, clock) -> None:
        self.clock = clock
        # cpu_s and gc_s are read on a clock of real time only
        self._real = bool(getattr(clock, "real_time", False))
        if self._real and _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)

    def rebind(self, clock) -> None:
        """Move the recorder onto another clock (the serving frontend's, so
        ``end_ts`` lies on the clock its caller times the ticks on).  Refused
        while a step is open; the gap origin resets, since the two clocks
        share no zero."""
        if self._cur is not None:
            raise RuntimeError("StepAnatomy.rebind: a step is open")
        self._bind(clock)
        self._last_end = None
        self._slow_logged = None

    # ------------------------------------------------------------- lifecycle

    def step_begin(self, hold: bool = False) -> None:
        """Open a step window.  Idempotent while a step is open: the
        serving frontend opens the window before its admission/preflight
        work and the engine's own ``step_begin`` then no-ops, so the two
        layers share one step without coordination.  ``hold=True`` (the
        serial serving tick) keeps the window open through the engine's
        ``step_end`` until ``step_end(release=True)``, so what the
        frontend does after the fold (``deliver``) lies inside the step."""
        if self._cur is not None:
            self._held = self._held or hold
            return
        t = self.clock.now()
        self._cur = StepRecord(self.total_steps)
        self._held = hold
        if self._last_end is not None:
            self._gap0 = t - self._last_end
            if self._gap0 < 0:   # clock-domain mixup must not corrupt tiling
                self._gap0 = 0.0
        else:
            self._gap0 = 0.0
        self._cur.after_idle = self._after_idle
        self._after_idle = False
        self._t = t
        if self._real:
            self._cpu0, self._gc0 = time.thread_time(), _gc_total_s
        if self._annotate is not None:
            self._range = self._annotate("ds.step")
            self._range.__enter__()

    def _advance(self, name: str) -> float:
        """Move the cursor to now (and, with a factory, put the instant
        ``ds.mark.<name>`` into the profile); the seconds it moved by."""
        t = self.clock.now()
        if self._annotate is not None:
            with self._annotate(_MARK_NAMES[name]):
                pass
        dt = t - self._t
        self._t = t
        return dt if dt > 0 else 0.0

    def mark(self, segment: str) -> None:
        """Attribute the cursor interval ``[last mark, now]`` to
        ``segment`` and advance the cursor.  Outside an open step (a
        frontend early-return path) the call is a no-op."""
        if self._cur is not None:
            self._cur.segments[segment] += self._advance(segment)

    def device_mark(self) -> None:
        """Attribute the cursor interval to ``device_s``: on a real clock
        the host's wait at the blocking output materialization."""
        if self._cur is not None:
            self._cur.device_s += self._advance("device_wait")

    def note_program(self, key: str, path: str, rows_decode: int = 0, rows_prefill: int = 0,
                     seqs_prefill: int = 0, tokens_real: int = 0, slots: int = 0) -> None:
        """Tag the open step with the program it dispatches (``key``, as
        ``InferenceEngineV2._key_label`` prints it: the attribution key)
        and the rows and positions of the packed batch.  A step that never
        dispatches (empty plan) keeps ``path=None`` and is DISCARDED at
        step_end: its host time folds into the next real step's host gap,
        which is exactly what that time is (loop tax without device work)."""
        cur = self._cur
        if cur is not None:
            cur.key, cur.path = key, path
            cur.rows_decode, cur.rows_prefill = int(rows_decode), int(rows_prefill)
            cur.seqs_prefill = int(seqs_prefill)
            cur.tokens_real, cur.slots = int(tokens_real), int(slots)

    def note_encode(self, key: str, patches_real: int, patches_padded: int, reencoded: bool = False) -> None:
        """One image through the vision tower's program ``key``: a row of
        ``encodes``.  ``vit_pairs`` is the (query, key) pairs a layer's
        attention needs, ``patches_real`` squared."""
        self.encodes.append({"ts": self.clock.now(), "key": key, "vit_images": 1,
                             "vit_patches_real": int(patches_real), "vit_patches_padded": int(patches_padded),
                             "vit_pairs": int(patches_real)**2, "vit_reencoded": int(bool(reencoded))})

    def note_prefill_uids(self, uids: tuple) -> None:
        """The prefilling requests the open step carries, as the serving
        frontend knows them: ``prefill_uids`` of the ``ds.step`` range."""
        if self._cur is not None:
            self._cur.prefill_uids = uids

    def note_first_token(self, row: dict) -> None:
        """A request's first token was delivered: ``row`` (``telemetry.spans.
        first_token_row``) joins ``first_tokens``, and with a factory it is
        the instant ``ds.first_token`` of the profile, with the request and
        the parts of its TTFT as metadata."""
        self.first_tokens.append(row)
        if self._annotate is not None:
            with self._annotate("ds.first_token") as instant:
                instant.set_metadata(**{k: v for k, v in row.items() if k == "uid" or k.endswith("_s")})

    def note_counts(self, expert_rows: int = 0, expert_rows_kernel: int = 0,
                    cache_counts: tuple = (0, 0), state_counts: Optional[dict] = None) -> None:
        """What the packed batch carries beyond its rows, noted once the
        program is enqueued (the passes over the rows then run under the
        device's busy time): the rows through the experts, ``cache_counts``
        (the geometry's ``step_counts`` summed over the rows: visible,
        walked) and ``state_counts`` (its ``state_counts``, by name)."""
        cur = self._cur
        if cur is not None:
            cur.expert_rows, cur.expert_rows_kernel = int(expert_rows), int(expert_rows_kernel)
            cur.attn_rows_visible, cur.attn_rows_walked = (int(c) for c in cache_counts)
            for name, count in (state_counts or {}).items():
                setattr(cur, name, int(count))

    def note_tokens(self, out: int, discarded: int = 0, real: int = 0,
                    expert_rows: int = 0, expert_rows_kernel: int = 0) -> None:
        """What the fold did with the step's tokens: ``out`` reached a
        sequence, ``discarded`` were computed and thrown away; ``real``
        adds positions whose use is known only now (a verify round's
        accepted + 1 a row), ``expert_rows`` their rows through the experts
        and ``expert_rows_kernel`` those of them through ``ds_gmm``."""
        cur = self._cur
        if cur is not None:
            cur.tokens_out += int(out)
            cur.tokens_discarded += int(discarded)
            cur.tokens_real += int(real)
            cur.expert_rows += int(expert_rows)
            cur.expert_rows_kernel += int(expert_rows_kernel)

    def note_compile(self, key: str, aot: bool = False) -> None:
        """One compile event (the engine's ``_step_fns`` grew an entry).
        A JIT cache miss (``aot=False``) is tagged warm-up until
        :meth:`mark_steady`; after it, counted as an unexpected
        steady-state recompile — the AOT regression signal.  A deliberate
        ``warm_all`` AOT compile (``aot=True``) is NEVER steady-state
        noise: it is the warm-up mechanism itself, and does not bump the
        per-step JIT-miss counter either."""
        idx = self._cur.index if self._cur is not None else self.total_steps
        rec = CompileRecord(key, idx, self._steady and not aot,
                            self.clock.now(), aot=aot)
        self.compiles.append(rec)
        if self._cur is not None and not aot:
            self._cur.compiles += 1
        if rec.steady:
            self.steady_state_recompiles += 1

    def note_idle(self) -> None:
        """The driver idled (an arrival/deadline ``wait_until`` jump):
        exclude the idle stretch from the anatomy.  Between steps the gap
        origin resets (next step's host gap starts at 0, flagged
        ``after_idle``); inside an open step the cursor snaps to now so
        the jump lands in no segment."""
        if self._cur is not None:
            self._t = self.clock.now()
            self._cur.after_idle = True
        else:
            self._last_end = None
        self._after_idle = True

    def step_end(self, release: bool = False) -> Optional[StepRecord]:
        """Close the step window: the residual cursor interval becomes
        ``bookkeeping``, the inter-step gap becomes ``host_gap_s``, and
        ``wall_s`` is the exact component sum (the tiling invariant).
        A window a frontend holds (``step_begin(hold=True)``) closes only
        with ``release=True``.  Returns the closed record, or None when
        the window stays open or the step never dispatched (discarded —
        see :meth:`note_program`)."""
        cur = self._cur
        if cur is None or (self._held and not release):
            return None
        t = self.clock.now()
        tail = t - self._t
        if tail > 0:
            cur.segments["bookkeeping"] += tail
        self._cur = None
        self._held = False
        rng, self._range = self._range, None
        if rng is not None:
            if cur.path is not None:
                rng.set_metadata(index=cur.index, key=cur.key, **cur.counts())
                if cur.prefill_uids:   # "+" between them: a comma ends a value of the profile's metadata
                    rng.set_metadata(prefill_uids="+".join(map(str, cur.prefill_uids)))
            rng.__exit__(None, None, None)
        if cur.path is None:
            # planned-but-empty step: keep the gap origin where it was so
            # this window folds into the next real step's host gap
            return None
        cur.host_gap_s = self._gap0
        cur.wall_s = cur.host_gap_s + cur.host_s() + cur.device_s
        cur.end_ts = t
        if self._real:
            cur.cpu_s, cur.gc_s = time.thread_time() - self._cpu0, _gc_total_s - self._gc0
        self._last_end = t
        self._retain(cur)
        self._judge(cur)
        return cur

    def _judge(self, rec: StepRecord) -> None:
        """The slow-step rule (module docstring): compare the closed step's
        own time with the median of its key's last steps, then add it to them."""
        own = rec.own_s()
        history = self._own_by_key.get(rec.key)
        if history is None:
            history = self._own_by_key[rec.key] = deque(maxlen=SLOW_HISTORY)
        if own >= SLOW_OVER_S and len(history) >= SLOW_MIN_STEPS:   # the median only where it can matter
            median = statistics.median(history)
            if own > SLOW_FACTOR * median and own - median >= SLOW_OVER_S:
                self._slow(rec, own, median)
        history.append(own)

    def _slow(self, rec: StepRecord, own: float, median: float) -> None:
        self.slow_steps.append({**rec.to_row(), "own_s": round(own, 9), "median_s": round(median, 9)})
        if self._slow_logged is not None and 0 <= rec.end_ts - self._slow_logged < SLOW_LOG_EVERY_S:
            return
        self._slow_logged = rec.end_ts
        top = sorted(HOST_SEGMENTS, key=lambda s: -rec.segments[s])[:2]
        logger.warning(
            f"ds.slow_step index={rec.index} key={rec.key} own_s={own:.6f} median_s={median:.6f} "
            f"host_gap_s={rec.host_gap_s:.6f} device_wait_s={rec.device_s:.6f} "
            + " ".join(f"{s}={rec.segments[s]:.6f}" for s in top) +
            f" cpu_s={rec.cpu_s:.6f} gc_s={rec.gc_s:.6f} compiles={rec.compiles} "
            f"rows_decode={rec.rows_decode} rows_prefill={rec.rows_prefill} seqs_prefill={rec.seqs_prefill} "
            f"tokens_real={rec.tokens_real} slots={rec.slots}")

    def charge_last_step(self, dt: float) -> Optional[StepRecord]:
        """Device charge for clock-driven frontends: a ``VirtualClock``/
        ``ReplicaClockView`` accounts the step cost via ``clock.on_step``
        AFTER the engine step returned, so the serving loop forwards the
        charged seconds here.  They go to the step that last dispatched:
        the open one (a held window: its cursor snaps to the clock, so
        the charged advance lands in no segment), else the last closed
        record, whose device and wall grow by ``dt`` while the gap origin
        re-anchors at the clock's current reading (a VirtualClock just
        advanced by the charge; a deferred ReplicaClockView has not, and
        its round advance shows up in the next step's host gap — the
        round-quantization the fleet simulator actually imposes)."""
        if not dt >= 0:
            raise ValueError(f"step charge cannot be negative (dt={dt})")
        cur = self._cur
        if cur is not None and cur.path is not None:
            cur.device_s += dt
            self._t = self.clock.now()
            return cur
        if not self.steps:
            return None
        rec = self.steps[-1]
        rec.device_s += dt
        rec.wall_s += dt
        self.total_device_s += dt
        self.total_wall_s += dt
        self._last_end = self.clock.now()
        rec.end_ts = self._last_end
        return rec

    def mark_steady(self) -> None:
        """Declare warm-up over: every later JIT cache miss is an
        unexpected steady-state recompile.  One-way by design — a harness
        that wants a fresh warm-up builds a fresh recorder."""
        self._steady = True

    @property
    def steady(self) -> bool:
        return self._steady

    def reset_steps(self) -> None:
        """Drop the per-step table and lifetime totals, keep the compile
        log and the steady boundary — the bench pattern: warm up, mark
        steady, reset, measure (warm-up steps must not dilute the
        measured host-gap fractions; warm-up COMPILES must stay on the
        record, they are what 'steady state' is defined against)."""
        self.steps.clear()
        self.slow_steps.clear()
        self.first_tokens.clear()
        self._own_by_key.clear()
        self.dropped_steps = 0
        self.total_steps = 0
        self.total_wall_s = self.total_host_s = 0.0
        self.total_device_s = self.total_host_gap_s = 0.0
        self._last_end = None
        self._after_idle = False
        self._cur = None
        self._held = False
        rng, self._range = self._range, None
        if rng is not None:
            rng.__exit__(None, None, None)

    # --------------------------------------------------------------- intake

    def _retain(self, rec: StepRecord) -> None:
        global _newest
        _newest = self
        if self.steps.maxlen is not None and len(self.steps) == self.steps.maxlen:
            self.dropped_steps += 1
        self.steps.append(rec)
        self.total_steps += 1
        self.total_wall_s += rec.wall_s
        self.total_host_s += rec.host_s()
        self.total_device_s += rec.device_s
        self.total_host_gap_s += rec.host_gap_s

    # -------------------------------------------------------------- queries

    @property
    def last_step(self) -> Optional[StepRecord]:
        return self.steps[-1] if self.steps else None

    def host_gap_fraction(self) -> Optional[float]:
        """Lifetime host-gap share of wall time — the one-number loop-tax
        gauge (None before the first step)."""
        if self.total_wall_s <= 0:
            return None
        return self.total_host_gap_s / self.total_wall_s

    def by_shape(self) -> Dict[str, dict]:
        """Per-program-key aggregation over the RETAINED steps (the deque
        window; ``dropped_steps`` tells the reader when that window is
        not the whole run).  Deterministic key order."""
        out: Dict[str, dict] = {}
        for rec in self.steps:
            agg = out.get(rec.key)
            if agg is None:
                agg = out[rec.key] = {
                    "steps": 0, "wall_s": 0.0, "host_s": 0.0,
                    "device_s": 0.0, "host_gap_s": 0.0, "compiles": 0,
                    **{c: 0 for c in COUNTS},
                    "segments": {s: 0.0 for s in HOST_SEGMENTS}}
            agg["steps"] += 1
            agg["wall_s"] += rec.wall_s
            agg["host_s"] += rec.host_s()
            agg["device_s"] += rec.device_s
            agg["host_gap_s"] += rec.host_gap_s
            agg["compiles"] += rec.compiles
            for c in COUNTS:
                agg[c] += getattr(rec, c)
            for s in HOST_SEGMENTS:
                agg["segments"][s] += rec.segments[s]
        for key in sorted(out):
            agg = out[key]
            wall = agg["wall_s"]
            out[key] = {
                "steps": agg["steps"],
                "wall_s": round(wall, 9),
                "host_s": round(agg["host_s"], 9),
                "device_s": round(agg["device_s"], 9),
                "host_gap_s": round(agg["host_gap_s"], 9),
                "host_gap_fraction": round(agg["host_gap_s"] / wall, 6)
                if wall > 0 else None,
                "compiles": agg["compiles"],
                **{c: agg[c] for c in COUNTS},
                "segments": {s: round(agg["segments"][s], 9)
                             for s in HOST_SEGMENTS},
            }
        return {k: out[k] for k in sorted(out)}

    def summary(self) -> dict:
        return {
            "steps": self.total_steps,
            "retained_steps": len(self.steps),
            "dropped_steps": self.dropped_steps,
            "slow_steps": len(self.slow_steps),
            "wall_s": round(self.total_wall_s, 9),
            "host_s": round(self.total_host_s, 9),
            "device_s": round(self.total_device_s, 9),
            "host_gap_s": round(self.total_host_gap_s, 9),
            "host_gap_fraction": None if self.total_wall_s <= 0
            else round(self.total_host_gap_s / self.total_wall_s, 6),
            "compiles": len(self.compiles),
            "steady_state_recompiles": self.steady_state_recompiles,
            "steady": self._steady,
        }

    def to_doc(self) -> dict:
        """The full deterministic export (what ``scripts/step_anatomy.py``
        verifies and folds):
        per-step table, compile log, per-program fold, summary.  Pure
        data, 9-dp rounding, sorted keys downstream.  Schema 3 = the
        program key as a step's identity, the counts, and the ``admit``
        and ``deliver`` segments; ``first_tokens`` (the requests' ways to
        their first tokens, in the rows' own key order) came later and
        changed no other key."""
        return {
            "schema": 3,
            "summary": self.summary(),
            "by_shape": self.by_shape(),
            "steps": [rec.to_row() for rec in self.steps],
            "compiles": [c.to_row() for c in self.compiles],
            "first_tokens": [{k: round(v, 9) if isinstance(v, float) else v for k, v in row.items()}
                             for row in self.first_tokens],
        }


class NullStepAnatomy:
    """Disabled recorder: every hook is a no-op and allocates nothing —
    the engine hot path costs one attribute read + one predicate per
    step when anatomy is off (pinned by tracemalloc tests)."""

    enabled = False
    steps: tuple = ()
    compiles: tuple = ()
    first_tokens: tuple = ()
    dropped_steps = 0
    total_steps = 0
    steady_state_recompiles = 0
    steady = False

    def step_begin(self, hold=False) -> None:
        pass

    def mark(self, segment) -> None:
        pass

    def device_mark(self) -> None:
        pass

    def note_program(self, key, path, rows_decode=0, rows_prefill=0, seqs_prefill=0, tokens_real=0, slots=0) -> None:
        pass

    def note_encode(self, key, patches_real, patches_padded, reencoded=False) -> None:
        pass

    def note_prefill_uids(self, uids) -> None:
        pass

    def note_first_token(self, row) -> None:
        pass

    def note_counts(self, expert_rows=0, expert_rows_kernel=0, cache_counts=(0, 0), state_counts=None) -> None:
        pass

    def note_tokens(self, out, discarded=0, real=0, expert_rows=0, expert_rows_kernel=0) -> None:
        pass

    def note_compile(self, key, aot=False) -> None:
        pass

    def note_idle(self) -> None:
        pass

    def step_end(self, release=False) -> None:
        return None

    def charge_last_step(self, dt) -> None:
        return None

    def mark_steady(self) -> None:
        pass

    def reset_steps(self) -> None:
        pass

    @property
    def last_step(self):
        return None

    def host_gap_fraction(self):
        return None

    def by_shape(self) -> dict:
        return {}

    def summary(self) -> dict:
        return {}

    def to_doc(self) -> dict:
        return {"schema": 3, "summary": {}, "by_shape": {}, "steps": [],
                "compiles": [], "first_tokens": []}


NULL_ANATOMY = NullStepAnatomy()
