"""Cache geometry: the one owner of "which pages does a sequence of ``n``
tokens hold, and where do they sit in its block-table row".

The arena (``models/llama_cache.init_kv_cache``) is a pool of pages of one
shape; what a page *holds* is the model's business.  Every host-side place
that used to divide a token count by the page size (``BlockedKVCache``,
``StateManager.truncate``, ``AdmissionController``, the engine's caps, the
KV snapshot paths) asks the geometry instead, and ``SplitFuseScheduler``
asks it where a prefill chunk must end.

A sequence's ``pages`` list is always in *order of need*: entry ``i`` is
the ``i``-th page the sequence came to need as it grew, so growing appends
and rewinding pops the tail whatever the layout.  ``slots(n)`` is the index
(a slice or an array) of the block-table row's columns that the first ``n``
entries fill.

Stdlib + numpy only: the admission controller and the scheduler import it.
"""

import numpy as np


def _rows_walked(seen, block_rows, calls, window=0):
    """Key rows the paged kernel's walk covers for consecutive tokens that
    see ``seen[i] + 1`` rows each and go through it in ``calls`` calls of
    equal length: every token of a call walks whole blocks up to the call's
    last token's last row, from the block of the first row the call's first
    token sees (block 0 unless a ``window`` bounds what a token sees).
    ``block_rows`` 0: no kernel walks."""
    if not seen.size or not block_rows:
        return 0
    a_call = -(-seen.size // calls)
    first = np.arange(seen.size) // a_call * a_call                     # its call's first token
    last = np.minimum(first + a_call, seen.size) - 1                    # ... and last
    begin = np.maximum(seen[first] - window + 1, 0) // block_rows if window else 0
    return int(((-(-(seen[last] + 1) // block_rows) - begin) * block_rows).sum())


class LinearGeometry:
    """Page ``i`` of a sequence holds the keys and values of tokens
    ``page_size * i .. page_size * i + page_size - 1`` for ever: what every
    softmax-attention twin uses."""

    #: a full page never changes again, so sequences with a common prefix
    #: may share it (the prefix cache) and a rewind is always possible
    pages_immutable = True
    #: whether a sequence also holds a state slot (``SlotPagesGeometry``)
    state_slots = False
    #: whether consecutive chunks of one sequence may be rows of one step (a
    #: run, ``SplitFuseScheduler.run_rows``): a step's rows are all written to
    #: the pages before any of them attends, each up to its own last token, so
    #: a row finds the rows before it there as it finds the steps before it
    chunk_runs = True

    def __init__(self, page_size: int):
        self.page_size = int(page_size)

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    def table_width(self, max_tokens: int) -> int:
        """Columns a block-table row needs for a sequence of ``max_tokens``."""
        return self.pages_for(max_tokens)

    def token_capacity(self, max_tokens: int) -> int:
        """Tokens a sequence may hold where its row was sized for ``max_tokens``."""
        return int(max_tokens)

    def slots(self, n_pages: int):
        return slice(0, n_pages)

    def rewind_floor(self, seen_tokens: int) -> int:
        """The shortest history ``truncate`` may still rewind to."""
        return 0

    def state_counts(self, start: int, n_tokens: int, calls: int = 1) -> dict:
        """Further named counts of the step records (``telemetry/step_anatomy.COUNTS``)
        that feeding tokens ``start .. start + n_tokens - 1`` in ``calls`` calls
        of equal length adds to; none here."""
        return {}

    def chunk_limit(self, start: int, n_tokens: int) -> int:
        """How many of ``n_tokens`` one chunk starting at ``start`` may carry
        (or one run of chunks, where ``chunk_runs``)."""
        return n_tokens

    def step_counts(self, start: int, n_tokens: int, block_rows: int = 0, calls: int = 1, window: int = 0) -> tuple:
        """What feeding tokens ``start .. start + n_tokens - 1`` does to the
        cache, for the step records: (``attn_rows_visible``,
        ``attn_rows_walked``).  Token ``t`` sees rows ``0 .. t``, the last
        ``window`` of them where every layer's attention has one.  The
        tokens go through the paged kernel in ``calls`` calls of equal length
        (one chunk, or the fused rung's one token a step), and a call walks
        whole blocks of ``block_rows`` key rows from its first token's first
        visible row up to its last token's last, for every one of its tokens."""
        t = np.arange(start, start + n_tokens)
        visible = np.minimum(t + 1, window) if window else t + 1
        return int(visible.sum()), _rows_walked(t, block_rows, calls, window)


class RingSummaryGeometry:
    """Two kinds of page a sequence, for chunked linear attention with
    ``chunk_size == page_size``: a *ring* of ``window / page_size`` pages of
    exact keys and values (the current window, overwritten in place when the
    next window starts) and one *summary* row a chunk, ``page_size`` rows to
    a page, growing for ever.  The block-table row is ``[ring | summary
    pages]``: the ring first, so a row built for the linear layout (the
    benchmark's check) reads as a valid one."""

    pages_immutable = False
    state_slots = False
    #: inside one window: the rows of a step all write their ring rows before
    #: any attends, and a summary row is read from the next window on only;
    #: ``chunk_limit`` ends a run, as it ends a chunk, where the window ends
    #: (a row of the next window would overwrite ring rows this one's read)
    chunk_runs = True
    token_capacity = LinearGeometry.token_capacity
    state_counts = LinearGeometry.state_counts

    def __init__(self, page_size: int, window: int):
        if window % (page_size * page_size):
            # a window's summary rows must fill whole summary pages
            raise ValueError(f"window {window} is no multiple of page_size^2 = {page_size * page_size}")
        self.page_size = int(page_size)
        self.window = int(window)
        self.ring = window // page_size
        self._summary_span = page_size * page_size   # tokens one summary page covers
        n_sum = window // self._summary_span
        need = np.concatenate([np.arange(self.ring) * page_size, np.arange(n_sum) * self._summary_span])
        col = np.concatenate([np.arange(self.ring), self.ring + np.arange(n_sum)])
        self._first_window = col[np.argsort(need, kind="stable")]   # columns of the first window's pages

    def pages_for(self, n_tokens: int) -> int:
        n = int(n_tokens)
        return min(-(-n // self.page_size), self.ring) + -(-n // self._summary_span)

    def table_width(self, max_tokens: int) -> int:
        return self.ring + -(-int(max_tokens) // self._summary_span)

    def slots(self, n_pages: int):
        """Order of need: ring page ``r`` from token ``page_size * r`` on,
        summary page ``s`` from token ``page_size^2 * s`` on, the ring page
        first where both start at one token.  Once the ring is whole only
        summary pages follow, each in the column of its own index."""
        head = self._first_window[:n_pages]
        return head if n_pages <= head.size else np.concatenate([head, np.arange(head.size, n_pages)])

    def rewind_floor(self, seen_tokens: int) -> int:
        """The start of the window the last seen token lies in: the ring
        still holds every exact row from there on, and a summary row is
        written again when its chunk completes again.  Rows of the window
        before it are gone."""
        return (max(int(seen_tokens), 1) - 1) // self.window * self.window

    def chunk_limit(self, start: int, n_tokens: int) -> int:
        """A chunk ends where its window ends: inside a chunk every query
        sees the same summary rows, so the twin can hand the paged kernel one
        start position a row.  So does a run of chunks: its rows share the ring."""
        return min(n_tokens, self.window - start % self.window)

    def step_counts(self, start: int, n_tokens: int, block_rows: int = 0, calls: int = 1) -> tuple:
        """(ring rows plus summary rows the queries can see, summed over
        them, and the rows the kernel's walk covers for them: as the linear
        geometry's, over the rows of the kernel's view, summaries first)."""
        t = np.arange(start, start + n_tokens)
        seen = t % self.window + t // self.window * (self.window // self.page_size)   # rows below the query's own
        return int((seen + 1).sum()), _rows_walked(seen, block_rows, calls)


class SlotPagesGeometry(LinearGeometry):
    """Pages for the layers whose keys and values grow with the sequence (one
    layer's in ``models/phi4flash_cache.py``, every attention layer's under
    one block table in ``models/granite_hybrid_cache.py``), laid out as the
    linear geometry's, plus one **state slot** a sequence for everything of
    fixed size: the recurrent layers' states and, with a ``window``, the
    window layers' rings.  The slot's index rides in the last column of the
    block-table row, so a row sized for ``n`` tokens holds a page less; slot
    0 is scratch, as page 0 is the null page, so a row built for the linear
    layout alone reads as a valid one.  The slot is allocated with the
    sequence and released with it (``ragged.StateManager``).
    ``state_bytes``: what one sequence's recurrent states take, every layer,
    where the step records are to count the bytes a step moves of them.
    ``run_tokens``: with a window whose rings the twin sizes for it, the most
    tokens one sequence may feed in a step (``chunk_limit``); ``ring_rows``:
    the rows one window layer's ring holds in a slot, where the step records
    are to count them (``models/trinity_cache.py`` gives both)."""

    #: the pages never change, but a slot's state belongs to one sequence
    #: and is not kept by position: nothing of it can be shared or rewound to
    pages_immutable = False
    state_slots = True

    def __init__(self, page_size: int, window: int = None, state_bytes: int = 0, chunk_runs: bool = False,
                 run_tokens: int = None, ring_rows: int = 0):
        super().__init__(page_size)
        self.window = None if window is None else int(window)
        self.state_bytes = int(state_bytes)
        self.run_tokens = None if run_tokens is None else int(run_tokens)
        self.ring_rows = int(ring_rows)
        #: a row's scan and convolution start from the slot's state and leave
        #: theirs there: two rows of one sequence in a step would start from
        #: the same state, and the second's would be the one kept.  True where
        #: the twin says it hands the state on inside the program (its entry
        #: in ``models/cache_zoo.CACHE_MODEL_REGISTRY``): a row that holds the
        #: slot of the row before it and starts where that row, a full chunk,
        #: ends is a *continuing row*: it starts from the state and the
        #: convolution's inputs that row leaves, not from the slot's, and the
        #: slot receives what the last row of the run leaves, once
        #: (``models/solar_open2_cache.continuing_rows``).  A ring needs no
        #: handing on: a step's rows all write their ring rows before any
        #: attends, so a run is sound as far as the ring's slack behind its
        #: window goes (``run_tokens``): a row further on would overwrite
        #: rows an earlier row of the run still sees
        self.chunk_runs = bool(chunk_runs)

    def token_capacity(self, max_tokens: int) -> int:
        return (self.table_width(max_tokens) - 1) * self.page_size

    def rewind_floor(self, seen_tokens: int) -> int:
        """A recurrent state cannot be rewound at all."""
        return int(seen_tokens)

    def chunk_limit(self, start: int, n_tokens: int) -> int:
        """A chunk, and a run of chunks, ends where the rings' slack ends."""
        return n_tokens if self.run_tokens is None else min(n_tokens, self.run_tokens)

    def state_counts(self, start: int, n_tokens: int, calls: int = 1) -> dict:
        """``ssm_rows``: token rows the recurrence advanced; with a window,
        ``window_rows_visible``: key rows a window layer's queries could see,
        ``min(t + 1, window)`` summed over them (one layer each); with
        ``state_bytes``, ``ssd_state_bytes``: the row's states read and
        written once in each of the ``calls`` calls its tokens go through;
        with ``ring_rows``, a call each: ``ring_rows_held``, the rows the
        sequence's ring holds (one layer's), ``ring_rows_seen``, those of
        them the call's last query can see, and ``full_rows_seen``, the rows
        it sees in a layer with pages (what a call reads of each at the least)."""
        counts = {"ssm_rows": int(n_tokens)}
        if self.window is not None:
            t = np.arange(start, start + n_tokens)
            counts["window_rows_visible"] = int(np.minimum(t + 1, self.window).sum())
            if self.ring_rows and n_tokens:
                last = t[np.minimum((np.arange(calls) + 1) * -(-int(n_tokens) // calls), int(n_tokens)) - 1]
                counts["ring_rows_held"] = self.ring_rows * int(calls)
                counts["ring_rows_seen"] = int(np.minimum(last + 1, self.window).sum())
                counts["full_rows_seen"] = int((last + 1).sum())
        if self.state_bytes:
            counts["ssd_state_bytes"] = 2 * self.state_bytes * int(calls)
        return counts
