"""State slots beside the pages (``inference/v2/geometry.SlotPagesGeometry``):
what a sequence of ``n`` tokens holds, the slot's life with its sequence in
``StateManager`` (allocate, exhaust, release on flush and on preempt), the
slot in the packed batch's rows, and admission by free slots.  Host code only:
no model runs here (``test_phi4flash_engine.py`` serves one through them).
"""

import types

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.geometry import LinearGeometry, RingSummaryGeometry, SlotPagesGeometry
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, StateManager
from deepspeed_tpu.serving.admission import AdmissionConfig, AdmissionController
from deepspeed_tpu.serving.request import ServingRequest

PAGE, WINDOW, CHUNK = 16, 512, 128


def _geometry():
    return SlotPagesGeometry(PAGE, WINDOW)


def _manager(state_slots=3, num_pages=64, max_pages_per_seq=10, max_batch=8):
    kv = BlockedKVCache(num_pages, PAGE, max_pages_per_seq, enable_prefix_cache=False, geometry=_geometry(),
                        state_slots=state_slots)
    return kv, StateManager(kv, max_batch=max_batch)


# ------------------------------------------------------------------- the geometry


@pytest.mark.parametrize("n_tokens, pages", [(0, 0), (1, 1), (16, 1), (17, 2), (3080, 193)])
def test_a_sequence_holds_one_layers_pages_whatever_its_length(n_tokens, pages):
    g = _geometry()
    assert g.pages_for(n_tokens) == pages == LinearGeometry(PAGE).pages_for(n_tokens)
    assert g.state_slots and not LinearGeometry(PAGE).state_slots and not RingSummaryGeometry(16, 256).state_slots


def test_the_rows_last_column_is_the_slots():
    """A row sized for 194 pages of tokens (the cell's) is 194 columns wide
    and holds 193 pages: 3,088 tokens."""
    g = _geometry()
    assert g.table_width(194 * PAGE) == 194 and g.token_capacity(194 * PAGE) == 193 * PAGE
    assert LinearGeometry(PAGE).token_capacity(194 * PAGE) == 194 * PAGE
    kv, _ = _manager(max_pages_per_seq=194, num_pages=400)
    assert (kv.table_width, kv.max_pages_per_seq, kv.max_tokens_per_seq) == (194, 193, 3088)


def test_a_recurrent_state_cannot_be_rewound_and_a_chunk_is_held_to_the_rings():
    g = _geometry()
    assert g.rewind_floor(700) == 700 and g.rewind_floor(0) == 0
    assert g.chunk_limit(300, 20) == 20


@pytest.mark.parametrize("start, n", [(0, 128), (448, 128), (2500, 1), (511, 2)])
def test_counts_of_a_step(start, n):
    t = np.arange(start, start + n)
    g = _geometry()
    assert g.state_counts(start, n) == {"ssm_rows": n, "window_rows_visible": int(np.minimum(t + 1, WINDOW).sum())}
    assert g.step_counts(start, n)[0] == int((t + 1).sum())       # the shared layer: as the linear geometry's
    assert LinearGeometry(PAGE).state_counts(start, n) == {}


@pytest.mark.parametrize("start, n, calls", [(0, 128, 1), (2500, 8, 8), (2500, 1, 1)])
def test_counts_of_a_step_without_a_window_and_with_state_bytes(start, n, calls):
    """Slots that hold recurrent states and no ring (``models/granite_hybrid_cache.py``):
    no window count, and the states' bytes a step moves, in and out, once a
    call the row's tokens go through (a chunk: one; a fused dispatch: its rounds)."""
    state = 36 * 2_097_152
    g = SlotPagesGeometry(PAGE, state_bytes=state)
    assert g.window is None and g.state_slots and not g.pages_immutable
    assert g.state_counts(start, n, calls) == {"ssm_rows": n, "ssd_state_bytes": 2 * state * calls}
    assert g.table_width(258 * PAGE) == 258 and g.token_capacity(258 * PAGE) == 257 * PAGE
    assert g.rewind_floor(700) == 700 and g.chunk_limit(300, 128) == 128
    assert "ssd_state_bytes" not in _geometry().state_counts(start, n, calls)      # rings and Mamba-1 states: not counted
    assert LinearGeometry(PAGE).state_counts(start, n, calls) == {}


def test_a_slot_comes_and_goes_the_same_without_a_window():
    kv = BlockedKVCache(64, PAGE, 10, enable_prefix_cache=False, geometry=SlotPagesGeometry(PAGE), state_slots=3)
    state = StateManager(kv, max_batch=8)
    seq = state.get_or_create(1, [5] * 20)
    assert seq.slot in (1, 2) and kv.slot_allocator.free_pages == 1
    state.flush(1)
    assert kv.slot_allocator.free_pages == 2
    with pytest.raises(NotImplementedError, match="prefix cache over SlotPagesGeometry"):
        BlockedKVCache(64, PAGE, 10, enable_prefix_cache=True, geometry=SlotPagesGeometry(PAGE), state_slots=3)


# ------------------------------------------------------- the slot's life in StateManager


def test_a_slot_comes_with_the_sequence_and_goes_with_it():
    kv, state = _manager(state_slots=3)                      # slot 0 is scratch: two sequences
    assert kv.slot_allocator.free_pages == 2
    a, b = state.get_or_create(1, [5] * 40), state.get_or_create(2, [6] * 20)
    assert sorted((a.slot, b.slot)) == [1, 2] and kv.slot_allocator.free_pages == 0
    assert state.get_or_create(1) is a and kv.slot_allocator.free_pages == 0       # no second slot for a known uid
    with pytest.raises(RuntimeError, match="state slots exhausted"):
        state.get_or_create(3, [7] * 5)
    assert 3 not in state.seqs
    state.flush(1)
    assert kv.slot_allocator.free_pages == 1 and a.slot == 0
    c = state.get_or_create(3, [7] * 5)
    assert c.slot in (1, 2) and c.slot != b.slot
    back = state.preempt(2)
    assert back is b and b.slot == 0 and not b.pages and kv.slot_allocator.free_pages == 1
    state.flush(3)
    assert kv.slot_allocator.free_pages == 2 and kv.allocator.free_pages == kv.num_pages - 1


def test_the_packed_rows_carry_pages_then_zeros_then_the_slot():
    kv, state = _manager(state_slots=4)
    a, b = state.get_or_create(1, [5] * 40), state.get_or_create(2, [6] * 20)
    batch = state.pack_groups([([(a, 40), (b, 20)], 4, 128)])
    tables = batch.block_tables
    assert tables.shape == (4, kv.table_width)
    assert tables[0, :3].tolist() == a.pages and tables[1, :2].tolist() == b.pages
    assert not tables[0, 3:-1].any() and not tables[1, 2:-1].any()
    assert tables[:, -1].tolist() == [a.slot, b.slot, 0, 0]           # padding rows: the scratch slot
    assert not tables[2:].any()


def test_geometries_without_slots_allocate_none():
    kv = BlockedKVCache(16, PAGE, 4, enable_prefix_cache=False, state_slots=9)
    assert kv.slot_allocator is None
    seq = StateManager(kv).get_or_create(1, [1, 2, 3])
    assert seq.slot == 0


# --------------------------------------------------------------- admission by free slots


def test_admission_waits_for_a_free_slot_and_nothing_raises():
    kv, state = _manager(state_slots=3, max_batch=8)
    engine = types.SimpleNamespace(kv=kv, state=state, cfg=types.SimpleNamespace(max_position_embeddings=4096))
    admission = AdmissionController(AdmissionConfig(), engine)
    req = lambda uid: ServingRequest(uid=uid, prompt=[3] * 30, arrival_ts=0.0, max_new_tokens=20)  # noqa: E731
    assert admission.submit_ok(req(1), 0) == (True, None)
    assert admission.can_start(req(1))
    state.get_or_create(1, [3] * 30)
    assert admission.can_start(req(2))
    state.get_or_create(2, [3] * 30)
    assert len(state.seqs) < state.max_batch and kv.allocator.free_pages > 50
    assert not admission.can_start(req(3))             # batch room and pages enough: no slot
    state.flush(1)
    assert admission.can_start(req(3))
    # a request longer than the row holds beside its slot is refused at the door
    long = ServingRequest(uid=9, prompt=[3] * (9 * PAGE), arrival_ts=0.0, max_new_tokens=PAGE)
    assert admission.submit_ok(long, 0) == (False, "exceeds_max_pages_per_seq")
