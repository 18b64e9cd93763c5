"""``attn_rows_visible`` and ``attn_rows_walked``: the key rows a step's
queries could see, and the key rows the paged kernel's walk covered for them
(``inference/v2/geometry.py``, summed by ``engine_v2._cache_counts`` into the
step records).  Hand-worked steps under both geometries, then an engine's own
records: counts, not speeds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import LinearGeometry, RingSummaryGeometry
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.ops.paged_attention import walk_block
from deepspeed_tpu.telemetry import StepAnatomy

#: geometry, (start, tokens, block rows, calls) -> (visible, walked), each worked by hand
HAND_WORKED = {
    # a decode row behind 299 tokens sees rows 0..299; the walk takes three blocks of 128
    "linear_decode_row": (LinearGeometry(16), (299, 1, 128, 1), (300, 384)),
    # a row that ends on a block's edge walks nothing it does not see
    "linear_decode_row_on_the_edge": (LinearGeometry(16), (255, 1, 128, 1), (256, 256)),
    # a chunk of 4 from 126: the queries see 127, 128, 129, 130 rows; all four walk to the chunk's end, two blocks
    "linear_chunk_across_a_block": (LinearGeometry(16), (126, 4, 128, 1), (127 + 128 + 129 + 130, 4 * 256)),
    # the fused rung: the same four tokens in four calls of one; the first two end inside the first block
    "linear_fused_rung": (LinearGeometry(16), (126, 4, 128, 4), (127 + 128 + 129 + 130, 128 + 128 + 256 + 256)),
    # a prefill from nothing, blocks of 32: 1 + 2 + ... + 40 seen, 40 queries walk 64 rows each
    "linear_first_chunk": (LinearGeometry(8), (0, 40, 32, 1), (820, 40 * 64)),
    # a window of 200 on every layer: the decode row at 299 sees rows 100..299 and its walk starts at block 0 all
    # the same; at 399 it sees 200..399 and starts at block 1: three blocks of the four
    "linear_window_decode_row": (LinearGeometry(16), (299, 1, 128, 1, 200), (200, 384)),
    "linear_window_walk_starts_later": (LinearGeometry(16), (399, 1, 128, 1, 200), (200, 384)),
    # a chunk of 4 from 126 under it: 127, 128, 129, 130 rows seen; from 400: four times 200, and blocks 1..3 of 128
    "linear_window_wider_than_the_context": (LinearGeometry(16), (126, 4, 128, 1, 200), (127 + 128 + 129 + 130, 4 * 256)),
    "linear_window_chunk": (LinearGeometry(16), (400, 4, 128, 1, 200), (800, 4 * 384)),
    # no token, no rows
    "linear_empty_row": (LinearGeometry(16), (77, 0, 128, 1), (0, 0)),
    # window 256, pages of 16: token 600 lies 88 into the third window behind 2 x 16 summary rows: it sees 32 + 89
    "ring_decode_row_third_window": (RingSummaryGeometry(16, 256), (600, 1, 128, 1), (121, 128)),
    # a chunk of 3 from 630 in that window: 32 + 119, 120, 121 rows seen, each walks 32 + 121 = 153 -> two blocks
    "ring_chunk_third_window": (RingSummaryGeometry(16, 256), (630, 3, 128, 1), (151 + 152 + 153, 3 * 256)),
    # the fused rung over a window's end: token 255 sees 256 rows, token 256 one ring row and 16 summaries
    "ring_fused_rung_across_a_window": (RingSummaryGeometry(16, 256), (255, 2, 128, 2), (256 + 17, 256 + 128)),
}


@pytest.mark.parametrize("case", list(HAND_WORKED))
def test_rows_visible_and_walked_by_hand(case):
    geometry, args, (visible, walked) = HAND_WORKED[case]
    assert geometry.step_counts(*args) == (visible, walked)


@pytest.mark.parametrize("attention_impl", ["flash", "reference"])
def test_a_linear_engines_records_fill_both_counts(attention_impl):
    """A tiny Llama engine under the linear geometry: every step's
    ``attn_rows_visible`` is the sum of ``t + 1`` over the tokens it fed, and
    ``attn_rows_walked`` is never less and less than a block and a chunk a
    token more, where the attention reads through the kernel (``flash``):
    where it does not, nothing walked."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32, scan_layers=True, remat=False, attention_impl=attention_impl)
    params = nn.meta.unbox(LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=24)
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=32, decode_bucket=4),
        max_new_tokens=12, enable_prefix_cache=False, decode_steps_per_dispatch=4, kv_dtype=jnp.float32))
    block = walk_block(kv.page_size, eng.kv.table_width, 2, 16, 4) * kv.page_size
    assert block == 128                                     # heads of 16 lanes: the pipeline brings blocks of 128 rows
    assert eng._walk_rows() == (block if attention_impl == "flash" else 0)
    anat = eng.set_anatomy(StepAnatomy())
    prompts = [np.arange(1, 1 + n).tolist() for n in (150, 37)]
    eng.generate(prompts, max_new_tokens=12)
    rows = [r.to_row() for r in anat.steps]
    assert any(r["key"].startswith("multi:") for r in rows) and any(r["key"].startswith("step:") for r in rows)
    fed = [n + 11 for n in (150, 37)]                       # a prompt and all sampled tokens but the last
    overshoot = sum(r["tokens_discarded"] for r in rows)    # the last rung's tokens past the limit were fed too
    lowest = sum(n * (n + 1) // 2 for n in fed)
    assert lowest <= sum(r["attn_rows_visible"] for r in rows) <= lowest + overshoot * (max(fed) + 4)
    for r in rows:
        slack = r["attn_rows_walked"] - r["attn_rows_visible"]
        if attention_impl == "flash":
            assert 0 <= slack < r["tokens_real"] * (block + 32), r
        else:
            assert r["attn_rows_walked"] == 0 < r["attn_rows_visible"], r


def test_a_run_is_counted_row_by_row():
    """A prompt of 150 tokens in chunks of 32 goes as a run of four rows and
    one of 22 tokens where the engine has a rung of four prefill rows
    (``max_seqs`` 8).  Each row walks the cache to its own end, so the steps'
    ``attn_rows_walked`` and ``attn_rows_visible`` are the sums over the same
    five chunks fed one a step (a run counted as one call would have every
    token walk to the run's end; ``test_xing4_twin.py`` has the case where that
    shows).  The records alone: no program runs (``_invoke`` hands back zeros)."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32, scan_layers=True, remat=False, attention_impl="flash")
    params = nn.meta.unbox(LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    totals = {}
    for run_rows in (4, 1):
        eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
            kv=PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=24),
            scheduler=SchedulerConfig(token_budget=256, max_seqs=8, prefill_chunk=32, decode_bucket=8),
            max_new_tokens=1, enable_prefix_cache=False, decode_steps_per_dispatch=1, kv_dtype=jnp.float32))
        assert eng.scheduler.run_rows == 4 and eng._walk_rows() == 128
        eng.scheduler.run_rows = run_rows
        eng._compiled_step = lambda groups: None
        eng._invoke = lambda fn, params, cache, tokens, start_pos, *rest: (np.zeros(start_pos.shape, np.int32), cache)
        eng.put([0], [np.arange(1, 151).tolist()])
        while not eng.state.seqs[0].done:
            eng.step()
        rows = [r.to_row() for r in eng.anatomy.steps]
        assert [(r["rows_prefill"], r["tokens_real"]) for r in rows] == (
            [(4, 128), (1, 22)] if run_rows == 4 else [(1, 32)] * 4 + [(1, 22)])
        totals[run_rows] = (sum(r["attn_rows_visible"] for r in rows), sum(r["attn_rows_walked"] for r in rows))
    chunks = [(0, 32), (32, 32), (64, 32), (96, 32), (128, 22)]
    by_hand = [LinearGeometry(8).step_counts(start, n, 128) for start, n in chunks]
    assert totals[4] == totals[1] == (150 * 151 // 2, sum(walked for _, walked in by_hand))
    assert totals[4][1] == 32 * 128 * 4 + 22 * 256


@pytest.mark.parametrize("window", [0, 24], ids=["full_layers", "window_layers"])
def test_a_decode_steps_walk_is_the_decode_forms(window):
    """Heads of 128 lanes, pages the kernel copies: a group of one position a
    row takes the kernel's decode form, whose walk goes by granules of 128 key
    rows from the first a token sees to the last (``walk_block(chunk=1)``, the
    function the kernel cuts its blocks by), a prompt's chunk the general
    form's blocks (here the table's 24 pages: 192 rows).  ``attn_rows_walked``
    of every step is that, row by row, with a window on every layer and with
    none; ``attn_decode_rows`` are the rows of the step's groups of one
    position, a call, ``attn_decode_rows_live`` those that carried a token,
    and with the prefill group's rows they are the step's kernel rows.  The
    records alone: no program runs (``_invoke`` hands back zeros)."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=256, intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=512, sliding_window=window,
                      dtype=jnp.float32, scan_layers=True, remat=False, attention_impl="flash")
    params = nn.meta.unbox(LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    k = 4
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=96, page_size=8, max_pages_per_seq=24),
        scheduler=SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=32, decode_bucket=4),
        max_new_tokens=12, enable_prefix_cache=False, decode_steps_per_dispatch=k, kv_dtype=jnp.float32))
    granule, block = eng._walk_rows(1), eng._walk_rows(32)
    assert granule == 128 == walk_block(8, 24, 2, 128, 4, chunk=1) * 8 and block == 192 == eng._walk_rows()
    eng._compiled_step = eng._compiled_multi_step = lambda *key: None

    def no_program(fn, params, cache, tokens, start_pos, *rest):
        fused = tokens.size == start_pos.size and eng.anatomy._cur.key.startswith("multi:")
        return np.zeros(start_pos.shape + ((k, ) if fused else ()), np.int32), cache

    eng._invoke = no_program
    fed = {}

    def spy(groups, calls=1):                                  # (first position, tokens, width, calls) of every kernel row
        fed[len(eng.anatomy.steps)] = [(start, n, width, calls) for work, _, width in groups
                                       for start, n in eng._kernel_rows(work, calls)]
        return counts(groups, calls)

    counts, eng._cache_counts = eng._cache_counts, spy
    eng.put([0, 1, 2], [np.arange(1, 151).tolist(), np.arange(1, 38).tolist(), np.arange(1, 131).tolist()])
    while not all(s.done for s in eng.state.seqs.values()):
        eng.step()
    rows = [r.to_row() for r in eng.anatomy.steps]
    assert any(r["key"].startswith("multi:") for r in rows) and any(":c32" in r["key"] for r in rows)

    def walked(start, n, width, calls):                        # by hand: every token of a call walks the call's span
        total, a_call, unit = 0, -(-n // calls), granule if width == 1 else block
        for at in range(start, start + n, a_call):
            end = min(at + a_call, start + n)
            first = max(at - window + 1, 0) // unit if window else 0
            total += (end - at) * (-(-end // unit) - first) * unit
        return total

    for i, r in enumerate(rows):
        assert r["attn_rows_walked"] == sum(walked(*row) for row in fed[i]), r
        calls = k if r["key"].startswith("multi:") else 1
        groups = [tuple(int(x[1:]) for x in g) for g in zip(*[iter(r["key"].split(":")[1:])] * 2)]
        groups = [(groups[0][0], 1)] if calls > 1 else groups                    # multi:b4:k4 is four rows of one position
        ones = sum(b for b, c in groups if c == 1)
        assert r["attn_decode_rows"] == calls * ones
        assert r["attn_decode_rows_live"] == sum(1 for row in fed[i] if row[2] == 1) * calls <= r["attn_decode_rows"]
        assert r["attn_decode_rows"] // calls + sum(b for b, c in groups if c > 1) == sum(b for b, _ in groups)
    assert sum(r["attn_decode_rows_live"] for r in rows) >= 3 * 11
    if window:                                                   # a decode row whose walk left its first granule out
        assert any(width == 1 and start - window + 1 >= granule for f in fed.values() for start, _, width, _ in f)


@pytest.mark.parametrize("k", [1, 4])
def test_the_counts_are_noted_after_the_enqueue_and_read_the_same(k):
    """The program's key and rows are on the open step before ``_invoke``
    enqueues it; the passes over the rows (``_cache_counts``) run after, and
    give what they would have given before: nothing between the two moves a
    sequence's ``seen_tokens``.  Both dispatch sites (``k``: the single step
    and the fused rung)."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32, scan_layers=True, remat=False, attention_impl="flash")
    params = nn.meta.unbox(LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=24),
        scheduler=SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=32, decode_bucket=4),
        max_new_tokens=8, enable_prefix_cache=False, decode_steps_per_dispatch=k, kv_dtype=jnp.float32))
    anat, invoke, counts, events = eng.anatomy, eng._invoke, eng._cache_counts, []

    def spy_invoke(fn, *args):
        cur = anat._cur
        assert cur.key is not None and cur.slots > 0 and cur.attn_rows_visible == 0 == cur.attn_rows_walked
        events.append(("invoke", {uid: s.seen_tokens for uid, s in eng.state.seqs.items()}))
        return invoke(fn, *args)

    def spy_counts(groups, calls=1):
        got = counts(groups, calls)
        events.append(("counts", {s.uid: s.seen_tokens for work, _, _ in groups for s, _ in work}, got))
        return got

    eng._invoke, eng._cache_counts = spy_invoke, spy_counts
    eng.generate([np.arange(1, 71).tolist(), np.arange(1, 21).tolist()], max_new_tokens=8)
    assert [e[0] for e in events] == ["invoke", "counts"] * len(anat.steps) and len(anat.steps) >= 4
    for (_, at_enqueue), (_, at_count, got), rec in zip(events[::2], events[1::2], anat.steps):
        assert all(at_enqueue[uid] == seen for uid, seen in at_count.items())      # as they stood before the enqueue
        assert got == (rec.attn_rows_visible, rec.attn_rows_walked) and 0 < got[0] <= got[1]
    assert any(r.key.startswith("multi:") for r in anat.steps) == (k > 1)
