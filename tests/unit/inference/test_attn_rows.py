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

    def spy_counts(work, calls=1):
        got = counts(work, calls)
        events.append(("counts", {s.uid: s.seen_tokens for s, _ in work}, got))
        return got

    eng._invoke, eng._cache_counts = spy_invoke, spy_counts
    eng.generate([np.arange(1, 71).tolist(), np.arange(1, 21).tolist()], max_new_tokens=8)
    assert [e[0] for e in events] == ["invoke", "counts"] * len(anat.steps) and len(anat.steps) >= 4
    for (_, at_enqueue), (_, at_count, got), rec in zip(events[::2], events[1::2], anat.steps):
        assert all(at_enqueue[uid] == seen for uid, seen in at_count.items())      # as they stood before the enqueue
        assert got == (rec.attn_rows_visible, rec.attn_rows_walked) and 0 < got[0] <= got[1]
    assert any(r.key.startswith("multi:") for r in anat.steps) == (k > 1)
