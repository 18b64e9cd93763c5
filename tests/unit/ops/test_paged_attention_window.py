"""The paged kernel's window bound, over cases of ``test_paged_attention.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama_cache import paged_attention
from deepspeed_tpu.ops.paged_attention import paged_attention_pallas

from test_paged_attention import CASES

WINDOW_CASES = {
    # the kernel copies its pages (blocks of 64 pages = 512 rows): a window inside the first block, one that starts
    # the walk at the second block, a chunk whose first and last query see different first blocks, a decode row
    "window_inside_the_first_block": ("heads_of_128_lanes", 6),
    "walk_starts_at_a_later_block": ("table_no_multiple_of_the_block", 40),
    "a_chunk_across_the_window_s_edge": ("decode_row_in_a_chunk_of_32", 20),
    "more_than_one_query_tile": ("more_than_one_query_tile", 9),
    "layer_named_in_the_whole_arena": ("layer_named_in_the_whole_arena", 300),
    # the decode form (one query position a row): a window smaller than a row's context, equal to it (the row of 128
    # keys) and larger; one that starts the walk at a later granule of 128 keys; one wider than every context but one
    "decode_window_of_a_granule": ("decode_two_key_heads_four_queries_each", 128),
    "decode_walk_starts_at_a_later_granule": ("decode_32_key_heads_one_query_each", 300),
    "decode_window_of_a_block": ("decode_four_key_heads_eight_queries_each", 512),
    "decode_window_in_a_traced_layer_s_lone_row": ("decode_a_lone_live_row", 200),
    # the pipeline brings the pages (blocks of 16 pages = 128 rows): steps before the first block are skipped
    "pipelined_walk_starts_at_a_later_block": ("heads_of_32_lanes_table_no_multiple_of_the_block", 40),
    "pipelined_heads_of_64_lanes": ("heads_of_64_lanes_decode_row_in_a_chunk_of_32", 150),
    "three_key_heads": ("three_key_heads", 3),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_window_bound_matches_jnp_form(case, dtype):
    """A first visible row as well as a last: the query at ``t`` sees keys
    ``t - window + 1 .. t``, as ``paged_attention(sliding_window=)`` has it,
    and a scale of the caller's in place of ``1 / sqrt(D)``."""
    base, window = WINDOW_CASES[case]
    q, pages, table, start, lens, page_size, layer = CASES[base]()
    q, pages = q.astype(dtype), pages.astype(dtype)
    as32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    expected = paged_attention(as32(q), as32(pages[layer]), table, start, lens, page_size,
                               sliding_window=window, scale=0.125)
    unbounded = paged_attention(as32(q), as32(pages[layer]), table, start, lens, page_size,
                                scale=0.125)
    assert float(jnp.abs(expected - unbounded).max()) > 1e-3          # the window hides something
    got = jax.jit(lambda q, pages: paged_attention_pallas(q, pages, table, start, lens, page_size, layer=layer,
                                                          window=window, scale=0.125, interpret=True))(q, pages)
    np.testing.assert_allclose(np.asarray(as32(got)), np.asarray(expected), atol=2e-5 if dtype == jnp.float32 else 3e-2)
    past = np.arange(q.shape[1])[None, :] >= np.asarray(lens)[:, None]
    np.testing.assert_array_equal(np.asarray(as32(got))[past], 0)
