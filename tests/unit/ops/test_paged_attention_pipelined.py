"""The cases of ``test_paged_attention.py`` whose pages the pipeline brings,
which of its cases go which way, and the kernel called as it is, not jitted."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama_cache import paged_attention
from deepspeed_tpu.ops.paged_attention import paged_attention_pallas

from test_paged_attention import CASES, PIPELINED, _setup, matches_jnp_golden


def test_the_cases_take_both_ways_a_block_arrives():
    """Which way is a matter of the page's shape alone: the cases are
    on both sides of it, in both types."""
    from deepspeed_tpu.ops.paged_attention import _copies_pages
    ways = {(case, size): _copies_pages(*make()[1].shape[-2:], size) for case, make in CASES.items() for size in (4, 2)}
    assert sum(ways.values()) >= 18 and sum(not w for w in ways.values()) >= 10
    assert ways["one_key_head", 4] and not ways["one_key_head", 2]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PIPELINED))
def test_pallas_matches_jnp_golden(case, dtype):
    matches_jnp_golden(case, dtype)


def test_pallas_decode_single_token():
    """C=1 pure-decode step (the FastGen hot path)."""
    q, pages, bt, sp, cl, ps = _setup(c=1, h=4, n_kv=2)
    expected = paged_attention(q, pages[0], bt, sp, cl, ps)
    got = paged_attention_pallas(q, pages, bt, sp, cl, ps, layer=0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_padding_rows_zeroed():
    q, pages, bt, sp, cl, ps = _setup()
    cl = cl.at[1].set(0)  # make row 1 a padding row
    got = paged_attention_pallas(q, pages, bt, sp, cl, ps, layer=0, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[1]), 0)
