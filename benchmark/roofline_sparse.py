"""Operations and bytes block-selected sparse attention needs for the one-token
rows of a decode step, from shapes and the step records alone, and the work of
a cell's traced stretch.  The algorithm's minimum, as in ``roofline.py``: the
same work whatever implements it.

A decode row at position ``t`` attends, a sparse layer and key head, the key
rows its selection names (``sparse_decode_rows_read`` of the step records: the
forced and the chosen blocks at ``t >= dense_len``, ``t + 1`` rows under it).
A named key row costs a query head ``4 d`` operations (the score over ``d``
numbers and the output over ``d``, a product and a sum each), ``rep`` query
heads a key head; its key and its value are read once, one key head's rows,
in the cache's precision.  The queries in and the outputs out (8 KB a row,
layer and key head beside 3 MB of rows) are left out, and so are the scoring
of the compressed keys, the top-k and the lists: the selection's work, not
the walk's.  A walk that moves whole pages of
every key head to use one head's rows (``ops/sparse_paged_attention.py``)
moves more than this count: that shows as a lower share, which is the point.
A prefill tile's reads are not counted at all (they do not go through the
kernel), so the count errs low.
"""

import trace_reduce


def decode_rows_call(rows_read: int, rep: int, d: int, elem_bytes: int = 2):
    """``rows_read`` named key rows (summed over rows, sparse layers and key
    heads), each met by ``rep`` query heads of ``d``: (FLOPs, bytes)."""
    return 4 * d * rep * rows_read, elem_bytes * 2 * d * rows_read


def shape_of(cfg: dict) -> tuple:
    """(sparse layers x key heads, query heads a key head, head size)."""
    layers = sum(kind == "minicpm4" for kind in cfg["mixer_types"])
    return (layers * cfg["num_key_value_heads"], cfg["num_attention_heads"] // cfg["num_key_value_heads"],
            cfg["head_dim"])


def traced_work(run: dict):
    """Least seconds by the roofline for the list walks of the traced
    stretch's steps: a step's named rows are its ``sparse_decode_rows_read``;
    a step is bound by its operations or by its bytes, so the steps' least
    times are added.  None where the records lack the count (a program
    without the twin)."""
    import roofline
    import roofline_mla
    rows = roofline_mla.traced_rows(run)
    if not rows or "sparse_decode_rows_read" not in rows[0] or run.get("peak") is None:
        return None
    _, rep, d = shape_of(run["config"])
    return sum(roofline.least_time_s(*decode_rows_call(r["sparse_decode_rows_read"], rep, d), run["peak"])
               for r in rows)


def kernel_seconds(reduced: dict, prefix: str = "ds_sparse_paged_attention") -> float:
    """Summed device time of the events whose operation is named
    ``ds_sparse_paged_attention``; 0 where the program has no such kernel."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith(prefix))
