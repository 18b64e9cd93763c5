"""Operations and bytes latent attention needs, from shapes alone, and the
work of a cell's traced stretch.  The algorithm's minimum, as in
``roofline.py``, a form at a time, since the two forms of the same attention
need different work (``H`` query heads, latent rank ``r``, rotary width ``p``,
head widths ``n`` for keys and ``v`` for values):

* **absorbed** (the query carried into the latent space, the cached rows
  multiplied as they lie): a (query, visible key) pair costs ``2 H ((r + p) +
  r)`` operations, the score over ``r + p`` numbers and the output over ``r``;
  nothing is rebuilt.  32 heads, 512 + 64: 69,632 a pair.
* **expanded** (keys and values rebuilt from the latents): a pair costs ``2 H
  ((n + p) + v)``, and every cached row a call reads costs ``2 r H (n + v)``
  to rebuild.  32 heads of 128 + 64 and 128: 20,480 a pair and 8,388,608 a row.

Bytes, either form: the visible cached rows once a call (``r + p`` numbers a
row: the lanes a device pads them to are not the algorithm's), the queries in
and the outputs out.  What carries the query into the latent space and the
attended latents out of it (``W_UK``, ``W_UV``) is the projections' work, not
the attention's, and is left out, so the count errs low.
"""

import trace_reduce


def absorbed_call(chunk: int, context: int, heads: int, rank: int, rope: int, elem_bytes: int = 2):
    """One row of one layer: ``chunk`` queries after ``context`` cached tokens,
    the chunk's own rows written: (FLOPs, bytes)."""
    pairs = chunk * context + chunk * (chunk + 1) // 2
    flops = 2 * heads * ((rank + rope) + rank) * pairs
    nbytes = elem_bytes * ((context + chunk) * (rank + rope) + chunk * heads * ((rank + rope) + rank))
    return flops, nbytes


def expanded_call(chunk: int, context: int, heads: int, rank: int, rope: int, nope: int, v: int, elem_bytes: int = 2):
    """The same call in the expanded form: (FLOPs, bytes)."""
    pairs = chunk * context + chunk * (chunk + 1) // 2
    flops = 2 * heads * ((nope + rope) + v) * pairs + 2 * rank * heads * (nope + v) * (context + chunk)
    nbytes = elem_bytes * ((context + chunk) * (rank + rope) + chunk * heads * ((nope + rope) + v))
    return flops, nbytes


def crossover_chunk(heads: int, rank: int, rope: int, nope: int, v: int) -> float:
    """Queries a call above which the expanded form does fewer operations
    over a long context (the rebuild a row against the saving a pair)."""
    return 2 * rank * heads * (nope + v) / (2 * heads * ((rank + rope) + rank) - 2 * heads * ((nope + rope) + v))


def shape_of(cfg: dict) -> tuple:
    """(layers, heads, latent rank, rotary width)."""
    return cfg["num_hidden_layers"], cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]


def traced_rows(run: dict):
    """The step records that ended inside the traced stretch: it ends with
    the window's last tick and is as long as the reduced trace's window.
    None where the program keeps no such records (``step_rows.window_rows``)."""
    import step_rows
    rows, trace = step_rows.window_rows(run), run.get("reduced")
    if not rows or not trace:
        return None
    end = run["ticks"][-1][1]
    return [r for r in rows if end - trace["window_s"] <= r["end_ts"] <= end]


def traced_work(run: dict):
    """Least seconds by the roofline for the absorbed attention of the traced
    stretch's steps, every layer: a step's pairs are its ``attn_rows_visible``
    (one layer's), its cached rows read ``mla_rows_read``, its queries
    ``tokens_real``; a step is bound by its operations or by its bytes, so the
    steps' least times are added.  None where the records lack the counts (a
    program without the latent twin)."""
    import roofline
    rows = traced_rows(run)
    if not rows or "mla_rows_read" not in rows[0] or run.get("peak") is None:
        return None
    layers, heads, rank, rope = shape_of(run["config"])
    least = 0.0
    for r in rows:
        flops = 2 * heads * ((rank + rope) + rank) * r["attn_rows_visible"]
        nbytes = 2 * (r["mla_rows_read"] * (rank + rope) + r["tokens_real"] * heads * ((rank + rope) + rank))
        least += layers * roofline.least_time_s(flops, nbytes, run["peak"])
    return least


def kernel_seconds(reduced: dict, prefix: str = "ds_mla_") -> float:
    """Summed device time of the events whose operation is named ``ds_mla_*``;
    0 where the program has no such kernel."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith(prefix))
