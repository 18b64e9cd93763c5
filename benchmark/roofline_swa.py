"""Operations and bytes that window-plus-full grouped-query attention needs
(window layers whose queries see the last ``window`` rows, in rings, beside
full layers whose queries see every row behind them, in pages), from shapes
and the step records alone, and the work of a cell's traced stretch.  The
algorithm's minimum, as in ``roofline.py``: the same work whatever
implements it.  bf16 (2 bytes an element).

A query at position ``t`` sees ``min(t + 1, window)`` rows in a window layer
and ``t + 1`` in a full one.  A visible row costs a query head ``4 d``
operations (the score over ``d`` numbers and the output over ``d``, a product
and a sum each).  A call (a row of a step: a chunk's tokens, or one token of a
decode row) reads the keys and values its last query sees once a layer, one
row of ``n_kv`` key heads for all the ``n_q / n_kv`` query heads that share
them; the queries come in and the outputs go out, ``n_q`` heads a token and
layer.  The step records give the sums (``telemetry/step_anatomy.COUNTS``):
``window_rows_visible`` and ``attn_rows_visible`` (visible rows, summed over
the step's queries, one layer of each kind), ``ring_rows_seen`` and
``full_rows_seen`` (rows the last query of each call sees, one layer of each
kind), ``tokens_real``.  The rotary turn, the head norms, the gate and the
writes are not the kernel's and are left out; a step is bound by its
operations or by its bytes, though its calls are each bound by their own, so
the count errs low.
"""

import re

import trace_reduce

SLIDING = "sliding_attention"


def layer_kinds(cfg: dict) -> list:
    """The kind of each layer held: the dense layers, then the expert layers (``refs/trinity.layer_kinds``)."""
    dense, n = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    first = cfg.get("expert_layers_from")
    first = dense if first is None else first
    return list(cfg["layer_types"][:dense]) + list(cfg["layer_types"][first:first + n - dense])


def shape_of(cfg: dict) -> tuple:
    """(query heads, key heads, head size, window layers, full layers)."""
    kinds = layer_kinds(cfg)
    window = sum(k == SLIDING for k in kinds)
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], window, len(kinds) - window


def step_work(window_visible: int, full_visible: int, ring_seen: int, full_seen: int, tokens: int, n_q: int,
              n_kv: int, d: int, window_layers: int, full_layers: int, elem_bytes: int = 2):
    """(FLOPs, bytes) of one step's attention, every layer, from its records'
    sums (one layer of each kind): the visible (query, row) pairs, the rows
    each call's last query sees, the tokens."""
    flops = 4 * d * n_q * (window_layers * window_visible + full_layers * full_visible)
    rows = elem_bytes * 2 * n_kv * d * (window_layers * ring_seen + full_layers * full_seen)
    q_io = elem_bytes * 2 * n_q * d * tokens * (window_layers + full_layers)
    return flops, rows + q_io


COUNTS = ("window_rows_visible", "attn_rows_visible", "ring_rows_seen", "full_rows_seen", "tokens_real")


def traced_work(run: dict):
    """Least seconds by the roofline for the attention of the traced
    stretch's steps: a step's least time is the larger of its operations over
    the peak rate and its bytes over the peak bandwidth, and the steps' are
    added.  None where the records lack the counts (a program without the
    twin) or the device's peaks are unknown."""
    import roofline
    import roofline_mla
    rows = roofline_mla.traced_rows(run)
    if not rows or any(c not in rows[0] for c in COUNTS) or run.get("peak") is None:
        return None
    shape = shape_of(run["config"])
    return sum(roofline.least_time_s(*step_work(*(r[c] for c in COUNTS), *shape), run["peak"]) for r in rows)


_ARENA = re.compile(r"bf16\[(\d+),\d+,\d+,2,\d+,\d+\]")


def kernel_seconds(reduced: dict, cfg: dict = None) -> dict:
    """Summed device time of the events whose operation is named
    ``ds_paged_attention``, the kernel both kinds of layer go through:
    ``{"all", "window", "full"}``.  The program draws the two kinds under the
    scopes ``ds_swa_window`` and ``ds_swa_full``, which the profiler keeps in
    an operation's metadata and not in the HLO text this reducer reads; here a
    call is told by the arena it is handed, whose leading axis is the number
    of layers of its kind ([window layers, ring pages, ...] against [full
    layers, pages, ...]).  Where both kinds have as many layers, or no
    configuration is given, the split is left out."""
    out = {"all": 0.0, "window": 0.0, "full": 0.0}
    *_, window_layers, full_layers = shape_of(cfg) if cfg else (0, 0)
    for e in reduced["events"]:
        if not trace_reduce.parse(e)[0].startswith("ds_paged_attention"):
            continue
        out["all"] += e[2] - e[1]
        layers = {int(n) for n in _ARENA.findall(e[0])}
        if window_layers != full_layers:
            if window_layers in layers and full_layers not in layers:
                out["window"] += e[2] - e[1]
            elif full_layers in layers and window_layers not in layers:
                out["full"] += e[2] - e[1]
    return out
