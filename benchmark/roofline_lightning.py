"""Operations and bytes one position of Lightning linear attention needs on a
state that lives in HBM (the decode step's ``ds_lightning_update``), from
shapes and the step records alone, and the work of a cell's traced stretch.
The algorithm's minimum, as in ``roofline.py``: a head's state ``[K, V]``
float32 read once and written once,

    S <- lambda_h S + k v^T;   o = S^T q.

A state element costs five operations: the decay's product; a product and a
sum for the outer-product write; a product and a sum for ``S^T q``.  The
norms, the rotary and the gate are a channel's, not a state element's, and
are left out, so the count errs low.
"""

import trace_reduce


def lightning_update_call(heads: int, d_key: int, d_value: int):
    """One row of one layer, one position: (FLOPs, bytes).  Bytes: the state
    in and out; ``q`` and ``k`` in a key channel; ``v`` in and ``o`` out a
    value channel; float32, as the kernel takes them."""
    state = heads * d_key * d_value
    return 5 * state, 4 * (2 * state + 2 * heads * d_key + 2 * heads * d_value)


def shape_of(cfg: dict) -> tuple:
    """(Lightning layers, heads, key size, value size)."""
    layers = sum(kind == "lightning-attn" for kind in cfg["mixer_types"])
    return layers, cfg["lightning_nh"], cfg["lightning_head_dim"], cfg["lightning_head_dim"]


def traced_work(run: dict):
    """{"flops", "bytes"} of the one-position updates inside the traced
    stretch, every Lightning layer, from the step records that ended in it:
    a step's ``lightning_state_bytes`` is the states its one-token rows moved
    (a state in and out a row, layer and call), from which the calls follow.
    None where the records lack the count (a program without the twin)."""
    import roofline_mla
    rows = roofline_mla.traced_rows(run)
    if not rows or "lightning_state_bytes" not in rows[0]:
        return None
    layers, heads, dk, dv = shape_of(run["config"])
    flops_each, bytes_each = lightning_update_call(heads, dk, dv)
    calls = sum(r["lightning_state_bytes"] for r in rows) / (8 * heads * dk * dv)      # (row, layer) calls
    return {"flops": calls * flops_each, "bytes": calls * bytes_each}


def kernel_seconds(reduced: dict) -> float:
    """Summed device time of the events named ``ds_lightning_update``; 0
    where the program has no such kernel."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith("ds_lightning_update"))
