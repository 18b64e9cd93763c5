"""Operations and bytes the routed experts of one chip's share need, from
shapes alone, the least time of a cell's traced stretch, and the device time
of the events that are the experts' products, in either form the dropless
layer takes (``deepspeed_tpu/moe/sharded_moe.py``: the grouped kernel
``ds_gmm`` over the sorted rows, or every held expert over every row).

The algorithm's minimum, as in ``roofline.py``: an expert that a step's rows
touch has its three matrices read once (``3 x hidden x width`` bfloat16
elements: 18.9 MB at 4096 x 768), and a chosen row that falls on a held
expert costs ``6 x hidden x width`` operations (three products of two
operations an element) and is read and written once.  The count takes the
**chosen** rows, never the dense form's ``held x rows``, so a share cannot
honestly pass 100%.

What a step's record holds is ``expert_rows`` (its tokens times the experts a
token: the choices over the router's whole width).  The program does not
bring back how many fell on a held expert (``exp_counts`` would be a second
transfer a dispatch: docs/OBSERVABILITY.md), so the held share of the choices
and the experts they touch are taken at their expectation under even routing:
``held / router`` of the choices, ``held x (1 - (1 - 1 / router)^choices)``
experts.  Random weights route evenly; a trained router's skew would touch
fewer experts, and the count would then err high in bytes by what it skipped.
"""

import re

import trace_reduce


def shape_of(cfg: dict) -> tuple:
    """(layers with routed experts, experts held, the router's width,
    experts a token, hidden size, an expert's width)."""
    held = cfg["num_local_experts"]
    return (cfg["num_hidden_layers"], held, cfg.get("router_experts") or held, cfg["num_experts_per_tok"],
            cfg["hidden_size"], cfg["intermediate_size"])


def call_work(choices: float, held: int, router: int, hidden: int, width: int, elem_bytes: int = 2):
    """One layer, one pass of ``choices`` (tokens x experts a token) through
    the router: (FLOPs, bytes) of this share's part."""
    rows = choices * held / router
    touched = held * (1.0 - (1.0 - 1.0 / router)**choices)
    return 6.0 * hidden * width * rows, elem_bytes * (3.0 * hidden * width * touched + 2.0 * hidden * rows)


def step_calls(row: dict) -> list:
    """The passes through the layers that one step record stands for, as
    their choices: a fused decode dispatch (``multi_decode``) is ``tokens_real
    / rows_decode`` rounds of ``rows_decode`` rows; every other step is one."""
    if not row.get("expert_rows"):
        return []
    rounds = max(1, round(row["tokens_real"] / row["rows_decode"])) if row["path"] == "multi_decode" else 1
    return [row["expert_rows"] / rounds] * rounds


def traced_work(run: dict):
    """Least seconds by the roofline for the routed products of the traced
    stretch's steps, every layer: a pass is bound by its operations or by its
    bytes, and the passes' least times are added.  None where the program
    keeps no step records or the configuration routes nothing."""
    import roofline
    import roofline_mla
    cfg = run["config"]
    if not cfg.get("num_local_experts") or run.get("peak") is None:
        return None
    rows = roofline_mla.traced_rows(run)
    if not rows or "expert_rows" not in rows[0]:
        return None
    layers, *shape = shape_of(cfg)
    held, router, _, hidden, width = shape
    return layers * sum(roofline.least_time_s(*call_work(choices, held, router, hidden, width), run["peak"])
                        for r in rows for choices in step_calls(r))


#: the name the dense form's products carry (``jax.named_scope`` in ``sharded_moe._experts_dense``)
DENSE_SCOPE = "ds_experts_dense"


def _dense_shapes(cfg: dict):
    """The arrays only the dense form makes: ``[held, rows, width]`` and
    ``[held, rows, hidden]``, whatever the rows."""
    _, held, _, _, hidden, width = shape_of(cfg)
    return re.compile(rf"\[{held},\d+,(?:{width}|{hidden})\]")


def kernel_seconds(reduced: dict, cfg: dict) -> dict:
    """Summed device time of the events that are the routed experts'
    products: ``{"grouped", "dense", "all"}``.  The grouped form is the
    kernel's name, ``ds_gmm``.  The dense form is XLA's own fusions: an event
    is counted where its text or its statistics carry the scope's name
    ``ds_experts_dense`` and, since the profiler's ``XLA Ops`` line keeps the
    HLO text and not the scope (docs/OBSERVABILITY.md), where an operand or
    the result has one of the shapes that only the dense form makes."""
    shapes = _dense_shapes(cfg)
    out = {"grouped": 0.0, "dense": 0.0}
    for e in reduced["events"]:
        name, opcode, _ = trace_reduce.parse(e)
        if opcode in trace_reduce.CONTAINERS:
            continue
        if name.startswith("ds_gmm"):
            out["grouped"] += e[2] - e[1]
        elif DENSE_SCOPE in e[0] or any(DENSE_SCOPE in str(v) for v in e[3].values()) or shapes.search(e[0]):
            out["dense"] += e[2] - e[1]
    out["all"] = out["grouped"] + out["dense"]
    return out
