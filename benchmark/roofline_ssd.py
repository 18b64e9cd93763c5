"""Operations and bytes one position of the Mamba-2 recurrence needs on a
state that lives in HBM (the decode step's ``ds_ssd_update``), from shapes
alone, and the work of a cell's traced stretch.  The algorithm's minimum, as
in ``roofline.py``: a head's state ``[P, N]`` float32 read once and written
once, ``S <- exp(dt A) S + (dt x) (x) B`` and ``y = S C``.

A state element costs five operations: the decay's product, the input's
product (``dt x`` times ``B``), the sum, the output's product with ``C`` and
its sum.  The exponent, ``dt x`` and the ``D x`` term are a head's or a
channel's, not a state element's, and are left out, so the count errs low.
"""

import trace_reduce
import traffic_gen


def ssd_update_call(heads: int, d_head: int, d_state: int):
    """One row of one layer, one position: (FLOPs, bytes).  Bytes: the state
    in and out; ``x`` in and ``y`` out a channel; ``B`` and ``C`` a state
    column; ``dt`` a head; float32, as the kernel takes them."""
    state = heads * d_head * d_state
    return 5 * state, 4 * (2 * state + 2 * heads * d_head + 2 * d_state + heads)


def shape_of(cfg: dict) -> tuple:
    """(Mamba layers, heads, head size, state size)."""
    return (sum(kind == "mamba" for kind in cfg["layer_types"]), cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"])


def traced_work(run: dict):
    """{"flops", "bytes"} of the one-position updates the measured requests'
    decode tokens needed inside the traced stretch, every Mamba layer; None
    where a request failed.  As ``roofline_sambay.traced_work``: the schedule
    is the mix's, a request's decode tokens (all but its first, which the
    prefill gives) are spread evenly from its first token to its end, and the
    part inside the stretch (the window's last ``min(4, seconds / 2)`` s) is
    counted.  Lead-in requests still running are left out, so the count errs
    low."""
    cfg, traffic, seconds, samples = run["config"], run["traffic"], run["seconds"], run["samples"]
    sched = [r for r in traffic_gen.serving_schedule(traffic, seconds, run["seed"], cfg["vocab_size"])
             if r["measured"]]
    if run["failed"] or any(len(samples[k]) != len(sched) for k in ("ttft_ms", "tpot_ms")):
        return None
    layers, *shape = shape_of(cfg)
    flops_each, bytes_each = ssd_update_call(*shape)
    w0, w1 = seconds - min(4.0, seconds / 2.0), seconds
    tokens = 0.0
    for i, r in enumerate(sched):
        first = r["due"] + 1e-3 * samples["ttft_ms"][i]
        end = first + 1e-3 * samples["tpot_ms"][i] * (r["max_new_tokens"] - 1)
        if end > first:
            tokens += max(0.0, min(end, w1) - max(first, w0)) / (end - first) * (r["max_new_tokens"] - 1)
    return {"flops": tokens * layers * flops_each, "bytes": tokens * layers * bytes_each}


def kernel_seconds(reduced: dict) -> float:
    """Summed device time of the events named ``ds_ssd_update``; 0 where the
    program has no such kernel."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith("ds_ssd_update"))

