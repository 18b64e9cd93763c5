"""Operations and bytes one position of the gated delta rule (Kimi Delta
Attention) needs on a state that lives in HBM (the decode step's
``ds_kda_update``), from shapes alone, and the work of a cell's traced
stretch.  The algorithm's minimum, as in ``roofline.py``: a head's state
``[K, V]`` float32 read once and written once,

    S <- diag(exp(g)) S;   S <- S + k (beta (v - S^T k))^T;   o = S^T q.

A state element costs seven operations: the decay's product; a product and
a sum for ``S^T k``; a product and a sum for the rank-one write; a product
and a sum for ``S^T q``.  The exponent, ``beta (v - .)`` and the norms are a
channel's or a head's, not a state element's, and are left out, so the count
errs low.
"""

import trace_reduce
import traffic_gen


def kda_update_call(heads: int, d_key: int, d_value: int):
    """One row of one layer, one position: (FLOPs, bytes).  Bytes: the state
    in and out; ``q``, ``k`` and ``g`` in a key channel; ``v`` in and ``o``
    out a value channel; ``beta`` a head; float32, as the kernel takes them."""
    state = heads * d_key * d_value
    return 7 * state, 4 * (2 * state + 3 * heads * d_key + 2 * heads * d_value + heads)


def shape_of(cfg: dict) -> tuple:
    """(KDA layers, heads, key size, value size)."""
    lin = cfg["linear_attn_config"]
    layers = sum(i not in cfg["gqa_layers"] for i in range(cfg["num_hidden_layers"]))
    return layers, lin["num_heads"], lin["head_dim"], lin["head_dim"]


def traced_work(run: dict):
    """{"flops", "bytes"} of the one-position updates the measured requests'
    decode tokens needed inside the traced stretch, every KDA layer; None
    where a request failed.  As ``roofline_ssd.traced_work`` counts Mamba-2's:
    the schedule is the mix's, a request's decode tokens (all but its first,
    which the prefill gives) are spread evenly from its first token to its
    end, and the part inside the stretch (the window's last ``min(4, seconds
    / 2)`` s) is counted.  Lead-in requests still running are left out, so
    the count errs low."""
    cfg, traffic, seconds, samples = run["config"], run["traffic"], run["seconds"], run["samples"]
    sched = [r for r in traffic_gen.serving_schedule(traffic, seconds, run["seed"], cfg["vocab_size"])
             if r["measured"]]
    if run["failed"] or any(len(samples[k]) != len(sched) for k in ("ttft_ms", "tpot_ms")):
        return None
    layers, *shape = shape_of(cfg)
    flops_each, bytes_each = kda_update_call(*shape)
    w0, w1 = seconds - min(4.0, seconds / 2.0), seconds
    tokens = 0.0
    for i, r in enumerate(sched):
        first = r["due"] + 1e-3 * samples["ttft_ms"][i]
        end = first + 1e-3 * samples["tpot_ms"][i] * (r["max_new_tokens"] - 1)
        if end > first:
            tokens += max(0.0, min(end, w1) - max(first, w0)) / (end - first) * (r["max_new_tokens"] - 1)
    return {"flops": tokens * layers * flops_each, "bytes": tokens * layers * bytes_each}


def kernel_seconds(reduced: dict) -> float:
    """Summed device time of the events named ``ds_kda_update``; 0 where the
    program has no such kernel."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith("ds_kda_update"))
