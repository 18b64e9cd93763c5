"""Operations and bytes the SambaY attention layers need (window layers that
see the last ``window`` rows, and one full-attention layer whose keys and
values the cross-attention layers read too), from shapes alone, and the work
of a cell's traced stretch.  The algorithm's minimum, as in ``roofline.py``:
every visible row multiplied once a query and head, the visible keys and
values read once a call and layer.  bf16 (2 bytes an element).

Heads are counted as the program packs them: ``n_q / 2`` query pairs and
``n_kv / 2`` key pairs of ``2d`` lanes.  A visible row costs a query pair
one product over ``2d`` lanes for its two scores (``q1 . k1`` and ``q2 .
k2``, ``d`` lanes each) and one for its values, ``4 x 2d`` operations: the
second softmax's values, which differential attention also multiplies, are
left out, so the count errs low.

A query at position ``t`` sees ``min(t + 1, window)`` rows in a window layer
and ``t + 1`` in a layer that reads the shared pages.
"""

import roofline_eva
import traffic_gen


def window_rows(t: int, window: int) -> int:
    return min(t + 1, window)


def attention_call(n: int, start: int, n_q: int, n_kv: int, d: int, window: int, window_layers: int,
                   shared_readers: int, elem_bytes: int = 2):
    """One row of one step, every attention layer: ``n`` queries at positions
    ``start..start+n-1``.  FLOPs: 4 x 2d a (query pair, visible row).  Bytes a
    layer: the rows the last query sees, keys and values, once; the queries in
    and the output out (``n_q`` heads of ``2d`` lanes, as the kernel takes them)."""
    pairs_w = sum(window_rows(t, window) for t in range(start, start + n))
    pairs_s = n * start + n * (n + 1) // 2
    flops = 4 * 2 * d * (n_q // 2) * (window_layers * pairs_w + shared_readers * pairs_s)
    row = elem_bytes * 2 * d * (n_kv // 2)           # one row's keys (or values), every pair
    q_io = 2 * n * elem_bytes * 2 * d * n_q
    nbytes = window_layers * (2 * row * window_rows(start + n - 1, window) + q_io) + \
        shared_readers * (2 * row * (start + n) + q_io)
    return flops, nbytes


def prefill(prompt: int, step: int, *shape):
    """A whole prompt fed in calls of ``step`` tokens from position 0 (the
    last one partial): the fewest calls a scheduler can make."""
    flops = nbytes = 0
    for s in range(0, prompt, step):
        f, b = attention_call(min(step, prompt - s), s, *shape)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def decode(prompt: int, n_tokens: int, *shape):
    """Decode steps that feed generated tokens 1..n_tokens-1, a query each."""
    flops = nbytes = 0
    for j in range(n_tokens - 1):
        f, b = attention_call(1, prompt + j, *shape)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def ssm_scan_call(n: int, d_inner: int, d_state: int):
    """One row of one Mamba layer's selective scan over ``n`` positions.
    FLOPs a (position, channel, state): the decay's exponent and product, the
    input's two products and the sum, the output's product and sum: 7.  Bytes:
    the float32 state in and out; ``u``, ``dt`` in and ``y`` out, float32, a
    channel a position; ``B`` and ``C`` a state a position."""
    return 7 * n * d_inner * d_state, 4 * (2 * d_inner * d_state + 3 * n * d_inner + 2 * n * d_state)


def shape_of(cfg: dict) -> tuple:
    """(n_q, n_kv, d, window, window layers, layers that read the shared pages)."""
    n_q, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    return (n_q, cfg["num_key_value_heads"], cfg["hidden_size"] // n_q, cfg["sliding_window"], layers // 4, layers // 4)


def traced_work(run: dict):
    """{"flops", "bytes"} of the attention the measured requests needed inside
    the traced stretch, every attention layer; None where a request failed.
    As ``roofline_eva.traced_work``: the schedule is the mix's, a request's
    prefill work is spread evenly from its admission to its first token and
    its decode work from there to its end, and the part inside the stretch
    (the window's last ``min(4, seconds / 2)`` s) is counted.  Lead-in
    requests still running and chunks cut shorter than ``prefill_chunk`` are
    left out, so the count errs low."""
    cfg, traffic, seconds, samples = run["config"], run["traffic"], run["seconds"], run["samples"]
    sched = [r for r in traffic_gen.serving_schedule(traffic, seconds, run["seed"], cfg["vocab_size"])
             if r["measured"]]
    if run["failed"] or any(len(samples[k]) != len(sched) for k in ("ttft_ms", "tpot_ms", "gen_late_ms", "queue_wait_ms")):
        return None
    shape = shape_of(cfg)
    step = cfg["engine"]["scheduler"]["prefill_chunk"]
    w0, w1 = seconds - min(4.0, seconds / 2.0), seconds
    flops = nbytes = 0.0
    for i, r in enumerate(sched):
        n_prompt, n_out = len(r["prompt"]), r["max_new_tokens"]
        admitted = r["due"] + 1e-3 * (samples["gen_late_ms"][i] + samples["queue_wait_ms"][i])
        first = r["due"] + 1e-3 * samples["ttft_ms"][i]
        end = first + 1e-3 * samples["tpot_ms"][i] * (n_out - 1)
        for a, b, work in ((admitted, first, prefill(n_prompt, step, *shape)), (first, end, decode(n_prompt, n_out, *shape))):
            if b > a:
                share = max(0.0, min(b, w1) - max(a, w0)) / (b - a)
                flops, nbytes = flops + share * work[0], nbytes + share * work[1]
    return {"flops": flops, "bytes": nbytes}


#: device time of the events named ``ds_paged_attention``, by the operation's own name
paged_kernel_seconds = roofline_eva.paged_kernel_seconds
