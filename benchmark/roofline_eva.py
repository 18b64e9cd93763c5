"""Operations and bytes EVA's attention needs (chunked linear attention: exact
keys and values of the query's own window, one summary row a chunk of the
windows before it), from shapes alone, and the work of a cell's traced
stretch.  The algorithm's minimum, as in ``roofline.py``: every visible row
multiplied once a query and head, the visible keys and values read once a
call.  bf16 (2 bytes an element).

A query at position ``t`` sees ``t % window + 1`` exact rows (its window up
to itself) and ``(t // window) x (window / chunk)`` summary rows (every chunk
of the complete windows before it): 2,048 + 128 a window at the published
sizes where softmax attention would see ``t + 1``.
"""

import trace_reduce
import traffic_gen


def visible_rows(t: int, window: int, chunk: int) -> int:
    return t % window + 1 + t // window * (window // chunk)


def eva_attention_call(n: int, start: int, n_heads: int, d: int, window: int, chunk: int, elem_bytes: int = 2):
    """One row of one layer: ``n`` queries at positions ``start..start+n-1``,
    all inside one window.  FLOPs: QK^T and PV, 2 x 2 x d a (query, visible
    row) pair and head.  Bytes: the rows the last query sees, keys and
    values, once; the queries in and the output out."""
    if start // window != (start + n - 1) // window:
        raise ValueError(f"a call of {n} queries from {start} crosses a window of {window}")
    pairs = n * visible_rows(start, window, chunk) + n * (n - 1) // 2
    flops = 4 * d * n_heads * pairs
    nbytes = elem_bytes * d * n_heads * (2 * visible_rows(start + n - 1, window, chunk) + 2 * n)
    return flops, nbytes


def eva_prefill(prompt: int, step: int, n_heads: int, d: int, window: int, chunk: int):
    """A whole prompt fed in calls of ``step`` tokens from position 0 (the
    last one partial); ``step`` divides the window, so no call crosses one.
    The fewest calls a scheduler can make: one that cuts them shorter reads
    the visible rows more often, never less."""
    flops = nbytes = 0
    for s in range(0, prompt, step):
        f, b = eva_attention_call(min(step, prompt - s), s, n_heads, d, window, chunk)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def eva_decode(prompt: int, n_tokens: int, n_heads: int, d: int, window: int, chunk: int):
    """Decode steps that feed generated tokens 1..n_tokens-1 (the first comes
    from the prefill), each a call of one query."""
    flops = nbytes = 0
    for j in range(n_tokens - 1):
        f, b = eva_attention_call(1, prompt + j, n_heads, d, window, chunk)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def traced_work(run: dict):
    """{"flops", "bytes"} of the attention the measured requests needed inside
    the traced stretch (the window's last ``min(4, seconds / 2)`` s), all
    layers; None where a request failed (the samples then no longer line up
    with the schedule).  A reader has no request records: the schedule is the
    mix's, the same in every run, and ``run["samples"]`` holds each finished
    measured request's waits in schedule order.  As
    ``serve_open_loop.attention_work``: a request's prefill work is spread
    evenly from its admission to its first token, its decode work from there
    to its end, and the part of each inside the stretch is counted.  Lead-in
    requests and chunks cut shorter than ``prefill_chunk`` are left out, so
    the count errs low."""
    cfg, traffic, seconds, samples = run["config"], run["traffic"], run["seconds"], run["samples"]
    sched = [r for r in traffic_gen.serving_schedule(traffic, seconds, run["seed"], cfg["vocab_size"])
             if r["measured"]]
    if run["failed"] or any(len(samples[k]) != len(sched) for k in ("ttft_ms", "tpot_ms", "gen_late_ms", "queue_wait_ms")):
        return None
    shape = (cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"],
             cfg["window_size"], cfg["chunk_size"])
    step = cfg["engine"]["scheduler"]["prefill_chunk"]
    w0, w1 = seconds - min(4.0, seconds / 2.0), seconds
    flops = nbytes = 0.0
    for i, r in enumerate(sched):
        prompt, n_out = len(r["prompt"]), r["max_new_tokens"]
        admitted = r["due"] + 1e-3 * (samples["gen_late_ms"][i] + samples["queue_wait_ms"][i])
        first = r["due"] + 1e-3 * samples["ttft_ms"][i]
        end = first + 1e-3 * samples["tpot_ms"][i] * (n_out - 1)
        for a, b, work in ((admitted, first, eva_prefill(prompt, step, *shape)),
                           (first, end, eva_decode(prompt, n_out, *shape))):
            if b > a:
                share = max(0.0, min(b, w1) - max(a, w0)) / (b - a)
                flops, nbytes = flops + share * work[0], nbytes + share * work[1]
    layers = cfg["num_hidden_layers"]
    return {"flops": flops * layers, "bytes": nbytes * layers}


def paged_kernel_seconds(reduced: dict) -> float:
    """Summed device time of the events named ``ds_paged_attention`` (by the
    operation's own name: the step may hold other custom calls)."""
    return sum(e[2] - e[1] for e in reduced["events"] if trace_reduce.parse(e)[0].startswith("ds_paged_attention"))
