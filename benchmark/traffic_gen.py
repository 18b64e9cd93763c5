"""The one traffic generator.  A traffic mix is a data file under
``benchmark/traffic/``; this module turns it, a window length and a seed into
the inputs of a run.

Serving (``kind: serve_open_loop``): every seed offers **the same requests at
the same times in the same order**; the seed draws the token ids (and, in
the harness, the weights).  The count is ``rate_per_s x seconds`` rounded
once.  The i-th of N requests takes the ``(i + 0.5) / N`` quantile of the
stated prompt distribution and, through a permutation, a quantile of the
output distribution.  The window is split into equal blocks of about
``block_s`` seconds; block b holds the requests whose index is ``b mod
n_blocks`` (so every block offers short and long ones, and the blocks' counts
differ by one at most) in a shuffled order, due at that many uniform draws
inside the block, sorted: a Poisson process given its count, so arrivals
bunch and thin out inside a block as a chat API's do.  Due times, pairing
and order come from the file's ``mix_seed``, not from ``--seed``: they are
part of the mix.  A lead-in of ``lead_in_s`` seconds of the same process
comes before the window, with negative due times: the stay of a median
request at the mix's rate (``median_stay_s`` of the file's ``at_rate``
readings), so that the window opens on a system about as full as it will
stay.  An open loop: a request is offered when it is due, whatever the
system has done with the ones before.

Why the schedule is the mix's and not the seed's (my chip runs, PR 23, six
seeds at 51 s, spread = distance between quartiles over the median): PR 22
drew count and lengths afresh and its runs spread by 2-4%.  With the count
and the multisets of lengths fixed but due times and pairing drawn from
``--seed``, TTFT and TPOT still spread by 8-9%: which long prompt meets
which neighbours is the schedule.  With one schedule for every seed
``tpot_p50_ms`` spreads by about 1%, as far apart as two runs of one seed.

Training (``kind: train_job``): batches of uniform token ids from the seed.
"""

import math

import numpy as np


# ------------------------------------------------------------------ quantiles


def _cdf(part: dict, x: float) -> float:
    dist = part["dist"]
    if dist == "lognormal":
        return 0.5 * (1.0 + math.erf(math.log(x / part["median"]) / (part["sigma"] * math.sqrt(2.0))))
    if dist == "pareto":
        return 0.0 if x <= part["scale"] else 1.0 - (part["scale"] / x) ** part["alpha"]
    if dist == "uniform":
        return min(1.0, max(0.0, (x - part["lo"]) / (part["hi"] - part["lo"])))
    if dist == "loguniform":
        return min(1.0, max(0.0, math.log(x / part["lo"]) / math.log(part["hi"] / part["lo"])))
    raise ValueError(f"unknown distribution {dist!r}")


def quantile(spec: dict, u: float) -> int:
    """The ``u`` quantile of the mixture ``spec["mixture"]``, clipped to
    ``spec["clip"]`` and rounded to a whole number of tokens.  The mixture's
    CDF is inverted by bisection, so every distribution needs only its CDF."""
    lo, hi = (float(v) for v in spec["clip"])
    total = sum(p["weight"] for p in spec["mixture"])

    def cdf(x):
        return sum(p["weight"] * _cdf(p, x) for p in spec["mixture"]) / total

    if cdf(lo) >= u:
        return int(lo)
    if cdf(hi) <= u:
        return int(hi)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if cdf(mid) < u:
            lo = mid
        else:
            hi = mid
    return int(round(0.5 * (lo + hi)))


def stratified_lengths(spec: dict, n: int) -> list:
    return [quantile(spec, (i + 0.5) / n) for i in range(n)]


# -------------------------------------------------------------------- serving


def _stretch(mix, traffic: dict, rate: float, t0: float, length: float) -> list:
    """Requests of one stretch [t0, t0 + length): (due, prompt_len, out_len).
    ``mix`` is the mix's own generator: pairing and order are the file's."""
    n = int(round(rate * length))
    if n == 0:
        return []
    prompts = stratified_lengths(traffic["prompt"], n)
    outputs = stratified_lengths(traffic["output"], n)
    pairing = mix.permutation(n)
    requests = [(prompts[i], outputs[pairing[i]]) for i in range(n)]
    n_blocks = max(1, min(n, int(round(length / traffic["block_s"]))))
    rows = []
    for b in range(n_blocks):
        mine = [requests[i] for i in range(b, n, n_blocks)]
        due = np.sort(mix.uniform(t0 + b * length / n_blocks, t0 + (b + 1) * length / n_blocks, len(mine)))
        order = mix.permutation(len(mine))
        rows += [(due[k], *mine[j]) for k, j in enumerate(order)]
    return rows


def serving_schedule(traffic: dict, seconds: float, seed: int, vocab: int,
                     rate_per_s: float = None, lead_in_s: float = None) -> list:
    """The requests of one run, sorted by due time: dicts with ``due``
    (seconds from the window's opening; negative in the lead-in), ``prompt``
    (token ids), ``max_new_tokens`` and ``measured``.  ``rate_per_s`` and
    ``lead_in_s`` take the file's place in a sweep."""
    rate = traffic["rate_per_s"] if rate_per_s is None else rate_per_s
    rng = np.random.default_rng(int(seed))
    mix = np.random.default_rng(int(traffic["mix_seed"]))
    lead = float(traffic["lead_in_s"] if lead_in_s is None else lead_in_s)
    rows = [(False, r) for r in _stretch(mix, traffic, rate, -lead, lead)] + \
        [(True, r) for r in _stretch(mix, traffic, rate, 0.0, float(seconds))]
    return [{"due": float(due), "measured": measured, "max_new_tokens": int(o_len),
             "prompt": rng.integers(1, vocab, int(p_len)).tolist()}
            for measured, (due, p_len, o_len) in rows]


def median_stay_s(traffic: dict, ttft_mean_ms: float, tpot_p50_ms: float) -> float:
    """Seconds a median request stays in the system where a first token
    takes ``ttft_mean_ms`` and each later one ``tpot_p50_ms``: the rule behind
    a mix's ``lead_in_s`` (whole seconds of it) and a sweep's lead-ins."""
    return (ttft_mean_ms + (quantile(traffic["output"], 0.5) - 1) * tpot_p50_ms) / 1e3


def lead_in_rule(traffic: dict, readings: dict = None) -> int:
    """``lead_in_s`` by the rule: whole seconds of a median request's stay
    under ``readings`` (``ttft_mean_ms``, ``tpot_p50_ms``); the file's
    ``at_rate`` readings, taken at its own rate, where none are given."""
    readings = readings or traffic["at_rate"]
    return max(1, round(median_stay_s(traffic, readings["ttft_mean_ms"], readings["tpot_p50_ms"])))


def longest_request(traffic: dict) -> tuple:
    """(prompt, output) clip limits: what every sequence slot must hold."""
    return int(traffic["prompt"]["clip"][1]), int(traffic["output"]["clip"][1])


# ------------------------------------------------------------------- training


def train_batches(traffic: dict, seed: int, vocab: int, global_batch: int):
    """An endless iterator of host batches, as a loader would hand them over:
    packed sequences of ``seq_len`` uniform token ids; each position is
    trained on the token that follows it."""
    rng = np.random.default_rng(int(seed))
    seq = int(traffic["seq_len"])
    mask = np.ones((global_batch, seq), np.float32)
    mask[:, -1] = 0.0  # the last position has no next token to predict
    while True:
        ids = rng.integers(0, vocab, (global_batch, seq), dtype=np.int32)
        yield {"input_ids": ids, "labels": np.roll(ids, -1, axis=1), "loss_mask": mask}
