"""tokens_per_tick -- layer: Inference engine; unit tokens; moves tpot_p50_ms.
Real (unpadded) tokens a tick with work carried, mean over the window's
ticks: tokens delivered plus the prompt tokens of the prefills that ended in
the tick."""


def read(run):
    ticks = run.get("ticks")
    if not ticks:
        return None
    return sum(t[2] + t[3] for t in ticks) / len(ticks)
