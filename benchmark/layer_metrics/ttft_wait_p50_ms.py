"""ttft_wait_p50_ms -- layer: Serving frontend; unit ms; moves ttft_p50_ms.
Median over the window's first tokens of ``late_s + queued_s + wait_s +
other_s``: everything before the first token in which no step of the engine ran
for anybody (the caller held the request, it queued, the ticks' own host work)."""
import first_token_rows


def read(run):
    return first_token_rows.median(run, first_token_rows.wait_ms)
