"""step_excess_share -- layer: Inference engine; unit share; moves
tpot_p50_ms.  What the window's steps took beyond the median step of their
own program key, summed, over the window's seconds (first tick's start to
last tick's end): small in a steady run, large in one that stalled."""
import step_rows


def read(run):
    rows = step_rows.window_rows(run)
    if not rows:
        return None
    start, end = step_rows.window_span(run)
    return step_rows.excess_share(rows, end - start)
