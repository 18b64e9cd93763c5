"""image_row_share -- layer: Inference engine; unit share; moves ttft_mean_ms.
Prefill tokens whose embedding was an image's row (``mm_tokens`` of the step
records) over all prefill tokens of the window's steps that held a prefill
row: how much of prefill came through the tower, the projector and the merge."""
import step_rows


def read(run):
    rows = step_rows.window_rows(run)
    if not rows or "mm_tokens" not in rows[0]:
        return None
    prefill = sum(r["tokens_real"] - r["rows_decode"] for r in rows if r["rows_prefill"])
    return sum(r["mm_tokens"] for r in rows) / prefill if prefill else None
