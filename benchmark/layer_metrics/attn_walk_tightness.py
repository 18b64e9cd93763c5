"""attn_walk_tightness -- layer: Kernels; unit share; moves tpot_p50_ms.
Key rows the step's queries could see over the key rows the paged kernel's
walk covered for them (whole blocks), over the window's step records: 1.0
where the kernel multiplied nothing a query cannot see."""
import step_rows


def read(run):
    return step_rows.share(step_rows.window_rows(run), "attn_rows_visible", "attn_rows_walked")
