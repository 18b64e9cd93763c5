"""expert_kernel_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Rows the routed experts multiplied that went through the grouped kernel
``ds_gmm``, over all of them, over the window's step records."""
import step_rows


def read(run):
    return step_rows.share(step_rows.window_rows(run), "expert_rows_kernel", "expert_rows")
