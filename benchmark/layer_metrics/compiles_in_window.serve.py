"""compiles_in_window.serve -- layer: Mesh, device, compile; unit count;
moves ttft_p50_ms.  JAX backend compiles between the window's opening and
the end of the drain; any makes the run incorrect."""


def read(run):
    return run["compiles_in_window"] if "samples" in run else None
