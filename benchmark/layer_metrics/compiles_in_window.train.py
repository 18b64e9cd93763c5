"""compiles_in_window.train -- layer: Mesh, device, compile; unit count;
moves train_tok_s_chip.  JAX backend compiles between the window's opening
and its close; any makes the run incorrect."""


def read(run):
    return run["compiles_in_window"] if "steps" in run else None
