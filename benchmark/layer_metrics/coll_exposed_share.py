"""coll_exposed_share -- layer: Training engine; unit share; moves
train_tok_s_chip.  Time a collective runs on the first chip while no other
operation does, over the traced window: a fraction in [0, 1], since the
exposed time is part of the window."""


def read(run):
    trace = run.get("reduced")
    if not trace or run["chips"] < 2:
        return None
    return trace["collective_exposed_s"] / trace["window_s"]
