"""setup_s (s, lower is better; host clock).  Process start to the window's
opening: imports, weights, engine, warm-up, reference check, lead-in."""


def read(run):
    return run["setup_s"]
