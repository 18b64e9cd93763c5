"""train_tok_s_chip (tokens/s/chip, higher is better; host clock).  Tokens
of the whole steps inside the window, each ended by ``block_until_ready``,
over the time from the first step's start to the last step's end, per chip."""


def read(run):
    if not run.get("steps"):
        return None
    return len(run["steps"]) * run["tokens_per_step"] / run["elapsed_s"] / run["chips"]
