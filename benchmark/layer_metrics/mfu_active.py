"""mfu_active -- layer: Models; unit %; moves train_tok_s_chip.  (6 x active
parameters + causal attention FLOPs) a token x tokens/s/chip over the chip's
peak, by ``roofline.mfu``; recomputation not counted.  "Active" counts the
experts a token is sent to, not those the program evaluates."""
import roofline


def read(run):
    if not run.get("steps") or run.get("peak") is None:
        return None
    rate = len(run["steps"]) * run["tokens_per_step"] / run["elapsed_s"] / run["chips"]
    return 100.0 * roofline.mfu(run["config"], run["seq_len"], rate, run["peak"])
