"""prefill_ms_per_ktok_mean -- layer: Inference engine; unit ms; moves ttft_mean_ms.
Mean over the window's first tokens (the program's own rows) of ``carried_s``
over ``prefill_tokens / 1000``: what a thousand prompt tokens cost in the steps
that carried the request, whatever it waited for before, between and behind them."""
import first_token_rows


def read(run):
    return first_token_rows.mean(run, first_token_rows.per_ktok_ms)
