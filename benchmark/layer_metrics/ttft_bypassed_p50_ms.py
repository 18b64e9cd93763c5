"""ttft_bypassed_p50_ms -- layer: Inference engine; unit ms; moves ttft_p50_ms.
Median over the window's first tokens of ``bypassed_s``: the steps that ran
while the request was in prefill and carried no chunk of it (the token budget
went to another prompt, a dispatch was in flight when it was admitted)."""
import first_token_rows


def read(run):
    return first_token_rows.median(run, first_token_rows.bypassed_ms)
