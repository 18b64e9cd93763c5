"""flash_roofline -- layer: Kernels; unit %; moves train_tok_s_chip.  Least
time by the roofline of the flash-attention calls in the trace, at the
cell's micro-batch and sequence, over the summed device time of their
events on the first chip.  The program gives its Pallas kernels no name, so
the events are the train step's ``tpu_custom_call`` operations (``_fwd2``,
``_dq2``, ``_dkv2`` are the only ones there): one with at most four operands
is a forward call, and every two of the others are one backward pass."""
import roofline
import trace_reduce


def read(run):
    trace = run.get("reduced")
    if not trace or "seq_len" not in run or run.get("peak") is None:
        return None
    cfg = run["config"]
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shape = (run["micro_batch_per_chip"], run["seq_len"], n_q, n_kv, cfg["hidden_size"] // n_q)
    calls = [e for e in trace["events"] if trace_reduce.PALLAS_CALL in e[0]]
    spent = sum(e[2] - e[1] for e in calls)
    if spent <= 0:
        return None
    forward = sum(1 for e in calls if trace_reduce.operand_count(e) <= 4)
    backward = (len(calls) - forward) / 2
    least = forward * roofline.least_time_s(*roofline.flash_forward_call(*shape), run["peak"]) \
        + backward * roofline.least_time_s(*roofline.flash_backward_call(*shape), run["peak"])
    return 100.0 * least / spent
