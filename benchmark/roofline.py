"""Operations and bytes the algorithms need, from shapes alone, and the
roofline that follows.  Kept with the benchmark so that no later PR can move
a count.  bf16 everywhere (2 bytes an element) unless a size is passed.

A share is least time over measured time: the larger of operations over the
peak rate and bytes over the peak bandwidth, over the kernel's device time.
Counts are the algorithm's minimum (what must be read once, multiplied
once), so a share cannot honestly pass 1.
"""


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


# ------------------------------------------------------------ paged attention


def paged_attention_call(chunk: int, start: int, n_q: int, n_kv: int, d: int, elem_bytes: int = 2):
    """One row of one layer: ``chunk`` query tokens at positions
    ``start..start+chunk-1`` over a cache that then holds ``start + chunk``
    tokens.  FLOPs: QK^T and PV, 2 x 2 x d for every (query, visible key)
    pair and query head.  Bytes: the visible keys and values once, the
    queries in and the output out."""
    pairs = chunk * start + chunk * (chunk + 1) // 2
    flops = 4 * d * n_q * pairs
    nbytes = elem_bytes * d * (2 * n_kv * (start + chunk) + 2 * n_q * chunk)
    return flops, nbytes


def paged_prefill(prompt: int, chunk: int, n_q: int, n_kv: int, d: int):
    """A whole prompt fed in chunks of ``chunk`` (the last one partial)."""
    flops = nbytes = 0
    for s in range(0, prompt, chunk):
        f, b = paged_attention_call(min(chunk, prompt - s), s, n_q, n_kv, d)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def paged_decode(prompt: int, n_tokens: int, n_q: int, n_kv: int, d: int):
    """Decode steps that feed generated tokens 1..n_tokens-1 (the first token
    comes from the prefill), each over the context before it."""
    flops = nbytes = 0
    for j in range(n_tokens - 1):
        f, b = paged_attention_call(1, prompt + j, n_q, n_kv, d)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


# ------------------------------------------------------------ flash attention


def flash_forward_call(batch: int, seq: int, n_q: int, n_kv: int, d: int, causal: bool = True,
                       elem_bytes: int = 2):
    """One forward call: QK^T and PV over the visible half (causal) of the
    seq x seq pairs; q, k, v read and o written once."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    flops = 4 * d * n_q * batch * pairs
    nbytes = elem_bytes * batch * seq * d * (2 * n_q + 2 * n_kv)
    return flops, nbytes


def flash_backward_call(batch: int, seq: int, n_q: int, n_kv: int, d: int, causal: bool = True,
                        elem_bytes: int = 2):
    """The backward pass of one call, however it is split into kernels: five
    products of the forward's size (scores again, dP, dV, dQ, dK), so 2.5 x
    the forward's operations; q, k, v, o, do read and dq, dk, dv written."""
    flops, _ = flash_forward_call(batch, seq, n_q, n_kv, d, causal)
    nbytes = elem_bytes * batch * seq * d * (4 * n_q + 4 * n_kv)
    return flops * 5 // 2, nbytes


# -------------------------------------------------------------- model counts


def active_matmul_params(cfg: dict) -> int:
    """Parameters a token multiplies in one forward pass ("N" of 6N): the
    attention projections, the router, the experts a token is sent to, a
    shared expert and its gate where the family has one, and the output head.
    The embedding lookup multiplies nothing and norms are not matmuls.  Reads
    the published key names of both families the benchmark runs."""
    h = cfg["hidden_size"]
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // n_q
    attn = h * d * (n_q + 2 * n_kv) + n_q * d * h
    n_experts = cfg.get("num_local_experts", cfg.get("num_experts"))
    expert_width = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    moe = h * n_experts + cfg["num_experts_per_tok"] * 3 * h * expert_width
    shared = cfg.get("shared_expert_intermediate_size", 0)
    if shared:
        moe += 3 * h * shared + h
    return cfg["num_hidden_layers"] * (attn + moe) + h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 x active parameters plus causal attention (forward 2 x 2 x d x n_q x
    seq/2 a token and layer, three times that with the backward pass).
    Recomputation is not counted."""
    h = cfg["hidden_size"]
    attn = 3 * 4 * h * (seq + 1) / 2 * cfg["num_hidden_layers"]
    return 6.0 * active_matmul_params(cfg) + attn


def mfu(cfg: dict, seq: int, tokens_per_s_per_chip: float, peak: dict) -> float:
    return train_flops_per_token(cfg, seq) * tokens_per_s_per_chip / peak["bf16_flops"]
