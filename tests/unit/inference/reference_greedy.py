"""The one greedy reference of ``tests/unit/inference``: a continuation by a
family's full-sequence model, which knows no pages, slots or batching.

One program a (model, width): the tokens are padded to ``width`` and the
logits read at ``len(toks) - 1``.  Every family that calls this is causal, so
the padding behind the tokens changes nothing before it; a walk over a token
list one longer each time would compile every layer anew at each length.
"""

import jax
import numpy as np


def greedy(apply, args, prompt, n, width, precision=None):
    """``n`` greedy tokens behind ``prompt``.  ``apply(args, tokens)`` maps
    int32 tokens ``[1, width]`` to logits ``[1, width, vocab]``; ``args`` is
    whatever pytree it needs (the parameters; Kimi-VL's image rows and their
    index beside them).  Hand the same ``apply`` object to every call of a
    module: the compiled program is kept by it."""
    assert len(prompt) + n <= width, (len(prompt), n, width)
    full = jax.jit(apply)
    toks = [int(t) for t in prompt]
    padded = np.zeros((1, width), np.int32)
    with jax.default_matmul_precision(precision):
        for _ in range(n):
            padded[0, :len(toks)] = toks
            toks.append(int(np.argmax(full(args, padded)[0, len(toks) - 1])))
    return toks[len(prompt):]
