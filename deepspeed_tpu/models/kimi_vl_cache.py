"""Kimi-VL through latent pages: the serving twin of models/kimi_vl.py.

Every twin's contract, ``apply(params, input_ids, start_pos, block_table,
cache, chunk_lens, last_only, groups) -> (logits, cache)``, plus what a model
with a tower adds to it:

* ``mm_index`` (one entry a slot of ``input_ids``, -1: the token's own
  embedding) and ``mm_rows`` (the engine's buffer of image rows, ``[units,
  rows a unit, hidden]``): a slot whose index is not negative takes that row
  of the buffer in the embedding's place, so a chunk may hold text and image
  slots and an image may span chunks.  Both None: a step without image slots,
  the program a model without a tower compiles.
* ``apply(params, patches, grid, method="encode_images")``: the tower, the
  merger and the projector on one bucket of patches (``models/kimi_vl.py``).

The page, the absorbed form and the geometry are ``models/xing4_cache.py``'s
(``absorbed_attend``, ``LatentPagesGeometry``, ``init_cache``, and the two
layer blocks around them, handed this family's ``layer_forward``): a token's
cache in a layer is the one row ``[c_kv | k_pe]`` all 16 heads share.  The
trunk is the plain pre-norm residual: the dense layers unrolled, the expert
layers one ``scan_blocks`` that reads its banks in place.
"""

import jax.numpy as jnp
from flax import linen as nn

from .kimi_vl import KimiVLConfig, VisionFront, embed_tokens, head_logits, layer_forward
from .llama_cache import flat_positions, flat_step, logits_as, sampled_rows, scan_blocks
from .xing4_cache import _DenseLayerCache, _SparseLayerCache, stacked_banks


class _LanguageModelWithCache(nn.Module):
    cfg: KimiVLConfig
    page_size: int

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens, last_only, groups, mm_index, mm_rows):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        x = embed_tokens(cfg, tokens, None if mm_index is None else mm_index.reshape(-1), mm_rows)     # [T, C]
        for i in range(cfg.first_k_dense_replace):
            x, cache = _DenseLayerCache(cfg, self.page_size, groups, layer_forward, name=f"dense_layers_{i}")(
                (x, cache), i, positions, block_table, start_pos, chunk_lens)
        if cfg.num_sparse_layers:
            (x, cache), _ = scan_blocks(_SparseLayerCache, cfg.num_sparse_layers, n_broadcast=5)(
                cfg, self.page_size, groups, layer_forward, name="layers")(
                    (x, cache), jnp.arange(cfg.num_sparse_layers), positions, block_table, start_pos, chunk_lens,
                    stacked_banks(self, cfg))
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(head_logits(cfg, x), input_ids, last_only), cache


class KimiVLForCausalLMWithCache(VisionFront):
    """The twin: every twin's ``apply``, with ``mm_index`` and ``mm_rows``
    behind it, and ``encode_images``."""
    page_size: int = 16
    #: a prefill group's step program takes an index a slot into the engine's image rows
    takes_image_rows = True

    def setup(self):
        self.setup_vision()
        self.language_model = _LanguageModelWithCache(self.cfg, self.page_size)

    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None,
                 mm_index=None, mm_rows=None):
        self.init_vision()
        return self.language_model(input_ids, start_pos, block_table, cache, chunk_lens, last_only, groups,
                                   mm_index, mm_rows)
