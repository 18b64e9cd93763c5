"""Qwen2-MoE — llama-style attention (qkv bias) + sparse MoE MLP with a
shared expert.

ref: deepspeed/inference/v2/model_implementations/qwen_v2_moe/.  Per block:
softmax-over-all-experts gating → top-k (optionally renormalized), experts
are gated-SiLU MLPs at ``moe_intermediate_size``, plus a dense shared
expert scaled by sigmoid(shared_expert_gate(x)).

The routed experts go through ``moe.sharded_moe.dropless_dispatch``, the
path served Mixtral takes: no capacity, no token dropped, a token's k
outputs weighted and added in float32 (HF's gather-based math).  Above
``DENSE_UP_TO_TOKENS`` tokens a data shard (a training step), and wherever
the rows are too few to reach most of the 60 experts (a decode step:
``sharded_moe.takes_sorted``), the [S, k] choices are sorted by expert and
the bank multiplies the routed rows, k a token, forward and backward; between
the two (a short forward) every expert multiplies every row, which costs the
same read of the weights.
The bank [NE, ...] is whole on every data shard (ZeRO-3 gathers it a layer,
an ``expert`` mesh axis too): for expert counts that need the experts kept
apart over the mesh, use deepspeed_tpu.moe.MoE (capacity dispatch).
"""

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .llama import (EMBED, LAYERS, MLP, VOCAB, LlamaAttention, LlamaConfig, RMSNorm, _logical,
                    activation_constraint)
from ..axes import EXPERT_EMBED, EXPERT_MLP, EXPERTS
from ..moe.sharded_moe import dropless_dispatch


@dataclass(frozen=True)
class Qwen2MoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5632          # dense (unused when all-sparse)
    moe_intermediate_size: int = 1408
    shared_expert_intermediate_size: int = 5632
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 60
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = False
    mlp_only_layers: tuple = ()   # HF mlp_only_layers: dense-MLP layer indices
    decoder_sparse_step: int = 1  # HF: layer i is sparse iff (i+1) % step == 0
    qkv_bias: bool = True
    max_position_embeddings: int = 8192
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = True
    attention_impl: str = "reference"

    def as_llama(self) -> LlamaConfig:
        return LlamaConfig(vocab_size=self.vocab_size, hidden_size=self.hidden_size,
                           intermediate_size=self.moe_intermediate_size,
                           num_hidden_layers=self.num_hidden_layers,
                           num_attention_heads=self.num_attention_heads,
                           num_key_value_heads=self.num_key_value_heads,
                           max_position_embeddings=self.max_position_embeddings,
                           rope_theta=self.rope_theta, rms_norm_eps=self.rms_norm_eps,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           attention_impl=self.attention_impl, attention_bias=self.qkv_bias)

    def layer_is_sparse(self, i: int) -> bool:
        """HF Qwen2MoeDecoderLayer rule: dense MLP for mlp_only_layers and
        off-step layers, sparse MoE otherwise."""
        return (i not in tuple(self.mlp_only_layers) and self.num_experts > 0
                and (i + 1) % max(1, self.decoder_sparse_step) == 0)

    @property
    def mixed_stack(self) -> bool:
        return any(not self.layer_is_sparse(i) for i in range(self.num_hidden_layers))

    @staticmethod
    def from_hf(hf_cfg, **overrides):
        fields = dict(vocab_size=hf_cfg.vocab_size,
                      hidden_size=hf_cfg.hidden_size,
                      intermediate_size=hf_cfg.intermediate_size,
                      moe_intermediate_size=hf_cfg.moe_intermediate_size,
                      shared_expert_intermediate_size=hf_cfg.shared_expert_intermediate_size,
                      num_hidden_layers=hf_cfg.num_hidden_layers,
                      num_attention_heads=hf_cfg.num_attention_heads,
                      num_key_value_heads=getattr(hf_cfg, "num_key_value_heads", hf_cfg.num_attention_heads),
                      num_experts=hf_cfg.num_experts,
                      num_experts_per_tok=hf_cfg.num_experts_per_tok,
                      norm_topk_prob=getattr(hf_cfg, "norm_topk_prob", False),
                      qkv_bias=getattr(hf_cfg, "qkv_bias", True),
                      max_position_embeddings=hf_cfg.max_position_embeddings,
                      rope_theta=getattr(hf_cfg, "rope_theta", 1e6),
                      rms_norm_eps=getattr(hf_cfg, "rms_norm_eps", 1e-6),
                      tie_word_embeddings=getattr(hf_cfg, "tie_word_embeddings", False),
                      mlp_only_layers=tuple(getattr(hf_cfg, "mlp_only_layers", None) or ()),
                      decoder_sparse_step=getattr(hf_cfg, "decoder_sparse_step", 1))
        fields.update(overrides)
        cfg = Qwen2MoeConfig(**fields)
        if cfg.mixed_stack and cfg.scan_layers:
            # mixed dense/sparse layers can't share one scanned body
            cfg = Qwen2MoeConfig(**{**cfg.__dict__, "scan_layers": False})
        return cfg


class Qwen2MoeSparseMLP(nn.Module):
    cfg: Qwen2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, NE, M = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
        dt = cfg.dtype

        gate_logits = nn.Dense(NE, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                               name="gate")(x.astype(jnp.float32))         # [B,S,NE]

        # EXPERT_EMBED/EXPERT_MLP exclude the expert mesh axis from the ZeRO
        # dims — the 'expert' axis is already consumed by the EXPERTS dim
        # (see moe/experts.py + module_inject/tp_rules.py)
        w_gate = self.param("w_gate", _logical(nn.initializers.lecun_normal(), (EXPERTS, EXPERT_EMBED, EXPERT_MLP)),
                            (NE, E, M), cfg.param_dtype)
        w_up = self.param("w_up", _logical(nn.initializers.lecun_normal(), (EXPERTS, EXPERT_EMBED, EXPERT_MLP)),
                          (NE, E, M), cfg.param_dtype)
        w_down = self.param("w_down", _logical(nn.initializers.lecun_normal(), (EXPERTS, EXPERT_MLP, EXPERT_EMBED)),
                            (NE, M, E), cfg.param_dtype)
        # softmax over all experts, top-k, the k outputs weighted and added in
        # float32: HF's math, with the experts multiplying the routed rows only
        with jax.named_scope("ds_moe_grouped"):
            out, _, exp_counts = dropless_dispatch(x.astype(dt), gate_logits,
                                                   (w_gate.astype(dt), w_up.astype(dt), w_down.astype(dt)),
                                                   cfg.num_experts_per_tok, normalize=cfg.norm_topk_prob)
        # rows each expert multiplied: their sum is tokens x k on the grouped
        # path, max over mean its load imbalance (a no-op unless the caller
        # makes "intermediates" mutable)
        self.sow("intermediates", "exp_counts", exp_counts)
        out = activation_constraint(out)

        # shared expert with sigmoid gate (HF: shared_expert_gate Linear(E,1))
        # shared kernels use the EXPERT-family EMBED rule (fsdp minus the
        # expert axis): inside this block the expert weights already exclude
        # 'expert' from their ZeRO dims, and mixing both conventions makes
        # the scan backward reshard the shared kernels' grads through an
        # SPMD involuntary full remat (r4 dryrun guard)
        sh = nn.Dense(cfg.shared_expert_intermediate_size, use_bias=False, dtype=dt,
                      param_dtype=cfg.param_dtype,
                      kernel_init=_logical(nn.initializers.lecun_normal(), (EXPERT_EMBED, MLP)),
                      name="shared_gate_proj")(x)
        su = nn.Dense(cfg.shared_expert_intermediate_size, use_bias=False, dtype=dt,
                      param_dtype=cfg.param_dtype,
                      kernel_init=_logical(nn.initializers.lecun_normal(), (EXPERT_EMBED, MLP)),
                      name="shared_up_proj")(x)
        sd = nn.Dense(E, use_bias=False, dtype=dt, param_dtype=cfg.param_dtype,
                      kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EXPERT_EMBED)),
                      name="shared_down_proj")(nn.silu(sh) * su)
        sgate = nn.Dense(1, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                         name="shared_expert_gate")(x.astype(jnp.float32))
        out = out + jax.nn.sigmoid(sgate) * sd.astype(jnp.float32)
        return out.astype(x.dtype)


class Qwen2MoeDenseMLP(nn.Module):
    """SwiGLU dense MLP for mlp_only/off-step layers (ref: HF Qwen2MoeMLP
    with config.intermediate_size)."""
    cfg: Qwen2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, names, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=_logical(nn.initializers.lecun_normal(), names), name=name)
        g = dense(cfg.intermediate_size, (EMBED, MLP), "gate_proj")(x)
        u = dense(cfg.intermediate_size, (EMBED, MLP), "up_proj")(x)
        return dense(cfg.hidden_size, (MLP, EMBED), "down_proj")(nn.silu(g) * u)


class Qwen2MoeBlock(nn.Module):
    cfg: Qwen2MoeConfig
    scanned: bool = False
    sparse: bool = True

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        lcfg = cfg.as_llama()
        h = x + LlamaAttention(lcfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_layernorm")(x),
            positions, segment_ids)
        mlp = Qwen2MoeSparseMLP(cfg, name="mlp") if self.sparse else Qwen2MoeDenseMLP(cfg, name="mlp")
        out = h + mlp(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_attention_layernorm")(h))
        if self.scanned:
            return out, None
        return out


class Qwen2MoeForCausalLM(nn.Module):
    cfg: Qwen2MoeConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None):
        cfg = self.cfg
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(input_ids)
        block_cls = Qwen2MoeBlock
        if cfg.remat:
            block_cls = nn.remat(Qwen2MoeBlock, prevent_cse=not cfg.scan_layers)
        if cfg.scan_layers:
            blocks = nn.scan(block_cls, variable_axes={"params": 0, "intermediates": 0}, split_rngs={"params": True},
                             in_axes=(nn.broadcast, nn.broadcast), length=cfg.num_hidden_layers,
                             metadata_params={nn.PARTITION_NAME: LAYERS})
            x, _ = blocks(cfg, scanned=True, name="layers")(x, positions, segment_ids)
        else:
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, sparse=cfg.layer_is_sparse(i), name=f"layers_{i}")(x, positions, segment_ids)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        if cfg.tie_word_embeddings:
            return embed.attend(x)
        return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                               name="lm_head")(x)
