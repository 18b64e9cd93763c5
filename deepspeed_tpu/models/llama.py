"""Llama-family causal LM, TPU-first.

This is the flagship training model (BASELINE.json configs 3–4: Llama-3-8B
ZeRO-3 / Ulysses 32k).  Where the reference injects fused CUDA kernels into a
HF torch module (ref: deepspeed/module_inject/containers/llama.py), we define
the model natively in flax.linen with:

  * ``nn.scan`` over the decoder stack — one compiled layer body, weights get
    a leading ``layers`` axis.  This is what makes ZeRO-3 memory behaviour
    fall out of XLA: sharded weights are all-gathered per scan iteration and
    freed after, the same live-window the reference's param coordinator
    maintains by hand (ref: runtime/zero/partitioned_param_coordinator.py).
  * logical axis names on every param, mapped to mesh axes by the sharding
    rules in ``module_inject/tp_rules.py`` (the AutoTP analog).
  * optional remat (``jax.checkpoint``) per layer — the analog of
    ``runtime/activation_checkpointing/checkpointing.py:948``.
  * a pluggable attention kernel (jnp reference or Pallas flash attention,
    or the Ulysses all-to-all wrapper from ``deepspeed_tpu.sequence``).
"""

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

# Logical axis vocabulary (consumed by module_inject/tp_rules.py)
BATCH = "batch"
SEQ = "seq_len"
from ..axes import EMBED, HEAD_DIM, HEADS, KV_HEADS, LAYERS, MLP, VOCAB  # noqa: F401 (canonical vocabulary)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    attention_impl: str = "reference"  # reference | flash | ulysses
    attention_bias: bool = False  # qkv bias (Qwen2-style checkpoints)
    attention_out_bias: bool = False  # o_proj bias (InternLM-1-style checkpoints)
    sliding_window: int = 0  # 0 = full attention; >0 = mistral-style window

    @staticmethod
    def from_hf(hf_cfg, **overrides):
        """Build from a transformers LlamaConfig (duck-typed)."""
        fields = dict(
            vocab_size=hf_cfg.vocab_size,
            hidden_size=hf_cfg.hidden_size,
            intermediate_size=hf_cfg.intermediate_size,
            num_hidden_layers=hf_cfg.num_hidden_layers,
            num_attention_heads=hf_cfg.num_attention_heads,
            num_key_value_heads=getattr(hf_cfg, "num_key_value_heads", hf_cfg.num_attention_heads),
            max_position_embeddings=hf_cfg.max_position_embeddings,
            rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
            rms_norm_eps=getattr(hf_cfg, "rms_norm_eps", 1e-5),
            tie_word_embeddings=getattr(hf_cfg, "tie_word_embeddings", False),
            attention_bias=getattr(hf_cfg, "attention_bias", False),
            # HF gates the window with use_sliding_window (qwen2 ships
            # sliding_window=32768 but use_sliding_window=False)
            sliding_window=((getattr(hf_cfg, "sliding_window", None) or 0)
                            if getattr(hf_cfg, "use_sliding_window", True) else 0),
        )
        # qwen2's max_window_layers keeps the first N layers full-attention;
        # mixed per-layer windows don't fit one scanned layer body
        mwl = getattr(hf_cfg, "max_window_layers", None)
        if fields["sliding_window"] and mwl is not None:
            if mwl >= hf_cfg.num_hidden_layers:
                fields["sliding_window"] = 0      # no layer actually windowed
            elif mwl > 0:
                raise NotImplementedError(
                    f"mixed full/window attention (max_window_layers={mwl} of "
                    f"{hf_cfg.num_hidden_layers}) is unsupported with scan-over-layers")
        fields.update(overrides)
        return LlamaConfig(**fields)


PRESETS = {
    "llama3-8b": LlamaConfig(vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
                             num_attention_heads=32, num_key_value_heads=8),
    # the reference FastGen headline model (blogs/deepspeed-fastgen: Llama-2-70B
    # served TP-sharded over 4 GPUs)
    "llama2-70b": LlamaConfig(vocab_size=32000, hidden_size=8192, intermediate_size=28672,
                              num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
                              rope_theta=10000.0),
    "llama2-7b": LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=11008, num_hidden_layers=32,
                             num_attention_heads=32, num_key_value_heads=32, rope_theta=10000.0),
    "tiny": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                        rope_theta=10000.0),
    "125m": LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048, num_hidden_layers=12,
                        num_attention_heads=12, num_key_value_heads=12, rope_theta=10000.0),
}


def _logical(init, names):
    return nn.with_logical_partitioning(init, names)


def _in_manual_mesh() -> bool:
    """True inside a shard_map body (e.g. the pipeline rotation): GSPMD-level
    sharding constraints are meaningless/illegal there."""
    from ..comm.mesh import in_manual_mesh
    return in_manual_mesh()


def _skip_constraint(x) -> bool:
    """Constraints are trace-time directives to GSPMD; eager values (golden
    tests calling attention outside jit) and shard_map bodies skip them."""
    return not isinstance(x, jax.core.Tracer) or _in_manual_mesh()


def _resolve_remat_policy(name: str):
    """jax.checkpoint_policies lookup plus 'flash_saveable': projection
    dots AND the flash kernel's tagged outputs (out + lse) are saved, so
    the backward runs the dedicated dq/dkv kernels against saved residuals
    instead of re-running the forward kernel first."""
    if name == "flash_saveable":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse"))
    if name == "flash_only":
        # memory-lean large-model policy: ONLY the flash kernel outputs are
        # saved (so the backward still runs the dedicated dq/dkv kernels, no
        # third attention pass) while every projection/MLP dot recomputes —
        # under scan-over-layers the residual stack stays O(layers·B·S·E)
        # instead of O(layers·B·S·intermediate)
        return jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    return getattr(jax.checkpoint_policies, name, None)


def activation_constraint(x):
    """Pin a [B, S, E] activation to the canonical (data×expert, seq, -)
    layout.  Without this, sharding propagation lets the embedding lookup
    inherit the table's ZeRO-3 fsdp sharding on the E dim, and the scan
    carry (B,S layout) then needs an SPMD "involuntary full
    rematerialization" reshard on while entry/exit — replicate + repartition
    of the whole residual stream, once forward and once backward."""
    from ..comm.mesh import BATCH_AXES, SEQ_AXIS, get_global_mesh, has_global_mesh
    if not has_global_mesh() or _skip_constraint(x):
        return x
    mesh = get_global_mesh()
    if all(mesh.shape.get(a, 1) == 1 for a in (*BATCH_AXES, SEQ_AXIS)):
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(BATCH_AXES, SEQ_AXIS if mesh.shape.get(SEQ_AXIS, 1) > 1 else None, None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def logits_constraint(logits):
    """Pin [B, S, V] logits to (data×expert, seq, tensor): with the lm_head
    kernel vocab-parallel (see tp_rules.vocab_rules) this keeps the matmul's
    fsdp all-gather on the weight side and the loss vocab-sharded over tp."""
    from ..comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, get_global_mesh, has_global_mesh
    if not has_global_mesh() or _skip_constraint(logits):
        return logits
    mesh = get_global_mesh()
    if all(mesh.shape.get(a, 1) == 1 for a in (*BATCH_AXES, SEQ_AXIS, TENSOR_AXIS)):
        return logits
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(BATCH_AXES,
                         SEQ_AXIS if mesh.shape.get(SEQ_AXIS, 1) > 1 else None,
                         TENSOR_AXIS if mesh.shape.get(TENSOR_AXIS, 1) > 1 else None)
    return jax.lax.with_sharding_constraint(logits, NamedSharding(mesh, spec))


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    unit_offset: bool = False  # the scale is 1 + weight (EvaByte's norm_add_unit_offset)

    @nn.compact
    def __call__(self, x):
        scale = self.param("weight", _logical(nn.initializers.ones_init(), (EMBED, )), (x.shape[-1], ),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + self.eps)
        scale = scale.astype(jnp.float32) + 1.0 if self.unit_offset else scale.astype(jnp.float32)
        return (normed * scale).astype(self.dtype)


def rotary_embedding(positions, head_dim, theta):
    """RoPE tables; fp32 for precision (ref kernel: csrc/transformer/inference
    rotary — here a pure-jnp pair that XLA fuses into the attention matmuls)."""
    inv_freq = 1.0 / (theta**(jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, S, D/2] ([T, D/2] of flat positions)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    # x: [B, S, N, D], or [T, N, D] with the tables of flat positions [T, D/2]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attn_logits_constraint(t):
    """Pin [B, N, Q, K] attention scores (and everything softmax derives from
    them) to the head-sharded layout the Ulysses all-to-all establishes.
    Without it, the backward recompute under jax.checkpoint resolves parts of
    the softmax head-sharded (from q/k) and parts seq-sharded (from the
    positions/mask side), and the partitioner falls back to involuntary full
    rematerialization between them."""
    from ..comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, get_global_mesh, has_global_mesh
    if not has_global_mesh() or _skip_constraint(t):
        return t
    mesh = get_global_mesh()
    head_axes = tuple(a for a in (SEQ_AXIS, TENSOR_AXIS) if mesh.shape.get(a, 1) > 1)
    if not head_axes and all(mesh.shape.get(a, 1) == 1 for a in BATCH_AXES):
        return t
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(BATCH_AXES, head_axes or None, None, None)
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def reference_attention(q, k, v, *, causal=True, segment_ids=None, sliding_window=0,
                        attn_bias=None):
    """Pure-jnp softmax attention (the golden path; swapped for the Pallas
    flash kernel via config.attention_impl).  ``sliding_window>0`` restricts
    each query to the last W keys (mistral).  ``attn_bias`` is an additive
    pre-softmax bias broadcastable to [B, N, Sq, Sk] (alibi slopes)."""
    b, sq, nh, hd = q.shape
    _, sk, nkv, _ = k.shape
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if attn_bias is not None:
        logits = logits + attn_bias.astype(jnp.float32)
    logits = _attn_logits_constraint(logits)
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
        if sliding_window and sliding_window > 0:
            mask = mask & (kpos > qpos - sliding_window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", probs.astype(v.dtype), v)


def chunked_attention(q, k, v, *, causal=True, segment_ids=None, sliding_window=0,
                      chunk_size=256, unroll_chunks=16):
    """Query-chunked attention with the softmax over the full key axis per
    chunk — never materializes the [B, N, S, S] score tensor that makes
    ``reference_attention`` HBM-bound at training sizes (each chunk's scores
    are [B, N, C, S] and die inside the scan iteration).  The online-softmax
    variant for host-offloaded KV lives in sequence/fpdt_layer.py; this one
    assumes K/V fit on-chip, which holds whenever the model itself does.
    ref role: csrc/transformer softmax/attention fusion — the memory shape of
    FlashAttention without the Pallas kernel.

    Short sequences (≤ ``unroll_chunks`` chunks) take an *unrolled* python
    loop with static per-chunk causal key ranges instead of ``lax.scan``:
    (a) chunk i only reads keys [0, (i+1)·C) — the scan path computes full
    [C, S] scores and masks, 2× the causal FLOPs; (b) XLA's scan VJP stacks
    residuals with dynamic_update_slice and differentiates through dynamic
    slices, which profiled HBM-bound at 19–32 TFLOP/s (~40 ms/step at bench
    size) — unrolled chunks autodiff into clean static-shape dots that run
    at MXU speed.  Long sequences keep the scan (compile-size bound)."""
    b, sq, nh, hd = q.shape
    _, sk, nkv, _ = k.shape
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if sq % chunk_size != 0 or sq < chunk_size:
        from ..utils.logging import logger
        logger.warning(f"chunked_attention: seq {sq} not a multiple of chunk {chunk_size}; "
                       "falling back to reference attention (full [B,N,S,S] scores)")
        return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                                   sliding_window=sliding_window)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    nc = sq // chunk_size
    kpos_full = jnp.arange(sk)

    if nc <= unroll_chunks and sq == sk:
        outs = []
        for i in range(nc):
            q_i = jax.lax.slice_in_dim(q, i * chunk_size, (i + 1) * chunk_size, axis=1)
            kend = (i + 1) * chunk_size if causal else sk
            kstart = 0
            if causal and sliding_window and sliding_window > 0:
                # earliest key visible to this chunk, rounded down to a lane-
                # friendly multiple so the slice stays tiled
                kstart = max(0, ((i * chunk_size - sliding_window + 1) // 128) * 128)
            k_i = jax.lax.slice_in_dim(k, kstart, kend, axis=1)
            v_i = jax.lax.slice_in_dim(v, kstart, kend, axis=1)
            s = jnp.einsum("bcnd,bknd->bnck", q_i, k_i,
                           preferred_element_type=jnp.float32) * scale
            qpos = i * chunk_size + jnp.arange(chunk_size)[:, None]
            kpos = kstart + jnp.arange(kend - kstart)[None, :]
            if causal:
                mask = qpos >= kpos
                if sliding_window and sliding_window > 0:
                    mask = mask & (kpos > qpos - sliding_window)
                s = jnp.where(mask[None, None], s, -1e30)
            if segment_ids is not None:
                q_seg = jax.lax.slice_in_dim(segment_ids, i * chunk_size, (i + 1) * chunk_size, axis=1)
                k_seg = jax.lax.slice_in_dim(segment_ids, kstart, kend, axis=1)
                s = jnp.where((q_seg[:, :, None] == k_seg[:, None, :])[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("bnck,bknd->bcnd", p.astype(v.dtype), v_i))
        return jnp.concatenate(outs, axis=1)

    qc = q.reshape(b, nc, chunk_size, nh, hd).transpose(1, 0, 2, 3, 4)  # [nc,B,C,N,D]

    def body(carry, args):
        q_i, i = args
        # [B,N,C,S] f32 scores for this query chunk only
        s = jnp.einsum("bcnd,bknd->bnck", q_i, k,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * chunk_size + jnp.arange(chunk_size)
        mask = jnp.ones((chunk_size, sk), bool)
        if causal:
            mask = qpos[:, None] >= kpos_full[None, :]
            if sliding_window and sliding_window > 0:
                mask = mask & (kpos_full[None, :] > qpos[:, None] - sliding_window)
        s = jnp.where(mask[None, None], s, -1e30)
        if segment_ids is not None:
            q_seg = jax.lax.dynamic_slice_in_dim(segment_ids, i * chunk_size, chunk_size, axis=1)
            seg_mask = q_seg[:, :, None] == segment_ids[:, None, :]
            s = jnp.where(seg_mask[:, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bnck,bknd->bcnd", p.astype(v.dtype), v)
        return carry, o

    # segment_ids prevents the static mask slice above from being traced with
    # a dynamic start when unused; keep i traced for the dynamic path
    _, out = jax.lax.scan(body, (), (qc, jnp.arange(nc)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, nh, hd)


def get_attention_impl(name: str) -> Callable:
    if name == "reference":
        return reference_attention
    if name == "chunked":
        return chunked_attention
    if name == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention
    if name == "ulysses":
        from ..sequence.layer import DistributedAttention
        return DistributedAttention(reference_attention)
    if name == "fpdt":
        from ..sequence.fpdt_layer import FPDTAttention
        return FPDTAttention(ulysses=False)
    if name == "ring":
        from ..sequence.ring import ring_attention
        return ring_attention
    raise ValueError(f"Unknown attention impl {name}")


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dense = partial(nn.DenseGeneral, use_bias=cfg.attention_bias, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        q = dense(features=(cfg.num_attention_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)),
                  name="q_proj")(x)
        k = dense(features=(cfg.num_key_value_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="k_proj")(x)
        v = dense(features=(cfg.num_key_value_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="v_proj")(x)
        cos, sin = rotary_embedding(positions, head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if cfg.sliding_window and cfg.attention_impl not in ("reference", "chunked", "flash"):
            raise NotImplementedError("sliding_window supports attention_impl reference/chunked/flash "
                                      "(ulysses/ring window masks land with those kernels)")
        attn_fn = get_attention_impl(cfg.attention_impl)
        kw = {"sliding_window": cfg.sliding_window} if cfg.sliding_window else {}
        out = attn_fn(q, k, v, causal=True, segment_ids=segment_ids, **kw)
        out = nn.DenseGeneral(features=cfg.hidden_size,
                              axis=(-2, -1),
                              use_bias=cfg.attention_out_bias,
                              dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                              name="o_proj")(out)
        return out


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        gate = dense(features=cfg.intermediate_size,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)),
                     name="gate_proj")(x)
        up = dense(features=cfg.intermediate_size,
                   kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)),
                   name="up_proj")(x)
        h = nn.silu(gate) * up
        return dense(features=cfg.hidden_size,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EMBED)),
                     name="down_proj")(h)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    scanned: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, pld_scale=None):
        cfg = self.cfg
        # pins the scan carry to (data×expert, seq, -) in BOTH directions:
        # the transpose of a constraint on the block input constrains the
        # backward carry (dx), which sharding propagation would otherwise
        # solve to E-sharded from the fsdp-sharded kernels, forcing an
        # involuntary full-remat reshard at the while boundary
        x = activation_constraint(x)
        # progressive layer drop: the whole block's residual contribution is
        # gated by pld_scale = keep_mask/keep_prob (ref: PLD paper eq. 6 and
        # runtime/progressive_layer_drop.py pld_layer_mask)
        s = 1.0 if pld_scale is None else pld_scale.astype(cfg.dtype)
        h = x + s * LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_layernorm")(x), positions, segment_ids)
        out = h + s * LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_attention_layernorm")(h))
        if self.scanned:
            return out, None
        return out


class ScannedBlocks(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, pld_scale=None):
        cfg = self.cfg
        block_cls = LlamaBlock
        if cfg.remat:
            policy = _resolve_remat_policy(cfg.remat_policy)
            block_cls = nn.remat(LlamaBlock, policy=policy, prevent_cse=not cfg.scan_layers)
        if cfg.scan_layers:
            blocks = nn.scan(block_cls,
                             variable_axes={"params": 0},
                             split_rngs={"params": True},
                             in_axes=(nn.broadcast, nn.broadcast, 0),
                             length=cfg.num_hidden_layers,
                             metadata_params={nn.PARTITION_NAME: LAYERS})
            if pld_scale is None:
                pld_scale = jnp.ones((cfg.num_hidden_layers, ), jnp.float32)
            x, _ = blocks(cfg, scanned=True, name="layers")(x, positions, segment_ids, pld_scale)
            return x
        for i in range(cfg.num_hidden_layers):
            s_i = None if pld_scale is None else pld_scale[i]
            x = block_cls(cfg, name=f"layers_{i}")(x, positions, segment_ids, s_i)
        return x


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig
    supports_pld = True  # engine passes pld_scale when PLD is configured

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, pld_scale=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
        embed = nn.Embed(num_embeddings=cfg.vocab_size,
                         features=cfg.hidden_size,
                         dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = activation_constraint(embed(input_ids))
        x = ScannedBlocks(cfg, name="model")(x, positions, segment_ids, pld_scale)
        x = activation_constraint(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        if cfg.tie_word_embeddings:
            logits = embed.attend(x)
        else:
            logits = nn.DenseGeneral(features=cfg.vocab_size,
                                     use_bias=False,
                                     dtype=cfg.dtype,
                                     param_dtype=cfg.param_dtype,
                                     kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                                     name="lm_head")(x)
        return logits_constraint(logits)


@jax.custom_vjp
def causal_lm_loss(logits, labels, loss_mask=None):
    """Token-mean cross entropy in fp32 (ref: sequence/cross_entropy.py's
    vocab-parallel CE is realised by GSPMD when lm_head is vocab-sharded).

    Computed as logsumexp(logits) - logits[label] rather than through
    log_softmax: the reductions stream over the vocab axis (XLA fuses the
    f32 cast into them), so no [B, S, V] f32 log-prob tensor is ever
    materialized — at bench size that tensor alone is 1 GB/step of HBM
    traffic.  The hand-written VJP emits dlogits = (softmax − onehot)·w
    directly in the logits dtype as one elementwise fusion over the saved
    bf16 logits; XLA's autodiff instead materializes the f32 softmax and
    converts it (profiled ~4 ms/step HBM-bound at bench size)."""
    loss, _ = _causal_lm_loss_fwd(logits, labels, loss_mask)
    return loss


def _causal_lm_loss_fwd(logits, labels, loss_mask):
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)  # [B, S]
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - tgt
    if loss_mask is not None:
        denom = jnp.maximum(loss_mask.sum(), 1.0)
        loss = (nll * loss_mask).sum() / denom
    else:
        denom = jnp.float32(nll.size)
        loss = nll.mean()
    return loss, (logits, labels, loss_mask, lse, denom)


def _causal_lm_loss_bwd(res, g):
    logits, labels, loss_mask, lse, denom = res
    w = g / denom
    if loss_mask is not None:
        w = w * loss_mask  # [B, S]
    else:
        w = jnp.broadcast_to(w, lse.shape)
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = labels[..., None] == jnp.arange(logits.shape[-1])[None, None, :]
    dlogits = ((p - onehot) * w[..., None]).astype(logits.dtype)
    return dlogits, None, None


causal_lm_loss.defvjp(_causal_lm_loss_fwd, _causal_lm_loss_bwd)


# --------------------------------------------------------------------------
# Pipeline-parallel building blocks (consumed by runtime/pipe/module.py).
# The reference expresses pipelined GPT models as a flat LayerSpec list
# (embed → N×block → norm → head); these are the Llama equivalents.  The
# block derives positions from the sequence length so the residual stream
# is the only tensor travelling through the pipeline rotation.


class LlamaEmbedLayer(nn.Module):
    cfg: LlamaConfig

    def setup(self):
        cfg = self.cfg
        self.embed_tokens = nn.Embed(num_embeddings=cfg.vocab_size,
                                     features=cfg.hidden_size,
                                     dtype=cfg.dtype,
                                     param_dtype=cfg.param_dtype,
                                     embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)))

    def __call__(self, input_ids):
        return self.embed_tokens(input_ids)

    def attend(self, x):
        """Tied LM head: logits via the embedding matrix (used by the
        pipeline's TiedLayerSpec forward_fn when tie_word_embeddings)."""
        return self.embed_tokens.attend(x)


class LlamaPipeBlock(nn.Module):
    """One decoder block with self-derived positions (pipeline body)."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return LlamaBlock(self.cfg, name="block")(x, positions)


class LlamaHeadLayer(nn.Module):
    """Final norm + LM head (last pipeline stage tail)."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        return nn.DenseGeneral(features=cfg.vocab_size,
                               use_bias=False,
                               dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                               name="lm_head")(x)


class LlamaNormLayer(nn.Module):
    """Final norm alone (last-stage tail when the LM head is tied)."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        return RMSNorm(self.cfg.rms_norm_eps, self.cfg.dtype, self.cfg.param_dtype, name="norm")(x)


def llama_pipeline_layers(cfg: LlamaConfig):
    """Flat layer list for PipelineModule (ref: the GPT2ModelPipe pattern in
    DeepSpeed examples built on pipe/module.py LayerSpec).  With
    ``tie_word_embeddings`` the head reuses the embedding matrix via
    TiedLayerSpec (ref: pipe/module.py TiedLayerSpec), matching
    LlamaForCausalLM's ``embed.attend`` path."""
    from ..runtime.pipe.module import LayerSpec, TiedLayerSpec
    blocks = [LayerSpec(LlamaPipeBlock, cfg) for _ in range(cfg.num_hidden_layers)]
    if cfg.tie_word_embeddings:
        embed = TiedLayerSpec("embed", LlamaEmbedLayer, cfg)
        head = TiedLayerSpec("embed", LlamaEmbedLayer, cfg,
                             forward_fn=lambda mod, variables, x: mod.apply(variables, x, method="attend"))
        return [embed] + blocks + [LayerSpec(LlamaNormLayer, cfg), head]
    return ([LayerSpec(LlamaEmbedLayer, cfg)] + blocks + [LayerSpec(LlamaHeadLayer, cfg)])
