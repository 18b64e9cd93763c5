"""InferenceEngineV2 — FastGen-style continuous batching on TPU.

Reference: ``deepspeed/inference/v2/engine_v2.py:33 InferenceEngineV2``
(``put:124`` takes (uids, token-id lists)) and ``engine_factory.py:69
build_hf_engine``.  The serving loop composes:

  SplitFuseScheduler (scheduler.py)  — token-budget step planning
  StateManager/BlockedKVCache (ragged.py) — page allocation + batch packing
  LlamaForCausalLMWithCache (models/llama_cache.py) — one chunked forward
    program serving prefill, continuation and decode
  paged_attention[_pallas] — the blocked-KV attention kernel

TPU specifics vs the reference:
  * ONE compiled step program per list of row groups ``((rows, width), ...)``:
    the decode bucket at one slot a row, and in a mixed step a prefill group
    of a few rows (a ladder of three) at a chunk a row beside it — the
    scheduler quantises both, so steady-state serving reuses a handful of
    programs instead of the reference's per-shape CUDA kernel launches.
  * the KV arena is donated through the jitted step, so XLA updates pages
    in place (the reference's global InferenceContext arena, inference_context.h).
  * sampling is greedy or categorical on-device; logits for each row are
    taken at its last *real* token via ``chunk_lens``.
"""

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.llama_cache import PagedKVConfig, reads_through_kernel, stack_layer_params
from ...comm.mesh import trace_mesh
from ...moe import sharded_moe
from ...ops.grouped_matmul import takes_kernel
from ...ops.paged_attention import takes_decode_form, walk_block
from ...telemetry.step_anatomy import NULL_ANATOMY, StepAnatomy
from ...utils.logging import logger
from ...utils.nvtx import profiler_range
from .geometry import LinearGeometry
from .ragged import BlockedAllocator, BlockedKVCache, RaggedBatch, SequenceImage, StateManager, image_digest
from .scheduler import SchedulerConfig, SplitFuseScheduler, StepPlan
from .spec import SpecConfig, SpecStats, make_drafter


def build_cache_model(cfg, page_size: int):
    """Per-arch paged-cache model dispatch (the reference's
    model_implementations registry role, ref: inference/v2/engine_factory.py
    arch switch)."""
    from ...models.cache_zoo import cache_twin
    return cache_twin(cfg).model(cfg, page_size=page_size)


def _init_cache(cfg, econfig):
    """What the engine keeps as ``cache``, made by the configuration's twin:
    the arena of pages, and where its geometry has state slots one a
    sequence the scheduler may run and the scratch slot 0 beside them, each
    sized for the scheduler's prefill chunk."""
    from ...models.cache_zoo import cache_twin
    return cache_twin(cfg).init_cache(cfg, econfig.kv, econfig.kv_dtype, econfig.scheduler.max_seqs + 1,
                                      econfig.scheduler.prefill_chunk)


def _table_width(cfg, kvcfg: PagedKVConfig) -> int:
    """Columns of a block-table row in ``cfg``'s step programs: what the
    geometry of its pages needs for ``kvcfg``'s token capacity."""
    from ...models.cache_zoo import cache_geometry
    return cache_geometry(cfg, kvcfg.page_size).table_width(kvcfg.max_pages_per_seq * kvcfg.page_size)


@dataclasses.dataclass(frozen=True)
class RaggedInferenceEngineConfig:
    """ref: inference/v2/config_v2.py RaggedInferenceEngineConfig."""
    kv: PagedKVConfig = PagedKVConfig()
    scheduler: SchedulerConfig = SchedulerConfig()
    max_new_tokens: int = 128
    eos_token_id: Optional[int] = None
    greedy: bool = True
    temperature: float = 1.0
    kv_dtype: object = jnp.bfloat16
    # KV page reuse across shared prompt prefixes
    # (ref: inference/v2/ragged/prefix_cache_manager.py)
    enable_prefix_cache: bool = True
    # pure-decode rounds fused into ONE compiled program (the reference's
    # CUDA-graphs analog): dispatch/host overhead amortizes K×, which
    # dominates decode at small models.  Sequences
    # hitting EOS mid-block have their surplus tokens discarded host-side.
    decode_steps_per_dispatch: int = 8
    # TP-sharded serving (ref: inference/v2/engine_v2.py:118 honors
    # tensor_parallel.tp_size; model_implementations/sharding/qkv.py et al.).
    # Weights shard via the logical-axis rules (module_inject/tp_rules.py),
    # the KV arena over its kv-heads dim, and GSPMD inserts the o_proj /
    # down_proj allreduces AutoTP hand-wires.  An explicit ``mesh=`` to the
    # engine takes precedence over this degree.
    tensor_parallel: int = 1
    # speculative decoding (spec/): a drafter proposes up to k tokens per
    # pure-decode round and ONE (k+1)-position verify dispatch emits
    # accepted+1 of them, greedy-parity by construction.  Greedy only; on
    # pure-decode rounds speculation takes precedence over the fused
    # multi-step rung (which stays the fallback when no row drafts or KV
    # pages are short).  None disables.
    spec: Optional[SpecConfig] = None


def _make_step_fn(model, qparams, greedy: bool, temperature: float, groups):
    """The unified SplitFuse step program: one chunked forward serving
    prefill, continuation and decode, then per-row last-token sampling.
    ``groups`` is the step's static list of row groups ``(rows, width)``; the
    tokens are their one flat axis, the other batch arrays one entry a row
    (models/llama_cache.py "Row groups").  Pure in (params, cache, batch
    arrays) so both the live engine and the AOT serving-budget path
    (compile_aot_serving) jit the same function.  ``image_args``: the program
    of a twin with a vision tower whose step holds a prefill group is called
    with two arguments more (``_takes_image_rows``), ``mm_index`` (one entry a
    slot: its row of ``mm_rows``, -1 the token's own embedding) and
    ``mm_rows`` (the engine's image rows)."""
    groups = tuple(groups)

    def step(params, cache, tokens, start_pos, block_tables, chunk_lens, rng, *image_args):
        if qparams is not None:
            params = {"params": qparams.dequantize(params["params"])}
        # logits of each row's LAST real token alone: the twin takes those rows
        # out before its final norm and head (models/llama_cache.sampled_rows)
        logits, cache = model.apply(params, tokens, start_pos, block_tables, cache, chunk_lens, True, groups,
                                    *image_args)
        row_logits = logits[:, 0]                                                      # [R, V]
        if greedy:
            next_tok = jnp.argmax(row_logits, axis=-1)
        else:
            next_tok = jax.random.categorical(rng, row_logits / temperature, axis=-1)
        return next_tok.astype(jnp.int32), cache

    return step


def _make_multi_fn(model, qparams, greedy: bool, temperature: float, batch: int, k: int):
    """The fused decode program: ``k`` one-token rounds in ONE dispatch, each
    round's sampled token the next round's input.  The arena is the loop's
    carry (and, in the scanned trunks, the carry of the layer loop inside it).
    Pure, like ``_make_step_fn``, and shared with the AOT path the same way."""

    def mstep(params, cache, tokens0, start_pos, block_tables, chunk_lens, rng):
        if qparams is not None:
            params = {"params": qparams.dequantize(params["params"])}

        def body(i, carry):
            cache, toks, out = carry
            logits, cache = model.apply(params, toks[:, None], start_pos + i,
                                        block_tables, cache, chunk_lens)
            row_logits = logits[:, 0]
            if greedy:
                nxt = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    jax.random.fold_in(rng, i),
                    row_logits / temperature, axis=-1).astype(jnp.int32)
            return (cache, nxt, out.at[:, i].set(nxt))

        out0 = jnp.zeros((batch, k), jnp.int32)
        cache, _, out = jax.lax.fori_loop(0, k, body, (cache, tokens0, out0))
        return out, cache

    return mstep


def _named(fn, label: str):
    """Name a step function after its program key (``step:b16:c1:b1:c128`` ->
    ``ds_step_b16_c1_b1_c128``) before ``jax.jit``, so the device trace's
    ``XLA Modules`` line reads ``jit_ds_step_b16_c1_b1_c128`` and tells a
    mixed step from a one-token step."""
    fn.__name__ = fn.__qualname__ = "ds_" + label.replace(":", "_")
    return fn


def _serving_shardings(model, cfg, econfig, mesh):
    """TP shardings shared by the live engine (_setup_tp) and the AOT budget
    path: params via the logical-axis rules (zero_stage=0), the scanned KV
    arena [L, P, page, 2, n_kv, hd] over its kv-heads dim, host-side batch
    arrays replicated.  One derivation so the AOT memory budget can never
    desynchronize from what the engine actually shards."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ...comm.mesh import TENSOR_AXIS
    from ...module_inject.tp_rules import param_shardings
    from ...models.cache_zoo import cache_geometry, cache_twin
    kvcfg = econfig.kv
    cache_abs = jax.eval_shape(lambda: _init_cache(cfg, econfig))
    toks1 = jnp.zeros((1, 1), jnp.int32)
    one = jnp.zeros((1, ), jnp.int32)
    bt1 = jnp.zeros((1, _table_width(cfg, kvcfg)), jnp.int32)
    abs_vars = jax.eval_shape(
        lambda cache: model.init(jax.random.PRNGKey(0), toks1, one, bt1, cache, jnp.ones((1, ), jnp.int32)), cache_abs)
    param_sh = param_shardings(abs_vars, mesh, zero_stage=0)
    repl = NamedSharding(mesh, P())
    if cache_geometry(cfg, kvcfg.page_size).state_slots:
        if mesh.shape.get(TENSOR_AXIS, 1) > 1:
            raise NotImplementedError("tensor-parallel serving of a model with state slots: the slots' arrays "
                                      "(rings, recurrent states) have no sharding rule yet")
        cache_sh = jax.tree.map(lambda _: repl, cache_abs)
    elif cache_twin(cfg).walk_rows is not None:
        # pages of a twin's own shape under a kernel of its own (latent pages [L, P, page, W])
        if mesh.shape.get(TENSOR_AXIS, 1) > 1:
            raise NotImplementedError("tensor-parallel serving of latent pages: a row is every head's, and no "
                                      "head-sharded call of ds_mla_absorbed is built yet")
        cache_sh = repl
    else:
        cache_sh = NamedSharding(mesh, P(None, None, None, None, TENSOR_AXIS, None))
    return abs_vars, cache_abs, param_sh, cache_sh, repl


def compile_aot_serving(cfg, mesh, engine_config: RaggedInferenceEngineConfig = None,
                        batch: int = 8, chunk: int = 1, fused_steps: int = 0, groups=None):
    """AOT-compile the TP-sharded serving step against an offline topology:
    the step program of ``chunk`` tokens a row (or of the row ``groups``
    given: a mixed step's ``((16, 1), (1, 128))``) or, with ``fused_steps``
    k > 1, the fused decode program of k one-token rounds
    (``multi:b<batch>:k<k>``).

    No weights are ever allocated — params/cache lower as ShapeDtypeStructs —
    so this proves a serving config (e.g. Llama-3-8B at TP8 on v5p) fits
    per-chip HBM without the chips: the compiler's own buffer assignment,
    paged-attention kernel and GSPMD allreduces included.  Returns
    (compiled, n_params); ``compiled.memory_analysis()`` has the budget.
    Ref: the reference sizes its serving worlds by launcher convention
    (inference/v2/engine_v2.py:118) — no equivalent no-hardware proof exists
    there."""
    import numpy as np

    from ...comm.mesh import trace_mesh
    eng_cfg = engine_config or RaggedInferenceEngineConfig()
    kvcfg = eng_cfg.kv
    model = build_cache_model(cfg, kvcfg.page_size)
    abs_params, cache_abs, param_sh, cache_sh, r = _serving_shardings(model, cfg, eng_cfg, mesh)
    if fused_steps > 1:
        tokens_shape = (batch, )
        step = _named(_make_multi_fn(model, None, eng_cfg.greedy, eng_cfg.temperature, batch, fused_steps),
                      InferenceEngineV2._key_label(("multi", batch, fused_steps)))
    else:
        groups = tuple(groups or ((batch, chunk), ))
        batch = sum(rows for rows, _ in groups)
        tokens_shape = (sum(rows * width for rows, width in groups), )
        step = _named(_make_step_fn(model, None, eng_cfg.greedy, eng_cfg.temperature, groups),
                      InferenceEngineV2._key_label(groups))
    jitted = jax.jit(step, donate_argnums=(1, ),
                     in_shardings=(param_sh, cache_sh, r, r, r, r, r),
                     out_shardings=(r, cache_sh))
    sds = jax.ShapeDtypeStruct
    args = (abs_params, cache_abs,
            sds(tokens_shape, jnp.int32), sds((batch, ), jnp.int32),
            sds((batch, _table_width(cfg, kvcfg)), jnp.int32), sds((batch, ), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    with mesh, trace_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(abs_params))
    return compiled, n_params


class InFlightStep:
    """A dispatched-but-not-folded engine step: the device program has
    been enqueued (JAX async dispatch) and the host-side fold inputs are
    snapshotted here, so ``complete_step`` can run an arbitrary amount of
    host work later — the async double-buffered serving tick schedules
    step g+1 while this one executes.  ``tokens`` is the un-materialized
    device array; everything else is plain host state captured at
    dispatch time (sequence descriptors by OBJECT identity, so a flush
    that replaced a uid while the step was in flight is detectable)."""

    __slots__ = ("kind", "tokens", "rows", "seqs", "drafts", "base_len", "k")

    def __init__(self, kind: str):
        self.kind = kind          # "single" | "multi" | "spec"
        self.tokens = None        # device array: sampled tokens / argmax
        self.rows = None          # single: [(uid, n, seq, row_index)]: a run's tokens, its last row
        self.seqs = None          # multi/spec: descriptor list at dispatch
        self.drafts = None        # spec: per-row draft token lists
        self.base_len = None      # spec: pre-splice history lengths
        self.k = None             # multi: fused rounds in the dispatch


class InferenceEngineV2:
    """Continuous-batching engine over a model whose per-sequence state lives
    in pages of one arena: keys and values of every token for the
    softmax-attention families, a ring of exact rows plus summary rows for
    chunked linear attention; and, where the geometry says so, in one state
    slot a sequence beside the pages (rings of window layers, recurrent
    states).  What a page holds, how many pages ``n`` tokens need and whether
    there is a slot is the geometry's (``self.kv.geometry``); ``self.cache``
    is whatever the configuration's twin makes to hold it (one array of
    pages, or pages and slots: ``cache_zoo.CacheTwin.init_cache``); the
    engine only hands the model's step programs the block-table rows."""

    def __init__(self, cfg, params, engine_config: RaggedInferenceEngineConfig = None,
                 rng: Optional[jax.Array] = None, mesh=None):
        self.econfig = engine_config or RaggedInferenceEngineConfig()
        # speculative decoding: greedy-only (the accept rule is an argmax
        # identity — under sampling, emitted tokens would need the full
        # rejection-sampling correction, not implemented), and the verify
        # slots must be charged against the scheduler's token budget
        if self.econfig.spec is not None and not self.econfig.greedy:
            logger.warning("spec decoding requires greedy sampling "
                           "(accept-longest-prefix parity is an argmax identity); "
                           "disabling speculation")
            self.econfig = dataclasses.replace(self.econfig, spec=None)
        if self.econfig.spec is not None and \
                self.econfig.scheduler.spec_verify_tokens == 0:
            self.econfig = dataclasses.replace(
                self.econfig, scheduler=dataclasses.replace(
                    self.econfig.scheduler,
                    spec_verify_tokens=self.econfig.spec.max_draft))
        self.drafter = (make_drafter(self.econfig.spec)
                        if self.econfig.spec is not None else None)
        self.spec_stats = SpecStats()
        # uid -> (proposed, accepted, rollback_pages) of the LAST step's
        # verify round (cleared every step): the serving frontend folds
        # these into per-request acceptance accounting and metrics
        self.last_spec_round: Dict[int, Tuple[int, int, int]] = {}
        self._spec_on: Dict[int, bool] = {}
        kvcfg = self.econfig.kv
        from ..quantization import QuantizedParams
        self.mesh = self._resolve_mesh(mesh)
        if self.mesh is not None and isinstance(params, QuantizedParams):
            raise NotImplementedError(
                "TP-sharded serving of weight-only-quantized checkpoints is not "
                "implemented (int8 blocks would need per-shard scale re-layout)")
        self.cfg = cfg
        self.model = build_cache_model(cfg, kvcfg.page_size)
        if not getattr(cfg, "scan_layers", True) and not getattr(cfg, "mixed_stack", False):
            # a tree trained with scan_layers=False names its layers
            # model/layers_<i>; every twin scans one stacked model/layers but
            # the mixed dense/sparse stack, whose layers differ in shape
            if isinstance(params, QuantizedParams):
                raise NotImplementedError("a quantized scan_layers=False tree cannot be stacked: "
                                          "stack it (stack_layer_params), then quantize")
            params = stack_layer_params(params, cfg.num_hidden_layers)
        # experts a token is routed to (0: no expert layer) of how many the
        # router chooses among, and whether the grouped product of a step this
        # engine traces is the kernel ds_gmm, for the step records'
        # expert_rows and expert_rows_kernel
        self._experts_per_tok = int(getattr(cfg, "num_experts_per_tok", 0) or 0)
        self._router_experts = next((int(n) for n in (getattr(cfg, name, 0) for name in (
            "router_width", "n_routed_experts", "num_local_experts", "num_experts")) if n), 0)
        with trace_mesh(self.mesh):
            self._experts_kernel = takes_kernel()
        # weight-only-quantized checkpoints: int8 stays in HBM, dequant is
        # traced into the step program (ref: inference/quantization kernels)
        if isinstance(params, QuantizedParams):
            self._qparams = params
            self.params = {"params": params.tree}
        else:
            self._qparams = None
            self.params = params
        from ...models.cache_zoo import cache_geometry
        geometry = cache_geometry(cfg, kvcfg.page_size)
        if self.econfig.spec is not None and geometry.state_slots:
            raise NotImplementedError(f"speculative decoding over {type(geometry).__name__}: a rejected draft has "
                                      "already advanced the slot's recurrent state, which cannot be rewound")
        self.kv = BlockedKVCache(kvcfg.num_pages, kvcfg.page_size, kvcfg.max_pages_per_seq,
                                 enable_prefix_cache=self.econfig.enable_prefix_cache, geometry=geometry,
                                 state_slots=self.econfig.scheduler.max_seqs + 1)
        if self.econfig.spec is not None and not self.kv.geometry.pages_immutable:
            # a verify chunk may cross a window and its rollback cannot be undone
            raise NotImplementedError(f"speculative decoding over {type(self.kv.geometry).__name__} "
                                      "(pages rewritten in place) is not implemented")
        self.state = StateManager(self.kv, max_batch=self.econfig.scheduler.max_seqs)
        self.scheduler = SplitFuseScheduler(self.econfig.scheduler)
        self.cache = _init_cache(cfg, self.econfig)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._max_new: Dict[int, int] = {}
        #: program key -> program: a step's row groups ``((rows, width), ...)``,
        #: ``("multi", batch, k)`` or ``("verify", batch, width)``
        self._step_fns: Dict[tuple, callable] = {}
        # a prompt alone in prefill may fill the rung its steps have for a
        # burst of arrivals with its own consecutive chunks, and no wider
        # one: what the rung of ``max_seqs`` rows costs the decode rows
        # that ride it is not measured (PERF.md section 7).  Where a
        # sequence's chunks cannot share a step the geometry says so
        # (``chunk_runs``) and the scheduler plans no run.
        rungs = self._prefill_rungs()
        self.scheduler.run_rows = rungs[-2] if len(rungs) > 1 else 1
        #: a twin with a vision tower: the buffer of image rows [units, rows a
        #: unit, hidden] the sequences' images own units of from their encode
        #: until prefill has passed them (unit 0 is scratch, where an encode's
        #: padding lands), and the buckets of patches an encode program takes
        self.mm_rows = self.mm_alloc = None
        self._image_rows = bool(getattr(self.model, "takes_image_rows", False))
        if self._image_rows:
            sched = self.econfig.scheduler
            merge = cfg.vision.merge
            if not sched.vision_patch_buckets or sched.vision_patch_buckets[0] % merge:
                raise ValueError("a model with a vision tower needs scheduler.vision_patch_buckets, whole merge blocks")
            self.mm_unit = sched.vision_patch_buckets[0] // merge
            if any(b % (self.mm_unit * merge) for b in sched.vision_patch_buckets):
                raise ValueError(f"vision_patch_buckets {sched.vision_patch_buckets}: each must be whole units of "
                                 f"the smallest's {self.mm_unit} rows")
            n_units = 1 + max(sched.vision_rows, sched.vision_patch_buckets[-1] // merge) // self.mm_unit
            self.mm_rows = jnp.zeros((n_units, self.mm_unit, cfg.hidden_size), cfg.dtype)
            self.mm_alloc = BlockedAllocator(n_units, "image rows")
        # per-step anatomy (telemetry/step_anatomy.py): every engine records
        # its steps (a ring of the last 8,192, drawn into a running profile
        # too); set_anatomy(None) switches to the NULL recorder, one
        # attribute read and one predicate a hook.  A serving frontend moves
        # the engine's own recorder, and no other, onto its clock.
        self.anatomy = StepAnatomy(max_steps=8192, annotate=profiler_range)
        self.anatomy_is_default = True
        self._fresh_compile = False
        self._param_sh = self._cache_sh = self._repl_sh = None
        if self.mesh is not None:
            self._setup_tp()

    def set_anatomy(self, anatomy):
        """Attach a :class:`~...telemetry.step_anatomy.StepAnatomy`
        recorder in place of the engine's own (None: the allocation-free
        NULL recorder, recording off).  The recorder's clock should be the
        serving clock when a frontend drives this engine, so host-gap
        windows and device charges live in one time domain."""
        self.anatomy = anatomy if anatomy is not None else NULL_ANATOMY
        self.anatomy_is_default = False
        return self.anatomy

    def _note_compile(self, key: str) -> None:
        """One JIT cache miss: the NEXT dispatch of this program pays the
        trace+compile synchronously, so the step's dispatch segment is
        tagged ``compile_wait`` and the compile tracker records the miss
        (warm-up vs steady-state — the AOT regression guard)."""
        self._fresh_compile = True
        self.anatomy.note_compile(key)

    def _expert_rows(self, tokens: int, group: int, live: Optional[int] = None) -> Dict[str, int]:
        """The step records' expert counts: the rows the routed experts
        multiplied for ``tokens`` real tokens, and how many of them went
        through the kernel ``ds_gmm``: all, where the grouped product is the
        kernel (``ops/grouped_matmul.takes_kernel``) and a model step of
        ``group`` slots of which ``live`` carry a token (``tokens`` of them,
        unless said: a round of a fused dispatch) takes the sorted form, else
        none.  It asks what ``moe/sharded_moe.dropless_moe`` asks:
        ``takes_sorted`` of the slots, as the traced program did, and where
        they say "dense" whether the live rows are under
        ``live_rows_sorted``, as the program does when it runs."""
        k, e = self._experts_per_tok, self._router_experts
        rows = tokens * k
        kernel = rows and self._experts_kernel and (
            sharded_moe.takes_sorted(group, k, e)
            or (tokens if live is None else live) <= sharded_moe.live_rows_sorted(group, k, e))
        return {"expert_rows": rows, "expert_rows_kernel": rows if kernel else 0}

    # ------------------------------------------------------------------ TP

    def _resolve_mesh(self, mesh):
        """Explicit mesh wins; else ``tensor_parallel > 1`` builds a pure-TP
        mesh over the first tp devices (ref: engine_v2.py:118 — the reference
        reads tp_size from config and expects the launcher to have sized the
        world; here the engine claims the devices itself)."""
        if mesh is not None:
            if mesh.size <= 1:
                return None
            if mesh.shape.get("tensor", 1) <= 1:
                raise ValueError(
                    f"serving mesh {dict(mesh.shape)} has no 'tensor' axis with degree > 1 — "
                    "the v2 engine shards over TP only; build it with e.g. "
                    "create_mesh(MeshSpec(data=1, tensor=N))")
            return mesh
        tp = self.econfig.tensor_parallel
        if tp <= 1:
            return None
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(f"tensor_parallel={tp} but only {len(devs)} devices visible")
        from ...comm.mesh import MeshSpec, create_mesh
        return create_mesh(MeshSpec(data=1, tensor=tp), devices=devs[:tp])

    def _setup_tp(self):
        """Shard weights + KV arena over the mesh's tensor axis.

        The serving analog of AutoTP (ref: model_implementations/sharding/
        qkv.py:14 et al. hand-shard each weight class): every cache twin
        already carries logical axis names on its params, so the training-side
        rules (module_inject/tp_rules.py, zero_stage=0) produce the same
        Megatron layout — q/k/v column-parallel over heads, o/down
        row-parallel, vocab-parallel embedding/lm_head — and GSPMD inserts
        the paired allreduces.  The KV arena shards over its kv-heads dim so
        per-chip KV bytes drop by 1/tp (the reference's
        ``kv_cache.py`` splits head_count across ranks the same way)."""
        from ...comm.mesh import TENSOR_AXIS
        mesh = self.mesh
        tp = mesh.shape.get(TENSOR_AXIS, 1)
        n_kv = self._pages().shape[-2]
        heads = self.cfg.num_attention_heads
        if tp > 1 and (n_kv % tp or heads % tp):
            raise ValueError(f"tensor_parallel={tp} must divide num_key_value_heads={n_kv} "
                             f"and num_attention_heads={heads}")
        _, _, self._param_sh, self._cache_sh, self._repl_sh = _serving_shardings(
            self.model, self.cfg, self.econfig, mesh)
        self.params = jax.device_put(self.params, self._param_sh)
        self.cache = jax.device_put(self.cache, self._cache_sh)
        logger.info(f"InferenceEngineV2: TP-sharded serving over tensor={tp} "
                    f"({mesh.size}-device mesh)")

    # ---------------------------------------------------------------- put

    def _placeholder_runs(self, tokens: Sequence[int]):
        """(first position, length) of each run of the placeholder id in ``tokens``."""
        at = np.flatnonzero(np.asarray(tokens) == self.cfg.media_placeholder_token_id)
        starts = np.flatnonzero(np.diff(at, prepend=-2) > 1)
        return at[starts], np.diff(np.append(starts, at.size))

    def check_images(self, tokens: Sequence[int], images, strict: bool = True) -> Optional[str]:
        """Why ``images`` ([(pixels [h w, 3, p, p] or [h w, 3 p p], (h, w)),
        ...]) cannot go with ``tokens``, or None: a grid is odd or empty, an
        image is over the largest bucket, the pixels are not the grid's, or
        the runs of the placeholder id are not one run of ``h w / merge`` an
        image, in order.  ``strict`` off: runs behind the images' own are
        let be (a resumed sequence's tokens hold what it generated, which may
        carry the placeholder's id and is text)."""
        if not self._image_rows:
            return "no_vision_tower"
        vc = self.cfg.vision
        want = []
        for pixels, (h, w) in images:
            if h <= 0 or w <= 0 or h % vc.merge_kernel_size[0] or w % vc.merge_kernel_size[1]:
                return "image_grid_odd"
            if h * w > self.econfig.scheduler.vision_patch_buckets[-1]:
                return "image_over_largest_bucket"
            if np.shape(pixels)[0] != h * w or int(np.prod(np.shape(pixels)[1:])) != vc.patch_dim:
                return "image_pixels_mismatch"
            want.append(h * w // vc.merge)
        lengths = self._placeholder_runs(tokens)[1].tolist()
        if (lengths if strict else lengths[:len(want)]) != want:
            return "image_placeholders_mismatch"
        return None

    def _sequence_images(self, tokens: Sequence[int], images, reencode: bool = False) -> List[SequenceImage]:
        reason = self.check_images(tokens, images, strict=False)
        if reason is not None:
            raise ValueError(f"images rejected: {reason}")
        vc = self.cfg.vision
        buckets = self.econfig.scheduler.vision_patch_buckets
        out = []
        for (pixels, (h, w)), start in zip(images, self._placeholder_runs(tokens)[0]):
            pixels = np.asarray(pixels).reshape(h * w, vc.patch_dim)
            out.append(SequenceImage(pixels=pixels, grid=(int(h), int(w)), start=int(start), rows=h * w // vc.merge,
                                     bucket=next(b for b in buckets if b >= h * w),
                                     digest=image_digest(pixels, (h, w)), reencoded=reencode))
        return out

    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[Sequence[int]],
            max_new_tokens: Optional[int] = None, images: Optional[Sequence] = None, reencode: bool = False) -> None:
        """Admit new sequences (ref: engine_v2.py:124 put).  ``images``: per
        sequence its images, ``[(pixels, (h, w)), ...]`` or None, for a model
        with a vision tower (``check_images`` says what is refused);
        ``reencode``: they were encoded before (a preempted request resumes),
        which the encode records count."""
        max_pos = getattr(self.cfg, "max_position_embeddings", None)
        images = list(images) if images is not None else [None] * len(batch_uids)
        seq_images = [self._sequence_images(t, i, reencode) if i else None for t, i in zip(batch_tokens, images)]
        # validate ALL before admitting ANY — a partial put would leave
        # earlier sequences admitted when a later one raises
        for uid, tokens in zip(batch_uids, batch_tokens):
            need = len(tokens) + (max_new_tokens or self.econfig.max_new_tokens)
            if max_pos is not None and need > max_pos:
                # learned/rotary position tables end here; clamped positions
                # would silently produce degraded logits (e.g. OPT's table)
                raise ValueError(f"sequence {uid}: prompt+max_new_tokens = {need} exceeds the "
                                 f"model's max_position_embeddings = {max_pos}")
        for uid, tokens, imgs in zip(batch_uids, batch_tokens, seq_images):
            self.state.get_or_create(uid, list(tokens), imgs)
            self._max_new[uid] = max_new_tokens or self.econfig.max_new_tokens

    # ------------------------------------------------------- the vision tower

    def _release_images(self, seq, passed_only: bool = False) -> None:
        """Give back the units of ``seq``'s images (those prefill has passed, or all)."""
        for img in seq.images:
            if img.units and (not passed_only or img.end <= seq.seen_tokens):
                self.mm_alloc.free(img.units)
                img.units, img.passed, img.pixels = [], True, None

    def _build_vit_jit(self, bucket: int):
        """The tower's program on a bucket of ``bucket`` patches: tower,
        merger and projector on one image, its rows written into the units
        given (``units`` [bucket / merge / unit]; padding's are unit 0)."""
        model, unit = self.model, self.mm_unit

        def encode(params, mm_rows, patches, grid, units):
            rows = model.apply(params, patches, grid, method="encode_images")
            return mm_rows.at[units].set(rows.reshape(units.shape[0], unit, rows.shape[-1]).astype(mm_rows.dtype))

        return jax.jit(_named(encode, self._key_label(("vit", bucket))), donate_argnums=(1, ))

    def _compiled_vit(self, bucket: int):
        key = ("vit", bucket)
        if key not in self._step_fns:
            logger.info(f"InferenceEngineV2: compiling the vision tower's program on {bucket} patches")
            self._step_fns[key] = self._build_vit_jit(bucket)
            self._note_compile(self._key_label(key))
        return self._step_fns[key]

    def _image_units(self, img: SequenceImage) -> int:
        """Units of the row buffer an image's bucket fills."""
        return img.bucket // self.cfg.vision.merge // self.mm_unit

    def image_row_index(self, img: SequenceImage) -> np.ndarray:
        """[rows] int32: the row of the (flattened) buffer each of the image's rows lies in, by its units."""
        k = np.arange(img.rows)
        return (np.asarray(img.units)[k // self.mm_unit] * self.mm_unit + k % self.mm_unit).astype(np.int32)

    def dispatch_encode(self, img: SequenceImage) -> None:
        """Enqueue the tower's program for one image that holds its units:
        the patches padded to the bucket (the copy to the device is made
        here), the units padded with the scratch unit 0."""
        h, w = img.grid
        patches = np.zeros((img.bucket, img.pixels.shape[1]), img.pixels.dtype)
        patches[:h * w] = img.pixels
        units = np.zeros((self._image_units(img), ), np.int32)
        units[:len(img.units)] = img.units
        self.mm_rows = self._compiled_vit(img.bucket)(self.params, self.mm_rows, jnp.asarray(patches),
                                                      jnp.asarray(img.grid, jnp.int32), jnp.asarray(units))

    def encode_images(self) -> List[dict]:
        """``iter_encode_images`` run to its end: the records of its dispatches."""
        return list(self.iter_encode_images())

    def iter_encode_images(self):
        """Dispatch the tower for images that wait, sequences in scheduling
        order and a sequence's images in prompt order, until the scheduler's
        ``vision_patches_per_tick`` padded patches are spent (0: no bound; the
        first image always goes).  A sequence's images take their units
        of the row buffer together or not at all, so two half-encoded
        sequences never wait for each other; one that finds no room waits for
        prefill to pass earlier images, and those behind it wait with it.
        A generator: one record after each dispatch (uid, key, bucket,
        patches_real, patches_padded, reencoded, done: the sequence's last
        image), so that a caller with a clock can time them one by one."""
        if not self._image_rows:
            return
        budget = self.econfig.scheduler.vision_patches_per_tick
        waiting = [s for s in self.state.seqs.values() if s.images_pending and not s.done]
        if self.scheduler.order_key is not None:
            waiting.sort(key=self.scheduler.order_key)
        spent = 0
        for seq in waiting:
            todo = [img for img in seq.images if not (img.encoded or img.passed)]
            if not todo[0].units:
                need = sum(self._image_units(img) for img in todo)
                if need > self.mm_alloc.free_pages:
                    break
                index = np.full((len(seq.tokens), ), -1, np.int32) if seq.mm_index is None else seq.mm_index
                for img in todo:
                    img.units = self.mm_alloc.allocate(self._image_units(img))
                    index[img.start:img.end] = self.image_row_index(img)
                seq.mm_index = index
            for img in todo:
                if budget and spent and spent + img.bucket > budget:
                    return
                h, w = img.grid
                self.dispatch_encode(img)
                img.encoded, img.pixels = True, None
                spent += img.bucket
                key = self._key_label(("vit", img.bucket))
                self.anatomy.note_encode(key, h * w, img.bucket, img.reencoded)
                yield {"uid": seq.uid, "key": key, "bucket": img.bucket, "patches_real": h * w,
                       "patches_padded": img.bucket, "reencoded": img.reencoded, "done": img is todo[-1]}

    def flush(self, uid: int) -> None:
        if self._image_rows and uid in self.state.seqs:
            self._release_images(self.state.seqs[uid])
        self.state.flush(uid)
        self._max_new.pop(uid, None)
        self._spec_on.pop(uid, None)
        self.last_spec_round.pop(uid, None)

    def preempt(self, uid: int):
        """Evict one sequence under KV pressure (serving frontend): pages
        released, descriptor returned for requeue-with-tokens-preserved.
        Unlike ``flush`` the uid must exist — preempting a finished/unknown
        sequence is a frontend bug, not a no-op."""
        self._max_new.pop(uid, None)
        self._spec_on.pop(uid, None)
        self.last_spec_round.pop(uid, None)
        if self._image_rows:
            self._release_images(self.state.seqs[uid])   # a resumed sequence encodes again
        return self.state.preempt(uid)

    def set_spec(self, uid: int, enabled: bool) -> None:
        """Per-sequence speculation opt-in/out (the serving frontend's
        per-request control).  No-op when the engine carries no spec
        config — a request asking for speculation on a spec-less engine
        just decodes normally."""
        if self.econfig.spec is not None:
            self._spec_on[uid] = bool(enabled)

    def single_step_page_demand(self, plan: Optional[StepPlan] = None) -> int:
        """KV pages the NEXT step needs beyond what its sequences hold, at
        the guaranteed-progress rung (decode k=1 — the fused multi-decode
        path already self-shrinks k under pressure in ``step``).  The
        serving frontend preflights this against ``allocator.free_pages``
        and preempts until the step fits, instead of letting ``pack_groups`` raise
        mid-step."""
        if plan is None:
            plan = self.scheduler.plan(self.state)
        return (sum(self.kv.pages_needed(s, 1) for s in plan.decode) +
                sum(self.kv.pages_needed(s, n) for s, n in plan.prefill))

    # --------------------------------------------------------------- step

    def _jit_kwargs(self):
        """Explicit shardings under TP: params/cache committed to their
        shards, host-side batch arrays (tokens, tables, positions) and the
        sampled tokens replicated."""
        if self.mesh is None:
            return {}
        r = self._repl_sh
        return dict(in_shardings=(self._param_sh, self._cache_sh, r, r, r, r, r),
                    out_shardings=(r, self._cache_sh))

    def _invoke(self, fn, *args):
        """Run a compiled step; under TP the trace happens inside the mesh +
        trace_mesh context so the Pallas paged kernel self-wraps in shard_map
        (ops/paged_attention._paged_sharded)."""
        if self.mesh is None:
            return fn(*args)
        from ...comm.mesh import trace_mesh
        with self.mesh, trace_mesh(self.mesh):
            return fn(*args)

    def _build_step_jit(self, groups: tuple):
        """The jitted single/mixed step program of the row ``groups`` — ONE
        builder shared by the lazy per-shape cache and the AOT ``warm_all``
        path, so the two can never trace different computations for the
        same key."""
        step = _make_step_fn(self.model, self._qparams, self.econfig.greedy, self.econfig.temperature, groups)
        return jax.jit(_named(step, self._key_label(groups)),
                       donate_argnums=(1, ), **self._jit_kwargs())

    def _takes_image_rows(self, groups: tuple) -> bool:
        """Whether the step program of ``groups`` takes ``mm_index`` and
        ``mm_rows``: a twin with a tower, a group wider than one token."""
        return self._image_rows and any(width > 1 for _, width in groups)

    def _build_multi_jit(self, batch: int, k: int):
        """The fused k-round decode program (shapes close over batch/k)."""
        mstep = _make_multi_fn(self.model, self._qparams, self.econfig.greedy,
                               self.econfig.temperature, batch, k)
        return jax.jit(_named(mstep, self._key_label(("multi", batch, k))),
                       donate_argnums=(1, ), **self._jit_kwargs())

    def _build_verify_jit(self, batch: int, width: int):
        """The speculative verify program (argmax at EVERY position)."""
        def vstep(params, cache, tokens, start_pos, block_tables, chunk_lens):
            if self._qparams is not None:
                params = {"params": self._qparams.dequantize(params["params"])}
            logits, cache = self.model.apply(params, tokens, start_pos,
                                             block_tables, cache, chunk_lens)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        kwargs = {}
        if self.mesh is not None:
            r = self._repl_sh
            kwargs = dict(in_shardings=(self._param_sh, self._cache_sh, r, r, r, r),
                          out_shardings=(r, self._cache_sh))
        return jax.jit(_named(vstep, self._key_label(("verify", batch, width))),
                       donate_argnums=(1, ), **kwargs)

    def _compiled_step(self, groups: tuple):
        if groups not in self._step_fns:
            logger.info(f"InferenceEngineV2: compiling step program of row groups {groups}")
            self._step_fns[groups] = self._build_step_jit(groups)
            self._note_compile(self._key_label(groups))
        return self._step_fns[groups]

    def _compiled_multi_step(self, batch: int, k: int):
        key = ("multi", batch, k)
        if key not in self._step_fns:
            logger.info(f"InferenceEngineV2: compiling multi-decode program batch={batch} k={k}")
            self._step_fns[key] = self._build_multi_jit(batch, k)
            self._note_compile(self._key_label(key))
        return self._step_fns[key]

    def _compiled_verify(self, batch: int, width: int):
        """The speculative VERIFY program: ONE chunked forward over
        ``width = max_draft + 1`` positions per row, returning the argmax
        at EVERY position (the model's own next-token choice after each
        fed prefix) instead of a single last-token sample.  Shorter drafts
        ride as ragged rows via ``chunk_lens`` — KV writes and attention
        mask at the per-row length, exactly like ragged prefill chunks —
        so steady-state serving keeps ONE verify program per batch
        bucket."""
        key = ("verify", batch, width)
        if key not in self._step_fns:
            logger.info(f"InferenceEngineV2: compiling verify program batch={batch} "
                        f"width={width}")
            self._step_fns[key] = self._build_verify_jit(batch, width)
            self._note_compile(self._key_label(key))
        return self._step_fns[key]

    # ------------------------------------------------------------- AOT set

    @staticmethod
    def _key_label(key) -> str:
        if key[0] == "multi":
            return f"multi:b{key[1]}:k{key[2]}"
        if key[0] == "verify":
            return f"verify:b{key[1]}:w{key[2]}"
        if key[0] == "vit":  # the vision tower on a bucket of patches
            return f"vit:p{key[1]}"
        # a step's row groups: ((16, 128), ) -> step:b16:c128, a mixed step's
        # ((16, 1), (1, 128)) -> step:b16:c1:b1:c128 (no "_": _named turns ":" into it)
        return "step:" + ":".join(f"b{rows}:c{width}" for rows, width in key)

    def step_shape_set(self) -> List[tuple]:
        """Enumerate every program key steady-state serving can reach,
        straight from the scheduler's bucket table: batch buckets are the
        ``decode_bucket`` multiples up to ``max_seqs``; a step is the decode
        bucket at one token a row and, with a prefill row in it, what
        ``_step_groups`` makes of the plan (the decode bucket beside each
        rung of ``_prefill_rungs`` at ``prefill_chunk``); the fused-decode
        rung adds its halving ladder (k_cfg, k_cfg/2, ..., 2 — exactly the
        pressure fallbacks ``_dispatch_inner`` walks); a drafter adds one
        verify width (``max_draft + 1``).
        This closure is what makes ``warm_all`` a guarantee rather than a
        heuristic: a steady-state dispatch outside this set would be an
        engine bug, and the ``engine/recompile_steady_state`` guard would
        name it."""
        sched = self.econfig.scheduler
        q = sched.decode_bucket
        maxb = self.state.max_batch
        batches = sorted({min(maxb, m * q) for m in range(1, -(-maxb // q) + 1)})
        keys: List[tuple] = [((b, 1), ) for b in batches]
        if sched.prefill_chunk > 1:
            keys += [((b, 1), (p, sched.prefill_chunk)) for b in batches for p in self._prefill_rungs()]
        k_cfg = self.econfig.decode_steps_per_dispatch
        if k_cfg > 1:
            ks = set()
            k = k_cfg
            while k > 1:
                ks.add(k)
                k //= 2
            keys += [("multi", b, k) for b in batches for k in sorted(ks)]
        if self.drafter is not None:
            width = self.econfig.spec.max_draft + 1
            keys += [("verify", b, width) for b in batches]
        if self._image_rows:
            keys += [("vit", p) for p in sched.vision_patch_buckets]
        return keys

    def _aot_program(self, key):
        """(the jitted program of one key, its arguments as abstract
        params/cache/inputs of the LIVE engine's shapes): what ``_aot_lower``
        lowers, and what a test traces to hold a program's jaxpr."""
        sds = jax.ShapeDtypeStruct
        params_abs = jax.tree.map(lambda x: sds(x.shape, x.dtype), self.params)
        cache_abs = jax.tree.map(lambda x: sds(x.shape, x.dtype), self.cache)
        rng_abs = sds(self.rng.shape, self.rng.dtype)

        def batch_args(b, w):
            return (sds((b, w), jnp.int32), sds((b, ), jnp.int32),
                    sds((b, self.kv.table_width), jnp.int32),
                    sds((b, ), jnp.int32))

        if key[0] == "multi":
            _, b, k = key
            jitted = self._build_multi_jit(b, k)
            args = (params_abs, cache_abs, sds((b, ), jnp.int32)) + \
                batch_args(b, 1)[1:] + (rng_abs, )
        elif key[0] == "verify":
            _, b, w = key
            jitted = self._build_verify_jit(b, w)
            args = (params_abs, cache_abs) + batch_args(b, w)
        elif key[0] == "vit":
            _, p = key
            jitted = self._build_vit_jit(p)
            args = (params_abs, sds(self.mm_rows.shape, self.mm_rows.dtype),
                    sds((p, self.cfg.vision.patch_dim), self.cfg.dtype), sds((2, ), jnp.int32),
                    sds((p // self.cfg.vision.merge // self.mm_unit, ), jnp.int32))
        else:
            jitted = self._build_step_jit(key)
            slots = sum(rows * width for rows, width in key)
            args = (params_abs, cache_abs, sds((slots, ), jnp.int32)) + \
                batch_args(sum(rows for rows, _ in key), 1)[1:] + (rng_abs, )
            if self._takes_image_rows(key):
                args += (sds((slots, ), jnp.int32), sds(self.mm_rows.shape, self.mm_rows.dtype))
        return jitted, args

    def _aot_lower(self, key):
        """Lower one program key against abstract params/cache (the
        ``compile_aot_serving`` machinery, aimed at the LIVE engine's
        shapes): nothing executes, no engine state moves — unlike
        ``warm_verify``'s all-padding dispatches.  The Lowered's text is
        what an integrity check reads to see which kernels the step
        really contains (``chip_smoke.py``)."""
        jitted, args = self._aot_program(key)
        if self.mesh is None:
            return jitted.lower(*args)
        from ...comm.mesh import trace_mesh
        with self.mesh, trace_mesh(self.mesh):
            return jitted.lower(*args)

    def _aot_compile(self, key):
        """Compile one program key ahead of time; the returned Compiled is
        call-compatible with the lazily jitted version because both come
        from the same builder."""
        return self._aot_lower(key).compile()

    def warm_all(self) -> Dict[str, object]:
        """AOT-compile the full reachable step set (``step_shape_set``)
        into the program cache, so steady-state serving NEVER pays a
        trace+compile inside a dispatch — the ROADMAP's AOT serving-step
        item.  ``ServingEngine`` startup and ``ReplicaPool`` recovery
        call this before entering dispatch.

        Failure stance: an ``engine.aot_compile`` chaos injection (or a
        real compiler error) on one key falls back to the lazy JIT path
        for that key — the first dispatch compiles it synchronously,
        slower but never wrong, and NEVER a dead replica.  Only
        ``InjectedCrash`` (simulated process death) propagates.  Each
        pre-compiled key lands in the compile log as ``aot=True`` —
        deliberate warm-up, exempt from the steady-state-recompile
        guard."""
        from ...resilience import fault_injection as _fi
        anat = self.anatomy
        compiled = cached = fallback = 0
        keys = self.step_shape_set()
        for key in keys:
            if key in self._step_fns:
                cached += 1
                continue
            label = self._key_label(key)
            try:
                _fi.check("engine.aot_compile")
                fn = self._aot_compile(key)
            except _fi.InjectedCrash:
                raise
            except Exception as e:
                fallback += 1
                logger.warning(f"InferenceEngineV2: AOT compile of {label} failed "
                               f"({e}); falling back to lazy JIT on first dispatch")
                continue
            self._step_fns[key] = fn
            compiled += 1
            anat.note_compile(label, aot=True)
        if anat.enabled and compiled:
            # inside an open step window the compile time is attributed
            # explicitly; outside one, mark() is a no-op by design
            anat.mark("aot_compile")
        # the step set is compiled: a compile from here on is a steady-state recompile
        anat.mark_steady()
        return {"compiled": compiled, "cached": cached, "fallback": fallback,
                "keys": [self._key_label(k) for k in keys]}

    def warm_verify(self, batch_sizes: Sequence[int]) -> None:
        """Pre-compile the speculative verify program for the given raw
        batch sizes (bucketed, width pinned at ``max_draft + 1``) by
        running one ALL-PADDING dispatch per bucket: every row has
        chunk_len 0 and an all-null block table, so KV writes land in the
        null scratch page and engine state is untouched.  Serving
        harnesses call this next to their step-program warmup — drafting
        is history-dependent, so a short warm generation may never reach a
        verify round, and the first real one would otherwise pay a
        multi-second jit inside measured request latency.  No-op without a
        spec config."""
        if self.drafter is None:
            return
        width = self.econfig.spec.max_draft + 1
        for b in sorted({self._bucket_batch(n) for n in batch_sizes}):
            fn = self._compiled_verify(b, width)
            zeros = jnp.zeros((b, ), jnp.int32)
            _, self.cache = self._invoke(
                fn, self.params, self.cache, jnp.zeros((b, width), jnp.int32),
                zeros, jnp.zeros((b, self.kv.table_width), jnp.int32), zeros)

    def _plan_drafts(self, seqs) -> List[List[int]]:
        """Draft up to ``max_draft`` tokens per decode row, then shrink
        under pressure.  Per-row caps keep the verify dispatch feasible by
        construction: a draft never proposes past the row's ``max_new``
        limit (emitting ``accepted + 1`` tokens, only ``remaining - 1``
        drafts can ever be useful), the verify-slot width the scheduler
        charges (``spec_verify_tokens``), the position table, or its page
        capacity.  Aggregate demand self-shrinks the same way the fused
        rung does — halve every draft until the arena can take the round
        AND the round's total fed tokens (1 + draft per row) fit the
        SplitFuse ``token_budget`` — so the KV-pressure preflight's k=1
        guarantee still holds when every draft reaches zero."""
        spec = self.econfig.spec
        sched = self.econfig.scheduler
        width = min(spec.max_draft, sched.spec_verify_tokens or spec.max_draft)
        cap = min(self.kv.max_tokens_per_seq,
                  getattr(self.cfg, "max_position_embeddings", None) or (1 << 30))
        drafts: List[List[int]] = []
        for s in seqs:
            if not self._spec_on.get(s.uid, True):
                drafts.append([])
                continue
            limit = self._max_new.get(s.uid, self.econfig.max_new_tokens)
            room = min(width, limit - len(s.generated) - 1,
                       cap - len(s.tokens))
            drafts.append(self.drafter.draft(s.tokens, room) if room > 0 else [])
        while any(drafts) and (
                sum(1 + len(d) for d in drafts) > sched.token_budget or
                sum(self.kv.pages_needed(s, 1 + len(d)) for s, d in zip(seqs, drafts))
                > self.kv.allocator.free_pages):
            drafts = [d[:len(d) // 2] for d in drafts]
        return drafts

    def _dispatch_spec(self, seqs, drafts: List[List[int]]) -> InFlightStep:
        """Enqueue one draft-verify round for a pure-decode batch: feed
        ``[last_sampled, draft_0 .. draft_{d-1}]`` per row through the
        verify program.  The accept fold (``_complete_spec``) accepts the
        longest prefix of drafts matching the model's per-position argmax
        host-side, emits ``accepted + 1`` tokens (the argmax after the
        last accepted draft rides along as the bonus/correction token),
        and rolls rejected tokens' KV back via ``StateManager.truncate``.
        Greedy outputs are byte-identical to non-speculative decode by
        construction — every emitted token IS the model's argmax given
        the exact accepted history."""
        from ...resilience import fault_injection as _fi
        anat = self.anatomy
        width = self.econfig.spec.max_draft + 1
        batch = self._bucket_batch(len(seqs))
        base_len = [len(s.tokens) for s in seqs]
        # drafts ride in the token history for pack_groups() (sliced back out
        # in the fold — they are verify INPUTS, not accepted output)
        for s, d in zip(seqs, drafts):
            s.tokens.extend(d)
        try:
            rb: RaggedBatch = self.state.pack_groups([([(s, 1 + len(d)) for s, d in zip(seqs, drafts)], batch, width)])
            if anat.enabled:
                anat.mark("verify_plan")
            fn = self._compiled_verify(batch, width)
            if anat.enabled:
                # tokens_real (accepted + 1 a row) is known at the fold
                anat.note_program(self._key_label(("verify", batch, width)), "spec_verify",
                                  rows_decode=len(seqs), slots=batch * width)
            _fi.check("engine.verify_step")  # chaos site: device loss mid-verify
            argmax, self.cache = self._invoke(fn, self.params, self.cache,
                                              jnp.asarray(rb.tokens.reshape(batch, width)),
                                              jnp.asarray(rb.start_pos), jnp.asarray(rb.block_tables),
                                              jnp.asarray(rb.chunk_lens))
            if anat.enabled:
                anat.mark("compile_wait" if self._fresh_compile else "dispatch")
        except BaseException:
            # a failed verify dispatch must never bake unverified drafts
            # into the history: restore every row's token list so a caller
            # that survives the error (chaos drill, retry layer) decodes
            # from exactly the pre-round state.  seen_tokens/pages were not
            # advanced yet; extra pages pack_groups() allocated are plain capacity
            # the next round reuses.
            for s, L in zip(seqs, base_len):
                del s.tokens[L:]
            raise
        inf = InFlightStep("spec")
        inf.tokens = argmax
        inf.seqs = list(seqs)
        inf.drafts = drafts
        inf.base_len = base_len
        return inf

    def _complete_spec(self, inf: InFlightStep) -> Dict[int, List[int]]:
        anat = self.anatomy
        seqs, drafts, base_len = inf.seqs, inf.drafts, inf.base_len
        try:
            argmax = np.asarray(inf.tokens)
        except BaseException:
            # the deferred readback surfaced the device failure here (the
            # pipelined tick blocks at complete, not dispatch): the
            # unverified drafts are still spliced into every still-live
            # row's history — restore exactly as the dispatch-path
            # handler does before re-raising
            for s, L in zip(seqs, base_len):
                if self.state.seqs.get(s.uid) is s:
                    del s.tokens[L:]
            raise
        if anat.enabled:
            anat.device_mark()

        out: Dict[int, List[int]] = {}
        eos = self.econfig.eos_token_id
        self.spec_stats.rounds += 1
        for i, (s, d) in enumerate(zip(seqs, drafts)):
            if self.state.seqs.get(s.uid) is not s:
                continue  # flushed while in flight (pipelined tick)
            L = base_len[i]
            s.seen_tokens += 1 + len(d)
            # g[j] = the model's choice for history index L+j given the
            # prefix through index L-1+j; draft j (at index L+j) is
            # accepted iff it equals g[j]
            g = [int(t) for t in argmax[i, :1 + len(d)]]
            a = 0
            while a < len(d) and d[a] == g[a]:
                a += 1
            del s.tokens[L:]
            before = len(s.generated)
            limit = self._max_new.get(s.uid, self.econfig.max_new_tokens)
            for t in d[:a] + [g[a]]:
                s.tokens.append(int(t))
                s.generated.append(int(t))
                if len(s.generated) >= limit or (eos is not None and int(t) == eos):
                    s.done = True
                    break
            # rollback: rejected drafts' KV lies past the accepted
            # boundary — clamp seen_tokens and return wholly-surplus pages
            # to the arena THIS step (free capacity is visible to the next
            # preflight immediately, not at sequence death)
            freed = self.state.truncate(s, min(L + a, len(s.tokens)))
            self.state.note_progress(s)
            out[s.uid] = list(s.generated[before:])
            self.spec_stats.proposed += len(d)
            self.spec_stats.accepted += a
            self.spec_stats.emitted += len(out[s.uid])
            self.spec_stats.rollback_pages += freed
            self.last_spec_round[s.uid] = (len(d), a, freed)
        if anat.enabled:
            # real: accepted + 1 a live row (last_spec_round holds this round
            # only); discarded: rejected drafts and rows flushed in flight
            n_real = sum(a + 1 for _, a, _ in self.last_spec_round.values())
            verified = sum(1 + len(d) for d in drafts)      # the slots that carried a token through the program
            anat.note_tokens(sum(len(v) for v in out.values()), verified - n_real, real=n_real,
                             **self._expert_rows(n_real, argmax.size, live=verified))
            anat.mark("sample_accept")
        return out

    def _dispatch_multi(self, seqs, k: int) -> InFlightStep:
        """Enqueue ``k`` fused decode rounds for a pure-decode batch."""
        batch = self._bucket_batch(len(seqs))
        for s in seqs:
            # capacity for the WHOLE block up front; pack_groups()'s per-token
            # ensure_capacity then finds nothing left to allocate.  Capped
            # at the row's remaining max_new budget: a short-tail row keeps
            # at most `remaining` of the k tokens, and KV writes past its
            # reservation land in the null scratch page — reserving the
            # full k would over-allocate pages the row can never use
            remaining = self._max_new.get(s.uid, self.econfig.max_new_tokens) \
                - len(s.generated)
            self.kv.ensure_capacity(s, min(k, remaining))
        rb: RaggedBatch = self.state.pack_groups([([(s, 1) for s in seqs], batch, 1)])

        anat = self.anatomy
        self.rng, sub = jax.random.split(self.rng)
        fn = self._compiled_multi_step(batch, k)
        if anat.enabled:
            anat.note_program(self._key_label(("multi", batch, k)), "multi_decode",
                              rows_decode=len(seqs), tokens_real=len(seqs) * k, slots=batch * k)
        toks, self.cache = self._invoke(fn, self.params, self.cache, jnp.asarray(rb.tokens),
                                        jnp.asarray(rb.start_pos), jnp.asarray(rb.block_tables),
                                        jnp.asarray(rb.chunk_lens), sub)
        if anat.enabled:
            # the passes over the rows run with the program already enqueued
            groups = [([(s, k) for s in seqs], batch, 1)]
            anat.note_counts(**self._expert_rows(len(seqs) * k, batch, live=len(seqs)),
                             cache_counts=self._cache_counts(groups, calls=k),
                             state_counts={**self._state_counts(groups[0][0], calls=k),
                                           **self._decode_form_counts(groups, calls=k)})
            anat.mark("compile_wait" if self._fresh_compile else "dispatch")
        inf = InFlightStep("multi")
        inf.tokens = toks
        inf.seqs = list(seqs)
        inf.k = k
        return inf

    def _complete_multi(self, inf: InFlightStep) -> Dict[int, List[int]]:
        anat = self.anatomy
        toks = np.asarray(inf.tokens)
        if anat.enabled:
            anat.device_mark()

        out: Dict[int, List[int]] = {}
        eos = self.econfig.eos_token_id
        k = inf.k
        for i, s in enumerate(inf.seqs):
            if self.state.seqs.get(s.uid) is not s:
                continue  # flushed while in flight (pipelined tick)
            before = len(s.generated)
            s.seen_tokens += k
            limit = self._max_new.get(s.uid, self.econfig.max_new_tokens)
            for t in toks[i]:
                s.tokens.append(int(t))
                s.generated.append(int(t))
                if len(s.generated) >= limit or (eos is not None and int(t) == eos):
                    # surplus tokens computed past EOS/limit are discarded;
                    # truncate() clamps the seen boundary past them AND
                    # returns their wholly-surplus KV pages to the arena
                    # this step (visible to the next KV-pressure preflight
                    # immediately — not held until the sequence dies)
                    s.done = True
                    break
            self.state.truncate(s, len(s.tokens))
            self.state.note_progress(s)
            out[s.uid] = list(s.generated[before:])
        if anat.enabled:
            # the overshoot past a row's EOS or limit, and whole rows flushed in flight
            n_out = sum(len(v) for v in out.values())
            anat.note_tokens(n_out, len(inf.seqs) * k - n_out)
            anat.mark("sample_accept")
        return out

    def _pages(self):
        """The arena of pages in ``self.cache``, which the paged kernel reads."""
        from ...models.cache_zoo import cache_twin
        return cache_twin(self.cfg).pages(self.cache)

    def _walk_rows(self, width: int = 0) -> int:
        """Key rows a block of the paged kernel's walk holds, as the kernel
        chooses it for this engine's pages (a tensor-parallel shard's key
        heads) and a group of rows ``width`` tokens wide (1: the granule of
        the kernel's decode form); 0 where the twin's attention does not read
        through it."""
        shape = self._kernel_page_shape()
        if shape is None:
            return 0
        own_walk = self._own_walk()
        if own_walk is not None:  # a kernel of the twin's own over pages of another shape
            return own_walk(self.kv.page_size, self.kv.table_width)
        return self.kv.page_size * walk_block(self.kv.page_size, self.kv.table_width, *shape, chunk=width)

    def _own_walk(self):
        from ...models.cache_zoo import cache_twin
        return cache_twin(self.cfg).walk_rows

    def _kernel_page_shape(self):
        """(key heads of a tensor-parallel shard, lanes, bytes an element) of
        the pages the paged kernel reads; None where the twin's attention does
        not read through a kernel."""
        cfg = self.cfg
        if not reads_through_kernel(getattr(cfg, "attention_impl", None), getattr(cfg, "alibi", False)):
            return None
        from ...comm.mesh import TENSOR_AXIS
        pages = self._pages()
        *_, n_kv, d = pages.shape
        tp = 1 if self.mesh is None else self.mesh.shape.get(TENSOR_AXIS, 1)
        return n_kv // tp, d, pages.dtype.itemsize

    def _kernel_rows(self, work, calls: int = 1):
        """(first position, tokens) of each row a step's (seq, tokens) work
        takes: a run of chunks in a single step is a row a chunk
        (``pack_groups``), and each row walks the cache to its own end; the
        fused rung's tokens (``calls`` of one each) are one row's."""
        if calls > 1:
            return [(s.seen_tokens, n) for s, n in work]
        chunk = self.econfig.scheduler.prefill_chunk
        return [(s.seen_tokens + at, min(chunk, n - at)) for s, n in work for at in range(0, max(n, 1), chunk)]

    def _cache_counts(self, groups, calls: int = 1) -> tuple:
        """The geometry's ``step_counts`` summed over the rows of a step's row
        groups [(work, rows, width)] (``_kernel_rows`` of each group's (seq,
        tokens) work), each row's tokens going through the paged kernel in
        ``calls`` calls, by blocks of the rows its walk takes at a step for a
        group of that width (``walked`` is 0 where no kernel walks)."""
        visible = walked = 0
        geometry = self.kv.geometry
        # a window that bounds every layer's walk: a Llama twin's over the linear geometry (a slot-holding twin's
        # window layers keep rings, which the records count by other names)
        window = int(getattr(self.cfg, "sliding_window", 0) or 0) if type(geometry) is LinearGeometry else 0
        bounds = {"window": window} if window else {}
        for work, _, width in groups:
            block_rows = self._walk_rows(width)
            for start, n in self._kernel_rows(work, calls):
                seen, covered = geometry.step_counts(start, n, block_rows, calls, **bounds)
                visible, walked = visible + seen, walked + covered
        return visible, walked

    def _decode_form_counts(self, groups, calls: int = 1) -> dict:
        """The step records' counts of the rows that went through the paged
        kernel's decode form: the rows of the step's groups of one position a
        row, a call (``attn_decode_rows``: what the program holds, padding
        included; a twin may hand the kernel each as several rows, a group of
        key heads each), and those of them that carried a token
        (``attn_decode_rows_live``: the others cost the form nothing).  None
        where the kernel takes its general form for every group, or no kernel
        of this module walks."""
        shape = self._kernel_page_shape()
        if shape is None or self._own_walk() is not None:
            return {}
        ones = [(work, rows) for work, rows, width in groups if takes_decode_form(width, *shape)]
        return {"attn_decode_rows": calls * sum(rows for _, rows in ones),
                "attn_decode_rows_live": calls * sum(len(work) for work, _ in ones)} if ones else {}

    def _state_counts(self, work, calls: int = 1) -> dict:
        """The step records' named counts of a geometry that has some (state
        slots, latent pages): its ``state_counts`` summed over the step's rows."""
        geometry = self.kv.geometry
        if type(geometry).state_counts is LinearGeometry.state_counts:  # none to add
            return {}
        total = {}
        for start, n in self._kernel_rows(work, calls):
            for name, count in geometry.state_counts(start, n, calls).items():
                total[name] = total.get(name, 0) + count
        return total

    def _bucket_batch(self, n: int) -> int:
        q = self.econfig.scheduler.decode_bucket
        return min(self.state.max_batch, -(-n // q) * q)

    def _prefill_rungs(self) -> Tuple[int, ...]:
        """Rows a mixed step's prefill group may have: the smallest rung that
        holds the plan's prefill rows is taken.  One row is a rung of its own
        (long prompts arrive one at a time: the usual mixed step holds one
        prefilling prompt), ``max_seqs`` is what a plan can hold at most, and
        four between them takes a burst of arrivals without the full
        rectangle's slots (PERF.md section 6, PR 35).  Each rung is a program
        for ``warm_all`` to compile."""
        return tuple(sorted({min(p, self.state.max_batch) for p in (1, 4, self.state.max_batch)}))

    def _step_groups(self, plan: StepPlan):
        """A single step's layout: its row groups as ``pack_groups`` takes them,
        [(work, rows, width)].  Rows of one token each are one group at one
        token a row.  With a wider prefill row in the plan: the decode bucket
        at one token a row (all padding where nothing decodes: dead slots that
        save a program) and the prefill rows in a group of their own at the
        chunk."""
        decode = [(s, 1) for s in plan.decode]
        work = decode + list(plan.prefill)
        chunk = self.econfig.scheduler.prefill_chunk
        # a prompt's last token is a row of one token too, unless an image's row takes its place
        if all(n == 1 for _, n in work) and not any(self._image_slot(seq, n) for seq, n in plan.prefill):
            return [(work, self._bucket_batch(len(work)), 1)]
        rows = next(p for p in self._prefill_rungs() if p >= len(self._kernel_rows(plan.prefill)))
        return [(decode, self._bucket_batch(max(len(decode), 1)), 1), (list(plan.prefill), rows, chunk)]

    @staticmethod
    def _image_slot(seq, n: int) -> bool:
        """Whether one of the next ``n`` tokens of ``seq`` takes an image's row."""
        return seq.mm_index is not None and bool((seq.mm_index[seq.seen_tokens:seq.seen_tokens + n] >= 0).any())

    def step(self, plan: Optional[StepPlan] = None) -> Dict[int, List[int]]:
        """Run one scheduled step; returns {uid: [new tokens]} for
        sequences that produced tokens this call — one token per uid on
        the single-step path, up to ``decode_steps_per_dispatch`` on the
        fused decode path.  ``plan`` lets a caller that already planned
        (the serving frontend's KV-pressure preflight) skip the re-plan;
        it must have been computed against the CURRENT state.

        Composition of the async-capable halves: ``dispatch_step``
        enqueues the device program and ``complete_step`` blocks at the
        readback and folds tokens — called back-to-back here, the serial
        loop is byte-identical to the pre-split engine (same dispatch
        order, same rng splits, same fold), and the pipelined serving
        tick interleaves its own host work between the two."""
        inf = self.dispatch_step(plan)
        if inf is None:
            return {}
        return self.complete_step(inf)

    def dispatch_step(self, plan: Optional[StepPlan] = None) -> Optional[InFlightStep]:
        """Plan (unless given one) and ENQUEUE one step on the device,
        without blocking on its outputs: JAX async dispatch returns as
        soon as the program is in flight, so the caller owns the device
        window for overlapped host work.  Returns None when there is
        nothing to run (empty plan).

        With a :class:`~...telemetry.step_anatomy.StepAnatomy` attached
        (``set_anatomy``), this opens the step window (``step_begin`` is
        idempotent — a frontend that planned first opens it itself) and
        the window stays OPEN across the in-flight stretch; an empty or
        failed dispatch closes it here so no window ever leaks."""
        anat = self.anatomy
        self._fresh_compile = False
        if anat.enabled:
            anat.step_begin()
        inflight = None
        try:
            if plan is None:
                if self._image_rows:   # who plans here encodes here (a serving frontend does both itself)
                    self.encode_images()
                plan = self.scheduler.plan(self.state)
                if anat.enabled:
                    anat.mark("schedule")
            inflight = self._dispatch_inner(plan)
            return inflight
        finally:
            if inflight is None and anat.enabled:
                anat.step_end()

    def complete_step(self, inf: InFlightStep) -> Dict[int, List[int]]:
        """Block on the in-flight step's readback and fold its tokens
        into engine state — the sample/accept half of ``step``.  Rows
        whose sequence was flushed while the step was in flight (the
        pipelined tick's expire path) are skipped by object identity;
        their computed tokens are discarded whole, never half-applied.
        Closes the anatomy step window even when the readback raises."""
        anat = self.anatomy
        try:
            if inf.kind == "spec":
                return self._complete_spec(inf)
            if inf.kind == "multi":
                return self._complete_multi(inf)
            return self._complete_single(inf)
        finally:
            if anat.enabled:
                anat.step_end()

    def _dispatch_inner(self, plan: StepPlan) -> Optional[InFlightStep]:
        anat = self.anatomy
        # per-step spec accounting: entries describe THIS step's verify
        # round only (the serving frontend reads them right after the
        # step's completion)
        self.last_spec_round.clear()
        if self.drafter is not None and plan.decode and not plan.prefill:
            # speculation outranks the fused rung on pure-decode rounds: a
            # round with any non-empty draft emits accepted+1 tokens per
            # drafting row for ONE dispatch.  When no row drafts (cold
            # history, per-request opt-out, page pressure shrank every
            # draft to zero) fall through to the fused/single-step rungs —
            # a drained-draft round must still make k=1 progress.
            drafts = self._plan_drafts(plan.decode)
            if anat.enabled:
                anat.mark("draft_plan")
            if any(drafts):
                return self._dispatch_spec(plan.decode, drafts)
        k_cfg = self.econfig.decode_steps_per_dispatch
        if k_cfg > 1 and plan.decode and not plan.prefill:
            # OVERSHOOT policy (r4): always run the full k rung and discard
            # surplus tokens host-side (the KV written past a row's limit
            # lies beyond its clamped seen boundary).  The pre-r4 halving
            # ladder (k, k/2, ... 1) matched `remaining` exactly but paid
            # the ~100-300ms fixed dispatch overhead per rung and compiled
            # a fresh single-step program for 1-token tails mid-serve —
            # 64 tokens cost 6 dispatches instead of 2.  k only shrinks
            # when the page arena, per-seq page capacity, or the position
            # table can't take the full block.
            max_pos = getattr(self.cfg, "max_position_embeddings", None) or (1 << 30)
            seq_room = min(min(self.kv.max_tokens_per_seq, max_pos) -
                           len(s.tokens) for s in plan.decode)
            k = k_cfg
            while k > 1 and (seq_room < k or sum(self.kv.pages_needed(s, k) for s in plan.decode)
                             > self.kv.allocator.free_pages):
                k //= 2
            if k > 1:
                return self._dispatch_multi(plan.decode, k)
        work: List = [(s, 1) for s in plan.decode] + list(plan.prefill)
        if not work:
            return None
        packed = self._step_groups(plan)
        groups = tuple((rows, width) for _, rows, width in packed)
        image_rows = self._takes_image_rows(groups)
        rb: RaggedBatch = self.state.pack_groups(packed, mm=image_rows)

        self.rng, sub = jax.random.split(self.rng)
        fn = self._compiled_step(groups)
        if anat.enabled:
            path = ("mixed" if plan.prefill and plan.decode
                    else "prefill" if plan.prefill else "decode")
            tokens_real = plan.planned_tokens
            anat.note_program(self._key_label(groups), path,
                              rows_decode=len(plan.decode), rows_prefill=len(self._kernel_rows(plan.prefill)),
                              seqs_prefill=len(plan.prefill), tokens_real=tokens_real, slots=rb.tokens.size)
        image_args = (jnp.asarray(rb.mm_index), self.mm_rows) if image_rows else ()
        next_tok, self.cache = self._invoke(fn, self.params, self.cache, jnp.asarray(rb.tokens),
                                            jnp.asarray(rb.start_pos), jnp.asarray(rb.block_tables),
                                            jnp.asarray(rb.chunk_lens), sub, *image_args)
        if anat.enabled:
            # the passes over the rows run with the program already enqueued
            state_counts = {**self._state_counts(work), **self._decode_form_counts(packed)}
            if image_rows:
                state_counts["mm_tokens"] = int((rb.mm_index >= 0).sum())
            anat.note_counts(**self._expert_rows(tokens_real, rb.tokens.size),
                             cache_counts=self._cache_counts(packed), state_counts=state_counts)
            anat.mark("compile_wait" if self._fresh_compile else "dispatch")
        inf = InFlightStep("single")
        inf.tokens = next_tok
        inf.rows = []
        for i, uid in enumerate(rb.uids):
            if uid < 0:
                continue
            n = int(rb.chunk_lens[i])
            if inf.rows and inf.rows[-1][0] == uid:   # a run's next row: the run is folded as one, at its last row
                n += inf.rows.pop()[1]
            inf.rows.append((int(uid), n, self.state.seqs[uid], i))
        return inf

    def _complete_single(self, inf: InFlightStep) -> Dict[int, List[int]]:
        anat = self.anatomy
        next_tok = np.asarray(inf.tokens)
        if anat.enabled:
            anat.device_mark()

        out: Dict[int, List[int]] = {}
        for uid, n, seq, i in inf.rows:
            if self.state.seqs.get(uid) is not seq:
                continue  # flushed while in flight (pipelined tick)
            seq.seen_tokens += n
            self.state.note_progress(seq)
            if seq.images:
                self._release_images(seq, passed_only=True)
            if seq.in_prefill:
                continue  # mid-prompt chunk: logits not used
            tok = int(next_tok[i])
            seq.tokens.append(tok)
            seq.generated.append(tok)
            out[uid] = [tok]
            eos = self.econfig.eos_token_id
            if len(seq.generated) >= self._max_new.get(uid, self.econfig.max_new_tokens) or \
                    (eos is not None and tok == eos):
                seq.done = True
        if anat.enabled:
            anat.note_tokens(len(out))
            anat.mark("sample_accept")
        return out

    # ----------------------------------------------------------- generate

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Optional[int] = None) -> List[List[int]]:
        """Synchronous convenience: admit all prompts, run steps to
        completion, return generated token lists in order."""
        uids = list(range(len(prompts)))
        base = max(self.state.seqs.keys(), default=-1) + 1
        uids = [base + u for u in uids]
        self.put(uids, prompts, max_new_tokens=max_new_tokens)
        pending = set(uids)
        while pending:
            before = sum(s.seen_tokens + len(s.generated) for s in self.state.seqs.values())
            self.step()
            after = sum(s.seen_tokens + len(s.generated) for s in self.state.seqs.values())
            if after == before:
                raise RuntimeError("generation step made no progress "
                                   "(token budget / batch capacity exhausted?)")
            for u in list(pending):
                if self.state.seqs[u].done:
                    pending.discard(u)
        outs = [list(self.state.seqs[u].generated) for u in uids]
        for u in uids:
            self.flush(u)
        return outs


def build_engine(cfg, params, engine_config: RaggedInferenceEngineConfig = None,
                 mesh=None):
    """Factory (ref: inference/v2/engine_factory.py:69 build_hf_engine —
    there it loads an HF checkpoint; here weights come from the training
    engine or a checkpoint restore, already in the shared param layout)."""
    return InferenceEngineV2(cfg, params, engine_config, mesh=mesh)
