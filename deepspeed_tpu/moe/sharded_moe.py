"""Mixture-of-Experts core: gating, and the two ways from tokens to experts.

Reference: ``deepspeed/moe/sharded_moe.py`` — ``TopKGate:449`` (top1/top2/topk
gating at ``:183,290,374``), ``MOELayer:533`` with all-to-all dispatch
(``_AllToAll:96``) to local ``Experts``.

Tokens are grouped by their data shard ([G, S, d], G sharded over the batch
axes), and a group is routed by one of two paths.  ``MoE.__call__`` picks
between them from the layer's ``drop_tokens`` flag and the mesh it can see,
and there is no other knob:

* **Capacity dispatch** (``top1_gating`` / ``topk_gating`` →
  ``dispatch_combine``), GShard-style and compiler-scheduled: gating makes
  per-group dispatch/combine tensors [S, E, C], the dispatch einsum makes
  [G, E, C, d], which is resharding-constrained from group-sharded to
  expert-sharded — GSPMD lowers that to the all-to-all the reference issues
  explicitly.  ``capacity = ceil(k * S / E * capacity_factor)``, clamped to
  ``min_capacity``; tokens beyond it are dropped.  Taken when
  ``drop_tokens=True`` (dropping is a different result, so a path of its
  own), and when ``drop_tokens=False`` on an ``expert`` mesh axis larger
  than 1, with capacity = S so that nothing is dropped: every expert then
  multiplies S rows, E/k times what the routing needs, but the exchange
  stays the one GSPMD knows (a ragged all-to-all is not built yet).

* **Dropless sorted dispatch** (``dropless_moe``), taken when
  ``drop_tokens=False`` and the experts are on one shard (every served
  Mixtral, its training twin when asked) and always by
  ``models/qwen2_moe.Qwen2MoeSparseMLP``, training and served, whose bank
  ZeRO-3 or an ``expert`` axis gathers whole a layer (the published model
  drops no token, and does not renormalise its k gate values:
  ``normalize``): the [S, k] choices are flattened and
  stable-sorted by expert id, the rows gathered, and the three products of
  the bank run over the ragged groups (``ops/grouped_matmul.grouped_matmul``:
  the Pallas kernel ``ds_gmm`` on a TPU where the call is one device's, forward
  and backward, ``jax.lax.ragged_dot`` on the CPU and under a mesh the
  compiler partitions), so the experts multiply k rows a live token and no
  [S, E, C] tensor exists.
  Rows a token mask removes (the padding of a serving step) sort behind the
  last group, are in no group, cost no product and come out as exact zeros.
  Which form a group takes is ``takes_sorted``'s rule of the rows, the
  choices a row and the router's experts: every expert multiplies every row
  instead (``_experts_dense``) where the rows are few enough to be bound by
  the weights' read and still choose nearly every expert (16 rows of 2 over
  8): there the bank's read is the whole cost either way, and the sort and
  the grouped kernel's fixed cost are not paid.  Where they cannot touch most
  of the bank (12 rows of 4 over 64) the sorted form reads the chosen experts
  alone, and a padding row costs nothing.  The rule is asked of the group's
  slots when the program is traced; where they say "dense" and a token mask
  says which slots live, both forms are compiled and the program asks the
  same rule of the live rows each time it runs (``sorted_up_to``: a decode
  bucket of 16 with three rows live reads the experts those three chose).
  That second lowering is held where the live rows can be under the bound as
  often as not (``live_rows_sorted``: 16 slots against 11 rows, not 144), and
  its dense branch holds the bank to the layout it lies in, or XLA would copy
  the stack for it.
  A scanned trunk may hand the layer the banks of all its layers, still
  stacked, and the layer's index (``layer``): the grouped product is a
  custom call on the TPU and would copy the slice the scan gives it.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.mesh import BATCH_AXES, EXPERT_AXIS, axis_size, get_global_mesh, get_trace_mesh, in_manual_mesh
from ..ops.grouped_matmul import grouped_matmul


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int, k: int) -> int:
    """ref: sharded_moe.py _capacity — ceil(k*S/E * factor), >= min_capacity."""
    cap = int(np.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top1_gating(logits,
                capacity: int,
                noisy_gate_policy: Optional[str] = None,
                rng=None,
                used_token_mask=None):
    """Top-1 gating (ref: sharded_moe.py:183 top1gating).

    logits: [S, E] per group.  Returns (l_aux, combine [S,E,C], dispatch
    [S,E,C] bool, exp_counts [E]).
    """
    s, e = logits.shape
    if noisy_gate_policy == "RSample" and rng is not None:
        noisy = logits + jax.random.gumbel(rng, logits.shape)
    else:
        noisy = logits
    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(noisy, axis=-1)  # [S]
    mask1 = _one_hot(idx1, e)  # [S, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # aux load-balancing loss (ref: l_aux = E * sum(me * ce))
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    locations1 = jnp.cumsum(mask1, axis=0) - mask1  # position within expert
    pos_in_expert = jnp.sum(locations1 * mask1, axis=-1)  # [S]
    keep = pos_in_expert < capacity
    mask1 = mask1 * keep[:, None]
    gate_val = jnp.sum(gates * mask1, axis=-1)  # [S], 0 for dropped

    loc_onehot = _one_hot(pos_in_expert.astype(jnp.int32), capacity) * keep[:, None]
    combine = gate_val[:, None, None] * mask1[:, :, None] * loc_onehot[:, None, :]
    dispatch = combine > 0
    exp_counts = jnp.sum(mask1, axis=0)
    return l_aux, combine, dispatch, exp_counts


def topk_gating(logits, k: int, capacity: int, drop_tokens: bool = True, normalize: bool = True):
    """Generic top-k gating (covers top2gating :290 and topkgating :374).

    Selection priority is expert-local arrival order after flattening the k
    choices (k-major), matching the reference's cumsum-over-(k*S) ordering.
    """
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # [S, k]
    if normalize:
        denom = jnp.sum(topk_vals, axis=-1, keepdims=True)
        topk_vals = topk_vals / jnp.maximum(denom, 1e-9)

    # masks per choice: [k, S, E]
    masks = _one_hot(topk_idx.transpose(1, 0), e)  # [k, S, E]

    # aux loss uses the top-1 mask (ref top2gating: mask1)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    l_aux = jnp.sum(me * ce) * e

    # order: choice-major flatten so 1st choices win capacity first
    flat = masks.reshape(k * s, e)
    locations = jnp.cumsum(flat, axis=0) - flat  # [k*S, E]
    pos = jnp.sum(locations * flat, axis=-1).reshape(k, s)
    keep = pos < capacity if drop_tokens else jnp.ones_like(pos, dtype=bool)

    combine = jnp.zeros((s, e, capacity), jnp.float32)
    for i in range(k):
        loc_onehot = _one_hot(pos[i].astype(jnp.int32), capacity) * keep[i][:, None]
        combine = combine + topk_vals[:, i][:, None, None] * masks[i][:, :, None] * loc_onehot[:, None, :]
    dispatch = combine > 0
    exp_counts = jnp.sum(masks.sum(0), axis=0)
    return l_aux, combine, dispatch, exp_counts


def dispatch_combine(x_grouped, combine, dispatch, expert_fn):
    """Dispatch → expert compute → combine, with GSPMD all-to-all.

    x_grouped: [G, S, d]; combine/dispatch: [G, S, E, C].
    expert_fn: [G?, E, C, d] → [E, C, d]-shaped output per group stack —
    called with dispatched [G, E, C, d].
    """
    mesh = get_global_mesh()
    has_ep = mesh.shape.get(EXPERT_AXIS, 1) > 1
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..comm.mesh import DATA_AXIS

    dispatched = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x_grouped.dtype), x_grouped)
    if has_ep:
        # groups go from (data, expert)-sharded to data-sharded while the
        # expert dim picks up the expert axis: GSPMD lowers this resharding
        # to the dispatch all-to-all (ref: _AllToAll sharded_moe.py:96)
        g = x_grouped.shape[0]
        dsize = mesh.shape.get(DATA_AXIS, 1)
        g_axis = DATA_AXIS if (dsize > 1 and g % dsize == 0) else None
        ep_sh = NamedSharding(mesh, P(g_axis, EXPERT_AXIS, None, None))
        dispatched = jax.lax.with_sharding_constraint(dispatched, ep_sh)
    expert_out = expert_fn(dispatched)  # [G, E, C, d_out]
    if has_ep:
        expert_out = jax.lax.with_sharding_constraint(expert_out, ep_sh)
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(expert_out.dtype), expert_out)
    return out


#: Rows up to which an expert's product is bound by the read of its weights:
#: over r rows it does 2*r*d*f operations on the 2*d*f bytes of its bf16
#: weights, r operations a byte, under the chip's operations a byte (197e12 /
#: 819e9 = 240 on a v5e).  Beyond it every expert over every row is E/k times
#: the work, and the group takes the sorted form whatever it touches.
DENSE_UP_TO_TOKENS = 256
#: Share of the bank from which the dense form may read all of it.  With
#: every row live the sorted form (the sort, the gathers, three custom calls a
#: layer, each expert's block read once) ties the dense one where the rows
#: touch 0.95 of the bank and loses 2-12% where they touch all of it; under
#: that it wins by the experts it does not read, and a padding row costs it
#: nothing (the sweep on the chip: PERF.md section 5, PR 47).
DENSE_FROM_BANK_SHARE = 0.95


def takes_sorted(s: int, k: int, e: int) -> bool:
    """Whether ``s`` rows, ``k`` choices a row over a router of ``e`` experts,
    take the sorted form: above ``DENSE_UP_TO_TOKENS`` rows, and wherever the
    rows cannot touch most of the bank.  ``s * k`` choices spread evenly touch
    ``1 - (1 - 1/e)^(s k)`` of the experts, the held ones of a share like all
    of them (16 rows of 2 over 8: 0.99, dense; 11 of them: 0.947; 12 rows of
    4 over 64: 0.53; 32 rows of 8 over 320: 0.55).  The one rule, asked of two
    numbers: of a group's slots, every one counted live, where
    ``dropless_moe`` is traced, and, where the slots say "dense", of the rows
    the token mask leaves live where the program runs (``live_rows_sorted``).
    The engine's step records ask it of both as well
    (``engine_v2._expert_rows``)."""
    return s > DENSE_UP_TO_TOKENS or 1.0 - (1.0 - 1.0 / e)**(s * k) < DENSE_FROM_BANK_SHARE


def sorted_up_to(k: int, e: int) -> int:
    """The most rows under ``DENSE_UP_TO_TOKENS`` of which ``takes_sorted``
    says "sorted" (it is monotone there): 11 rows of 2 over 8, 47 of 4 over
    64, 31 of 6 over 64, 119 of 8 over 320.  What a program that holds both
    forms compares its live rows with; 0 where no row count is sorted."""
    s = 0
    while s < DENSE_UP_TO_TOKENS and takes_sorted(s + 1, k, e):
        s += 1
    return s


def live_rows_sorted(s: int, k: int, e: int) -> int:
    """For a group of ``s`` slots that ``takes_sorted`` calls dense and that
    comes with a token mask: the live rows up to which its program takes the
    sorted form all the same, both forms compiled (``sorted_up_to``), or 0
    where it holds the dense form alone: where the sorted form would be taken
    for fewer than half of the live counts the group can have.  A second
    lowering costs every such program's set-up (0.4-0.9 s each on the chip's
    host, PERF.md section 6, PR 48), a decode bucket of 16 slots is under its
    11 rows most of the time, and a step of 144 slots only in a prompt's last
    chunk.  ``dropless_moe`` compares its live tokens with this number where
    it runs, and the engine's step records theirs (``engine_v2._expert_rows``)."""
    rows = sorted_up_to(k, e)
    return rows if 2 * rows >= s else 0


def _experts_dense_in_place(x, top_vals, expert, group_sizes, bank, layer):
    """``_experts_dense`` as a branch of a conditional, with the bank held to
    the layout it lies in: XLA gives a branch's operands the layout its
    products like best, and would copy a whole stack of banks a layer to hand
    the dense form its matrices transposed (2.6 GB at Xing4's widths, 2.8 at
    Mixtral's) while the grouped kernel beside it reads them as they are."""
    from jax.experimental.layout import Layout, with_layout_constraint
    bank = tuple(with_layout_constraint(w, Layout(major_to_minor=tuple(range(w.ndim)))) for w in bank)
    return _experts_dense(x, top_vals, expert, group_sizes, bank, layer)


def _experts_grouped(x, top_vals, expert, group_sizes, bank, layer):
    """Sort the [S, k] choices by expert, multiply by ragged groups, un-sort,
    weight and add.  Dead rows (expert id E) sort behind the last group."""
    s, k = expert.shape
    e = group_sizes.shape[0]
    if layer is not None:
        # the banks of all L layers, read in place as L*E groups of which this
        # layer's E have rows: the grouped product is a custom call on the
        # TPU, and a custom call reads no slice of a stack without a copy
        n = bank[0].shape[0] * e
        bank = tuple(w.reshape((n, ) + w.shape[2:]) for w in bank)
        group_sizes = jax.lax.dynamic_update_slice(jnp.zeros((n, ), jnp.int32), group_sizes, (layer * e, ))
    w_gate, w_up, w_down = bank
    # flat row i is choice i % k of token i // k
    order = jnp.argsort(expert.reshape(s * k), stable=True)
    rows = jnp.take(x, order // k, axis=0)  # [S*k, d], sorted by expert
    h = jax.nn.silu(grouped_matmul(rows, w_gate, group_sizes)) * grouped_matmul(rows, w_up, group_sizes)
    y = grouped_matmul(h, w_down, group_sizes).astype(jnp.float32)
    # what the grouped product leaves beyond the groups' sum is not defined on the TPU
    y = jnp.where((jnp.arange(s * k) < jnp.sum(group_sizes))[:, None], y, 0.0)
    back = jnp.zeros((s * k, ), order.dtype).at[order].set(jnp.arange(s * k, dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(s, k, -1)
    return jnp.sum(y * top_vals[:, :, None], axis=1)


def _experts_dense(x, top_vals, expert, group_sizes, bank, layer):
    """Every expert multiplies every row; a row's k choices are picked by
    the weights [S, E], zero elsewhere and on dead rows (expert id E).  The
    products lie under the scope ``ds_experts_dense``, the dense form's name
    beside the grouped kernel's ``ds_gmm`` (docs/OBSERVABILITY.md "The
    experts' products in a device trace")."""
    e = group_sizes.shape[0]
    with jax.named_scope("ds_experts_dense"):
        if layer is not None:  # an einsum reads its slice of the stack in place
            bank = tuple(jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False) for w in bank)
        w_gate, w_up, w_down = bank
        h = jax.nn.silu(jnp.einsum("sd,edf->esf", x, w_gate)) * jnp.einsum("sd,edf->esf", x, w_up)
        y = jnp.einsum("esf,efd->esd", h, w_down).astype(jnp.float32)
        weights = jnp.sum(_one_hot(expert, e) * top_vals[:, :, None], axis=1)
        return jnp.einsum("se,esd->sd", weights, y)


def dropless_moe(x, logits, bank, k: int, token_mask=None, noise=None, layer=None, normalize: bool = True,
                 scoring: str = "softmax", select_bias=None, route_scale: float = 1.0, held=None):
    """Route one group with no capacity: every live token through its k
    highest experts.

    x: [S, d] in the compute dtype; logits: [S, E] float32; bank: the
    experts' (w_gate, w_up [E, d, f], w_down [E, f, d]) in the compute
    dtype — or, with ``layer`` (an index, traced or not), the banks of a
    whole scanned trunk [L, E, ...], of which layer ``layer``'s are read in
    place; token_mask: [S] bool or None — rows that carry no token go to no
    expert and come out as zeros, and where the slots alone would take the
    dense form the rows it leaves live choose the form as the program runs
    (``takes_sorted``); noise: [S, E] or None, added to the logits for the
    choice only (``top1_gating``'s RSample).

    Three router forms, all in float32, chosen by what the model publishes:

    * softmax over all experts, the k largest, renormalised over the k
      (``scoring="softmax"``, the default; Mixtral, and the capacity path's
      gate values) -- or not renormalised, where the model publishes
      ``norm_topk_prob: false`` (``normalize=False``: Qwen2-MoE);
    * sigmoid scores, one an expert (``scoring="sigmoid"``: the published
      ``scoring_func``), the k largest, ``w = s / (sum + 1e-20)`` over the k
      where ``norm_topk_prob`` is true;
    * either with a learned **selection bias** [E] added to the scores for
      the choice alone (``select_bias``: ``topk_method: "noaux_tc"``'s
      ``e_score_correction_bias``): the weights are the unbiased scores of
      the experts so chosen.

    ``route_scale`` (``routed_scaling_factor``) multiplies the k weights
    after the renormalisation.  A token's k outputs are weighted and added in
    float32.  Returns (out [S, d] float32, l_aux, exp_counts [E] int32).

    ``held = (first, count)``: the bank holds experts ``first .. first +
    count - 1`` of the router's ``E`` alone, [count, ...] (one chip's share
    of a layer whose experts are divided over several).  The router keeps its
    ``E`` outputs and its k a token, and the weights are renormalised over
    all k chosen, as published; the choices that fall on an expert held
    elsewhere become dead rows (they go to no expert, as a padding row's do),
    so ``out`` is this share's part of the layer's sum and the shares of all
    the chips add up to the uncut layer's.  ``exp_counts`` [count] counts the
    held experts' rows.  No exchange and nothing in the absent experts' place.
    """
    s, e = logits.shape
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router scoring {scoring!r}: softmax or sigmoid")
    gates = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    if noise is None and select_bias is None:
        top_vals, top_idx = jax.lax.top_k(gates, k)  # [S, k]
    else:
        choice = gates if noise is None else logits + noise
        if select_bias is not None:
            choice = choice + select_bias.astype(jnp.float32)
        _, top_idx = jax.lax.top_k(choice, k)
        top_vals = jnp.take_along_axis(gates, top_idx, axis=-1)
    if normalize and k > 1:
        total = jnp.sum(top_vals, axis=-1, keepdims=True)
        top_vals = top_vals / (jnp.maximum(total, 1e-9) if scoring == "softmax" else total + 1e-20)
    if route_scale != 1.0:
        top_vals = top_vals * route_scale
    live = jnp.ones((s, ), bool) if token_mask is None else token_mask

    # aux load-balancing loss on the top-1 mask (ref: l_aux = E * sum(me * ce))
    mask1 = _one_hot(top_idx[:, 0], e) * live[:, None]
    l_aux = jnp.sum(jnp.mean(gates, axis=0) * jnp.mean(mask1, axis=0)) * e
    if held is not None:
        first, e = held
        if bank[0].shape[-3] != e:
            raise ValueError(f"held={held}: the bank holds {bank[0].shape[-3]} experts, not {e}")
        top_idx = top_idx - first      # the bank's own numbering; outside [0, e): an expert held elsewhere
        live = live[:, None] & (top_idx >= 0) & (top_idx < e)
    else:
        live = live[:, None]
    expert = jnp.where(live, top_idx, e)  # [S, k]; a dead row goes to expert id E: to none
    group_sizes = jnp.bincount(expert.reshape(-1), length=e + 1)[:e].astype(jnp.int32)

    args = (x, top_vals, expert, group_sizes, bank, layer)
    if takes_sorted(s, k, logits.shape[1]):
        out = _experts_grouped(*args)
    else:
        # the slots say "dense".  With a mask both forms go into the program and it asks the rule of the live tokens
        # where it runs, as the step records do (of the tokens, not of the rows that fall on a held expert: the
        # held ones of a share are touched like all of them); with none every slot lives
        live_up_to = 0 if token_mask is None else live_rows_sorted(s, k, logits.shape[1])
        if live_up_to:
            out = jax.lax.cond(jnp.sum(token_mask) <= live_up_to, _experts_grouped, _experts_dense_in_place, *args)
        else:
            out = _experts_dense(*args)
    return out, l_aux, group_sizes


def dropless_dispatch(x, logits, bank, k: int, token_mask=None, noise=None, layer=None, normalize: bool = True,
                      scoring: str = "softmax", select_bias=None, route_scale: float = 1.0, held=None):
    """``dropless_moe`` over a batch [B, S, ...]: one group a data shard.

    Without capacity a token's output does not depend on its group, so the
    groups are there for the sort alone: across the data axes it would gather
    every token.  Under a governing mesh (``trace_mesh``) whose batch axes
    divide B the batch is routed shard by shard inside ``shard_map``; else
    (one device, a tensor-only mesh, already inside a manual mesh) as one
    group.  ``l_aux`` is the mean over the shards, ``exp_counts`` their sum.

    The bank enters replicated: where ZeRO-3 partitions it that is the
    all-gather a dense product would need too, of the compute dtype the
    caller cast it to, and its cotangent, a psum inside the manual region and
    a slice outside, leaves a TPU as one fused reduce-scatter.  ``scoring``,
    ``select_bias`` (replicated, closed over) and ``route_scale`` are
    ``dropless_moe``'s router forms.
    """
    from jax.sharding import PartitionSpec as P

    def one_group(x, logits, token_mask, noise, bank, layer):
        flat = lambda a: None if a is None else a.reshape((-1, ) + a.shape[2:])
        out, l_aux, counts = dropless_moe(flat(x), flat(logits), bank, k, flat(token_mask), flat(noise), layer,
                                          normalize, scoring, select_bias, route_scale, held)
        return out.reshape(x.shape[:2] + out.shape[1:]), l_aux, counts

    mesh = get_trace_mesh()
    batch_axes = () if mesh is None or in_manual_mesh() else tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if not batch_axes or x.shape[0] % axis_size(mesh, *batch_axes):
        return one_group(x, logits, token_mask, noise, bank, layer)

    def one_shard(*args):
        out, l_aux, counts = one_group(*args)
        return out, jax.lax.pmean(l_aux, batch_axes), jax.lax.psum(counts, batch_axes)

    rows = P(batch_axes)
    # manual over the whole mesh where its other axes are trivial: under a
    # partly manual mesh XLA's CPU compiler aborts on the bfloat16 psum that
    # the replicated bank's cotangent is
    manual = mesh.axis_names if mesh.size == axis_size(mesh, *batch_axes) else batch_axes
    return jax.shard_map(one_shard, mesh=mesh, in_specs=(rows, rows, rows, rows, P(), P()), out_specs=(rows, P(), P()),
                         axis_names=set(manual), check_vma=False)(x, logits, token_mask, noise, bank, layer)
