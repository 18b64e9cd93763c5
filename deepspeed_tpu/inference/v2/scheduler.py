"""Dynamic SplitFuse continuous-batching scheduler.

Reference: FastGen's scheduling policy (``deepspeed/inference/v2/engine_v2.py
put()`` + the SplitFuse description in ``blogs/deepspeed-fastgen``): each
engine step runs a *fixed token budget*, filled by (a) every running decode
sequence (1 token each) and (b) chunks of pending prefills — long prompts
are split across steps, short ones fused, keeping step latency flat.

Here the budget additionally quantises to a few chunk-size buckets so XLA
reuses a handful of compiled programs (TPU static shapes) instead of
recompiling per ragged shape — the scheduling *policy* is the reference's,
the *shapes* are TPU-friendly.
"""

import dataclasses
from typing import List, Tuple

from .ragged import SequenceDescriptor, StateManager


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    token_budget: int = 512            # ref: max ragged batch token count
    max_seqs: int = 64                 # ref: max ragged sequence count
    prefill_chunk: int = 128           # SplitFuse chunk quantum
    decode_bucket: int = 8             # decode batch rounds up to a multiple
    # speculative decoding (engine_v2 sets this from SpecConfig.max_draft):
    # the verify-slot width a speculating decode row may grow to.  Verify
    # rounds run ONLY on pure-decode steps (no prefill planned), so plan()
    # keeps charging mixed steps 1 token per bucketed decode row — charging
    # 1+k there would throttle prefill for verify work that cannot happen.
    # The budget is enforced where verify slots are actually planned:
    # engine_v2._plan_drafts caps each row's draft at this width and
    # shrinks the round until its total fed tokens (1 + draft per row) fit
    # token_budget.
    spec_verify_tokens: int = 0
    # a model with a vision tower (models/kimi_vl.py): the buckets of patches
    # an image is padded to (one encode program each), the padded patches the
    # encode dispatches of one serving tick may carry (0: no bound; a tick's
    # decode rows wait behind them), and the rows of image embeddings the
    # engine's buffer holds for the sequences whose prefill has not passed them
    vision_patch_buckets: Tuple[int, ...] = ()
    vision_patches_per_tick: int = 0
    vision_rows: int = 0

    def __post_init__(self):
        object.__setattr__(self, "vision_patch_buckets", tuple(sorted(int(b) for b in self.vision_patch_buckets)))


@dataclasses.dataclass
class StepPlan:
    """One engine step = one decode batch + one batch of prefill work: a
    chunk a prefilling sequence, or a run of consecutive chunks of it
    (``n_tokens`` over ``prefill_chunk``: ``SplitFuseScheduler.run_rows``)."""
    decode: List[SequenceDescriptor]
    prefill: List[Tuple[SequenceDescriptor, int]]   # (seq, n_tokens)

    @property
    def planned_tokens(self) -> int:
        """Real tokens this step will feed: one per decode row plus the
        prefill chunk tokens — the serving ``step_cost`` model's input
        and the step-anatomy row's token-volume attribution (one
        definition, two consumers, no drift)."""
        return len(self.decode) + sum(n for _, n in self.prefill)


class SplitFuseScheduler:

    def __init__(self, config: SchedulerConfig):
        self.config = config
        # optional ordering hook (the serving frontend installs FCFS-with-
        # aging here): ``order_key(seq) -> sortable``, lowest served first.
        # None keeps dict-insertion (put) order — the historical behaviour
        # for direct engine users.
        self.order_key = None
        # rows of ``prefill_chunk`` a step's prefill work may take where a
        # sequence's consecutive chunks may be rows of one step (a run): the
        # engine sets it to the rung of prefill rows its step programs have
        # for a burst (``InferenceEngineV2._prefill_rungs``).  1: a chunk a
        # sequence and no more, whatever the budget has left.
        self.run_rows = 1

    def plan(self, manager: StateManager) -> StepPlan:
        cfg = self.config
        # paused sequences (mid-KV-migration — serving/kvtransfer) keep
        # their state and pages but take no step work: their pages must stay
        # byte-stable while export chunks overlap the other sequences' steps
        # nor does a sequence whose images the tower has yet to encode
        running = [s for s in manager.seqs.values() if not s.done and not s.paused and not s.images_pending]
        if self.order_key is not None:
            running.sort(key=self.order_key)
        decodes = [s for s in running if s.in_decode]
        prefills = [s for s in running if s.in_prefill and not s.in_decode]

        decodes = decodes[:cfg.max_seqs]
        # TOKEN BUDGET charges the BUCKETED decode count: the compiled step
        # pads the batch to a decode_bucket multiple, and the padded rows
        # flow through the whole program whether or not they carry tokens.
        # The SEQUENCE-SLOT bound below keeps the RAW count — the engine
        # buckets the COMBINED decode+prefill work (_bucket_batch), so a
        # prefill can ride in a padding slot; charging bucketed decode there
        # would starve prefill whenever decode_bucket approaches max_seqs
        n_bucketed = min(cfg.max_seqs,
                         -(-len(decodes) // cfg.decode_bucket) * cfg.decode_bucket) \
            if decodes else 0
        budget = cfg.token_budget - n_bucketed

        plan_prefill: List[Tuple[SequenceDescriptor, int]] = []
        for seq in prefills:
            if budget <= 0 or len(plan_prefill) + len(decodes) >= cfg.max_seqs:
                break
            n = manager.kv.geometry.chunk_limit(seq.seen_tokens,
                                                min(seq.remaining_prefill, cfg.prefill_chunk, budget))
            if n <= 0:
                # defensive: unreachable under the current filters (prefills
                # all have remaining_prefill >= 1, budget > 0 checked above)
                # — but a zero-work seq must SKIP, not break: breaking would
                # starve every sequence queued behind it
                continue
            plan_prefill.append((seq, n))
            budget -= n
        if budget > 0 and 0 < len(plan_prefill) < self.run_rows and manager.kv.geometry.chunk_runs:
            self._run_ahead(manager.kv, decodes, plan_prefill, budget)
        return StepPlan(decode=decodes, prefill=plan_prefill)

    def _run_ahead(self, kv, decodes, plan_prefill, budget: int) -> None:
        """Give the rows that ``run_rows`` has spare to the planned sequences,
        in their order: each takes further whole chunks behind its first, as
        far as its prompt, the geometry (``chunk_limit``: where a run must
        end), the token budget and the pages go.  Pages: what the allocator
        gives without a preemption beyond what the plan needs as it stands
        (free pages, and the prefix cache's cold ones, evicted here as
        ``ensure_capacity`` would evict them when the step is packed), so a
        run is never what makes a plan not fit (``serving/kv_pressure``)."""
        chunk = self.config.prefill_chunk
        spare = self.run_rows - len(plan_prefill)
        held = sum(kv.pages_needed(s, 1) for s in decodes) + sum(kv.pages_needed(s, n) for s, n in plan_prefill)
        for i, (seq, n) in enumerate(plan_prefill):
            if spare <= 0 or budget <= 0:
                break
            if n < chunk:   # its prompt, its window or the budget ends inside the first chunk
                continue
            more = kv.geometry.chunk_limit(seq.seen_tokens,
                                           min(seq.remaining_prefill, (1 + spare) * chunk, n + budget)) - n
            first = kv.pages_needed(seq, n)
            while more > 0:
                pages = kv.pages_needed(seq, n + more) - first
                if held + pages <= kv.free_pages_on_demand(held + pages):
                    break
                more -= (more - 1) % chunk + 1    # a row less
            if more <= 0:
                continue
            plan_prefill[i] = (seq, n + more)
            held += pages
            budget -= more
            spare -= -(-more // chunk)
