"""A request's way to its first token (``ServingEngine`` +
``telemetry/spans.py`` + ``StepAnatomy.first_tokens``): every request that
reaches a first token leaves one row whose parts are non-negative and sum to
its TTFT, in the serial and in the pipelined tick, on a virtual clock and on
one that moves at every reading; the phase spans of the same request carve
its PREFILL into ``phase/prefill`` (a step that carried it ran),
``phase/prefill_bypassed`` (a step ran and passed it by) and
``phase/prefill_wait`` with the same seconds; the rows outlive their engine
through ``recorders()``, export the same bytes in two runs of one seed, and
``why_slow.py`` and ``trace_report.py`` tile a trace with the new phases and
fail on a sabotaged one."""

import gc
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import ServingConfig, ServingEngine, VirtualClock
from deepspeed_tpu.serving.request import RequestState
from deepspeed_tpu.telemetry import MetricsRegistry, Tracer, recorders, to_chrome_trace
from deepspeed_tpu.telemetry.spans import FIRST_TOKEN_PARTS

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_kimi_vl import small  # noqa: E402
from test_kimi_vl_serving import engine as kimi_engine  # noqa: E402
from test_kimi_vl_serving import request as kimi_request  # noqa: E402

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

CFG = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128, rope_theta=1e4, dtype=jnp.float32,
                  scan_layers=True, remat=False)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def kimi():
    return small()


def tiny(params, k=1, num_pages=40, token_budget=64, **over):
    kv = PagedKVConfig(num_pages=num_pages, page_size=4, max_pages_per_seq=16)
    sched = SchedulerConfig(token_budget=token_budget, max_seqs=4, prefill_chunk=8, decode_bucket=2)
    return build_engine(CFG, params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=k, max_new_tokens=6, **over))


class TickingClock:
    """A real clock's stand-in that is deterministic: every reading lies a
    millisecond behind the last and ``on_step`` charges nothing (as
    ``WallClock``), so a step's window is what two readings say, a pipelined
    tick admits with its last dispatch in flight, and waits are not zero."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        self.t += 1e-3
        return self.t

    def wait_until(self, ts):
        self.t = max(self.t, ts)

    def on_step(self, cost):
        return None


def frontend(eng, clock_kind, pipelined):
    clock = VirtualClock() if clock_kind == "virtual" else TickingClock()
    tracer = Tracer(clock=clock)
    serve = ServingEngine(eng, clock=clock, tracer=tracer, metrics=MetricsRegistry(),
                          config=ServingConfig(async_dispatch=pipelined, step_cost=lambda n: 0.01 + 0.001 * n))
    return serve, tracer


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 60, n).tolist()


def overlap(windows, t0, t1):
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in windows)


def parts_of_spans(tracer, req):
    """The row's parts as the request's phase spans give them, up to its first token."""
    root = next(s for s in tracer.spans if s.name == "request" and s.attrs["uid"] == req.uid)
    got = dict.fromkeys(FIRST_TOKEN_PARTS, 0.0)
    name_of = {"phase/prefill": "carried_s", "phase/prefill_bypassed": "bypassed_s", "phase/prefill_wait": "wait_s",
               "phase/vision_encode": "vision_encode_s"}
    for s in tracer.spans:
        if s.trace_id != root.trace_id or not s.name.startswith("phase/"):
            continue
        t0, t1 = s.start_ts, min(s.end_ts, req.first_token_ts)
        if t1 <= t0:
            continue
        if s.name == "phase/queued":
            late = max(0.0, min(t1, req.submit_ts) - t0)
            got["late_s"] += late
            got["queued_s"] += t1 - t0 - late
        else:
            got[name_of.get(s.name, "other_s")] += t1 - t0
    return got


def check_rows(serve, tracer, reqs):
    """What holds for every request: a row, its parts non-negative and summing
    to the TTFT, and the same seconds in the request's phase spans."""
    rows = {row["uid"]: row for row in serve.engine.anatomy.first_tokens}
    assert sorted(rows) == sorted(r.uid for r in reqs) and all(r.state is RequestState.DONE for r in reqs)
    for req in reqs:
        row = rows[req.uid]
        assert row is req.ttft_row and list(row)[8:15] == list(FIRST_TOKEN_PARTS)
        assert all(row[p] >= 0.0 for p in FIRST_TOKEN_PARTS), row
        assert row["ttft_s"] == req.ttft == req.first_token_ts - req.arrival_ts
        assert abs(sum(row[p] for p in FIRST_TOKEN_PARTS) - row["ttft_s"]) <= 1e-9, row
        spans = parts_of_spans(tracer, req)
        assert all(abs(spans[p] - row[p]) <= 1e-9 for p in FIRST_TOKEN_PARTS), (row, spans)
        assert (row["prompt_tokens"], row["preemptions"]) == (len(req.prompt), req.preemptions)
        assert row["arrival_ts"] <= row["submit_ts"] <= row["admitted_ts"] <= row["first_dispatch_ts"] \
            < row["last_carried_ts"] <= row["first_token_ts"]
    return rows


# ------------------------------------------------------------ the scenarios


def lone_prompt(params, kimi, clock_kind, pipelined):
    """40 tokens alone, a chunk of 8 a step: nothing passes it by."""
    serve, tracer = frontend(tiny(params), clock_kind, pipelined)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": prompt(1, 40), "max_new_tokens": 3}])
    row = check_rows(serve, tracer, reqs)[reqs[0].uid]
    assert (row["prefill_steps"], row["prefill_tokens"], row["bypassed_s"]) == (5, 40, 0.0)
    assert row["carried_s"] == pytest.approx(overlap(reqs[0].carry_windows, 0.0, reqs[0].first_token_ts), abs=1e-12)
    if clock_kind == "virtual":    # time moves with the steps alone
        assert row["carried_s"] == row["ttft_s"] == pytest.approx(0.01 * 5 + 0.001 * 40)
    else:
        assert row["wait_s"] > 0.0
    assert serve.metrics.histogram("serving/ttft_carried_s").count == 1
    assert serve.metrics.histogram("serving/ttft_wait_s").count == serve.metrics.histogram("serving/ttft_s").count


def two_prompts_one_chunk(params, kimi, clock_kind, pipelined):
    """A token budget of one chunk and two prompts at once: the first takes
    the budget, the second is passed by for as long as the first's steps take."""
    serve, tracer = frontend(tiny(params, token_budget=8), clock_kind, pipelined)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": prompt(s, 20), "max_new_tokens": 3} for s in (2, 3)])
    rows = check_rows(serve, tracer, reqs)
    first, second = (rows[r.uid] for r in reqs)
    assert first["bypassed_s"] == 0.0 and second["bypassed_s"] > 0.0
    # the steps that passed the second by are the first's, as far as they lie in the second's PREFILL and carried none of it
    a, b = reqs
    alone = [w for w in a.carry_windows if w not in b.carry_windows]
    assert second["bypassed_s"] == pytest.approx(overlap(alone, b.admitted_ts, b.first_token_ts), abs=1e-12)
    assert second["prefill_tokens"] == 20 and second["first_dispatch_ts"] > second["admitted_ts"]


def due_under_a_fused_dispatch(params, kimi, clock_kind, pipelined):
    """A request due while a fused dispatch of 4 decode steps runs: the caller
    holds it until the tick ends (``late_s``); a pipelined tick on a real clock
    admits it with the dispatch still in flight, and what is left of that
    dispatch passes it by."""
    eng = tiny(params, k=4)
    serve, tracer = frontend(eng, clock_kind, pipelined)
    first = serve.submit(prompt(4, 5), max_new_tokens=6)
    for _ in range(10):
        serve.tick()
        fused = serve._inflight[0].kind == "multi" if pipelined else (eng.anatomy.last_step.key or "").startswith("multi")
        if fused:
            break
    assert fused and first.state is RequestState.DECODE
    t0, t1 = (serve._inflight[2], serve.clock.now()) if pipelined else serve._run_last
    second = serve.submit(prompt(5, 7), max_new_tokens=3, arrival_ts=0.5 * (t0 + t1))
    serve.drain()
    rows = check_rows(serve, tracer, [first, second])
    row = rows[second.uid]
    assert row["late_s"] > 0.0 and row["prefill_steps"] == 1
    assert (row["bypassed_s"] > 0.0) == (pipelined and clock_kind == "ticking"), row


def preempted_in_prefill(params, kimi, clock_kind, pipelined):
    """Pages run out while the younger prompt prefills: it is evicted, queues
    and prefills again, so more positions are computed for it than its prompt has."""
    serve, tracer = frontend(tiny(params, num_pages=13, token_budget=12, enable_prefix_cache=False), clock_kind,
                             pipelined)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": prompt(6, 20), "max_new_tokens": 6},
                      {"arrival_ts": 0.0, "prompt": prompt(7, 30), "max_new_tokens": 2}])
    rows = check_rows(serve, tracer, reqs)
    victim = rows[reqs[1].uid]
    assert victim["preemptions"] == 1 and victim["prefill_tokens"] > victim["prompt_tokens"] == 30
    assert victim["queued_s"] > 0.0     # the second wait for pages is the queue's too


def prefix_cache_hit(params, kimi, clock_kind, pipelined):
    """The second request's prompt lies in the prefix cache but for its end."""
    serve, tracer = frontend(tiny(params), clock_kind, pipelined)
    tokens = prompt(8, 26)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": tokens, "max_new_tokens": 2}])
    reqs += serve.run([{"arrival_ts": serve.clock.now(), "prompt": tokens, "max_new_tokens": 2}])
    rows = check_rows(serve, tracer, reqs)
    cold, warm = (rows[r.uid] for r in reqs)
    assert cold["prefill_tokens"] == 26 and 0 < warm["prefill_tokens"] < 26 == warm["prompt_tokens"]
    assert warm["carried_s"] < cold["carried_s"]


def with_images(params, kimi, clock_kind, pipelined):
    """Two images at 32 padded patches a tick: the second waits a tick for the
    tower while the text request's step runs; that wait is the tower's, not a bypass."""
    cfg, _, _, kimi_params = kimi
    serve, tracer = frontend(kimi_engine(cfg, kimi_params, per_tick=32), clock_kind, pipelined)
    rng = np.random.default_rng(0)
    p, images = kimi_request(rng, [(4, 6), (8, 6)])
    seen = serve.submit(p, max_new_tokens=3, images=images)
    text = serve.submit(rng.integers(1, 400, 20).tolist(), max_new_tokens=3)
    serve.drain(max_ticks=200)
    rows = check_rows(serve, tracer, [seen, text])
    row = rows[seen.uid]
    assert row["vision_encode_s"] > 0.0 and rows[text.uid]["vision_encode_s"] == 0.0
    assert row["vision_encode_s"] == pytest.approx(seen.encode_windows[0][1] - seen.encode_windows[0][0], abs=1e-12)
    assert row["first_dispatch_ts"] >= seen.encode_windows[0][1]


SCENARIOS = [lone_prompt, two_prompts_one_chunk, due_under_a_fused_dispatch, preempted_in_prefill, prefix_cache_hit,
             with_images]


@pytest.mark.parametrize("clock_kind", ["virtual", "ticking"])
@pytest.mark.parametrize("pipelined", [False, True], ids=["serial", "pipelined"])
@pytest.mark.parametrize("scenario", SCENARIOS, ids=[s.__name__ for s in SCENARIOS])
def test_the_row_tiles_the_ttft_and_equals_the_spans(params, kimi, scenario, pipelined, clock_kind):
    scenario(params, kimi, clock_kind, pipelined)


# ----------------------------------------------------------- beside the rows


def serve_three(params, pipelined=False):
    eng = tiny(params, token_budget=8)
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    serve = ServingEngine(eng, clock=clock, tracer=tracer,
                          config=ServingConfig(async_dispatch=pipelined, step_cost=lambda n: 0.01 + 0.001 * n))
    reqs = serve.run([{"arrival_ts": 0.013 * i, "prompt": prompt(10 + i, 12 + 5 * i), "max_new_tokens": 3}
                      for i in range(3)])
    return eng, tracer, reqs


def test_rows_outlive_an_unreferenced_engine(params):
    """The benchmark's readers come when the run's engine has gone: the rows
    are with the recorder that closed the newest step."""
    eng, _, reqs = serve_three(params)
    marker, uids = id(eng.anatomy), [r.uid for r in reqs]
    del eng, reqs
    gc.collect()
    kept = [r for r in recorders() if id(r) == marker]
    assert len(kept) == 1 and sorted(row["uid"] for row in kept[0].first_tokens) == uids


@pytest.mark.parametrize("pipelined", [False, True], ids=["serial", "pipelined"])
def test_two_runs_of_one_seed_export_the_same_bytes(params, pipelined):
    """``to_doc()`` carries the rows, rounded to 9 places in their own key order."""
    docs = [json.dumps(serve_three(params, pipelined)[0].anatomy.to_doc()) for _ in range(2)]
    assert docs[0] == docs[1]
    rows = json.loads(docs[0])["first_tokens"]
    assert len(rows) == 3 and all(row[k] == round(row[k], 9) for row in rows for k in ("ttft_s", *FIRST_TOKEN_PARTS))
    assert list(rows[0]) == ["uid", "arrival_ts", "submit_ts", "admitted_ts", "first_dispatch_ts", "last_carried_ts",
                             "first_token_ts", "ttft_s", *FIRST_TOKEN_PARTS, "prompt_tokens", "prefill_tokens",
                             "prefill_steps", "preemptions"]


def test_the_step_range_names_the_prefilling_requests_it_carried(params):
    """``prefill_uids`` of a ``ds.step`` range and the instant ``ds.first_token``, as a factory sees them."""
    from deepspeed_tpu.telemetry import StepAnatomy

    class Range:
        def __init__(self, log, name):
            self.log, self.name, self.meta = log, name, {}

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.log.append((self.name, dict(self.meta)))
            return False

        def set_metadata(self, **kw):
            self.meta.update(kw)

    log = []
    eng = tiny(params, token_budget=8)
    clock = VirtualClock()
    eng.set_anatomy(StepAnatomy(clock=clock, annotate=lambda name: Range(log, name)))
    serve = ServingEngine(eng, clock=clock)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": prompt(s, 12), "max_new_tokens": 2} for s in (20, 21)])
    steps = [meta for name, meta in log if name == "ds.step" and meta]
    # 8 of the first; 4 and 4; the first decodes and its bucket of 2 leaves 6 of the budget, so 6 and 2 of the second
    assert [m.get("prefill_uids") for m in steps[:4]] == ["0", "0+1", "1", "1"]
    assert all("prefill_uids" not in m for m in steps[4:])
    firsts = [meta for name, meta in log if name == "ds.first_token"]
    assert [m["uid"] for m in firsts] == [r.uid for r in reqs]
    assert all(set(m) == {"uid", "ttft_s", *FIRST_TOKEN_PARTS} for m in firsts)


def script(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO_ROOT, "scripts", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["why_slow", "trace_report"])
def test_the_scripts_tile_the_new_phases_and_fail_on_a_sabotaged_one(params, tmp_path, name):
    _, tracer, reqs = serve_three(params)
    doc = to_chrome_trace(tracer.spans)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"phase/prefill", "phase/prefill_bypassed"} <= names
    report = script(name).fold(doc, tol=1e-6)
    assert report["verification"]["mismatches"] == 0 and report["n_requests"] == 3
    if name == "why_slow":
        assert report["causes"]["prefill_bypassed"]["total_s"] > 0 and "unknown:prefill_bypassed" not in report["causes"]
        assert report["ttft_gap"]["by_cause"]["prefill_bypassed"] > 0     # a prompt behind another is a slowdown
    else:
        assert report["critical_path"]["prefill_bypassed"]["total_s"] > 0
    good, bad = tmp_path / "good.json", tmp_path / "bad.json"
    good.write_text(json.dumps(doc))
    victim = next(e for e in doc["traceEvents"] if e.get("ph") == "X" and e["name"] == "phase/prefill_bypassed")
    victim["dur"] -= 5e3    # 5 ms of a passed-by stretch lost
    bad.write_text(json.dumps(doc))
    cli = os.path.join(REPO_ROOT, "scripts", name + ".py")
    assert subprocess.run([sys.executable, cli, str(good)], capture_output=True).returncode == 0
    broken = subprocess.run([sys.executable, cli, str(bad)], capture_output=True)
    assert broken.returncode == 1 and b"MISMATCH" in broken.stderr
