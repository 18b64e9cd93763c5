#!/usr/bin/env python3
"""The benchmark's own rehearsal: a command of the benchmark, not part of the
repository's tier-1 tests.  Runs here on the CPU, with no chip, and reports
nothing as a run.

    python3 benchmark/selfcheck.py            # checks (a)-(d), a minute or two
    python3 benchmark/selfcheck.py --aot      # offline compiles for v5e:2x2, minutes
    python3 benchmark/selfcheck.py --rehearse <cell> [--trace 1]   # one cell of (a)
    python3 benchmark/selfcheck.py --rehearse <cell> --sweep 2,2,4 [--set PATH=JSON]   # run.py's builder's modes
    python3 benchmark/selfcheck.py --limits <cell> --seeds 12 --dump chiprun_out   # on the chip

 (a) every cell of BENCHMARK.json end to end at its files' ``rehearsal``
     sizes on virtual CPU devices, both ``--trace`` values;
 (b) ``trace_reduce`` against the recorded trace under ``fixtures/``;
 (c) ``traffic_gen``: for twelve seeds a serving mix has the same count, the
     same requests (prompt and output lengths) at the same due times, and
     twelve different sets of token ids; the due times bunch as a Poisson
     process does, and every block of the window holds the same count;
 (d) ``roofline`` on hand-worked shapes.

``--aot`` compiles the cells' step programs at published widths against a
described ``v5e:2x2`` (no chip attached): it shows what the chip's compiler
refuses and the bytes a program needs, never a time.  ``--limits`` is the
builder's mode behind the limits of ``correct``: on the chip, at the cell's
own size, the numbers the check compares for the program and for the
lower-precision control, a line per seed.
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load(*path):
    with open(os.path.join(HERE, *path)) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------- (a)


def check_cells():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cell in bench()["workloads"]:
        for trace in (0, 1):
            cmd = [sys.executable, os.path.abspath(__file__), "--rehearse", cell["name"], "--seed",
                   str(2 ** 31 + 11), "--trace", str(trace)]
            out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=900)
            last = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
            assert out.returncode == 0, f"{cell['name']} trace={trace} exited {out.returncode}:\n{out.stderr[-2000:]}"
            result = json.loads(last)
            assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0, last
            assert result["rehearsal"] and result["metrics"] == {} and result["metrics_read"], last
            print(f"(a) {cell['name']} trace={trace}: correct, attempted {result['attempted']}, "
                  f"readers ran: {', '.join(result['metrics_read'])}")
    # with no TPU the benchmark's command must refuse and print no result
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", bench()["workloads"][0]["name"],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and '"metrics"' not in out.stdout, "a run without a TPU must fail"
    print("(a) without a TPU the command exits non-zero and prints no result")


# ---------------------------------------------------------------------- (b)


def check_trace_reduce():
    import trace_reduce
    u, sub, ln = trace_reduce.union, trace_reduce.subtract, trace_reduce.length
    assert u([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert sub([[0, 10]], [[1, 2], [4, 6]]) == [[0, 1], [2, 4], [6, 10]]
    assert abs(ln(sub([[0, 3], [5, 8]], [[2, 6]])) - 4.0) < 1e-12
    # a hand-made trace: two device operations and a collective that overlaps one of them
    made = {"devices": {0: [("fusion.1", 0.0, 1.0, {}), ("all-gather.2", 0.5, 2.0, {}),
                            ("%attn.3 = bf16[2,8]{1,0} custom-call(s32[2]{0} %a, bf16[2,8]{1,0} %q), "
                             'custom_call_target="tpu_custom_call"', 3.0, 4.0, {})]},
            "host": [("tick", -0.5, 2.2, {}), ("submit", 2.3, 2.9, {})]}
    r = trace_reduce.reduce(made)
    assert abs(r["window_s"] - 4.5) < 1e-12 and abs(r["busy_s"] - 3.0) < 1e-12
    assert abs(r["collective_exposed_s"] - 1.0) < 1e-12
    assert [(k, round(v, 9)) for k, v in r["idle_gaps"]] == [("submit", 1.0), ("tick", 0.5)]
    assert trace_reduce.kernel_events(r, trace_reduce.PALLAS_CALL) == [1.0]
    assert trace_reduce.operand_count(r["events"][2]) == 2
    print("(b) interval arithmetic and a hand-made trace reduce to the numbers worked by hand")
    fixture = os.path.join(HERE, "fixtures", "small.xplane.pb")
    want = load("fixtures", "small.expected.json")
    got = trace_reduce.reduce(trace_reduce.load(fixture))
    for key, value in want["numbers"].items():
        assert abs(got[key] - value) <= 1e-9 + 1e-6 * abs(value), (key, got[key], value)
    for needle, (count, seconds) in want["kernels"].items():
        ev = trace_reduce.kernel_events(got, needle)
        assert len(ev) == count and abs(sum(ev) - seconds) <= 1e-6 * seconds, (needle, len(ev), sum(ev))
    assert [k for k, _ in got["idle_gaps"][:len(want["idle_gap_owners"])]] == want["idle_gap_owners"]
    print(f"(b) the recorded trace reduces to the numbers beside it ({', '.join(want['numbers'])})")


# ---------------------------------------------------------------------- (c)


def check_traffic():
    import traffic_gen
    done = set()
    for cell in bench()["workloads"]:
        traffic = load("traffic", cell["traffic"] + ".json")
        if traffic["kind"] != "serve_open_loop" or cell["traffic"] in done:
            continue
        done.add(cell["traffic"])
        shapes, tokens = set(), set()
        for seed in [0, 1, 2, 3, 5, 8, 13, 21, 34, 2 ** 31 - 1, 2 ** 31 + 7, 3000000019]:
            sched = traffic_gen.serving_schedule(traffic, bench()["run_seconds"], seed, 32000)
            shapes.add(tuple((round(r["due"], 9), r["measured"], len(r["prompt"]), r["max_new_tokens"]) for r in sched))
            tokens.add(tuple(sched[0]["prompt"][:8]))
            assert all((0 <= r["due"] < bench()["run_seconds"]) == r["measured"] for r in sched)
        assert len(shapes) == 1 and len(tokens) == 12, (cell["traffic"], len(shapes), len(tokens))
        sched = [r for r in next(iter(shapes)) if r[1]]
        prompts, outs = sorted(r[2] for r in sched), sorted(r[3] for r in sched)
        # arrivals are uniform draws inside blocks of equal count: gaps vary as a Poisson
        # process's do (coefficient of variation near 1; paced slots would give 0)
        due = [r[0] for r in sched]
        gaps = [b - a for a, b in zip(due, due[1:])]
        mean = sum(gaps) / len(gaps)
        cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
        n_blocks = max(1, round(bench()["run_seconds"] / traffic["block_s"]))
        counts = [sum(1 for d in due if int(d * n_blocks / bench()["run_seconds"]) == b) for b in range(n_blocks)]
        assert cv > 0.6 and max(counts) - min(counts) <= 1, (cell["traffic"], cv, counts)
        print(f"(c) {cell['traffic']}: 12 seeds, each {len(sched)} measured requests "
              f"(+{len(next(iter(shapes))) - len(sched)} lead-in), prompt tokens {sum(prompts)} (min {prompts[0]}, "
              f"median {prompts[len(prompts) // 2]}, max {prompts[-1]}), output tokens {sum(outs)}: the same requests "
              f"at the same times in the same order, 12 different sets of token ids; gaps between arrivals vary by "
              f"{cv:.2f} of their mean, {n_blocks} blocks hold {counts}")


# ---------------------------------------------------------------------- (d)


def check_roofline():
    import roofline
    # one decode row: 1 query over a context that then holds 1000 tokens, 32q/8kv heads of 128
    f, b = roofline.paged_attention_call(1, 999, 32, 8, 128)
    assert f == 4 * 128 * 32 * 1000 and b == 2 * 128 * (2 * 8 * 1000 + 2 * 32), (f, b)
    # one prefill chunk of 128 at the start of a prompt: 128*129/2 visible pairs
    f, b = roofline.paged_attention_call(128, 0, 32, 8, 128)
    assert f == 4 * 128 * 32 * 8256 and b == 2 * 128 * (2 * 8 * 128 + 2 * 32 * 128)
    # a flash forward call, batch 1, 2048 tokens, 16 heads of 128: 2048*2049/2 pairs
    f, b = roofline.flash_forward_call(1, 2048, 16, 16, 128)
    assert f == 4 * 128 * 16 * 2098176 and b == 2 * 2048 * 128 * 64
    assert roofline.flash_backward_call(1, 2048, 16, 16, 128)[0] == f * 5 // 2
    # N of 6N, by hand.  Mixtral, 3 layers: attention 4096*128*(32+16) + 32*128*4096 = 41,943,040;
    # router 4096*8 = 32,768; two experts 2*3*4096*14336 = 352,321,536; head 4096*32000 = 131,072,000
    mix = load("configs", "mixtral-8x7b-serve-1chip.json")
    assert roofline.active_matmul_params(mix) == 3 * (41943040 + 32768 + 352321536) + 131072000
    # Qwen1.5-MoE, 4 layers: attention 4*2048*2048 = 16,777,216; router 2048*60 = 122,880; four experts
    # 4*3*2048*1408 = 34,603,008; shared expert 3*2048*5632 + 2048 = 34,605,056; head 2048*151936 = 311,164,928
    qwen = load("configs", "qwen15-moe-a2.7b-zero3-4chip.json")
    layer = 16777216 + 122880 + 34603008 + 34605056
    assert roofline.active_matmul_params(qwen) == qwen["num_hidden_layers"] * layer + 311164928
    print("(d) paged-attention, flash and 6N counts equal the hand-worked numbers")


# --------------------------------------------------------- --record-fixture


def record_fixture(out_dir):
    """On the chip: trace a fraction of a second of small jitted work under
    the benchmark's own host spans, and write the trace and the numbers it
    reduces to.  The numbers are this reducer's own, looked over by hand
    against ``trace_reduce.describe``; the check then holds every later PR
    to them."""
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    import harness
    import trace_reduce
    if jax.devices()[0].platform != "tpu":
        sys.exit("selfcheck: --record-fixture needs the chip")
    step = jax.jit(lambda x, w: jnp.tanh(x @ w))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.001
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(6):
        with harness.span("tick"):
            for _ in range(3):
                x = step(x, w)
            x.block_until_ready()
            time.sleep(0.004)
        with harness.span("submit"):
            time.sleep(0.003)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    trace_reduce.describe(path, top=8)
    r = trace_reduce.reduce(trace_reduce.load(path))
    names = sorted({e[0] for e in r["events"]})
    expected = {"numbers": {k: r[k] for k in ("window_s", "busy_s", "collective_s", "collective_exposed_s")},
                "kernels": {n: [len(trace_reduce.kernel_events(r, n)), sum(trace_reduce.kernel_events(r, n))]
                            for n in names[:3]},
                "idle_gap_owners": [k for k, _ in r["idle_gaps"]],
                "idle_gaps": r["idle_gaps"], "device_ops": r["device_ops"][:10]}
    with open(os.path.join(out_dir, "small.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected, indent=1))
    shutil.rmtree(tmp, ignore_errors=True)


# -------------------------------------------------------------------- --aot


def aot():
    """Offline compiles for a described v5e:2x2.  Never reported as a run."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from jax.experimental import topologies

    import harness
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    gb = 1e9
    for cell in bench()["workloads"]:
        cfg, traffic = load("configs", cell["config"] + ".json"), load("traffic", cell["traffic"] + ".json")
        pcfg = harness.program_config(cfg)
        mesh = create_mesh(MeshSpec(), devices=topo.devices[:cell["chips"]])
        if traffic["kind"] == "train_job":
            import deepspeed_tpu as ds
            import traffic_gen
            model = harness.load_symbol(cfg["program"]["model"])(pcfg)
            batch = next(traffic_gen.train_batches(traffic, 0, cfg["vocab_size"],
                                                   traffic["micro_batch_per_chip"] * cell["chips"]))
            engine, _, _, _ = ds.initialize(model=model, mesh=mesh, config={
                **cfg["engine"]["deepspeed"], "train_batch_size": batch["input_ids"].shape[0], "steps_per_print": 0})
            compiled = engine.compile_aot(batch)
            text = compiled.as_text()
            found = {k: k in text for k in ("all-gather", "reduce-scatter", "tpu_custom_call")}
            m = compiled.memory_analysis()
            print(f"--aot {cell['name']}: train step compiles for v5e:2x2; a chip holds arguments "
                  f"{m.argument_size_in_bytes / gb:.2f} GB + temporaries {m.temp_size_in_bytes / gb:.2f} GB "
                  f"(aliased {m.alias_size_in_bytes / gb:.2f}), peak {m.peak_memory_in_bytes / gb:.2f} GB; "
                  f"in the program: {found}")
        else:
            from deepspeed_tpu.inference.v2.engine_v2 import compile_aot_serving

            from kinds import serve_open_loop
            e = cfg["engine"]
            econf = serve_open_loop.engine_config(cfg, traffic)
            for chunk in (e["scheduler"]["prefill_chunk"], 1):
                compiled, n = compile_aot_serving(pcfg, mesh, econf, batch=e["scheduler"]["max_seqs"], chunk=chunk)
                m = compiled.memory_analysis()
                print(f"--aot {cell['name']}: step b{e['scheduler']['max_seqs']} c{chunk} compiles for one v5e "
                      f"chip ({n / 1e9:.2f}B parameters); arguments {m.argument_size_in_bytes / gb:.2f} GB + "
                      f"temporaries {m.temp_size_in_bytes / gb:.2f} GB (aliased {m.alias_size_in_bytes / gb:.2f}), "
                      f"peak {m.peak_memory_in_bytes / gb:.2f} GB; kernel in program: {'tpu_custom_call' in compiled.as_text()}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--only", help="comma-separated subset of a,b,c,d")
    ap.add_argument("--record-fixture", metavar="DIR", help="on the chip: record fixtures/small.xplane.pb anew")
    ap.add_argument("--rehearse", metavar="CELL", help="one cell at its files' rehearsal sizes on virtual CPU devices")
    ap.add_argument("--limits", metavar="CELL", help="on the chip: the numbers `correct` compares, program and control")
    ap.add_argument("--seed", type=int, default=0, help="with --rehearse and --limits: the (first) seed")
    ap.add_argument("--seeds", type=int, default=12, help="with --limits: how many seeds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0, help="with --rehearse")
    ap.add_argument("--sweep", help="with --rehearse: run.py's --sweep at the rehearsal sizes")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=JSON", help="with --rehearse: run.py's --set")
    ap.add_argument("--dump", metavar="DIR", help="with --limits: keep the per-position readings there")
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    if args.aot:
        return aot()
    if args.rehearse:
        import run
        return run.execute(*run.open_cell(args.rehearse, args.seed, 4.0, bool(args.trace), run.rates(args.sweep),
                                          rehearse=True, assignments=args.set))
    if args.limits:
        import importlib

        import run
        # the control is the reference alone: one chip holds it, whatever the cell runs on
        _, ctx, _ = run.open_cell(args.limits, args.seed, 0.0, False, chips=1)
        kind = importlib.import_module("kinds." + ctx["traffic"]["kind"])
        return kind.limits(ctx, [args.seed + i for i in range(args.seeds)], args.dump)
    if args.record_fixture:
        return record_fixture(args.record_fixture)
    checks = {"a": check_cells, "b": check_trace_reduce, "c": check_traffic, "d": check_roofline}
    for key in (args.only.split(",") if args.only else checks):
        checks[key]()
    print("selfcheck: all passed")


if __name__ == "__main__":
    main()
