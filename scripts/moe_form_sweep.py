#!/usr/bin/env python3
"""A builder's sweep on the chip, not a cell: one dropless expert layer alone
(``moe/sharded_moe.dropless_moe`` jitted by itself over a ``layer=`` stack
[L, E, ...]), in the dense form, the sorted form, and as the layer's own rule
has it (``rule``: where the slots say "dense", a mask is given and the bound is
at least half the slots, ``live_rows_sorted``, both forms under one
conditional that asks ``takes_sorted`` of the live rows), at the banks the
benchmark's cells hold.  PERF.md section 5's table "The expert layer
alone" is this script's output (PR 47: the two forms; PR 48: the conditional).

    chiprun -- python3 scripts/moe_form_sweep.py --banks mixtral --slots 16,144

A row a (bank, slots, live rows): ms a call of each form (host clock round
``block_until_ready`` over ``--calls`` calls, the median of three rounds), the
experts the live rows touched, the reads of those and of the whole bank at the
chip's 819 GB/s, the forms' distance from the dense one (relative l2), and
what each compiled program holds beside its arguments (``temp_mb``: a copy of
a bank would show there).  ``--trace bank:slots:live`` prints that point's
longest device operations a call, form by form, and with ``--raw`` one event's
whole HLO text and profiler statistics an operation (what the profiler keeps
of a ``jax.named_scope``: ``docs/OBSERVABILITY.md`` "The experts' products in
a device trace").  ``--tiny`` cuts the widths
by 16 for a rehearsal on the CPU (counts and values, never a time).
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.moe import sharded_moe  # noqa: E402

#: name: (the router's experts, held here, hidden, expert width, experts a row, layers of the stack, scoring)
BANKS = {
    "mixtral": (8, 8, 4096, 14336, 2, 3, "softmax"),
    "xing4": (64, 64, 3584, 1024, 4, 6, "sigmoid"),
    "kimivl": (64, 64, 2048, 1408, 6, 7, "sigmoid"),
    "solar": (320, 40, 4096, 1280, 8, 4, "sigmoid"),
    "granite4hs": (72, 36, 4096, 768, 10, 1, "softmax"),
}
FORMS = ("dense", "sorted", "rule")
HBM_BYTES_S = 819e9
RULE = sharded_moe.takes_sorted


def layer_fn(form, k, scoring, held, layer):
    """The layer jitted with the rule held to one form, or left as it is."""

    def fn(x, w_router, bias, bank, mask):
        sharded_moe.takes_sorted = RULE if form == "rule" else (lambda s, k, e: form == "sorted")
        try:
            out, _, counts = sharded_moe.dropless_moe(x, x.astype(jnp.float32) @ w_router, bank, k, mask, None, layer,
                                                      True, scoring, bias if scoring == "sigmoid" else None, 1.0, held)
        finally:
            sharded_moe.takes_sorted = RULE
        return out, counts

    return jax.jit(fn)


def timed(fn, args, calls, rounds=3):
    jax.block_until_ready(fn(*args))
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(rounds):
        t0 = time.perf_counter()  # dslint-ok(determinism): a chip measurement reads the host's real clock
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        ms.append(1e3 * (time.perf_counter() - t0) / calls)  # dslint-ok(determinism): as above
    return sorted(ms)[len(ms) // 2], out


def traced(tag, fn, args, calls, raw=False):
    """The point's longest device operations, microseconds a call."""
    import trace_reduce
    tdir = tempfile.mkdtemp(prefix="moe_form_sweep_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    red = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(tdir)))
    print(f"sweep_trace: {tag} device_busy_us_a_call={1e6 * red['busy_s'] / calls:.1f}", flush=True)
    for key, seconds in red["device_ops"][:12]:
        times = red["op_counts"][key] / calls
        print(f"sweep_op: {tag} {1e6 * seconds / calls:9.1f} us x{times:<4.1f} {key}", flush=True)
        if raw:
            event = next(e for e in red["events"] if trace_reduce.display_name(e) == key)
            print(f"sweep_raw: {tag} {event[0][:700]!r} stats={ {k: str(v)[:200] for k, v in event[3].items()} }",
                  flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--banks", default="mixtral")
    ap.add_argument("--slots", default="16,144")
    ap.add_argument("--live", default="1,2,3,4,6,8,10,11,12,16", help="live rows; those over the slots are left out, "
                    "and every slot live is always read")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--trace", default="", help="points bank:slots:live whose device operations are printed")
    ap.add_argument("--raw", action="store_true", help="with --trace: an event's HLO text and statistics an operation")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "moe_form_sweep.json"))
    a = ap.parse_args()
    forms = a.forms.split(",")
    points = set(filter(None, a.trace.split(",")))
    dev = jax.devices()[0]
    print(f"sweep: device {dev.platform} {dev.device_kind}; DENSE_FROM_BANK_SHARE {sharded_moe.DENSE_FROM_BANK_SHARE}",
          flush=True)
    rows = []
    for name in a.banks.split(","):
        e_router, e_held, d, f, k, layers, scoring = BANKS[name]
        if a.tiny:
            d, f = d // 16, f // 16
        held = None if e_held == e_router else (0, e_held)
        ks = jax.random.split(jax.random.PRNGKey(48), 6)

        def draw(key, shape, scale):
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)

        bank = tuple(jnp.stack([draw(jax.random.fold_in(ks[i], n), (e_held, ) + shape, shape[0]**-0.5)
                                for n in range(layers)]) for i, shape in enumerate(((d, f), (d, f), (f, d))))
        w_router = jax.random.normal(ks[3], (d, e_router), jnp.float32) * d**-0.5
        bias = jax.random.normal(ks[4], (e_router, ), jnp.float32) * 0.01
        expert_bytes = 3 * d * f * 2
        print(f"sweep: {name}: {k} of {e_router} a row ({e_held} held), sorted up to "
              f"{sharded_moe.sorted_up_to(k, e_router)} live rows", flush=True)
        for s in (int(n) for n in a.slots.split(",")):
            x = draw(jax.random.fold_in(ks[5], s), (s, d), 1.0)
            fns = {form: layer_fn(form, k, scoring, held, layers // 2) for form in forms}
            args = (x, w_router, bias, bank, jnp.ones((s, ), bool))
            temp = {form: fns[form].lower(*args).compile().memory_analysis().temp_size_in_bytes for form in forms}
            print(f"sweep: {name} {s} slots: takes_sorted {RULE(s, k, e_router)}, both forms up to "
                  f"{sharded_moe.live_rows_sorted(s, k, e_router)} live rows; temp_mb "
                  + " ".join(f"{form} {temp[form] / 1e6:.1f}" for form in forms), flush=True)
            for live in sorted({int(n) for n in a.live.split(",") if int(n) < s} | {s}):
                args = (x, w_router, bias, bank, jnp.arange(s) < live)
                row = {"bank": name, "slots": s, "live": live, "rule_says_sorted": bool(
                    RULE(s, k, e_router) or live <= sharded_moe.live_rows_sorted(s, k, e_router))}
                outs = {}
                for form in forms:
                    row[form + "_ms"], (out, counts) = timed(fns[form], args, a.calls)
                    row[form + "_ms"] = round(row[form + "_ms"], 4)
                    outs[form] = np.asarray(out, np.float32)
                touched = int((np.asarray(counts) > 0).sum())
                row.update(touched=touched, read_touched_ms=round(1e3 * touched * expert_bytes / HBM_BYTES_S, 4),
                           read_bank_ms=round(1e3 * e_held * expert_bytes / HBM_BYTES_S, 4))
                if "dense" in outs:
                    norm = max(float(np.linalg.norm(outs["dense"])), 1e-30)
                    row["rel_l2"] = {form: float(np.linalg.norm(outs[form] - outs["dense"]) / norm)
                                     for form in forms if form != "dense"}
                rows.append(row)
                print("sweep_row:", json.dumps(row), flush=True)
                if f"{name}:{s}:{live}" in points:
                    for form in forms:
                        traced(f"{name}:{s}:{live}:{form}", fns[form], args, a.calls, a.raw)
        del bank, fns, args, x, outs, out, counts
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump(rows, fh, indent=0)


if __name__ == "__main__":
    main()
