#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``); the mix's ``kind`` names the module
under ``kinds/`` that drives it; each metric of the cell is read by
``end_to_end/<metric>.py`` or ``layer_metrics/<metric>.py``.  Nothing here
branches on a cell's, a configuration's or a metric's name.

The last line of standard output is the result, one JSON object.  A run that
finds no TPU, too few chips, or a ``device_kind`` that ``peaks.py`` does not
hold exits non-zero and prints no result.

Two builder's modes, never used by the driver: ``--sweep r1,r2,...`` (several
offered rates after one set-up, to find a serving mix's knee or, with one
rate several times, its spread) and ``--set path=json`` (a value of the
cell's files replaced for this process, as ``config.engine.scheduler.max_seqs=64``
or ``traffic.rate_per_s=1.2``: how a setting is read before a file takes
it; the result line then names what was set under ``set``).  The CPU
rehearsal and the readings behind the limits of ``correct`` are modes of
``selfcheck.py``, which calls ``open_cell`` and ``execute`` below.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the program under test; ``benchmark/`` itself is sys.path[0]


def load_json(*path):
    with open(os.path.join(HERE, *path)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def assign(files: dict, assignments: list) -> None:
    """``--set``: each ``path=json`` replaces one value of ``files``
    (``config`` or ``traffic`` first, then the keys down to it)."""
    for item in assignments:
        path, value = item.split("=", 1)
        *parents, leaf = path.split(".")
        node = files
        for key in parents:
            node = node[key]
        node[leaf] = json.loads(value)


def rates(text) -> list:
    """``--sweep``'s comma-separated rates, or None."""
    return [float(r) for r in text.split(",")] if text else None


def reader(folder: str, name: str):
    """The ``read`` function of ``<folder>/<name>.py`` (a name may hold dots)."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_of(cell: dict, entries: list) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in entries if "workloads" not in m or cell["name"] in m["workloads"]]


def open_cell(workload: str, seed: int, seconds: float, trace: bool, sweep=None, rehearse=False, chips=None,
              assignments=()):
    """(BENCHMARK.json, the run's context, the device as JAX reports it).
    ``rehearse`` (selfcheck only) takes the files' ``rehearsal`` sizes on
    virtual CPU devices: control flow, never a device metric."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        sys.exit(f"benchmark: no cell {workload!r} in BENCHMARK.json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        config, traffic = merge(config, config["rehearsal"]), merge(traffic, traffic["rehearsal"])
    assign({"config": config, "traffic": traffic}, assignments)

    import harness
    parts = harness.SetupParts(T_START)
    device = harness.open_device(chips or cell["chips"], rehearse)
    parts.mark("start_and_imports")
    ctx = {"cell": cell, "config": config, "traffic": traffic, "chips": cell["chips"], "seed": seed,
           "seconds": seconds, "trace": trace, "parts": parts, "sweep": sweep, "rehearse": rehearse,
           "set": list(assignments)}
    return bench, ctx, device


def execute(bench: dict, ctx: dict, device: dict) -> None:
    """Run the cell and print the result line."""
    import peaks
    cell, trace, rehearse = ctx["cell"], ctx["trace"], ctx["rehearse"]
    run = importlib.import_module("kinds." + ctx["traffic"]["kind"]).run(ctx)
    if run is None:  # the sweep reports no result
        return
    run.update(ctx)
    run["peak"] = None if rehearse else peaks.match_device_kind(device["kind"])

    folder = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(cell, bench["per_layer"] if trace else bench["end_to_end"]):
        value = reader(folder, m["name"])(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} found nothing to read")
            continue  # a per-layer reader that finds nothing leaves its metric out
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device["memory_peak_bytes"] = max(run["hbm_peak_bytes"])
    result = {"correct": run["correct"], "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": device}
    reduced = run["reduced"]
    if reduced is not None:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": [list(kv) for kv in reduced["device_ops"][:10]],
                               "idle_gaps": [list(kv) for kv in reduced["idle_gaps"][:10]]}
    if ctx["set"]:
        result["set"] = ctx["set"]  # not the cell as its files have it
    if rehearse:
        # a CPU run shows control flow: the readers ran, their numbers are not device metrics
        result.update(rehearsal=True, metrics={}, metrics_read=sorted(metrics))
    print(json.dumps(result), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", help="builder's mode: comma-separated offered rates")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=JSON",
                    help="builder's mode: replace one value of the cell's files for this process")
    args = ap.parse_args()
    execute(*open_cell(args.workload, args.seed, args.seconds, bool(args.trace), rates(args.sweep),
                       assignments=args.set))


if __name__ == "__main__":
    main()
