"""Tier-1 wiring for dslint (r11 tentpole): the repo stays lint-clean,
each checker demonstrably catches its violation class (fixture pairs under
tests/unit/analysis/fixtures/), suppressions demand a reason, and the JSON
output is byte-identical across runs.

Same pattern as the old test_atomic_writes.py: the
CLI module is loaded by path, so this also covers the standalone import
trick (dslint never imports jax — that is what keeps the full-repo run
inside its 5 s budget)."""

import importlib.util
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
FIXTURES = os.path.join(os.path.dirname(__file__), "analysis", "fixtures")


def _load_cli():
    path = os.path.join(REPO_ROOT, "scripts", "dslint.py")
    spec = importlib.util.spec_from_file_location("dslint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(paths, root, checkers=None):
    return _load_cli().run_dslint(paths, root=root, checkers=checkers)


def _findings(subdir, checkers=None):
    root = os.path.join(FIXTURES, subdir)
    return _run([root], root=root, checkers=checkers).findings


def _by_checker(findings, name):
    return [f for f in findings if f.checker == name]


# --------------------------------------------------------------- the repo

def test_repo_is_lint_clean():
    runner = _run(["deepspeed_tpu", "scripts"], root=REPO_ROOT)
    assert not runner.findings, "\n".join(f.human() for f in runner.findings)
    # the checkers really ran
    assert runner.files, "nothing scanned?"
    assert runner.suppressed_count > 0, \
        "the repo carries documented suppressions; zero honored means the " \
        "marker scan broke"


def test_cli_exit_codes_and_speed():
    import shutil
    import time
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")
    shutil.rmtree(os.path.join(REPO_ROOT, ".dslint_cache"), ignore_errors=True)

    def timed(*extra):
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
             *extra, "deepspeed_tpu", "scripts"],
            cwd=REPO_ROOT, capture_output=True, text=True, env=env,
            timeout=60)
        return r, time.perf_counter() - t0

    ok, cold_s = timed()
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # the stated contract is <5s over the repo; 15s of slack absorbs CI
    # load while still catching a checker that regresses to a crawl
    assert cold_s < 15, f"full-repo dslint took {cold_s:.1f}s"
    # incremental cache (r17): the warm run replays per-file findings
    # keyed on content hashes — measurably faster, identical verdict
    warm, warm_s = timed()
    assert warm.returncode == 0, warm.stdout + warm.stderr
    assert warm_s < cold_s / 2, \
        f"warm dslint ({warm_s:.2f}s) not measurably faster than cold " \
        f"({cold_s:.2f}s) — cache miss?"
    nocache, nocache_s = timed("--no-cache")
    assert nocache.returncode == 0
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
         "--no-cache",  # keep the committed fixture tree pristine
         "--root", os.path.join(FIXTURES, "determinism"),
         os.path.join(FIXTURES, "determinism")],
        cwd=REPO_ROOT, capture_output=True, text=True, env=env, timeout=60)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "[determinism]" in bad.stdout


def test_cache_warm_json_byte_identical_and_invalidates(tmp_path):
    """The cache replays byte-identical --json, and a content change is a
    miss (per-file hash keying), never a stale verdict."""
    import shutil
    fixture = os.path.join(FIXTURES, "kvlife")
    root = tmp_path / "tree"
    shutil.copytree(fixture, root)
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")

    def run_json():
        return subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
             "--json", "--root", str(root), "--checkers", "kv-lifetime",
             str(root)],
            cwd=REPO_ROOT, capture_output=True, env=env, timeout=60)

    cold = run_json()
    warm = run_json()
    assert cold.stdout == warm.stdout, "warm replay diverged from cold run"
    assert (root / ".dslint_cache" / "cache.json").exists()
    doc = json.loads(cold.stdout)
    assert doc["findings"], "kvlife fixture must produce findings"
    # edit the violating file: the fix must be SEEN (cache invalidated)
    viol = root / "deepspeed_tpu" / "serving" / "violating.py"
    viol.write_text("def fine():\n    return 0\n")
    fixed = run_json()
    assert json.loads(fixed.stdout)["findings"] == []


def test_json_output_byte_identical_across_runs():
    base = [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
            "--json", "deepspeed_tpu", "scripts"]
    # LIVE determinism first — --no-cache, or a warm replay would make
    # this comparison vacuous (cached bytes == cached bytes always)
    live = [subprocess.run(base + ["--no-cache"], cwd=REPO_ROOT,
                           capture_output=True, timeout=60).stdout
            for _ in range(2)]
    assert live[0] == live[1], "dslint --json is not deterministic"
    # replay fidelity: the cached path must emit the live bytes exactly
    warm = subprocess.run(base, cwd=REPO_ROOT, capture_output=True,
                          timeout=60).stdout
    assert warm == live[0], "cache replay diverged from the live run"
    doc = json.loads(live[0])
    assert doc["findings"] == []
    assert doc["version"] == 1


# ------------------------------------------------- per-checker fixtures

def test_determinism_checker_fixtures():
    f = _findings("determinism", checkers=["determinism"])
    bad = _by_checker(f, "determinism")
    assert {x.path for x in bad} == {"violating.py"}
    msgs = "\n".join(x.message for x in bad)
    assert "wall-clock" in msgs
    assert "filesystem-dependent" in msgs
    assert "global RNG" in msgs
    assert len([x for x in bad if "global RNG" in x.message]) == 2
    # iteration, selection, and `== expected` (list equality is
    # order-sensitive; only `in` membership is sanctioned on a listing)
    assert len([x for x in bad if "filesystem-dependent" in x.message]) == 3


def test_crash_transparency_checker_fixtures():
    f = _findings("crash", checkers=["crash-transparency"])
    bad = _by_checker(f, "crash-transparency")
    assert len(bad) == 3, [x.human() for x in bad]
    assert all(x.path == "deepspeed_tpu/serving/violating.py" for x in bad)
    assert all("InjectedCrash" in x.message for x in bad)
    # beyond the plain swallow: a trailing bare raise does not count when a
    # conditional return can bypass it, nor when a branch raises a
    # DIFFERENT exception (laundering the crash into a retryable type)
    assert bad[0].line < bad[1].line < bad[2].line


def test_fault_sites_checker_fixtures():
    bad = _by_checker(_findings("faultsites_bad", checkers=["fault-sites"]),
                      "fault-sites")
    msgs = [x.message for x in bad]
    assert any("ckpt.not_a_site" in m for m in msgs), msgs
    assert any("serving.also_missing" in m for m in msgs), msgs
    assert any("swap.read" in m and "no production probe" in m
               for m in msgs), msgs
    clean = _by_checker(_findings("faultsites_clean", checkers=["fault-sites"]),
                        "fault-sites")
    assert clean == []


def test_event_registry_checker_fixtures():
    bad = _by_checker(_findings("events_bad", checkers=["event-registry"]),
                      "event-registry")
    msgs = "\n".join(x.message for x in bad)
    assert "serving/not_registered" in msgs
    assert "serving/phase/" in msgs          # dynamic family unregistered
    assert "serving/dead" in msgs            # registered, never emitted
    clean = _by_checker(_findings("events_clean", checkers=["event-registry"]),
                        "event-registry")
    assert clean == []


def test_atomic_write_checker_fixtures():
    f = _findings("atomic", checkers=["atomic-write"])
    bad = _by_checker(f, "atomic-write")
    assert {x.path for x in bad} == {"deepspeed_tpu/checkpoint/violating.py"}
    assert any("open" in x.message for x in bad)
    assert any("savez" in x.message for x in bad)
    assert len(bad) == 2


def test_kv_lifetime_checker_fixtures():
    f = _findings("kvlife", checkers=["kv-lifetime"])
    bad = _by_checker(f, "kv-lifetime")
    assert {x.path for x in bad} == {"deepspeed_tpu/serving/violating.py"}
    msgs = "\n".join(x.message for x in bad)
    # the flow-sensitive classes: leak on the exception edge, discarded
    # result, a can-raise statement before the None-guard, and a
    # conditional return that walks out holding the pages
    assert len(bad) == 4, [x.human() for x in bad]
    assert "exception exit" in msgs
    assert "discarded" in msgs
    assert "function exit" in msgs


def test_state_machine_checker_fixtures():
    f = _findings("statemachine", checkers=["state-machine"])
    bad = _by_checker(f, "state-machine")
    assert {x.path for x in bad} == {"deepspeed_tpu/serving/violating.py"}
    msgs = "\n".join(x.message for x in bad)
    assert len(bad) == 4, [x.human() for x in bad]
    assert "missing member(s): DRAINING" in msgs      # table exhaustiveness
    assert "direct state write" in msgs               # bypassed transition
    assert "declared unreachable" in msgs             # undeclared target
    assert "state dispatch over PhaseState" in msgs   # partial dispatch


def test_crash_transparency_interproc_fixtures():
    f = _findings("crashhop", checkers=["crash-transparency-interproc"])
    bad = _by_checker(f, "crash-transparency-interproc")
    assert len(bad) == 1, [x.human() for x in bad]
    assert bad[0].path == "deepspeed_tpu/serving/violating.py"
    assert "emit_swallow" in bad[0].message
    assert "one hop down" in bad[0].message
    # clean.py calls the re-raising helper from a guarded try AND the
    # swallowing helper outside any guard — neither is a finding


def test_flow_checkers_deterministic_under_shuffled_file_order():
    """CFG/call-graph determinism: the same file set fed in any argument
    order produces identical findings (the index and walk both sort)."""
    root = os.path.join(FIXTURES, "statemachine")
    files = []
    for dirpath, _dirs, names in os.walk(root):
        files += [os.path.join(dirpath, n) for n in names
                  if n.endswith(".py")]
    checkers = ["kv-lifetime", "state-machine",
                "crash-transparency-interproc"]
    a = _run(sorted(files), root=root, checkers=checkers)
    b = _run(sorted(files, reverse=True), root=root, checkers=checkers)
    assert a.to_json() == b.to_json()
    assert [f.human() for f in a.findings] == [f.human() for f in b.findings]


def test_state_machines_doc_drift_is_a_finding(tmp_path):
    """Sabotage: edit a declared transition table without --sync and the
    committed STATE_MACHINES.md must become a finding."""
    pkg = tmp_path / "deepspeed_tpu" / "serving"
    pkg.mkdir(parents=True)
    module = pkg / "states.py"
    module.write_text(
        "import enum\n\n\n"
        "class GateState(enum.Enum):\n"
        "    OPEN = 'open'\n"
        "    SHUT = 'shut'\n\n\n"
        "_ALLOWED = {\n"
        "    GateState.OPEN: {GateState.SHUT},\n"
        "    GateState.SHUT: {GateState.OPEN},\n"
        "}\n\n\n"
        "class Gate:\n"
        "    def __init__(self):\n"
        "        self.state = GateState.OPEN\n\n"
        "    def to(self, state, ts):\n"
        "        self.state = state\n")
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")
    sync = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
         "--sync-state-machines", "--root", str(tmp_path),
         str(tmp_path / "deepspeed_tpu")],
        cwd=REPO_ROOT, capture_output=True, text=True, env=env, timeout=60)
    assert sync.returncode == 0, sync.stdout + sync.stderr
    assert (tmp_path / "docs" / "STATE_MACHINES.md").exists()
    clean = _run([str(tmp_path / "deepspeed_tpu")], root=str(tmp_path),
                 checkers=["state-machine"]).findings
    assert clean == [], [x.human() for x in clean]
    # sabotage the TABLE (not the doc): SHUT becomes terminal
    module.write_text(module.read_text().replace(
        "GateState.SHUT: {GateState.OPEN},", "GateState.SHUT: set(),"))
    drifted = _run([str(tmp_path / "deepspeed_tpu")], root=str(tmp_path),
                   checkers=["state-machine"]).findings
    assert any("differs from the declared transition tables" in x.message
               for x in drifted), [x.human() for x in drifted]


def test_suppressions_require_reason_and_known_checker():
    f = _findings("suppression")
    sup = _by_checker(f, "suppression")
    msgs = "\n".join(x.message for x in sup)
    assert "without a reason" in msgs
    assert "unknown checker" in msgs
    # a reasonless/unknown marker does NOT suppress the underlying finding
    det = _by_checker(f, "determinism")
    assert {x.path for x in det} == {"violating.py"}
    assert len(det) == 2
    # clean.py: well-formed marker, nothing surfaced
    assert not any(x.path == "clean.py" for x in f)
    # serving/multi.py: two markers on ONE line (crash-transparency +
    # determinism), each with its own reason — both must suppress (the
    # first marker's reason must not swallow the second marker)
    assert not any(x.path == "serving/multi.py" for x in f), \
        [x.human() for x in f]


def test_partial_scan_skips_no_emitter_direction():
    """`dslint.py path/to/one_file.py` must not spray 'dead registry
    entry' findings — absent emitters are an artifact of scan scope."""
    runner = _run([os.path.join("deepspeed_tpu", "checkpoint", "engine.py")],
                  root=REPO_ROOT, checkers=["event-registry"])
    assert not any("no emitter" in x.message for x in runner.findings), \
        [x.human() for x in runner.findings]


def test_unknown_checker_name_is_an_error():
    """A typo'd --checkers must not silently lint nothing and exit 0."""
    import pytest
    with pytest.raises(ValueError, match="unknown checker"):
        _run(["deepspeed_tpu"], root=REPO_ROOT, checkers=["determinsm"])
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "dslint.py"),
         "--checkers", "crash-transparancy", "deepspeed_tpu"],
        cwd=REPO_ROOT, capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "unknown checker" in r.stderr


def test_doc_table_drift_is_a_finding(tmp_path):
    """Sabotage the committed OBSERVABILITY.md event table in a copy of the
    tree layout and the event-registry checker must fail it."""
    import shutil
    root = tmp_path
    (root / "deepspeed_tpu" / "telemetry").mkdir(parents=True)
    shutil.copy(os.path.join(REPO_ROOT, "deepspeed_tpu", "telemetry",
                             "event_registry.py"),
                root / "deepspeed_tpu" / "telemetry" / "event_registry.py")
    (root / "docs").mkdir()
    with open(os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    (root / "docs" / "OBSERVABILITY.md").write_text(
        doc.replace("| `fleet/dispatch` | event |",
                    "| `fleet/dispatch` | DRIFTED |"))
    emitter = root / "deepspeed_tpu" / "emitter.py"
    emitter.write_text("def f(emit):\n    emit('fleet/dispatch', 1.0)\n")
    f = _run([str(root / "deepspeed_tpu")], root=str(root),
             checkers=["event-registry"]).findings
    assert any("differs from" in x.message for x in f), \
        [x.human() for x in f]
