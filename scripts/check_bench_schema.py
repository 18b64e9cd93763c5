#!/usr/bin/env python
"""Validate every BENCH_*.json at the repo root against a per-file schema.

The round-5 advisor flagged README-vs-artifact drift: a bench script's
output format changes, the committed artifact silently keeps the old shape,
and downstream readers (README tables, the driver, the next round's
reviewer) disagree about what a field means.  This checker pins each
artifact family to an explicit schema and runs as a tier-1 test
(tests/unit/test_bench_schema.py), so a bench-script schema change that
forgets to regenerate its committed artifact fails CI instead of shipping.

Schema language (deliberately tiny, no external deps):
  tuple of types            — isinstance check ("number" = int/float, bool excluded)
  dict                      — nested object; keys prefixed '?' are optional;
                              other keys on the object are ALLOWED (schemas
                              pin what readers rely on, not every field)
  [elem_spec]               — list whose every element matches elem_spec
  callable(value) -> error  — custom predicate, returns None or error string
  ("nullable", spec)        — None or spec
"""

import glob
import json
import os
import sys

NUM = (int, float)
STR = (str, )
INT = (int, )
BOOL = (bool, )
DICT = (dict, )


def _pct_ordered(p):
    """Percentile summary: p50 <= p95 <= p99 when present."""
    if not isinstance(p, dict):
        return f"expected percentile dict, got {type(p).__name__}"
    for k in ("p50", "p95", "p99", "n"):
        if k not in p:
            return f"missing percentile key {k!r}"
    vals = [p["p50"], p["p95"], p["p99"]]
    if any(v is None for v in vals):
        return None if all(v is None for v in vals) else f"mixed null percentiles: {vals}"
    if not (p["p50"] <= p["p95"] <= p["p99"]):
        return f"percentiles out of order: {vals}"
    return None


_SWEEP_POINT = {
    "arrival_rate": NUM, "offered_rps": NUM, "submitted": INT, "completed": INT,
    "rejected": INT, "timed_out": INT, "preemptions": INT, "deadline_met": INT,
    "rejection_rate": NUM, "preemption_rate": NUM, "goodput_rps": NUM,
    "ttft": _pct_ordered, "tpot": _pct_ordered, "queue_wait": _pct_ordered,
}

_LEGACY_THROUGHPUT = {"metric": STR, "value": NUM, "unit": STR, "extra": DICT}

_ROUTER_POINT = {
    "policy": STR, "n_replicas": INT, "arrival_rate": NUM, "offered_rps": NUM,
    "submitted": INT, "completed": INT, "timed_out": INT, "rejected": INT,
    "dispatches": INT, "failovers": INT, "deadline_met": INT, "goodput_rps": NUM,
    "affinity": {"hits": INT, "misses": INT, "hit_rate": ("nullable", NUM)},
    "migration": {"started": INT, "chunks": INT, "completed": INT,
                  "fallbacks": INT, "failover_reuse": INT,
                  "migrated_requests": INT, "kv_imports": INT,
                  "import_fallbacks": INT},
    "failover": {"kills": INT, "requeued": INT, "recovery_times": [NUM],
                 "unrecovered": INT},
    "ttft": _pct_ordered, "tpot": _pct_ordered, "e2e": _pct_ordered,
}


def _disagg_record(v):
    """The disaggregation receipt (bench_router.py run_disaggregation_leg):
    the 2-prefill + 2-decode fleet must beat the monolithic 4-replica one
    on p99 TTFT AND p99 TPOT over the same mixed long/short workload, with
    zero output divergence, migrations actually completing through the
    KV-import fast path, and the per-request migration cost materialized
    as exactly one ``phase/migrating`` telemetry span per migrated
    request.  A committed artifact where disaggregation lost (or lied
    about outputs) is a regression, not a benchmark."""
    if not isinstance(v, dict):
        return f"expected disaggregation object, got {type(v).__name__}"
    for k in ("workload", "roles", "monolithic", "disaggregated",
              "zero_divergence", "divergent_requests", "migration_spans"):
        if k not in v:
            return f"missing disaggregation key {k!r}"
    if v["zero_divergence"] is not True or v["divergent_requests"] != 0:
        return (f"output divergence recorded ({v['divergent_requests']} "
                "request(s)) — the migration identical-outputs contract broke")
    roles = v["roles"]
    if not (isinstance(roles, list) and "prefill" in roles and "decode" in roles):
        return f"roles {roles!r} do not split the fleet into prefill + decode"
    errors = []
    for side in ("monolithic", "disaggregated"):
        _check(v[side], _ROUTER_POINT, f"disaggregation.{side}", errors)
    if errors:
        return "; ".join(errors)
    mono, dis = v["monolithic"], v["disaggregated"]
    if mono["completed"] != dis["completed"]:
        return (f"not an equal-completion pair: monolithic {mono['completed']} "
                f"vs disaggregated {dis['completed']}")
    mig = dis["migration"]
    if not (mig["completed"] > 0 and mig["kv_imports"] > 0):
        return f"migration never took the KV-import fast path: {mig}"
    spans = v["migration_spans"]
    n_spans = spans.get("count", 0)
    # AT LEAST one positive-width span per migrated request; a request
    # legitimately re-enters MIGRATING after a transient fallback (each
    # interval folds to its own span), so exact equality only holds on a
    # fallback-free run
    if n_spans < mig["migrated_requests"] or mig["migrated_requests"] <= 0:
        return (f"migrating phase spans ({n_spans}) < migrated requests "
                f"({mig['migrated_requests']}) — migration cost invisible "
                "in telemetry")
    if mig["fallbacks"] == 0 and n_spans != mig["migrated_requests"]:
        return (f"fallback-free run but migrating spans ({n_spans}) != "
                f"migrated requests ({mig['migrated_requests']})")
    for k in ("ttft", "tpot"):
        m, d = mono[k]["p99"], dis[k]["p99"]
        if m is None or d is None or not d < m:
            return f"disaggregated p99 {k} {d} does not beat monolithic {m}"
    return None


def _autoscale_record(v):
    """The overload-control-plane receipt (bench_router.py
    run_autoscale_leg): the SLA autoscaler must beat static-max
    provisioning by >= 30% replica-steps over the same flash crowd while
    the premium tenant's SLA holds, with zero output divergence (brownout
    may only TRUNCATE best-effort outputs, never change a token), every
    brownout rung entered also exited by end of sweep, per-tenant
    accounting closed, and the autoscaled leg byte-identical when
    repeated.  A committed artifact where the control plane lost any of
    those is a regression, not a benchmark."""
    if not isinstance(v, dict):
        return f"expected autoscale object, got {type(v).__name__}"
    for k in ("workload", "tenants", "static", "autoscaled",
              "replica_step_saving", "premium_sla_held",
              "divergent_requests", "zero_divergence",
              "determinism_repeat_identical", "brownout"):
        if k not in v:
            return f"missing autoscale key {k!r}"
    if v["determinism_repeat_identical"] is not True:
        return "autoscaled flash-crowd leg not byte-identical across runs"
    if v["zero_divergence"] is not True or v["divergent_requests"] != 0:
        return (f"output divergence recorded ({v['divergent_requests']} "
                "request(s)) between static-max and autoscaled provisioning")
    saving = v["replica_step_saving"]
    if not isinstance(saving, (int, float)) or isinstance(saving, bool) \
            or saving < 0.30:
        return (f"replica_step_saving {saving!r} < 0.30 — the autoscaler "
                "must save >= 30% replica-steps vs static max")
    if v["premium_sla_held"] is not True:
        return "premium tenant SLA not held across the flash crowd"
    errors = []
    for side in ("static", "autoscaled"):
        rec = v[side]
        _check(rec, {"replica_steps": INT, "rounds": INT, "submitted": INT,
                     "completed": INT, "tenants": DICT,
                     "ttft": _pct_ordered}, f"autoscale.{side}", errors)
        if errors:
            return "; ".join(errors)
        for name, t in rec["tenants"].items():
            if t.get("closed") is not True:
                return (f"autoscale.{side}: tenant {name!r} accounting did "
                        "not close (submitted != completed+timed_out+rejected)")
    if not (v["static"]["replica_steps"] > v["autoscaled"]["replica_steps"] > 0):
        return (f"replica-step counts not ordered: static "
                f"{v['static']['replica_steps']} vs autoscaled "
                f"{v['autoscaled']['replica_steps']}")
    bo = v["brownout"]
    if not isinstance(bo, dict) or bo.get("balanced") is not True:
        return f"brownout ladder not balanced (a rung entered was never exited): {bo}"
    if not bo.get("entered"):
        return "brownout ladder never engaged — the flash crowd did not exercise degradation"
    asc = v["autoscaled"].get("autoscaler") or {}
    if not (asc.get("n_up", 0) >= 1 and asc.get("n_down", 0) >= 1):
        return ("autoscaler never scaled both up and down: "
                f"{asc.get('decisions')}")
    return None


def _prefix_directory_record(v):
    """The fleet-prefix-directory receipt (bench_router.py
    run_prefix_directory_leg, docs/SERVING.md "Prefix directory"): over
    the same diurnal shared-prefix workload, directory routing must reach
    a >= 0.95 affinity hit rate (beating the recorded probe baseline),
    beat probe-based prefix_affinity on p99 TTFT at equal goodput (same
    completions, same deadline hits), complete >= 1 cold-replica KV
    prefix import through the fast path, keep outputs byte-identical
    between the legs, and repeat byte-identically.  A committed artifact
    where the directory lost any of those is a regression, not a
    benchmark."""
    if not isinstance(v, dict):
        return f"expected prefix_directory object, got {type(v).__name__}"
    for k in ("workload", "probe", "directory", "probe_hit_rate",
              "directory_hit_rate", "prefix_imports", "zero_divergence",
              "divergent_requests", "determinism_repeat_identical"):
        if k not in v:
            return f"missing prefix_directory key {k!r}"
    if v["determinism_repeat_identical"] is not True:
        return "prefix_directory leg not byte-identical across runs"
    if v["zero_divergence"] is not True or v["divergent_requests"] != 0:
        return (f"output divergence recorded ({v['divergent_requests']} "
                "request(s)) between probe and directory routing")
    hr = v["directory_hit_rate"]
    if not isinstance(hr, (int, float)) or isinstance(hr, bool) or hr < 0.95:
        return (f"directory hit rate {hr!r} < 0.95 — the directory must "
                "turn probe-level affinity into cluster-wide warmth")
    phr = v["probe_hit_rate"]
    if not isinstance(phr, (int, float)) or isinstance(phr, bool) or not phr < hr:
        return f"probe baseline hit rate {phr!r} not below directory {hr}"
    if not (isinstance(v["prefix_imports"], int) and v["prefix_imports"] >= 1):
        return ("no cold-replica KV prefix import completed through the "
                "fast path — the cluster-wide-warmth half never engaged")
    errors = []
    for side in ("probe", "directory"):
        _check(v[side], _ROUTER_POINT, f"prefix_directory.{side}", errors)
    if errors:
        return "; ".join(errors)
    probe, d = v["probe"], v["directory"]
    if (d["completed"], d["deadline_met"]) != \
            (probe["completed"], probe["deadline_met"]):
        return (f"not an equal-goodput pair: directory completed/met "
                f"{d['completed']}/{d['deadline_met']} vs probe "
                f"{probe['completed']}/{probe['deadline_met']}")
    m, dd = probe["ttft"]["p99"], d["ttft"]["p99"]
    if m is None or dd is None or not dd < m:
        return f"directory p99 TTFT {dd} does not beat probe {m}"
    pfx = d.get("prefix")
    if not isinstance(pfx, dict) or pfx.get("imports") != v["prefix_imports"]:
        return (f"directory-side prefix accounting {pfx!r} disagrees with "
                f"the record's prefix_imports {v['prefix_imports']}")
    return None


def _partition_record(v):
    """The partition-tolerance receipt (bench_router.py run_partition_leg,
    docs/SERVING.md "Control-plane transport"): the same diurnal workload
    over a perfect vs a degraded control fabric (5% loss + one partition
    window with lease expiry, re-dispatch and fencing firing mid-run).
    The committed record must show ZERO output divergence (degradation is
    allowed to cost time, never tokens), goodput within the declared
    degradation bound of the clean run, the loss/partition/lease machinery
    actually exercised, and the lossy leg byte-identical when repeated."""
    if not isinstance(v, dict):
        return f"expected partition object, got {type(v).__name__}"
    for k in ("workload", "lease", "loss_p", "partition_window", "clean",
              "lossy", "goodput_ratio", "goodput_bound", "zero_divergence",
              "divergent_requests", "determinism_repeat_identical",
              "control_plane"):
        if k not in v:
            return f"missing partition key {k!r}"
    if v["determinism_repeat_identical"] is not True:
        return "lossy partition leg not byte-identical across runs"
    if v["zero_divergence"] is not True or v["divergent_requests"] != 0:
        return (f"output divergence recorded ({v['divergent_requests']} "
                "request(s)) — the degraded control plane changed tokens")
    bound = v["goodput_bound"]
    if not isinstance(bound, (int, float)) or isinstance(bound, bool) \
            or not 0 < bound <= 1:
        return f"goodput_bound {bound!r} is not a declared ratio in (0, 1]"
    ratio = v["goodput_ratio"]
    if not isinstance(ratio, (int, float)) or isinstance(ratio, bool) \
            or ratio < bound:
        return (f"goodput ratio {ratio!r} under the declared degradation "
                f"bound {bound} — the fleet degraded more than it promised")
    errors = []
    for side in ("clean", "lossy"):
        _check(v[side], _ROUTER_POINT, f"partition.{side}", errors)
    if errors:
        return "; ".join(errors)
    clean, lossy = v["clean"], v["lossy"]
    if clean["completed"] != lossy["completed"] or lossy["timed_out"] or \
            lossy["rejected"]:
        return (f"not an equal-completion pair: clean {clean['completed']} "
                f"vs lossy {lossy['completed']} (timed_out="
                f"{lossy['timed_out']}, rejected={lossy['rejected']}) — "
                "degradation may only cost time")
    cp = v["control_plane"]
    tr = cp.get("transport") if isinstance(cp, dict) else None
    if not isinstance(tr, dict) or tr.get("dropped", 0) <= 0 \
            or tr.get("partition_dropped", 0) <= 0:
        return (f"the degraded leg exercised no loss/partition: {tr} — "
                "an unperturbed 'degradation' receipt proves nothing")
    if cp.get("lease_expirations", 0) < 1:
        return ("no lease expired inside the partition window — the "
                "split-brain machinery (expiry/re-dispatch/fencing) did "
                "not fire in the committed receipt")
    return None


def _control_loops_record(v):
    """The closed-loop-control receipt (bench_router.py
    run_control_loops_leg, docs/SERVING.md "Closed-loop control"), three
    sub-records.  ``adaptive_lease``: under heavy steps + control-plane
    loss the static lease must record >= 1 FALSE expiry while the
    adaptive lease (same base numbers) records ZERO — yet still detects
    a real injected kill inside its widened-lease band, with zero output
    divergence and byte-identical repeats.  ``predictive``: the
    arrival-rate forecast must beat reactive autoscaling on premium p99
    TTFT at near-equal replica-step spend (<= the declared spend bound),
    zero divergence, byte-identical repeats.  ``kv_quota``: the page
    quota must actually reject (>= 1), every tenant's accounting must
    close under rejection, and the unbounded tenant must complete all of
    its submitted work."""
    if not isinstance(v, dict):
        return f"expected control_loops object, got {type(v).__name__}"
    for k in ("adaptive_lease", "predictive", "kv_quota"):
        if not isinstance(v.get(k), dict):
            return f"missing/invalid control_loops sub-record {k!r}"
    al = v["adaptive_lease"]
    for k in ("workload", "loss_p", "lease", "max_scale", "static",
              "adaptive", "static_false_expiries", "adaptive_false_expiries",
              "lease_resizes", "kill", "zero_divergence",
              "divergent_requests", "determinism_repeat_identical"):
        if k not in al:
            return f"missing adaptive_lease key {k!r}"
    if al["determinism_repeat_identical"] is not True:
        return "adaptive-lease leg not byte-identical across runs"
    if not (isinstance(al["static_false_expiries"], int)
            and al["static_false_expiries"] >= 1):
        return ("static lease recorded no false expiry under heavy steps "
                f"({al['static_false_expiries']!r}) — the adaptive "
                "comparison is vacuous")
    if al["adaptive_false_expiries"] != 0:
        return (f"adaptive lease false-fenced "
                f"{al['adaptive_false_expiries']!r} time(s) — sizing must "
                "absorb benign heartbeat loss")
    if not (isinstance(al["lease_resizes"], int) and al["lease_resizes"] >= 1):
        return "adaptive lease never resized — the gap EWMA fed nothing"
    kill = al["kill"]
    if not isinstance(kill, dict):
        return f"adaptive_lease.kill is not an object: {kill!r}"
    lat, bound = kill.get("latency"), kill.get("bound")
    for name, x in (("latency", lat), ("bound", bound)):
        if not isinstance(x, (int, float)) or isinstance(x, bool):
            return f"adaptive_lease.kill.{name} is not a number ({x!r})"
    if lat > bound:
        return (f"real kill detected {lat} after injection, outside the "
                f"widened-lease band {bound} — adaptive sizing traded "
                "real-death detection away")
    if al["zero_divergence"] is not True or al["divergent_requests"] != 0:
        return (f"output divergence recorded ({al['divergent_requests']} "
                "request(s)) between static and adaptive lease sizing")
    pr = v["predictive"]
    for k in ("workload", "reactive", "predictive", "premium_p99_ttft",
              "spend_ratio", "spend_bound", "zero_divergence",
              "divergent_requests", "determinism_repeat_identical"):
        if k not in pr:
            return f"missing predictive key {k!r}"
    if pr["determinism_repeat_identical"] is not True:
        return "predictive autoscale leg not byte-identical across runs"
    if pr["zero_divergence"] is not True or pr["divergent_requests"] != 0:
        return (f"output divergence recorded ({pr['divergent_requests']} "
                "request(s)) between reactive and predictive autoscaling")
    ttfts = pr["premium_p99_ttft"]
    if not isinstance(ttfts, dict):
        return f"premium_p99_ttft is not an object: {ttfts!r}"
    re_p99, pr_p99 = ttfts.get("reactive"), ttfts.get("predictive")
    for name, x in (("reactive", re_p99), ("predictive", pr_p99)):
        if not isinstance(x, (int, float)) or isinstance(x, bool):
            return f"premium_p99_ttft.{name} is not a number ({x!r})"
    if not pr_p99 < re_p99:
        return (f"predictive premium p99 TTFT {pr_p99} does not beat "
                f"reactive {re_p99} — the forecast bought nothing")
    sb = pr["spend_bound"]
    if not isinstance(sb, (int, float)) or isinstance(sb, bool) or sb < 1.0:
        return f"spend_bound {sb!r} is not a declared ratio >= 1"
    sr = pr["spend_ratio"]
    if not isinstance(sr, (int, float)) or isinstance(sr, bool) or sr > sb:
        return (f"predictive replica-step spend ratio {sr!r} over the "
                f"declared bound {sb} — not a near-equal-spend win")
    kq = v["kv_quota"]
    for k in ("workload", "tenants", "fleet", "rejects",
              "accounting_closed", "unbounded_tenant_unharmed"):
        if k not in kq:
            return f"missing kv_quota key {k!r}"
    if not (isinstance(kq["rejects"], int) and kq["rejects"] >= 1):
        return ("the KV page quota never rejected — the quota loop went "
                "unexercised in the committed receipt")
    if kq["accounting_closed"] is not True:
        return ("tenant accounting did not close under quota rejection "
                "(submitted != completed+timed_out+rejected)")
    if kq["unbounded_tenant_unharmed"] is not True:
        return ("the unbounded tenant lost work to its neighbor's quota — "
                "quotas must isolate, not leak")
    fleet = kq["fleet"]
    if not isinstance(fleet, dict) or \
            fleet.get("kv_quota_rejects") != kq["rejects"]:
        return (f"fleet-side quota accounting "
                f"{fleet.get('kv_quota_rejects')!r} disagrees with the "
                f"record's rejects {kq['rejects']!r}")
    errors = []
    for side, rec in (("adaptive_lease.static", al["static"]),
                      ("adaptive_lease.adaptive", al["adaptive"]),
                      ("predictive.reactive", pr["reactive"]),
                      ("predictive.predictive", pr["predictive"]),
                      ("kv_quota.fleet", fleet)):
        _check(rec, _ROUTER_POINT, f"control_loops.{side}", errors)
    if errors:
        return "; ".join(errors)
    return None


def _router_sweep_invariants(v):
    """The fleet bench's acceptance receipts: >= 3 points, the
    prefix_affinity policy actually hit its cache somewhere, and every
    scripted kill recovered in finite time."""
    import math
    if not isinstance(v, list) or len(v) < 3:
        return "sweep must cover >= 3 (replica count x policy) points"
    aff = [p for p in v if isinstance(p, dict) and p.get("policy") == "prefix_affinity"]
    if not aff:
        return "sweep must include the prefix_affinity policy"
    if not any(((p.get("affinity") or {}).get("hit_rate") or 0) > 0 for p in aff):
        return "prefix_affinity sweep points record no affinity hits (hit_rate > 0)"
    kills = 0
    for p in v:
        fo = p.get("failover") if isinstance(p, dict) else None
        if not isinstance(fo, dict):
            continue
        kills += fo.get("kills", 0)
        if fo.get("unrecovered", 0):
            return f"unrecovered failover at policy={p.get('policy')} " \
                   f"n_replicas={p.get('n_replicas')}"
        times = fo.get("recovery_times", [])
        if fo.get("kills", 0) and (len(times) != fo["kills"] or
                                   any(not (isinstance(t, (int, float)) and math.isfinite(t))
                                       for t in times)):
            return f"kill without a finite recovery time at policy={p.get('policy')} " \
                   f"n_replicas={p.get('n_replicas')}: {times}"
    if kills == 0:
        return "no sweep point exercised the kill schedule"
    return None

def _spec_pair(v):
    """The speculative-decoding receipt (bench_serving.py run_spec_pair):
    greedy parity must hold, acceptance_rate must be a real ratio, and the
    spec-on column must not be SLOWER per token than spec-off at equal
    goodput (same completions, same deadline hits) — a committed artifact
    where speculation lost is a regression, not a benchmark."""
    if not isinstance(v, dict):
        return f"expected spec-pair object, got {type(v).__name__}"
    for k in ("greedy_parity", "acceptance_rate", "proposed", "accepted",
              "rollback_pages", "max_draft", "drafter", "off", "on"):
        if k not in v:
            return f"missing spec-pair key {k!r}"
    if v["greedy_parity"] is not True:
        return "greedy_parity must be true (spec-on output diverged)"
    ar = v["acceptance_rate"]
    if not isinstance(ar, (int, float)) or isinstance(ar, bool) or not (0.0 <= ar <= 1.0):
        return f"acceptance_rate {ar!r} not in [0, 1]"
    if not (isinstance(v["proposed"], int) and v["proposed"] > 0):
        return "spec pair proposed no draft tokens — speculation never engaged"
    errors = []
    for side in ("off", "on"):
        _check(v[side], _SWEEP_POINT, f"spec.{side}", errors)
    if errors:
        return "; ".join(errors)
    on, off = v["on"], v["off"]
    if (on["completed"], on["deadline_met"]) != (off["completed"], off["deadline_met"]):
        return (f"not an equal-goodput pair: on completed/met "
                f"{on['completed']}/{on['deadline_met']} vs off "
                f"{off['completed']}/{off['deadline_met']}")
    p50_on, p50_off = on["tpot"]["p50"], off["tpot"]["p50"]
    if p50_on is None or p50_off is None or p50_on > p50_off:
        return f"spec-on p50 TPOT {p50_on} exceeds spec-off {p50_off}"
    return None


def _validate_attribution(v):
    """The flight-recorder/attribution receipt (bench_router.py
    run_attribution_leg -> BENCH_ROUTER_ATTRIB.json, scripts/why_slow.py,
    docs/OBSERVABILITY.md "Flight recorder"): a lossy/brownout run whose
    per-request slowdown attribution must TILE — every request's named
    causes sum to its e2e within the declared tolerance (re-verified HERE
    from the committed per-request table, not trusted from the summary) —
    with >= 80% of the p99-p50 TTFT gap attributed to named slowdown
    causes, SLO burn-rate alerts firing only inside the injected
    degradation window (and clearing after it), and the whole leg
    byte-identical when repeated."""
    if not isinstance(v, dict):
        return f"expected attribution object, got {type(v).__name__}"
    for k in ("metric", "value", "unit", "schema_version", "workload",
              "degradation", "slo", "attribution", "alerts",
              "determinism_repeat_identical", "recorder"):
        if k not in v:
            return f"missing attribution key {k!r}"
    if v["schema_version"] != 1:
        return f"schema_version {v['schema_version']} != 1"
    if v["determinism_repeat_identical"] is not True:
        return "attribution leg not byte-identical across runs"
    att = v["attribution"]
    if not isinstance(att, dict) or not isinstance(att.get("requests"), list):
        return "attribution record carries no per-request table"
    ver = att.get("verification") or {}
    # the re-check must not trust a loosened tolerance DECLARED BY the
    # artifact itself — that would let a regenerated receipt mask a real
    # attribution gap; the acceptance bar is 1e-6, full stop
    tol = min(float(ver.get("tol", 1e-6)), 1e-6)
    if ver.get("partial_trace"):
        return ("attribution ran on a partial (span-evicted) trace — the "
                "committed receipt must fold a complete one")
    if ver.get("mismatches", 1) != 0:
        return (f"attribution verification recorded {ver.get('mismatches')} "
                "mismatch(es) — causes do not tile e2e")
    # re-verify the tiling from the committed table itself: a summary that
    # CLAIMS zero mismatches over a table that has one is exactly the
    # drift this checker exists for
    for i, r in enumerate(att["requests"]):
        causes = r.get("causes") or {}
        resid = sum(causes.values()) - r.get("e2e", 0.0)
        # the committed values are independently rounded to 9 decimals
        # (each cause + e2e contributes up to 0.5e-9), so pad tol by the
        # worst-case rounding bound — a legitimately-tiled artifact must
        # not fail the re-check on rounding noise alone
        if abs(resid) > tol + 0.5e-9 * (len(causes) + 1):
            return (f"attribution.requests[{i}] (trace {r.get('trace_id')}): "
                    f"causes sum {sum(causes.values())} != e2e {r.get('e2e')} "
                    f"(residual {resid:g} > tol {tol:g})")
    gap = att.get("ttft_gap") or {}
    frac = gap.get("attributed_fraction")
    if not isinstance(frac, (int, float)) or isinstance(frac, bool) \
            or frac < 0.8:
        return (f"ttft_gap.attributed_fraction {frac!r} < 0.8 — the p99-p50 "
                "TTFT gap is not explained by named causes")
    deg = v["degradation"]
    t0, t1 = deg.get("t0"), deg.get("t1")
    if not (isinstance(t0, (int, float)) and isinstance(t1, (int, float))
            and t1 > t0):
        return f"degradation window [{t0}, {t1}] is not a real interval"
    alerts = v["alerts"]
    if not isinstance(alerts, list) or not alerts:
        return ("no SLO alert fired — the injected degradation never "
                "tripped the burn-rate monitor")
    for i, a in enumerate(alerts):
        fired, cleared = a.get("fired_ts"), a.get("cleared_ts")
        if not isinstance(fired, (int, float)) or not t0 <= fired <= t1:
            return (f"alerts[{i}] fired at {fired!r}, outside the injected "
                    f"degradation window [{t0}, {t1}]")
        if not isinstance(cleared, (int, float)) or cleared <= fired:
            return f"alerts[{i}] never cleared (cleared_ts={cleared!r})"
    rec = v["recorder"]
    tracks = rec.get("tracks") if isinstance(rec, dict) else None
    if not isinstance(tracks, dict) or \
            not any(t.startswith("ctrl/") for t in tracks):
        return (f"flight recorder retained no ctrl/* track ({tracks!r}) — "
                "the control plane left no black-box trail")
    return None


_ANATOMY_SEGMENTS = ("admit", "schedule", "draft_plan", "verify_plan",
                     "aot_compile", "compile_wait", "dispatch", "sample_accept",
                     "deliver", "overlap", "bookkeeping", "promote_wait")


def _validate_anatomy_leg(leg, name):
    """One serial/pipelined leg of the step-anatomy receipt: tiling
    re-verified from the committed per-step table (not trusted from the
    summary), ZERO steady-state recompiles, the compile log agreeing with
    the declared counter, and a host-gap fraction for every bucket."""
    if not isinstance(leg, dict):
        return f"legs.{name}: expected object, got {type(leg).__name__}"
    for k in ("steady_state_recompiles", "serving", "kv", "report",
              "anatomy"):
        if k not in leg:
            return f"legs.{name}: missing key {k!r}"
    if leg["steady_state_recompiles"] != 0:
        return (f"legs.{name}: {leg['steady_state_recompiles']} steady-state "
                "recompile(s) after the warm-up boundary — the AOT step set "
                "is not closed (the regression guard this receipt exists for)")
    anatomy = leg["anatomy"]
    steps = anatomy.get("steps") if isinstance(anatomy, dict) else None
    if not isinstance(steps, list) or not steps:
        return f"legs.{name}: anatomy record carries no per-step table"
    # re-verify the tiling from the committed table itself: a summary that
    # CLAIMS tiling over a table that breaks it is exactly the drift this
    # checker exists for.  The acceptance bar is 1e-6, full stop; the
    # committed components are independently rounded to 9 decimals, so pad
    # by their worst-case rounding bound.
    pad = 0.5e-9 * (len(_ANATOMY_SEGMENTS) + 3)
    for i, row in enumerate(steps):
        segs = row.get("segments") or {}
        missing = [s for s in _ANATOMY_SEGMENTS if s not in segs]
        if missing:
            return f"legs.{name}.anatomy.steps[{i}]: missing segment(s) {missing}"
        resid = row.get("wall_s", 0.0) - (row.get("host_gap_s", 0.0)
                                          + sum(segs[s] for s in _ANATOMY_SEGMENTS)
                                          + row.get("device_s", 0.0))
        if abs(resid) > 1e-6 + pad:
            return (f"legs.{name}.anatomy.steps[{i}] ({row.get('key')}): "
                    f"components do not tile wall_s (residual {resid:g})")
    # the compile log must agree with the declared counter; deliberate AOT
    # warm-up compiles (aot=true) are never steady-state entries
    steady = [c for c in (anatomy.get("compiles") or []) if c.get("steady")]
    if len(steady) != leg["steady_state_recompiles"]:
        return (f"legs.{name}: compile log records {len(steady)} steady "
                f"entr(ies) but declares {leg['steady_state_recompiles']}")
    if any(c.get("steady") and c.get("aot")
           for c in (anatomy.get("compiles") or [])):
        return (f"legs.{name}: compile log tags an AOT warm-up compile as a "
                "steady-state recompile — the recorder contract broke")
    shapes = (leg["report"] or {}).get("by_shape")
    if not isinstance(shapes, dict) or not shapes:
        return f"legs.{name}: report carries no per-bucket (by_shape) fold"
    for key, agg in shapes.items():
        frac = agg.get("host_gap_fraction")
        if frac is None and agg.get("wall_s", 0.0) > 0:
            return (f"legs.{name}.by_shape[{key!r}]: no host_gap_fraction "
                    "despite wall time")
        if frac is not None and not (isinstance(frac, (int, float))
                                     and not isinstance(frac, bool)
                                     and 0.0 <= frac <= 1.0):
            return (f"legs.{name}.by_shape[{key!r}]: host_gap_fraction "
                    f"{frac!r} not in [0, 1]")
    rep_ver = (leg["report"] or {}).get("verification") or {}
    if rep_ver.get("mismatches", 1) != 0:
        return (f"legs.{name}: report verification recorded "
                f"{rep_ver.get('mismatches')} mismatch(es) — the committed "
                "receipt must tile")
    return None


def _gap_fraction(leg):
    frac = ((leg.get("report") or {}).get("totals") or {}) \
        .get("host_gap_fraction")
    return frac if isinstance(frac, (int, float)) \
        and not isinstance(frac, bool) else None


def _validate_step_anatomy(v):
    """The step-anatomy receipt (bench_serving.py run_anatomy_leg ->
    BENCH_STEP_ANATOMY.json, scripts/step_anatomy.py, docs/OBSERVABILITY.md
    "Step anatomy"), schema v3: the SAME workload served twice — the
    strictly serial tick loop and the async double-buffered one — each leg
    re-verified for tiling and ZERO steady-state recompiles (the AOT step
    set must be closed in BOTH modes), greedy token streams byte-identical
    between the legs (per request, asserted by the producer and declared
    here), pipelined host-gap fraction no worse than serial, and — when a
    wall-clock comparison section is present — a pipelined ``overlap``
    share of wall time STRICTLY above the serial loop's (which is 0: it
    never runs host work under a dispatch in flight) at equal goodput:
    the loop tax the async dispatch exists to hide under device time.
    (Up to schema v2 the receipt compared host-gap fractions; since the
    tick's ``admit`` and ``deliver`` are segments of the step the gap is
    the caller's loop in both modes and orders nothing.)"""
    if not isinstance(v, dict):
        return f"expected step-anatomy object, got {type(v).__name__}"
    for k in ("metric", "value", "unit", "schema_version", "workload",
              "greedy_parity", "determinism_repeat_identical", "legs",
              "wall"):
        if k not in v:
            return f"missing step-anatomy key {k!r}"
    if v["schema_version"] != 3:
        return f"schema_version {v['schema_version']} != 3"
    if v["greedy_parity"] is not True:
        return ("greedy_parity is not true — the pipelined loop's token "
                "streams diverged from the serial loop's")
    # byte-identical regeneration is a VIRTUAL-clock property: wall-clock
    # receipts carry real timings that legitimately differ across runs
    # (the tiling + recompile bars below still bind them)
    if (v["workload"] or {}).get("virtual_clock") \
            and v["determinism_repeat_identical"] is not True:
        return "virtual-clock anatomy legs not byte-identical across runs"
    legs = v["legs"]
    if not isinstance(legs, dict):
        return f"legs: expected object, got {type(legs).__name__}"
    for name in ("serial", "pipelined"):
        if name not in legs:
            return f"legs: missing leg {name!r}"
        err = _validate_anatomy_leg(legs[name], name)
        if err:
            return err
    g_serial, g_pipe = _gap_fraction(legs["serial"]), \
        _gap_fraction(legs["pipelined"])
    if g_serial is not None and g_pipe is not None and g_pipe > g_serial:
        return (f"pipelined host_gap_fraction {g_pipe} > serial {g_serial} "
                "— async dispatch made the loop tax WORSE")
    wall = v["wall"]
    if wall is not None:
        # the wall-clock after-leg: real timings, so numbers vary across
        # runs — but the ordering is the receipt.  Strictly above, at
        # equal goodput (same completion counts): hiding host work under
        # device time by shedding load would not be a win.
        if not isinstance(wall, dict):
            return f"wall: expected object or null, got {type(wall).__name__}"
        for k in ("serial_overlap_fraction", "pipelined_overlap_fraction",
                  "serial_completed", "pipelined_completed"):
            if not isinstance(wall.get(k), (int, float)) \
                    or isinstance(wall.get(k), bool):
                return f"wall.{k} is not a number ({wall.get(k)!r})"
        if not wall["pipelined_overlap_fraction"] \
                > wall["serial_overlap_fraction"]:
            return (f"wall-clock pipelined overlap fraction "
                    f"{wall['pipelined_overlap_fraction']} not strictly "
                    f"above serial {wall['serial_overlap_fraction']}")
        if wall["pipelined_completed"] != wall["serial_completed"]:
            return (f"wall-clock legs completed different request counts "
                    f"(serial {wall['serial_completed']} vs pipelined "
                    f"{wall['pipelined_completed']}) — not an equal-goodput "
                    "comparison")
    return None


_TERMINAL_STATES = {"done", "timed_out", "rejected"}


def _validate_trace(doc):
    """Telemetry trace artifact (deepspeed_tpu.telemetry.write_chrome_trace,
    Chrome Trace Event Format).  Pins the invariants a trace consumer
    (Perfetto, scripts/trace_report.py) relies on: well-formed events,
    per-track monotonic timestamps, every span's parent existing in the
    same trace, and serving request spans closing in a terminal state."""
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        return "expected a Chrome-trace object with a traceEvents list"
    errors = []
    last_ts = {}                      # (pid, tid) -> last X-event start ts
    span_ids = {}                     # trace_id -> set of span ids
    parents = []                      # (trace_id, parent_id, name)
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict) or ev.get("ph") not in ("M", "X", "i"):
            errors.append(f"traceEvents[{i}]: unknown/missing ph "
                          f"{ev.get('ph') if isinstance(ev, dict) else ev!r}")
            continue
        if "pid" not in ev or "tid" not in ev or "name" not in ev:
            errors.append(f"traceEvents[{i}]: missing pid/tid/name")
            continue
        if ev["ph"] == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            errors.append(f"traceEvents[{i}]: non-numeric ts {ts!r}")
            continue
        args = ev.get("args") or {}
        if ev["ph"] == "X":
            if not (isinstance(ev.get("dur"), (int, float)) and ev["dur"] >= 0):
                errors.append(f"traceEvents[{i}] ({ev['name']}): bad dur "
                              f"{ev.get('dur')!r}")
            track = (ev["pid"], ev["tid"])
            if ts < last_ts.get(track, float("-inf")):
                errors.append(f"traceEvents[{i}] ({ev['name']}): ts {ts} goes "
                              f"BACKWARDS on track {track} (monotonic per-track "
                              "order violated)")
            last_ts[track] = ts
            if "trace_id" not in args or "span_id" not in args:
                errors.append(f"traceEvents[{i}] ({ev['name']}): span without "
                              "trace_id/span_id args")
                continue
            span_ids.setdefault(args["trace_id"], set()).add(args["span_id"])
            if args.get("parent_id") is not None:
                parents.append((args["trace_id"], args["parent_id"], ev["name"]))
            if ev["name"] == "request" and \
                    args.get("state") not in _TERMINAL_STATES:
                errors.append(f"traceEvents[{i}]: request span closed in "
                              f"non-terminal state {args.get('state')!r}")
    for trace_id, parent_id, name in parents:
        if parent_id not in span_ids.get(trace_id, ()):
            errors.append(f"span {name!r} (trace {trace_id}): parent "
                          f"{parent_id} does not exist in its trace")
    if errors:
        return "; ".join(errors[:8]) + \
            (f"; ... {len(errors) - 8} more" if len(errors) > 8 else "")
    return None


def _tier_tpot(p):
    """Active-set TPOT summary: non-null ordered percentiles (a leg with
    zero measured gaps has no latency claim and fails loudly)."""
    if not isinstance(p, dict):
        return f"expected percentile dict, got {type(p).__name__}"
    for k in ("p50", "p95", "p99"):
        if p.get(k) is None:
            return f"missing/null percentile {k!r}"
    if not (p["p50"] <= p["p95"] <= p["p99"]):
        return f"percentiles out of order: {p}"
    return None


_TIER_LEG = {
    "sessions": INT, "completed": INT, "preemptions": INT,
    "tpot_active": _tier_tpot, "n_gaps": INT, "elapsed": NUM,
}


def _kv_tier_record(v):
    """The tiered-KV receipt (bench_serving.py run_kv_tier_leg): the host
    tier must buy >= 3x resident-session capacity — every session
    completing in BOTH legs — with every on-leg resume taking the
    snapshot-import fast path (zero recompute fallbacks), the prefetch
    hiding > 50% of promoted bytes under other sessions' device windows,
    and on-leg active-set p99 TPOT inside the committed equal-latency
    bar.  A committed artifact where parking cost latency or resumes
    silently recomputed is a regression, not a benchmark."""
    if not isinstance(v, dict):
        return f"expected kv_tier object, got {type(v).__name__}"
    errors = []
    _check(v, {
        "metric": STR, "value": NUM, "unit": STR,
        "schema_version": lambda x: None if x == 1 else f"schema_version {x} != 1",
        "workload": {"prompt_len": INT, "new_tokens": INT, "turns": INT,
                     "think": NUM, "prefetch_lead": NUM, "h2d_page_s": NUM,
                     "seed": INT, "dryrun": BOOL, "virtual_clock": BOOL,
                     "kv": DICT, "scheduler": DICT},
        "arena": {"usable_pages": INT, "pages_per_session": INT,
                  "page_bound_sessions": INT, "max_seqs": INT},
        "off": _TIER_LEG,
        "on": {**_TIER_LEG, "parks": INT, "resumes": INT, "demotions": INT,
               "promotions": INT, "kv_imports": INT,
               "kv_import_fallbacks": INT, "prefetch_hidden_frac": NUM,
               "host_pages_peak": INT},
        "equal_tpot": {"off_p99": NUM, "on_p99": NUM, "ratio": NUM, "bar": NUM},
        "determinism_repeat_identical": BOOL,
    }, "kv_tier", errors)
    if errors:
        return "; ".join(errors)
    if v["metric"] != "resident_session_capacity_ratio" or v["unit"] != "x":
        return f"wrong metric envelope: {v['metric']!r} [{v['unit']!r}]"
    off, on = v["off"], v["on"]
    if v["value"] < 3.0 or on["sessions"] < 3 * off["sessions"]:
        return (f"capacity ratio {v['value']} (on {on['sessions']} vs off "
                f"{off['sessions']}) below the 3x bar")
    for side, leg in (("off", off), ("on", on)):
        if leg["completed"] != leg["sessions"]:
            return (f"{side} leg lost sessions: {leg['completed']}/"
                    f"{leg['sessions']} completed")
    if on["kv_import_fallbacks"] != 0 or on["kv_imports"] < on["resumes"]:
        return (f"resumes did not all take the KV-import fast path: "
                f"imports={on['kv_imports']} resumes={on['resumes']} "
                f"fallbacks={on['kv_import_fallbacks']}")
    if on["parks"] != on["resumes"] or on["parks"] == 0:
        return f"unbalanced park/resume ledger: {on['parks']}/{on['resumes']}"
    if not on["prefetch_hidden_frac"] > 0.5:
        return (f"prefetch hid only {on['prefetch_hidden_frac']} of promoted "
                "bytes (> 0.5 required)")
    eq = v["equal_tpot"]
    if eq["ratio"] > eq["bar"]:
        return (f"on-leg p99 active TPOT {eq['on_p99']} vs off {eq['off_p99']} "
                f"(ratio {eq['ratio']}) outside the equal-latency bar {eq['bar']}")
    if v["workload"]["dryrun"] and v["determinism_repeat_identical"] is not True:
        return "dryrun artifact not byte-identical across regenerations"
    return None


_SESSION_LEG = {
    "policy": STR, "turn_ttft": DICT, "turns_completed": INT, "stalls": INT,
    "tool_results": INT, "sessions_closed": INT, "abandoned": INT,
    "elapsed": NUM, "session_sticky_hits": INT, "session_failovers": INT,
    "session_parks": INT, "session_resumes": INT, "kv_imports": INT,
}


def _sessions_record(v):
    """The agentic-session receipt (scripts/bench_sessions.py): the
    session subsystem (sticky-with-failover affinity + park-between-
    stalls) must beat the stateless round-robin baseline on p99
    turn-TTFT on a >= 20-session multi-turn tool-calling mix, at EQUAL
    goodput (every turn of every session completed in BOTH legs), with
    every stall parked through the tier and resumed, zero transcript
    divergence against per-session goldens, and byte-identical dryrun
    regeneration.  A committed artifact where affinity lost turns or
    parking changed bytes is a regression, not a benchmark."""
    if not isinstance(v, dict):
        return f"expected sessions object, got {type(v).__name__}"
    errors = []
    _check(v, {
        "schema": lambda x: None if x == 1 else f"schema {x} != 1",
        "mode": STR, "units": STR, "n_replicas": INT,
        "agentic_mix": {
            "workload": {"seed": INT, "n_sessions": INT, "n_turns": INT,
                         "n_stalls": INT, "mean_turns_per_session": NUM},
            "baseline": _SESSION_LEG,
            "sessions": _SESSION_LEG,
            "p99_turn_ttft_ratio": NUM,
            "sticky_hit_rate": NUM,
            "divergence": INT,
            "deterministic": ("nullable", BOOL),
        },
    }, "sessions", errors)
    if errors:
        return "; ".join(errors)
    mix = v["agentic_mix"]
    w = mix["workload"]
    if w["n_sessions"] < 20:
        return f"only {w['n_sessions']} sessions (>= 20 required)"
    if w["n_turns"] <= w["n_sessions"] or w["n_stalls"] <= 0:
        return (f"workload not agentic: {w['n_turns']} turns / "
                f"{w['n_sessions']} sessions, {w['n_stalls']} stalls")
    for side in ("baseline", "sessions"):
        leg = mix[side]
        if leg["turns_completed"] != w["n_turns"] \
                or leg["sessions_closed"] != w["n_sessions"] \
                or leg["abandoned"] != 0:
            return (f"{side} leg lost work: {leg['turns_completed']}/"
                    f"{w['n_turns']} turns, {leg['sessions_closed']}/"
                    f"{w['n_sessions']} sessions, {leg['abandoned']} abandoned"
                    " — goodput must be EQUAL before latency is compared")
    sess = mix["sessions"]
    if sess["session_parks"] != sess["session_resumes"] \
            or sess["session_parks"] != w["n_stalls"]:
        return (f"unbalanced stall ledger: parks={sess['session_parks']} "
                f"resumes={sess['session_resumes']} stalls={w['n_stalls']}")
    if sess["session_sticky_hits"] <= 0:
        return "affinity never stuck (session_sticky_hits == 0)"
    p99_base = mix["baseline"]["turn_ttft"].get("p99")
    p99_sess = sess["turn_ttft"].get("p99")
    if not (isinstance(p99_base, (int, float))
            and isinstance(p99_sess, (int, float))):
        return f"missing p99 turn-TTFT: base={p99_base} sessions={p99_sess}"
    if not (mix["p99_turn_ttft_ratio"] > 1.0 and p99_sess < p99_base):
        return (f"session serving did not beat stateless p99 turn-TTFT: "
                f"{p99_sess} vs {p99_base} "
                f"(ratio {mix['p99_turn_ttft_ratio']})")
    if mix["divergence"] != 0:
        return (f"{mix['divergence']} transcript(s) diverged from the "
                "per-session goldens")
    if v["mode"] == "dryrun" and mix["deterministic"] is not True:
        return "dryrun artifact not byte-identical across regenerations"
    return None


SCHEMAS = {
    # per-round driver transcripts
    "BENCH_r*.json": {"n": INT, "cmd": STR, "rc": INT, "tail": STR, "?parsed": DICT},
    # telemetry trace artifacts (scripts/bench_*.py --trace)
    "BENCH_ROUTER_TRACE.json": _validate_trace,
    "BENCH_SERVING_TRACE.json": _validate_trace,
    # slowdown-attribution + SLO burn-rate receipt (scripts/why_slow.py)
    "BENCH_ROUTER_ATTRIB.json": _validate_attribution,
    # per-step engine anatomy receipt (scripts/step_anatomy.py)
    "BENCH_STEP_ANATOMY.json": _validate_step_anatomy,
    # tiered-KV resident-session capacity receipt (bench_serving.py --kv-tier)
    "BENCH_KV_TIER.json": _kv_tier_record,
    # agentic-session receipt (scripts/bench_sessions.py)
    "BENCH_SESSIONS.json": _sessions_record,
    # single-metric bench artifacts (bench.py-style envelope)
    "BENCH_SCALE.json": {"metric": STR, "value": NUM, "unit": STR,
                         "?vs_baseline": NUM, "extra": DICT},
    "BENCH_LONGCTX.json": {"metric": STR, "value": NUM, "unit": STR,
                           "?vs_baseline": NUM, "extra": DICT},
    # the SLA serving harness (scripts/bench_serving.py, schema v3)
    "BENCH_SERVING.json": {
        "metric": STR, "value": NUM, "unit": STR,
        "schema_version": lambda v: None if v == 3 else f"schema_version {v} != 3",
        "sla": {"ttft_budget": NUM, "tpot_budget": NUM, "kill_on_deadline": BOOL},
        "workload": {"n_requests": INT, "seed": INT, "dryrun": BOOL,
                     "virtual_clock": BOOL, "kv": DICT, "scheduler": DICT},
        "sweep": lambda v: (None if isinstance(v, list) and len(v) >= 3
                            else "sweep must cover >= 3 arrival rates"),
        "sweep[]": [_SWEEP_POINT],     # element schema, validated below
        "spec": _spec_pair,
        "closed_loop": {**{k: v for k, v in _SWEEP_POINT.items()
                           if k not in ("arrival_rate", "offered_rps")},
                        "concurrency": INT},
        "engine_throughput": ("nullable", _LEGACY_THROUGHPUT),
    },
    # the fleet router harness (scripts/bench_router.py, schema v6)
    "BENCH_ROUTER.json": {
        "metric": STR, "value": NUM, "unit": STR,
        "schema_version": lambda v: None if v == 6 else f"schema_version {v} != 6",
        "sla": {"ttft_budget": NUM, "tpot_budget": NUM},
        "workload": {"n_requests": INT, "seed": INT, "arrival_rate": NUM,
                     "prefix_groups": INT, "prefix_pages": INT, "dryrun": BOOL,
                     "virtual_clock": BOOL, "kv": DICT, "scheduler": DICT},
        "replica_counts": [INT],
        "policies": [STR],
        "sweep": _router_sweep_invariants,
        "sweep[]": [_ROUTER_POINT],
        "disaggregation": _disagg_record,
        "autoscale": _autoscale_record,
        "prefix_directory": _prefix_directory_record,
        "partition": _partition_record,
        "control_loops": _control_loops_record,
    },
}


def _check(value, spec, path, errors):
    if isinstance(spec, tuple) and spec and spec[0] == "nullable":
        if value is None:
            return
        return _check(value, spec[1], path, errors)
    if isinstance(spec, tuple):
        if isinstance(value, bool) and bool not in spec:
            errors.append(f"{path}: expected {spec}, got bool")
        elif not isinstance(value, spec):
            errors.append(f"{path}: expected {tuple(t.__name__ for t in spec)}, "
                          f"got {type(value).__name__}")
        return
    if callable(spec):
        err = spec(value)
        if err:
            errors.append(f"{path}: {err}")
        return
    if isinstance(spec, list):
        if not isinstance(value, list):
            errors.append(f"{path}: expected list, got {type(value).__name__}")
            return
        for i, v in enumerate(value):
            _check(v, spec[0], f"{path}[{i}]", errors)
        return
    assert isinstance(spec, dict), spec
    if not isinstance(value, dict):
        errors.append(f"{path}: expected object, got {type(value).__name__}")
        return
    for key, sub in spec.items():
        if key.endswith("[]"):  # auxiliary element schema for a list key
            base = key[:-2]
            if isinstance(value.get(base), list):
                _check(value[base], sub, f"{path}.{base}", errors)
            continue
        optional = key.startswith("?")
        name = key[1:] if optional else key
        if name not in value:
            if not optional:
                errors.append(f"{path}: missing required key {name!r}")
            continue
        _check(value[name], sub, f"{path}.{name}", errors)


def validate_all(root: str):
    """Check every BENCH_*.json under ``root``; returns a list of errors."""
    errors = []
    matched = set()
    # exact filenames claim their file before any glob pattern can: a future
    # exact schema whose name also matches BENCH_r*.json must not be
    # validated against the loose per-round transcript shape
    ordered = sorted(SCHEMAS.items(), key=lambda kv: "*" in kv[0])
    for pattern, spec in ordered:
        for fp in sorted(glob.glob(os.path.join(root, pattern))):
            name = os.path.basename(fp)
            if name in matched:   # exact-name schemas win over BENCH_r* glob
                continue
            matched.add(name)
            try:
                with open(fp) as f:
                    doc = json.load(f)
            except Exception as e:
                errors.append(f"{name}: unreadable JSON ({e})")
                continue
            _check(doc, spec, name, errors)
    unmatched = {os.path.basename(p) for p in glob.glob(os.path.join(root, "BENCH_*.json"))}
    for name in sorted(unmatched - matched):
        errors.append(f"{name}: no schema registered in scripts/check_bench_schema.py "
                      "(add one — unschema'd artifacts are how drift ships)")
    return errors


def main():
    root = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    errors = validate_all(root)
    for e in errors:
        print(f"SCHEMA ERROR: {e}")
    n = len(glob.glob(os.path.join(root, "BENCH_*.json")))
    print(f"checked {n} BENCH_*.json artifacts: "
          f"{'OK' if not errors else f'{len(errors)} error(s)'}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
