"""dslint incremental cache: findings keyed on content hashes.

A warm full-repo run costs one sha256 sweep (~tens of ms) instead of a
parse + 8-checker walk of every file (~seconds).  Correctness stance:
several checkers are **cross-file** (event/fault-site registries, the
call graph, the state-machine tables, doc sync), so a single changed
file can move findings in *other* files — the cache therefore replays a
stored run only when EVERY input matches:

* the selected checker set,
* the resolved file list and each file's content hash (per-file keyed,
  exactly as the findings are stored),
* the analysis package's own sources (editing a checker invalidates
  everything it ever reported).

Anything else is a full re-run that refreshes the store.  Replayed
output is byte-identical to the live run's ``--json`` (asserted in
tier-1): findings are stored per file plus a cross-file remainder
(docs artifacts) and re-sorted through the same ``Finding`` path.

Persistence is ``.dslint_cache/cache.json`` under the repo root,
published with the same temp + fsync + atomic-rename discipline as
``resilience/atomic_io.py`` — re-implemented here in ~10 lines because
``analysis/`` must stay importable without the deepspeed_tpu package
(the no-jax load is what keeps dslint inside its runtime budget).  A
torn or unreadable cache file is treated as a miss, never an error.
``--no-cache`` bypasses reads and writes entirely.
"""

import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding, render_json, render_summary

CACHE_DIR = ".dslint_cache"
CACHE_NAME = "cache.json"
VERSION = 1
#: distinct (checker set x file set) run records retained, LRU by use
MAX_RUNS = 8


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _atomic_write_text(path: str, text: str) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def analysis_sources_hash() -> str:
    """Hash of every .py in the analysis package itself — a checker edit
    must invalidate every cached verdict it produced."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    names = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                names.append(os.path.join(dirpath, fn))
    for path in sorted(names):
        h.update(os.path.relpath(path, pkg).encode())
        h.update(_sha256_file(path).encode())
    return h.hexdigest()


class DslintCache:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.path = os.path.join(self.root, CACHE_DIR, CACHE_NAME)

    # ------------------------------------------------------------- hashing

    def file_hashes(self, files: Sequence[str]) -> List[Tuple[str, str]]:
        """(root-relative path, sha256) per file, sorted by rel path —
        the per-file half of the scan key.  The non-``.py`` artifacts the
        finish-phase checkers read (``docs/*.md`` generated tables) are
        folded in too: a hand-edited STATE_MACHINES.md must be a cache
        MISS, or the drift-as-finding contract dies in the warm path."""
        seen = {}
        for path in list(files) + self._artifact_files():
            rel = os.path.relpath(path, self.root).replace(os.sep, "/")
            if rel not in seen:
                seen[rel] = _sha256_file(path)
        return sorted(seen.items())

    def _artifact_files(self) -> List[str]:
        out = []
        # generated doc tables (event-registry/state-machine drift
        # checks) — same stance as analysis_sources_hash: editing any
        # input re-runs everything
        d = os.path.join(self.root, "docs")
        try:
            for fn in sorted(os.listdir(d)):
                if fn.endswith(".md"):
                    out.append(os.path.join(d, fn))
        except OSError:
            pass
        # the event registry is loaded from run.root by its checker even
        # when the scan paths don't cover it (partial invocations)
        reg = os.path.join(self.root, "deepspeed_tpu", "telemetry",
                           "event_registry.py")
        if os.path.isfile(reg):
            out.append(reg)
        return out

    def scan_key(self, checker_names: Sequence[str],
                 hashes: Sequence[Tuple[str, str]]) -> str:
        doc = {"version": VERSION,
               "checkers": sorted(checker_names),
               "files": list(hashes),
               "analysis": analysis_sources_hash()}
        return hashlib.sha256(
            json.dumps(doc, sort_keys=True).encode()).hexdigest()

    # -------------------------------------------------------------- replay

    def _load(self) -> Optional[dict]:
        try:
            with open(self.path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(doc, dict) or doc.get("version") != VERSION:
            return None
        return doc

    def lookup(self, key: str,
               hashes: Sequence[Tuple[str, str]]) -> Optional[dict]:
        """The stored run for ``key``, or None.  Belt-and-braces: every
        per-file record's hash must still match the hash the key was
        computed from (a corrupted store reads as a miss).  The scanned
        set may be a subset of the hashed set — checkers with narrow
        ``applies()`` scopes skip files that still feed the key."""
        doc = self._load()
        if doc is None:
            return None
        rec = doc.get("runs", {}).get(key)
        if rec is None:
            return None
        want = dict(hashes)
        for rel, entry in rec.get("per_file", {}).items():
            if entry.get("hash") != want.get(rel):
                return None
        self._touch(doc, key)
        return rec

    def _touch(self, doc: dict, key: str) -> None:
        """Refresh ``key``'s recency on a warm HIT — the eviction order
        is LRU by *use*, and the everyday invocation that always hits
        must never be the one evicted by eight one-off runs."""
        order = [k for k in doc.get("order", []) if k != key] + [key]
        if order == doc.get("order"):
            return
        doc["order"] = order
        try:
            _atomic_write_text(self.path, json.dumps(doc, sort_keys=True))
        except OSError:
            pass

    def findings_of(self, rec: dict) -> List[Finding]:
        out = []
        for rel in sorted(rec.get("per_file", {})):
            for line, checker, message in rec["per_file"][rel]["findings"]:
                out.append(Finding(rel, line, checker, message))
        for path, line, checker, message in rec.get("cross", []):
            out.append(Finding(path, line, checker, message))
        out.sort(key=lambda f: f.sort_key)
        return out

    # --------------------------------------------------------------- store

    def result_of(self, rec: dict) -> "CachedResult":
        return CachedResult(rec, self.findings_of(rec))

    def store(self, key: str, checker_names: Sequence[str],
              hashes: Sequence[Tuple[str, str]], scanned: Sequence[str],
              findings: Sequence[Finding], suppressed: int) -> None:
        doc = self._load() or {"version": VERSION, "order": [], "runs": {}}
        scanned_set = set(scanned)
        per_file: Dict[str, dict] = {
            rel: {"hash": h, "findings": []}
            for rel, h in hashes if rel in scanned_set}
        cross = []
        for f in findings:
            if f.path in per_file:
                per_file[f.path]["findings"].append(
                    [f.line, f.checker, f.message])
            else:
                cross.append([f.path, f.line, f.checker, f.message])
        doc["runs"][key] = {
            "checkers": sorted(checker_names),
            "files_scanned": len(scanned),
            "suppressed": suppressed,
            "per_file": per_file,
            "cross": cross,
        }
        order = [k for k in doc.get("order", []) if k != key] + [key]
        for stale in order[:-MAX_RUNS]:
            doc["runs"].pop(stale, None)
        doc["order"] = order[-MAX_RUNS:]
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            _atomic_write_text(self.path, json.dumps(doc, sort_keys=True))
        except OSError:
            pass  # a read-only tree still lints, just never warm


class CachedResult:
    """Replayed run with the Runner's exact output surface — ``to_json``
    and ``summary`` go through the same ``core.render_*`` helpers the
    live Runner uses, so warm output is byte-identical to cold by
    construction (asserted in tier-1)."""

    from_cache = True

    def __init__(self, rec: dict, findings: List[Finding]):
        self.findings = findings
        self.checker_names = list(rec["checkers"])
        self.files_scanned = int(rec["files_scanned"])
        self.suppressed_count = int(rec["suppressed"])

    def to_json(self) -> str:
        return render_json(self.checker_names, self.files_scanned,
                           self.suppressed_count, self.findings)

    def summary(self) -> str:
        return render_summary(self.files_scanned, self.suppressed_count,
                              self.findings)
