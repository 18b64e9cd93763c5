"""The documents name files of this repo; every such name must exist.

A document that quotes a script, a module or a committed artifact drifts in
silence when the file is renamed or deleted.  Every back-ticked token that
looks like a path of this repo is held to the tree: a token under a
top-level directory (``scripts/...``, ``deepspeed_tpu/...``, ``benchmark/...``,
``tests/...``), one under a package of ``deepspeed_tpu/`` written without the
prefix (``serving/fleet/sim.py``), or a bare ``*.py``/``*.json``/``*.md`` name,
which must be a file at the root or a file's name somewhere in the tree.
``docs/PERF.md`` is left out: it is the record of earlier rounds and names
what they had."""

import os
import re

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
PACKAGE = os.path.join(REPO_ROOT, "deepspeed_tpu")
DOCUMENTS = ["README.md", "docs/ANALYSIS.md", "docs/CONFIG.md", "docs/OBSERVABILITY.md", "docs/RESILIENCE.md",
             "docs/SERVING.md", "docs/STATE_MACHINES.md", ".claude/skills/verify/SKILL.md"]
TOP_LEVEL = ("scripts", "deepspeed_tpu", "benchmark", "tests", "docs", "bin", "examples")
_TOKEN = re.compile(r"`([^`\n]+)`")
_BARE_FILE = re.compile(r"^[\w.-]+\.(py|json|jsonl|md)$")
_PLACEHOLDER = re.compile(r"[*<>{}$ ,()=\[\]]|\.\.\.")
_FILE_OR_DIR = re.compile(r"(\.(py|json|jsonl|md)|/)$")
#: bare names that are no file of this repo: the reference's source, and what a checkpoint directory holds
NOT_OURS = {"zoadam.py", "manifest.json", "meta.json"}


@pytest.fixture(scope="module")
def basenames():
    names = set()
    for dirpath, dirnames, filenames in os.walk(REPO_ROOT):
        dirnames[:] = [d for d in dirnames if not d.startswith(".") and d != "__pycache__"]
        names.update(filenames)
    return names


def _missing(text, basenames):
    missing = []
    for token in sorted(set(_TOKEN.findall(text))):
        # `tests/x.py::test_name`, `engine_v2.py:84-90`
        path = re.sub(r":[:\w\[\]-]*$", "", token.split("::")[0])
        if not path or _PLACEHOLDER.search(path):
            continue
        if "/" in path:
            first = path.split("/")[0]
            if first in TOP_LEVEL:
                found = os.path.exists(os.path.join(REPO_ROOT, path))
            elif _FILE_OR_DIR.search(path) and os.path.isdir(os.path.join(PACKAGE, first)):
                # `serving/fleet/sim.py`, `serving/kvtier/`; `serving/ttft` is an event's name
                found = os.path.exists(os.path.join(PACKAGE, path))
            else:
                continue
        elif _BARE_FILE.match(path) and path not in NOT_OURS:
            found = path in basenames
        else:
            continue
        if not found:
            missing.append(token)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_paths_a_document_names_exist(document, basenames):
    with open(os.path.join(REPO_ROOT, document)) as f:
        text = f.read()
    missing = _missing(text, basenames)
    assert not missing, f"{document} names files that are not in the tree: {missing}"
