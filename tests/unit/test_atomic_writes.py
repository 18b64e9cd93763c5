"""Tier-1 guard: durability-sensitive writers go through the atomic-write
helper (r7 tentpole).  A bare
``open(path, "w")`` on a checkpoint or committed-artifact path tears under
a crash — scripts/check_atomic_writes.py forbids it outside
resilience/atomic_io.py, and this test runs the checker over the repo plus
proves the checker still catches the violation classes it exists for."""

import importlib.util
import os
import textwrap

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _load_checker():
    path = os.path.join(REPO_ROOT, "scripts", "check_atomic_writes.py")
    spec = importlib.util.spec_from_file_location("check_atomic_writes", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_has_no_bare_writes_on_sensitive_paths():
    mod = _load_checker()
    errors = mod.validate_all(REPO_ROOT)
    assert not errors, "\n".join(errors)


def test_checker_catches_planted_violations(tmp_path):
    mod = _load_checker()
    pkg = tmp_path / "deepspeed_tpu" / "checkpoint"
    pkg.mkdir(parents=True)
    (pkg / "writer.py").write_text(textwrap.dedent("""
        import json, numpy as np
        def save(path, obj, arrs):
            with open(path, "w") as f:          # violation: bare text write
                json.dump(obj, f)
            np.savez(path + ".npz", **arrs)     # violation: direct savez
            with open(path + ".bin", mode="wb") as f:  # violation: mode kw
                f.write(b"x")
            with open(path) as f:               # fine: read
                return f.read()
    """))
    errors = mod.validate_all(str(tmp_path))
    assert len(errors) == 3, errors
    assert any("open" in e and ":4:" in e for e in errors)
    assert any("savez" in e for e in errors)


def test_checker_respects_allow_marker_and_scope(tmp_path):
    mod = _load_checker()
    pkg = tmp_path / "deepspeed_tpu" / "checkpoint"
    pkg.mkdir(parents=True)
    (pkg / "ok.py").write_text(
        'def f(p):\n'
        '    with open(p, "w") as f:  # atomic-ok: test fixture\n'
        '        f.write("x")\n')
    # same bare write OUTSIDE the sensitive set is not this lint's business
    other = tmp_path / "deepspeed_tpu" / "monitor"
    other.mkdir(parents=True)
    (other / "free.py").write_text(
        'def f(p):\n'
        '    with open(p, "w") as f:\n'
        '        f.write("x")\n')
    assert mod.validate_all(str(tmp_path)) == []
