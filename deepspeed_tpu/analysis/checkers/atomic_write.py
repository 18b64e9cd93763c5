"""Checker ``atomic-write``: durability-sensitive writers go through
``resilience/atomic_io.py`` (temp + fsync + rename) — the r8 lint
(scripts/check_atomic_writes.py), migrated into the framework; the old
script remains as a thin shim over this checker.

Inside the sensitive path set, every ``open(..., "w"/"wb"/"a"/"x"/"+")``
and every direct ``.savez``/``.savez_compressed`` must either use the
helper or justify itself.  Both the legacy ``# atomic-ok: <why>`` marker
and ``# dslint-ok(atomic-write): <why>`` are honored — the legacy marker
is grandfathered so r8's call-site annotations keep working unchanged.
"""

import ast
import fnmatch

from ..core import Checker, FileContext

SENSITIVE_GLOBS = [
    "deepspeed_tpu/checkpoint/*.py",
    "deepspeed_tpu/runtime/checkpoint_engine.py",
    "deepspeed_tpu/runtime/swap_tensor/*.py",
    "deepspeed_tpu/resilience/*.py",
    "scripts/aot_membudget.py",
]

LEGACY_MARKER = "atomic-ok"
# '+' catches in-place mutation ('r+'/'rb+') — the same torn-file class
WRITE_MODES = ("w", "a", "x", "+")
FORBIDDEN_ATTRS = ("savez", "savez_compressed")


def _open_mode(call: ast.Call):
    """The mode of an ``open()`` call when statically known ('r' default)."""
    mode = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return "r"
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return mode.value
    return None  # dynamic — not flagged


class AtomicWriteChecker(Checker):
    name = "atomic-write"
    description = ("bare writes on durability-sensitive paths must use "
                   "resilience.atomic_io")

    def applies(self, rel: str) -> bool:
        return any(fnmatch.fnmatch(rel, g) for g in SENSITIVE_GLOBS)

    def _legacy_allowed(self, ctx: FileContext, lineno: int) -> bool:
        return 0 < lineno <= len(ctx.lines) and LEGACY_MARKER in ctx.lines[lineno - 1]

    def visit(self, node, ctx: FileContext):
        if not isinstance(node, ast.Call):
            return
        func = node.func
        if isinstance(func, ast.Name) and func.id == "open":
            mode = _open_mode(node)
            if mode is not None and any(m in mode for m in WRITE_MODES) \
                    and not self._legacy_allowed(ctx, node.lineno):
                ctx.report(self.name, node.lineno,
                           f"bare open(..., {mode!r}) on a "
                           "durability-sensitive path — use "
                           "resilience.atomic_io (or justify with "
                           f"'# {LEGACY_MARKER}: <why>')")
        elif isinstance(func, ast.Attribute) and func.attr in FORBIDDEN_ATTRS \
                and not self._legacy_allowed(ctx, node.lineno):
            ctx.report(self.name, node.lineno,
                       f"direct .{func.attr}(...) on a durability-sensitive "
                       "path — use resilience.atomic_io.atomic_savez (or "
                       f"justify with '# {LEGACY_MARKER}: <why>')")
