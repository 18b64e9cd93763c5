"""Checker registry.  Add a checker: subclass ``core.Checker`` in a module
here, then list it in ``ALL`` (docs/ANALYSIS.md walks through an example)."""

from .atomic_write import AtomicWriteChecker
from .crash_transparency import CrashTransparencyChecker
from .crash_transparency_interproc import CrashTransparencyInterprocChecker
from .determinism import DeterminismChecker
from .event_registry import EventRegistryChecker
from .fault_sites import FaultSiteChecker
from .kv_lifetime import KVLifetimeChecker
from .state_machine import StateMachineChecker

ALL = (
    DeterminismChecker,
    CrashTransparencyChecker,
    CrashTransparencyInterprocChecker,
    FaultSiteChecker,
    EventRegistryChecker,
    AtomicWriteChecker,
    KVLifetimeChecker,
    StateMachineChecker,
)


def all_checkers():
    return [cls() for cls in ALL]


def checker_names():
    return [cls.name for cls in ALL]
