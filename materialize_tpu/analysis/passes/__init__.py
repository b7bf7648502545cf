"""The mzlint pass registry: import a pass module, list its rules here."""

from .blocking import BlockingUnderLock
from .collective_rule import CollectiveCoherence
from .crashsafety import CrashSwallow, DurableCleanup
from .dtype64 import Dtype64
from .hygiene import ListenerHygiene
from .metrics_rule import MetricsCoherence
from .races import LockDiscipline
from .reactor_rule import ReactorDiscipline
from .registry_rules import CtpCoherence, DyncfgCoherence, SqlstateCoherence
from .tracer import TracedCoercion, TracedNpCall, TracedSearchsorted

ALL_RULES = [
    LockDiscipline(),
    BlockingUnderLock(),
    CrashSwallow(),
    DurableCleanup(),
    TracedCoercion(),
    TracedNpCall(),
    TracedSearchsorted(),
    Dtype64(),
    DyncfgCoherence(),
    SqlstateCoherence(),
    CtpCoherence(),
    ListenerHygiene(),
    CollectiveCoherence(),
    MetricsCoherence(),
    ReactorDiscipline(),
]

RULES_BY_ID = {r.id: r for r in ALL_RULES}
