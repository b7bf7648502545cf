"""metrics-coherence: every counter the engine maintains must be
observable (the lint_metrics check, registered on the shared framework).

This rule is FUNCTIONAL, not AST-walking: it boots an in-memory
coordinator, drives one table + materialized view + peek through it,
greps the source tree for counter-name literals, then renders
``metrics_text()`` and materializes every ``INTROSPECTION_TABLES`` entry
through real SQL (so the virtual-collection encode path is exercised and
row arity is checked against the declared schema). It costs a few seconds
of engine boot, which is why it is the one rule carrying
``functional = True`` — the CLI still runs it under ``--all``, and
``--rules`` can select around it for sub-second iteration.
"""

from __future__ import annotations

import os
import re
import sys
import threading
from pathlib import Path

from ..core import Finding, Project, Rule

REQUIRED_FAMILIES = (
    "mzt_persist_ops_total",
    "mzt_persist_op_duration_ns",
    "mzt_persist_blob_bytes_total",
    "mzt_mesh_exchange_frames_total",
    "mzt_mesh_exchange_bytes_total",
    "mzt_heartbeat_rtt_seconds",
    "mzt_dataflow_tick_duration_ns",
    "mzt_device_exchange_programs_total",
    "mzt_device_exchange_mesh_devices",
    "mzt_device_exchange_retries_total",
    # encode-once fan-out: the delivered/encoded ratio is the whole point
    # of the shared frame ring, so both legs must stay observable
    "mzt_egress_frames_encoded_total",
    "mzt_egress_frames_delivered_total",
    # the spine's fixed-capacity head (arrangement/spine.py): merges say the
    # mechanism engages, spills and bypasses say how often it cannot
    "mzt_arrangement_head_merges_total",
    "mzt_arrangement_head_spills_total",
    "mzt_arrangement_head_bypass_total",
    # the accumulable reduce operators (dataflow/runtime.py): step wall,
    # groups whose output changed, live groups, per (dataflow, operator),
    # steps whose own error batch was empty (not handed on) or carried, and
    # steps whose table merge was clean or flagged a double collision
    "mzt_reduce_step_duration_ns",
    "mzt_reduce_groups_changed_total",
    "mzt_reduce_state_groups",
    "mzt_reduce_error_batches_total",
    "mzt_reduce_state_merges_total",
    # the delta join's stages (rows in and out, as the host knew them) and the
    # TopK operators (step wall, rows gathered back), per (dataflow, operator)
    "mzt_join_stage_rows_total",
    "mzt_topk_step_duration_ns",
    "mzt_topk_rows_regathered_total",
    # UpdateBatch.build (repr/batch.py) by where its columns lived: `host`
    # says the ingest asked XLA for no program, `device` should stay 0 there
    "mzt_batch_build_total",
)

_BUMP = re.compile(r'(?:\.bump|\.record_max)\(\s*"([a-z_]+)"')
_SHARING = re.compile(r'self\.stats\[\s*"([a-z_]+)"\s*\]')

_DEFAULT_ROOT = Path(__file__).resolve().parents[3]


def _pkg(root: Path | None) -> Path:
    return (root or _DEFAULT_ROOT) / "materialize_tpu"


def overload_counter_names(root: Path | None = None) -> set:
    """Every OverloadStats counter name bumped anywhere in the package."""
    names: set = set()
    for path in sorted(_pkg(root).rglob("*.py")):
        names.update(_BUMP.findall(path.read_text()))
    return names


def sharing_counter_names(root: Path | None = None) -> set:
    return set(
        _SHARING.findall(
            (_pkg(root) / "arrangement" / "trace_manager.py").read_text()
        )
    )


def lint(root: Path | None = None) -> list:
    """The functional check; returns human-readable violation strings."""
    root = root or _DEFAULT_ROOT
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))

    # import the subsystems whose module-level registrations we assert on
    import materialize_tpu.cluster.controller  # noqa: F401
    import materialize_tpu.cluster.mesh  # noqa: F401
    import materialize_tpu.parallel.devicemesh.exchange  # noqa: F401
    import materialize_tpu.persist.location  # noqa: F401
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.adapter.introspection import (
        INTROSPECTION_TABLES,
        introspection_rows,
    )
    from materialize_tpu.frontend.http_server import metrics_text

    violations: list = []
    coord = Coordinator()
    coord.execute("CREATE TABLE lint_t (a int)")
    coord.execute("INSERT INTO lint_t VALUES (1), (2)")
    coord.execute(
        "CREATE MATERIALIZED VIEW lint_mv AS"
        " SELECT a, count(*) AS n FROM lint_t GROUP BY a"
    )
    coord.execute("SELECT * FROM lint_mv")

    # seed every statically-known overload counter at 0 so the exposition
    # must carry it even before the first real bump
    for name in sorted(overload_counter_names(root)):
        coord.overload.bump(name, 0)

    text = metrics_text(coord, threading.Lock())

    for name in sorted(overload_counter_names(root)):
        if f'mzt_overload_counter{{name="{name}"}}' not in text:
            violations.append(
                f"overload counter {name!r} is bumped in the source but "
                "absent from the /metrics exposition (mzt_overload_counter)"
            )
    for name in sorted(sharing_counter_names(root)):
        if f'mzt_trace_sharing_counter{{name="{name}"}}' not in text:
            violations.append(
                f"trace-sharing counter {name!r} is maintained by the trace "
                "manager but absent from /metrics (mzt_trace_sharing_counter)"
            )
    for fam in REQUIRED_FAMILIES:
        if f"# TYPE {fam} " not in text:
            violations.append(
                f"registry family {fam!r} missing from /metrics — its "
                "registering module was dropped or the name changed"
            )

    for name, desc in sorted(INTROSPECTION_TABLES.items()):
        arity = len(desc.columns)
        try:
            rows = introspection_rows(coord, name)
        except Exception as e:  # missing/broken populator
            violations.append(f"{name}: populator raised {type(e).__name__}: {e}")
            continue
        for r in rows:
            if len(r) != arity:
                violations.append(
                    f"{name}: populator row arity {len(r)} != declared "
                    f"schema arity {arity} (row: {r!r})"
                )
                break
        try:  # the full SQL path: virtual collection snapshot + decode
            coord.execute(f"SELECT * FROM {name}")
        except Exception as e:
            violations.append(
                f"{name}: SELECT * faulted with {type(e).__name__}: {e}"
            )
    return violations


class MetricsCoherence(Rule):
    id = "metrics-coherence"
    description = (
        "every maintained counter surfaces in /metrics; every "
        "introspection relation materializes at its declared arity"
    )
    functional = True

    def check_project(self, project: Project):
        for v in lint(project.root):
            yield Finding(self.id, "materialize_tpu/obs/metrics.py", 1, v)
