"""materialize_tpu — a TPU-native incremental-view-maintenance streaming SQL engine.

A ground-up re-design of the capabilities of MaterializeInc/materialize
(reference layer map: SURVEY.md §1) for TPU hardware:

- The *data plane* — arrangement maintenance, join / reduce / top_k / MFP
  kernels — runs as JAX/XLA programs over fixed-capacity columnar update
  batches resident in HBM. Each dataflow "tick" is a single jitted function
  ``state -> (state', outputs)``: no host↔device ping-pong inside a tick.
- The *control plane* — progress tracking (frontiers/antichains), capability
  logic, catalog, coordination — stays on the host, mirroring the reference's
  split where timely's progress tracking is tiny next to its data plane
  (reference: doc/developer/platform/architecture-db.md:40-108).

Everything is built on the universal currency of the reference engine: update
triples ``(row, time, diff)`` plus frontier statements (reference:
doc/developer/change-data-capture.md:5-13), here laid out as structure-of-array
device batches with diff==0 padding (padding annihilates under every IVM
operator, so kernels compose without masks).
"""

import jax

# The engine's core dtypes are u64 timestamps and i64 diffs, matching the
# reference's `mz_repr::Timestamp` (u64 ms) and `Diff` (i64)
# (reference: src/repr/src/timestamp.rs:46, src/repr/src/diff.rs:11).
# Row hashes are u32 (repr/hashing.py): 64-bit integer ops are emulated on
# the 32-bit TPU VPU, so the sort/search/route hot path stays 32-bit and
# collisions are handled by key-equality verification.
jax.config.update("jax_enable_x64", True)

# Kernel shapes recur across ticks, restarts, and processes (pow2-bucketed
# capacities); the persistent compilation cache turns the per-shape XLA
# compile into a one-time cost per checkout. On for accelerators (where a
# fused tick compiles for minutes); off under JAX_PLATFORMS=cpu, where the
# XLA AOT loader warns about machine-feature mismatches. Where
# JAX_COMPILATION_CACHE_DIR is set JAX already keeps its cache there and no
# directory is set in code; otherwise the cache lives at one fixed path
# inside the checkout (the path is part of the cache key, so it must not move).
import os as _os

if _os.environ.get("JAX_PLATFORMS", "") != "cpu":
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(
                _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

__version__ = "0.1.0"
