"""The benchmark's TPC-H load generator in the program's full schema, seeded from `--seed`.

`materialize_tpu/storage/generator.py::TpchGenerator` is what `LOAD GENERATOR
TPCH` runs: TPC-H's eight tables with every column of the specification, the
catalog's tables as the generator describes them (`tables()`), RF1 + RF2 at
SF x 1,500 orders per refresh. It has no SEED option: it draws the snapshot
from a fixed stream and the refreshes from seed 0. This subclass draws all of
it from `--seed` (three streams of one seed: the snapshot, the refreshes'
first columns, their other columns), logs how many source updates each
refresh carried, and answers to the harness's names for the host draws
(`snapshot`, `refresh_rows`, `live`). `chipbench/run.py` puts it where the
coordinator constructs its generator (the one seam; see the configuration
file), so the program gets only generated inputs through its own ingest path.
Its live rows, table -> column name -> values on the host, are what the plain
reference reads. The data is the program generator's, not dbgen's: the
configuration file lists where they differ.
"""

from __future__ import annotations

import numpy as np

from materialize_tpu.storage.generator import TpchGenerator


class Generator(TpchGenerator):
    """`TpchGenerator` with every draw from `seed` and a log of each refresh's updates."""

    def __init__(self, sf: float = 0.01, seed: int = 0, segment_codes=None):
        super().__init__(sf=sf, seed=seed, segment_codes=segment_codes)
        self.snapshot_rng = np.random.default_rng([int(seed), 0])
        self.rng = np.random.default_rng([int(seed), 1])
        self.extra_rng = np.random.default_rng([int(seed), 2])
        self.updates_by_ts: dict = {}  # refresh timestamp -> source updates it carried

    def snapshot(self) -> dict:
        """Draws the snapshot on the host: table -> columns as the program ingests them."""
        return self.initial()

    def refresh(self, tick: int, frac: float = 0.001, deletes: bool = True) -> dict:
        """One refresh as the coordinator's `advance()` asks for it."""
        rows = self.refresh_rows(frac, deletes)
        self.updates_by_ts[int(tick)] = sum(len(d) for _cols, d in rows.values())
        return {t: self.batch(t, cols, tick, d) for t, (cols, d) in rows.items()}
