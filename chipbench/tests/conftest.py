"""The benchmark's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q -p no:cacheprovider

(tier-1 runs `tests/` only). They take a few minutes: two of them drive whole
rehearsal runs of the harness."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
