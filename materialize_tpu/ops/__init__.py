"""Device operators. A public op is a Python function over a jitted `_name`:
the jitted twin names the device program (`jit__name` in profiles, in
`chipbench/metrics/` and in the compile cache's key), and the public name is
what callers, and the harness's byte counters, wrap. Where the twin only
forwards its arguments it stays for that reason.
"""

from .consolidate import advance_times, consolidate

__all__ = ["advance_times", "consolidate"]
