"""Device time (ms) of the named XLA programs in the traced window, per refresh made in it.

`programs` lists program names as the device trace has them (`jit__...`).
Nothing is returned where the run was not traced, made no refresh, or none
of the programs ran on the device (a parent commit that lacks them)."""


def read(run: dict, programs: list):
    t = run["trace"]
    n = run["values"].get("traced_refreshes")
    if t is None or not n:
        return None
    seconds = sum(t["programs"].get(p, {"seconds": 0.0})["seconds"] for p in programs)
    if seconds <= 0:
        return None
    return 1e3 * seconds / n
