"""Idle share (%) of the device in the traced window: 1 - the union of the
intervals in which an operation ran on it, over the window."""


def read(run: dict):
    t = run["trace"]
    if t is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
