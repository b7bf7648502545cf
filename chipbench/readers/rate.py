"""A count of the whole window over the window's whole elapsed time."""


def read(run: dict, count: str):
    n = run["counts"][count]
    return n / run["window_s"] if n and run["window_s"] > 0 else None
