"""One number the harness took itself (host clock, jax.monitoring, the device)."""


def read(run: dict, key: str, scale: float = 1.0):
    v = run["values"].get(key)
    return None if v is None else v * scale
