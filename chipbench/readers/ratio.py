"""One of the harness's numbers over another; nothing where the divisor is 0."""


def read(run: dict, numerator: str, denominator: str, scale: float = 1.0):
    den = run["values"].get(denominator)
    num = run["values"].get(numerator)
    if not den or num is None:
        return None
    return scale * num / den
