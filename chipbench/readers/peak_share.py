"""One of the harness's numbers as a share (%) of one of the chip's peaks
(`chipbench/peaks.json`); nothing where the number is 0 or missing."""


def read(run: dict, key: str, peak: str):
    v = run["values"].get(key)
    if not v:
        return None
    return 100.0 * v / run["peaks"][peak]
