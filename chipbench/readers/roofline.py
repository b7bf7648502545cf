"""Share (%) of the HBM roofline of the counted kernels in the traced window.

`programs` maps a counted kernel (a key of the metric file's `kernels`, whose
calls `chipbench/work.py` turned into bytes from shapes) to the name of its
XLA program in the device trace. The share is (sum of bytes / peak HBM
bytes/s) over (sum of device time of those programs). HBM bounds all four:
they sort, search, gather and add, and do no matrix work. Nothing is returned
where no such program ran on the device."""


def read(run: dict, programs: dict):
    t = run["trace"]
    if t is None:
        return None
    nbytes = sum(run["work"]["bytes"].get(k, 0) for k in programs)
    seconds = sum(t["programs"].get(p, {"seconds": 0.0})["seconds"] for p in programs.values())
    if nbytes <= 0 or seconds <= 0:
        return None
    return 100.0 * (nbytes / run["peaks"]["hbm_bytes_per_s"]) / seconds
