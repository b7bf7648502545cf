"""XLA program executions on the device in the traced window, per refresh made in it."""


def read(run: dict):
    t = run["trace"]
    n = run["values"].get("traced_refreshes")
    if t is None or not n or not t["executions"]:
        return None
    return t["executions"] / n
