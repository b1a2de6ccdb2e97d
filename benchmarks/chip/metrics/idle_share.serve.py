"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window."""


def read(rec):
    tr = rec.get("trace")
    if not rec.get("serve") or not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
