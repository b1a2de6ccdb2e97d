"""Mean time of one batched decode call inside the window, from the
engine's ``decode_times`` (host clock around the call, which ends in a
blocking read of the sampled tokens)."""


def read(rec):
    s = rec.get("serve")
    if not s or not s["decode_times"]:
        return None
    return 1e3 * sum(s["decode_times"]) / len(s["decode_times"])
