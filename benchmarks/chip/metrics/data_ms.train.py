"""Mean host time per window step spent in ``TokenStream.next_batch`` (the
benchmark's span around the call)."""


def read(rec):
    t = rec.get("train")
    if not t or not t["next_batch_s"]:
        return None
    return 1e3 * sum(t["next_batch_s"]) / len(t["next_batch_s"])
