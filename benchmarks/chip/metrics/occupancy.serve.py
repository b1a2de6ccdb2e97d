"""Share of decode slots that held a live sequence, over the window's decode
steps: decode_tokens / decode_steps / max_slots (engine counters)."""


def read(rec):
    s = rec.get("serve")
    if not s or not s["decode_steps"]:
        return None
    return 100.0 * s["decode_tokens"] / s["decode_steps"] / s["max_slots"]
