"""Mean device time of one run of the prefill-chunk program
(``serve_chunk``) in the traced window: the program's time on the "XLA
Modules" line over its runs (programs.py)."""


def read(rec):
    p = (rec.get("trace") or {}).get("programs", {}).get("serve_chunk")
    if not rec.get("serve") or not p or not p["runs"]:
        return None
    return 1e3 * p["seconds"] / p["runs"]
