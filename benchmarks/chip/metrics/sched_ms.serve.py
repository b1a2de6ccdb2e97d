"""Mean host time of one scheduler step spent other than waiting on the
device: the program's ``serve.step`` spans in the window less the
``serve.decode.wait`` and ``serve.first_token`` spans inside them, over the
number of steps (programs.py)."""

WAITS = ("serve.decode.wait", "serve.first_token")


def read(rec):
    sp = (rec.get("trace") or {}).get("program_spans", {})
    step = sp.get("serve.step")
    if not rec.get("serve") or not step or not step["count"]:
        return None
    waits = sum(sp[n]["seconds"] for n in WAITS if n in sp)
    return 1e3 * (step["seconds"] - waits) / step["count"]
