"""The fused quantized-AdamW kernels' share of their roofline in the traced
window: the least time of both passes over every leaf they update
(costs/quant_adamw.py), once per window step, over the summed device time of
the ``pallas_call``s that ``qadamw_absmax`` and ``qadamw_update`` make."""
import core


def read(rec):
    t, tr = rec.get("train"), rec.get("trace")
    k = (tr or {}).get("kernels", {})
    dev = k.get("qadamw_absmax", 0.0) + k.get("qadamw_update", 0.0)
    if not t or not dev or not t["steps"]:
        return None
    qa = core.load_module(core.HERE / "costs" / "quant_adamw.py")
    least = sum(core.least_seconds(qa.cost(r, c), rec["peaks"])
                for r, c in t["moment_leaves"])
    return 100.0 * t["steps"] * least / dev
