"""The paged-attention kernel's share of its roofline in the traced window:
the least time to read the live KV rows the window's decode steps needed
(costs/paged_attn.py, every layer) over the summed device time of the
``pallas_call`` that ``paged_decode_attn`` makes."""
import core


def read(rec):
    s, tr = rec.get("serve"), rec.get("trace")
    t = (tr or {}).get("kernels", {}).get("paged_decode_attn")
    if not s or not t or not s["attn_rows"]:
        return None
    a = core.reference(rec["config"]).arch(rec["config"])
    pa = core.load_module(core.HERE / "costs" / "paged_attn.py")
    c = pa.cost(s["attn_rows"], s["max_slots"] * s["decode_steps"], a.heads,
                a.kv_heads, a.head_dim, rec["workload"]["kv_bits"])
    return 100.0 * a.layers * core.least_seconds(c, rec["peaks"]) / t
