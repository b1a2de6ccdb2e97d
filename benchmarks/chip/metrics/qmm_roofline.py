"""The int8 matmul kernel's share of its roofline in the traced window: the
least time of every call the window's decode steps and prefill chunks made
(costs/qmm.py, one call per matmul weight and layer) over the summed device
time of the ``pallas_call`` that ``qmm`` makes."""
import core
import weights


def read(rec):
    s, tr = rec.get("serve"), rec.get("trace")
    t = (tr or {}).get("kernels", {}).get("qmm")
    if not s or not t:
        return None
    a = core.reference(rec["config"]).arch(rec["config"])
    qmm = core.load_module(core.HERE / "costs" / "qmm.py")
    bits = rec["workload"]["weight_bits"]
    least = 0.0
    for din, dout in weights.mat_shapes(a).values():
        for rows, calls in ((s["max_slots"], s["decode_steps"]),
                            (s["chunk_tokens"], s["prefill_chunks"])):
            c = qmm.cost(rows, din, dout, code_bytes=bits / 8)
            least += calls * a.layers * core.least_seconds(c, rec["peaks"])
    return 100.0 * least / t
