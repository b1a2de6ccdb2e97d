"""Model operations of the window's served tokens over the window's seconds
and the chip's bf16 peak: every prefill and decode token's matmuls
(costs/model_flops.py), plus decode attention over the live KV rows.
Prefill attention is left out, so this is a lower bound."""
import core


def read(rec):
    s = rec.get("serve")
    if not s or not s["window_s"]:
        return None
    a = core.reference(rec["config"]).arch(rec["config"])
    mf = core.load_module(core.HERE / "costs" / "model_flops.py")
    w = mf.matmul_weights(a.layers, a.d, a.heads, a.kv_heads, a.head_dim,
                          a.ff, a.vocab)
    flops = (2.0 * w * (s["decode_tokens"] + s["prefill_tokens"])
             + 4.0 * a.layers * a.heads * a.head_dim * s["attn_rows"])
    return 100.0 * flops / (s["window_s"] * rec["peaks"]["bf16_flops"])
