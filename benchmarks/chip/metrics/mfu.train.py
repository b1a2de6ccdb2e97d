"""Model operations of the window's trained tokens (forward and backward,
costs/model_flops.py; recomputation not counted) over the window's seconds
and the chip's bf16 peak."""
import core


def read(rec):
    t = rec.get("train")
    if not t or not t["window_s"]:
        return None
    a = core.reference(rec["config"]).arch(rec["config"])
    mf = core.load_module(core.HERE / "costs" / "model_flops.py")
    w = mf.matmul_weights(a.layers, a.d, a.heads, a.kv_heads, a.head_dim,
                          a.ff, a.vocab)
    per_tok = mf.train_per_token(w, a.layers, a.heads, a.head_dim,
                                 rec["mix"]["seq_len"])
    return 100.0 * t["tokens"] * per_tok / (t["window_s"]
                                            * rec["peaks"]["bf16_flops"])
