"""The benchmark's one seam to the system under test: its model config and
its parameter tree, built from the configuration file and the benchmark's
own weights (``weights.py``)."""
from __future__ import annotations

import core

# values the program runs and cannot change
FIXED = {"rms_norm_eps": 1e-6, "embedding_multiplier": 1.0,
         "residual_multiplier": 1.0, "logits_scaling": 1.0}
ACT = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}
MLP = ("up", "gate", "down")
ATTN = ("q", "k", "v", "o")


def model_config(a, cfg: dict, plan):
    """The program's ModelConfig for a configuration file, refusing values
    the program cannot run."""
    from repro.models.transformer import ModelConfig

    for k, v in FIXED.items():
        if float(cfg.get(k, v)) != v:
            raise core.BenchError(f"the program runs {k}={v}, the "
                                  f"configuration asks {cfg[k]}")
    if abs(a.attn_mult - a.head_dim ** -0.5) > 1e-12:
        raise core.BenchError("the program scales attention by head_dim^-0.5")
    if a.act not in ACT:
        raise core.BenchError(f"the program has no activation {a.act!r}")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=a.layers, d_model=a.d,
        n_heads=a.heads, n_kv_heads=a.kv_heads, head_dim=a.head_dim,
        d_ff=a.ff, vocab_size=a.vocab, mlp_act=ACT[a.act],
        rope_theta=a.rope_theta, tie_embeddings=True, precision=plan)


def params(w: dict, bits: int = 0) -> dict:
    """The benchmark's weights as the program's parameter tree; with
    ``bits`` the (codes, scale) pairs become the program's int QTensors."""
    from repro.quant import QScheme, QTensor

    scheme = QScheme.int_symmetric(bits or 8, scaling="channel",
                                   rounding="nearest", channel_axis=-2)

    def mat(x):
        return {"w": QTensor(x[0], x[1], scheme) if bits else x}

    lw = w["layers"]
    return {"embed": {"table": w["embed"]},
            "final_norm": {"g": w["final_norm"]},
            "layers": {"ln1": {"g": lw["ln1"]}, "ln2": {"g": lw["ln2"]},
                       "attn": {n: mat(lw[n]) for n in ATTN},
                       "mlp": {n: mat(lw[n]) for n in MLP}}}


def leaves(p: dict) -> dict:
    """A program parameter-shaped tree → the benchmark's flat leaf names."""
    lw = p["layers"]
    out = {"embed": p["embed"]["table"], "final_norm": p["final_norm"]["g"],
           "ln1": lw["ln1"]["g"], "ln2": lw["ln2"]["g"]}
    out.update({n: lw["attn"][n]["w"] for n in ATTN})
    out.update({n: lw["mlp"][n]["w"] for n in MLP})
    return out
