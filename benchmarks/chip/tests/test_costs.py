"""The cost functions against hand counts at small shapes."""
import pytest

import core

COSTS = core.HERE / "costs"


def load(name):
    return core.load_module(COSTS / f"{name}.py")


def test_qmm_hand_count():
    # y[2,4] = x[2,3] · W[3,4]: 2·2·3·4 operations; bytes: x bf16 2·3·2,
    # codes 3·4, scales 4·4, f32 out 2·4·4
    c = load("qmm").cost(2, 3, 4)
    assert c == {"flops": 48.0, "bytes": 12 + 12 + 16 + 32}
    assert load("qmm").cost(2, 3, 4, code_bytes=0.5)["bytes"] == 66.0


def test_paged_attn_hand_count():
    # 10 live rows, 2 KV heads of 8 int8 codes + a 4-byte scale, K and V;
    # q and out: 2 slots × 4 heads × 8 bf16 each
    c = load("paged_attn").cost(rows=10, slots=2, heads=4, kv_heads=2,
                                head_dim=8, kv_bits=8)
    assert c["bytes"] == 2 * 10 * 2 * (8 + 4) + 2 * 2 * 2 * 4 * 8
    assert c["flops"] == 4 * 10 * 4 * 8
    bf16 = load("paged_attn").cost(10, 2, 4, 2, 8, kv_bits=0)
    assert bf16["bytes"] == 2 * 10 * 2 * 16 + 256


def test_quant_adamw_hand_count():
    n = 256 * 128
    c = load("quant_adamw").cost(256, 128)
    pass1 = n * 6 + 2 * 8 * 1 * 128 * 4 + 2 * 4 * 128
    pass2 = n * 14 + n * 6 + 4 * 4 * 128
    assert c["bytes"] == pass1 + pass2
    assert c["flops"] == 30 * n


def test_model_flops_hand_count():
    mf = load("model_flops")
    # attention 4·(2+2)·2 + 2·2·4, MLP 3·4·8, read-out 10·4
    w = mf.matmul_weights(layers=1, d=4, heads=2, kv_heads=1, head_dim=2,
                          ff=8, vocab=10)
    assert w == 48 + 96 + 40
    assert mf.forward(w, 1, 2, 2, positions=3) == 2 * w + 4 * 2 * 2 * 3
    assert mf.train_per_token(w, 1, 2, 2, seq_len=5) == \
        3 * mf.forward(w, 1, 2, 2, 3)


PEAKS = core.load_json(core.HERE / "peaks.json")["devices"]["TPU v5 lite"]


@pytest.mark.parametrize("rows,bound", [(24, "bandwidth"), (256, "compute")])
def test_qmm_bound_at_the_cell_shapes(rows, bound):
    """Decode's 24 slots stream weights; a 256-token chunk computes."""
    c = load("qmm").cost(rows, 4096, 12800)
    assert core.bound(c, PEAKS) == bound


def test_paged_attn_and_quant_adamw_are_bandwidth_bound():
    assert core.bound(load("paged_attn").cost(24 * 800, 24, 32, 8, 128, 8),
                      PEAKS) == "bandwidth"
    assert core.bound(load("quant_adamw").cost(12 * 1536, 6144),
                      PEAKS) == "bandwidth"
