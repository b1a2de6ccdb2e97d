"""Operations and bytes of ``_paged_attn_kernel`` over one decode step of
one layer, from its shapes.

Each of the ``rows`` live KV rows (summed over the slots, each at its length
after the step's append) is read once as K and once as V: ``head_dim`` codes
per KV head plus one f32 scale per KV head and row when the pool is
quantized. q enters and the output leaves in bf16 (slots × heads × head_dim).
Scores and the weighted sum are 2 · head_dim operations per query head and
row each. Bandwidth bounds the kernel.
"""


def cost(rows: int, slots: int, heads: int, kv_heads: int, head_dim: int,
         kv_bits: int) -> dict:
    code = head_dim * (kv_bits / 8 if kv_bits else 2)
    scale = 4 if kv_bits else 0
    kv = 2.0 * rows * kv_heads * (code + scale)
    qo = 2.0 * 2 * slots * heads * head_dim
    return {"flops": 4.0 * rows * heads * head_dim, "bytes": kv + qo}
