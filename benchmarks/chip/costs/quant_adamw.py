"""Operations and bytes of the fused quantized-moment AdamW update on one
(R, C) leaf: ``_absmax_kernel`` then ``_update_kernel``, from their shapes.

Pass 1 reads g (f32) and both int8 code planes and writes per-row-block
column absmaxes (two (8·R/br, C) f32 planes, br = 256 rows). Pass 2 reads the
f32 master, g, both code planes and the u32 rounding plane, and writes the
master and both code planes. Scale rows are (1, C) f32. About 30 operations
per element: bandwidth bounds both passes.
"""

BLOCK_ROWS = 256


def cost(r: int, c: int) -> dict:
    n = float(r) * c
    absmax_out = 2 * 8 * max(1, -(-r // BLOCK_ROWS)) * c * 4.0
    pass1 = n * (4 + 1 + 1) + absmax_out + 2 * 4.0 * c
    pass2 = n * (4 + 4 + 1 + 1 + 4) + n * (4 + 1 + 1) + 4 * 4.0 * c
    return {"flops": 30.0 * n, "bytes": pass1 + pass2}
