"""Operations and bytes of one ``_qmm_kernel`` call, from its shapes.

y[M, N] = x[M, K] · (codes[K, N] ⊙ scale[1, N]): x in bf16, int8 codes, f32
scales and an f32 output. M is the rows the caller has (decode: the slots;
a prefill chunk: its tokens), before the wrapper pads them to the MXU tile.
Bandwidth bounds the kernel at decode's rows (the slots: about 2·M
operations per weight byte, under the v5e's 240); compute bounds it at a
256-token prefill chunk.
"""


def cost(m: int, k: int, n: int, code_bytes: float = 1.0) -> dict:
    return {"flops": 2.0 * m * k * n,
            "bytes": 2.0 * m * k + code_bytes * k * n + 4.0 * n
            + 4.0 * m * n}
