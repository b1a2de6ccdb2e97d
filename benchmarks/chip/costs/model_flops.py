"""Model operations per token of the decoder the configurations describe,
for the ``mfu`` metrics: 2 operations per weight of every matmul (the
attention projections, the gated MLP and the tied read-out over the real
vocabulary) and 4 · heads · head_dim per layer and attended position for
attention scores and values. Training takes three times the forward
(forward, and the backward's two matmuls per weight); recomputation under
rematerialisation is not counted. Compute bounds a whole step.
"""


def matmul_weights(layers: int, d: int, heads: int, kv_heads: int,
                   head_dim: int, ff: int, vocab: int) -> int:
    attn = d * (heads + 2 * kv_heads) * head_dim + heads * head_dim * d
    return layers * (attn + 3 * d * ff) + vocab * d


def forward(weights: int, layers: int, heads: int, head_dim: int,
            positions: float) -> float:
    """One token attending to ``positions`` earlier-or-equal positions."""
    return 2.0 * weights + 4.0 * layers * heads * head_dim * positions


def train_per_token(weights: int, layers: int, heads: int, head_dim: int,
                    seq_len: int) -> float:
    """Forward and backward, causal attention at the sequence's mean
    attended length (seq_len + 1) / 2."""
    return 3.0 * forward(weights, layers, heads, head_dim,
                         (seq_len + 1) / 2.0)
