"""Model operations of one training step of a dense decoder.

Forward and backward of the matrix products the published model needs,
backward counted as twice the forward. Causal attention counts, for each
query, the keys it may see. Recomputation (remat) is not counted; norms,
activations and the softmax are not matrix products and are left out.
"""


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    D, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    H, KV, F = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    hd = D // H
    per_token = L * (2 * D * (H + 2 * KV) * hd      # q, k, v
                     + 2 * H * hd * D               # o
                     + 2 * 3 * D * F)               # gate, up, down
    per_token += 2 * D * V                          # tied head
    pairs = seq * (seq + 1) / 2                     # causal (query, key)
    attention = L * H * 2 * (2 * hd) * pairs        # q.k and w.v
    return 3.0 * (batch * seq * per_token + batch * attention)
