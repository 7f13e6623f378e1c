"""Model operations of one training step of a Mamba-2 language model.

Forward and backward of the products the published chunked SSD algorithm
(arXiv 2405.21060, listing 1) needs at chunk length Q, backward counted as
twice the forward: the projections, the depthwise convolution, within each
chunk C.B^T and the weighted sum over the positions a query may see, the
chunk states B^T x, their passing from chunk to chunk and their read-out
C.h, and the tied head. Recomputation (remat) is not counted; norms, gates
and exponentials are left out.
"""


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    D, L, V, N = m["d_model"], m["n_layers"], m["vocab_size"], m["d_state"]
    Din = m["expand"] * D
    P = m["headdim"]
    H = Din // P
    Q = min(m["chunk"], seq)
    proj = 2 * D * (2 * Din + 2 * N + H) + 2 * Din * D
    conv = 2 * 4 * (Din + 2 * N)
    seen = (Q + 1) / 2                              # causal pairs per query
    intra = 2 * N * seen + 2 * H * P * seen         # C.B^T, weighted sum
    states = 2 * N * H * P                          # B^T x into chunk state
    readout = 2 * N * H * P                         # C.h from past chunks
    passing = 2 * H * N * P / Q                     # one update per chunk
    per_token = L * (proj + conv + intra + states + readout + passing) \
        + 2 * D * V
    return 3.0 * batch * seq * per_token
