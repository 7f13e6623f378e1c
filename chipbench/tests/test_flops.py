"""Each family's operation count against a count by hand at a small size."""
from chipbench.flops import dense, ssm


def test_dense_by_hand():
    m = {"d_model": 8, "n_layers": 2, "vocab_size": 10, "n_heads": 2,
         "n_kv_heads": 1, "d_ff": 16}
    # per token and layer: q 2*8*8, k and v 2*2*8*4, o 2*8*8, mlp 3*2*8*16
    layer = 128 + 128 + 128 + 768
    head = 2 * 8 * 10
    # seq 3: query i sees i+1 keys, 6 pairs; per pair and head 2*4 for q.k
    # and 2*4 for w.v, 2 heads, 2 layers
    attention = 6 * 2 * 16 * 2
    want = 3 * (1 * 3 * (2 * layer + head) + attention)
    assert dense.train_step_flops(m, batch=1, seq=3) == want


def test_ssm_by_hand():
    m = {"d_model": 4, "n_layers": 1, "vocab_size": 10, "d_state": 2,
         "expand": 2, "headdim": 4, "chunk": 2}
    # Din 8, H 2, P 4, N 2, chunk 2
    proj = 2 * 4 * (16 + 4 + 2) + 2 * 8 * 4
    conv = 2 * 4 * (8 + 4)
    intra = 2 * 2 * 1.5 + 2 * 2 * 4 * 1.5
    states = readout = 2 * 2 * 2 * 4
    passing = 2 * 2 * 2 * 4 / 2
    head = 2 * 4 * 10
    per_token = proj + conv + intra + states + readout + passing + head
    assert ssm.train_step_flops(m, batch=2, seq=4) == \
        3 * 2 * 4 * per_token
