"""Operation and byte counts against a hand-worked case."""
from bench.lib.flops import (Shapes, Work, decode_steps, layer_matmul_flops,
                             prefill_steps, read_call)

M = Shapes(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
           d_ff=16, vocab=10)


def test_layer_matmul_flops():
    # q 8x(4*2) + k,v 2 x 8x(2*2) + o (4*2)x8 = 64+64+64 = 192; MLP 3*8*16=384
    assert layer_matmul_flops(M) == 2 * (192 + 384)


def test_read_call_hand_worked():
    # 3 queries at positions 4,5,6: contexts 5,6,7 => 18 tokens attended
    f, b = read_call(M, 4, 3)
    assert f == 4 * 2 * 4 * 2 * 18
    # per layer: q+out 2*3*4*2*2 = 96 bytes; K,V of 7 tokens 7*2*2*2*2 = 112
    assert b == 2 * (96 + 112)


def test_prefill_and_decode_work():
    w = prefill_steps(M, 0, 5, 2)      # chunks [0,2) [2,4) [4,5)
    assert (w.rows, w.ctx_sum) == (5, 1 + 2 + 3 + 4 + 5)
    d = decode_steps(M, 5, 2)          # positions 5, 6
    assert (d.rows, d.logit_rows, d.ctx_sum) == (2, 2, 6 + 7)
    t = Work()
    t.add(w)
    t.add(d)
    assert t.model_flops(M) == (7 * 2 * layer_matmul_flops(M)
                                + 4 * 2 * 4 * 2 * (15 + 13) + 2 * 8 * 10 * 2)
