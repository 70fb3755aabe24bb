"""Operations and bytes against hand counts."""
from bench.metrics import ops

# a small model: d 8, ffn 16, 2 layers, 4 query heads / 2 kv heads of 2
D = dict(d=8, f=16, layers=2, hq=4, hk=2, dh=2, vocab=32)
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}


def test_layer_products_and_weights():
    assert ops.layer_gemms(D) == [(8, 16), (8, 8), (8, 32), (16, 8)]
    # per layer 8*16 + 8*8 + 8*32 + 16*8 = 576; head 8*32 = 256
    assert ops.matmul_params(D) == 2 * 576 + 256


def test_gemm_cost_and_least_time():
    flops, nbytes = ops.gemm(3, 8, 16)
    assert flops == 2 * 3 * 8 * 16 == 768
    assert nbytes == 2 * (8 * 16 + 3 * 8 + 3 * 16) == 400
    assert ops.least_time(768, 400, PEAKS) == 40.0    # memory bound
    assert ops.least_time(5000, 10, PEAKS) == 50.0    # compute bound


def test_gemm_least_time_of_a_pass():
    t = ops.gemm_least_time(D, 1, 1, PEAKS)
    per_layer = sum(ops.least_time(*ops.gemm(1, k, n), PEAKS)
                    for k, n in ops.layer_gemms(D))
    head = ops.least_time(*ops.gemm(1, 8, 32), PEAKS)
    assert t == 2 * per_layer + head
    assert ops.gemm_least_time(D, 0, 5, PEAKS) == 0.0


def test_decode_attention_counts_a_shared_prefix_once():
    kv_tok = 2 * 2 * 2 * 2          # K and V, 2 heads of 2, bf16
    qo = 2 * 4 * 2 * 2              # query in, output out, per row
    # two rows of context 10 sharing a 6-token prefix, one unshared of 3
    flops, nbytes = ops.decode_attention(D, [(10, 0, 6), (10, 0, 6),
                                             (3, -1, 0)])
    assert flops == 2 * (4 * 4 * 2 * (10 + 10 + 3))
    distinct = 6 + 4 + 4 + 3
    assert nbytes == 2 * (distinct * kv_tok + 3 * qo)
    # without sharing the same rows read every position
    _, unshared = ops.decode_attention(D, [(10, -1, 0), (10, -1, 0),
                                           (3, -1, 0)])
    assert unshared - nbytes == 2 * 6 * kv_tok


def test_model_flops():
    assert ops.model_flops(D, 3, 30) == (2 * ops.matmul_params(D) * 3
                                         + 4 * 2 * 4 * 2 * 30)
