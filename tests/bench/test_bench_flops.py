"""The yardstick's operation and byte counts against hand counts."""
import pytest

from bench_cells import read
from bench import flops, peaks

QWEN = read("bench/configs/qwen3-4b.json")


def test_qwen3_4b_decode_flops_by_hand():
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560, and the
    # MLP's gate, up and down 2560x9728; then the 2560x151936 lm head
    layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert flops.layer_matmul_params(QWEN) == layer == 100_925_440
    head = 2560 * 151936
    assert flops.decode_token_flops(QWEN, 0) == 2 * (36 * layer + head)
    # attention: QK and PV, 2 flops a multiply-add, 32 heads of 128
    assert flops.decode_token_flops(QWEN, 100) - flops.decode_token_flops(
        QWEN, 0) == 36 * 4 * 32 * 128 * 100


def test_prefill_skips_cached_prefix():
    full = flops.prefill_flops(QWEN, 64)
    half = flops.prefill_flops(QWEN, 64, cached=32)
    per_tok = 2 * 36 * flops.layer_matmul_params(QWEN)
    attn = 36 * 4 * 32 * 128
    assert full - half == 32 * per_tok + attn * sum(range(1, 33))
    assert flops.prefill_flops(QWEN, 64, cached=64) == 2 * 2560 * 151936


@pytest.mark.parametrize("lengths", [[1], [16], [17, 300], [1024] * 16])
def test_paged_attention_counts_needed_work_not_hkv_times(lengths):
    f, b = flops.paged_attention_need(QWEN, lengths, page_tokens=16)
    # each of the 32 query heads against its own KV head only: QK and PV
    assert f == sum(4 * 32 * 128 * n for n in lengths)
    # the kernel scores every query head against all 8 KV heads' rows:
    # 8 times this; the count must not include that
    pages = sum(-(-n // 16) for n in lengths)
    kv = 2 * pages * 16 * 8 * 128 * 2  # K and V pages, bf16
    assert b == kv + len(lengths) * 2 * 32 * 128 * 2


def test_roofline_bound_and_peaks():
    p = peaks.of("TPU v5 lite")
    t, bound = flops.roofline_s(197e12, 0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = flops.roofline_s(0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.of("TPU v99")
