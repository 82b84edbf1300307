"""Operations and bytes from shapes, pinned to counts worked by hand."""
import pytest

import _paths  # noqa: F401
import work

GRANITE = dict(n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
               head_dim=64, d_ff=8192, vocab=49155)
DEEPSEEK_PP = dict(n_layers=10, d_model=4096, n_heads=32, n_kv_heads=32,
                   head_dim=128, d_ff=11008, vocab=102400)


def test_granite_decode_call():
    # one query against 1000 live keys, 40 layers:
    # 4 * 32 heads * 64 * 1000 * 40
    # = 327,680,000 operations; per layer q and out 2 * 32 * 64 values
    # plus K and V 2 * 1000 * 8 * 64 values, 2 bytes each:
    # (4,096 + 1,024,000) * 2 = 2,056,192 bytes, * 40 = 82,247,680
    assert work.decode_call(GRANITE, 1000) == {"flops": 327_680_000,
                                               "bytes": 82_247_680}


def test_deepseek_chunk_call():
    # 512 queries at positions 512..1023: each sees 513..1024 keys, so
    # 512 * 512 + 512 * 513 / 2 = 393,472 pairs; 4 * 32 * 128 * pairs
    # * 10 layers = 64,466,452,480.  Bytes per layer: q and out
    # 2 * 512 * 32 * 128, K and V 2 * 1024 * 32 * 128, 2 bytes each =
    # 25,165,824; * 10 = 251,658,240
    assert work.chunk_call(DEEPSEEK_PP, 512, 512) == {
        "flops": 64_466_452_480, "bytes": 251_658_240}


def test_request_work_adds_up():
    w = work.request_work(GRANITE, 600, 128, 256)
    # chunks of 256, 256 and 88 tokens
    chunks = [work.chunk_call(GRANITE, p, n)
              for p, n in ((0, 256), (256, 256), (512, 88))]
    assert w["chunk_flops"] == sum(c["flops"] for c in chunks)
    assert w["decode_flops"] == sum(work.decode_call(GRANITE, 600 + j)
                                    ["flops"] for j in range(1, 128))
    per_token = 40 * work.layer_matmul_flops(GRANITE) + \
        work.head_flops(GRANITE)
    # 600 prompt tokens through the layers, the head once for the prompt
    # and once per later token, attention over the causal prompt and
    # each later token's context
    assert w["model_flops"] == (
        600 * 40 * work.layer_matmul_flops(GRANITE) + 128 *
        work.head_flops(GRANITE) + work.attn_flops(GRANITE, 600, 600)
        + 127 * 40 * work.layer_matmul_flops(GRANITE) + w["decode_flops"])
    assert per_token == pytest.approx(2 * 2.53e9, rel=0.02)


def test_short_prompt_is_not_chunked():
    assert work.request_work(GRANITE, 256, 2, 256)["chunk_flops"] == 0
