"""Hand-counted operations and bytes of the served model's mathematics."""
import math

import pytest

import work
from models import qwen


def conf(**kw):
    """qwen3-4b's shapes, with ``kw`` changed."""
    base = dict(model_type="qwen3", num_hidden_layers=36, hidden_size=2560,
                intermediate_size=9728, num_attention_heads=32,
                num_key_value_heads=8, head_dim=128, vocab_size=151936,
                circulant_block={"attn": 128, "ffn": 128, "head": 0},
                served={"kv_pool_dtype": "float32"})
    base.update(kw)
    return base


def shapes(**kw):
    return qwen.Shapes.of(conf(**kw))


def test_fft_count_at_block_128():
    # 2.5 * 128 * log2(128) = 2.5 * 128 * 7
    assert work.fft_flops(128) == 2240.0


def test_circulant_projection_hand_count():
    # 2560 -> 4096 at k = 128: q = 20 input FFTs, p = 32 inverse FFTs,
    # 32 * 20 blocks of 65 bins, 8 real operations per complex MAC
    want = (20 + 32) * 2240.0 + 8.0 * 32 * 20 * 65
    assert work.projection_flops(2560, 4096, 128) == want
    # k = 0 is a dense matmul
    assert work.projection_flops(2560, 4096, 0) == 2.0 * 2560 * 4096


def test_layer_projections_sum_the_seven():
    s = shapes()
    p = work.projection_flops
    want = (p(2560, 4096, 128) + 2 * p(2560, 1024, 128) + p(4096, 2560, 128)
            + 2 * p(2560, 9728, 128) + p(9728, 2560, 128))
    assert qwen.layer_projection_flops(s) == want


def test_head_flops():
    assert qwen.head_flops(shapes()) == 2.0 * 2560 * 151936


def test_prefill_counts_the_head_once_and_causal_attention():
    c = conf(num_hidden_layers=1)
    s = qwen.Shapes.of(c)
    n = 10
    attn = 4.0 * 32 * 128 * sum(range(1, n + 1))
    want = n * qwen.layer_projection_flops(s) + attn + qwen.head_flops(s)
    assert work.prefill_flops(c, n) == pytest.approx(want)


def test_kv_bytes_for_a_known_schedule():
    # two slots decode 3 steps from prompts of 5 and 9 tokens: the queries
    # attend 6, 7, 8 and 10, 11, 12 positions
    c = conf(num_hidden_layers=2, num_key_value_heads=2, head_dim=4)
    contexts = [6, 7, 8, 10, 11, 12]
    per_pos = 2 * 2 * 2 * 4 * 4          # K and V, layers, heads, dim, bytes
    assert sum(work.kv_read_bytes(c, x) for x in contexts) == \
        per_pos * sum(contexts)


def test_decode_flops_split():
    c = conf()
    s = qwen.Shapes.of(c)
    x = 1000
    assert work.decode_flops(c, x) == pytest.approx(
        s.layers * qwen.layer_projection_flops(s) + work.kernel_flops(c, x)
        + qwen.head_flops(s))
    assert work.kernel_flops(c, x) == 36 * 4.0 * 32 * 128 * x


def test_shapes_from_config_files():
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "..", "benchmarks", "chip", "configs",
                        "qwen2.5-3b.json")
    with open(path) as f:
        c = json.load(f)
    s = qwen.Shapes.of(c)
    assert (s.heads, s.kv_heads, s.head_dim, s.kv_bytes) == (16, 2, 128, 4)
    assert math.isclose(work.kv_read_bytes(c, 1) / 36, 2 * 2 * 128 * 4)
