"""Tiny cells for the benchmark's CPU tests: the configuration files'
keys at a size a test run can hold."""


def tiny_conf(qk_norm: bool, qkv_bias: bool) -> dict:
    """Qwen3's mechanism with ``qk_norm``, Qwen2's with ``qkv_bias``."""
    return {
        "name": "tiny", "model_type": "qwen3" if qk_norm else "qwen2",
        "hidden_size": 256, "intermediate_size": 512,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 1024,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": True, "qk_norm": qk_norm,
        "qkv_bias": qkv_bias,
        "circulant_block": {"attn": 64, "ffn": 64, "head": 0},
        "served": {"param_dtype": "float32", "activation_dtype": "float32",
                   "kv_pool_dtype": "float32",
                   "matmul_precision": "default", "page_size": 16,
                   "decode_chunk": 4, "admission": "reserve"},
    }


TINY_MIX = {"name": "tiny", "arrivals": "poisson",
            "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 56},
            "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
            "shape_seed": 3}


def tiny_cell(qk_norm=True, qkv_bias=False, gap_limit=0.05):
    import run
    return run.Cell(
        name="tiny.chat", chips=1, conf=tiny_conf(qk_norm, qkv_bias),
        mix=dict(TINY_MIX),
        params={"rate_per_s": 6.0, "max_slots": 4, "num_pages": 40,
                "n_check": 3, "check": {"served_logit_gap": gap_limit}},
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("tokens_per_s", "tokens/s"), ("ttft_p95_s", "s"),
            ("tpot_p95_s", "s"), ("setup_s", "s"))],
        per_layer=[])
