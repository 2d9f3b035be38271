"""The benchmark's one door into the system under test: build its
configuration from a configuration file, build the continuous engine the
launcher serves with, and count its compiles.  Nothing else of the
benchmark imports the program."""
from __future__ import annotations

import os

import jax

DTYPES = {"float32": "f32", "bfloat16": "bf16", "int8": "int8"}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import (ArchConfig, AttentionConfig,
                                    CompressionConfig)
    served = conf["served"]
    if served["param_dtype"] != "float32":
        raise ValueError("the program keeps its parameters in float32")
    return ArchConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attention=AttentionConfig(
            num_heads=conf["num_attention_heads"],
            num_kv_heads=conf["num_key_value_heads"],
            head_dim=conf["head_dim"], rope_theta=float(conf["rope_theta"]),
            qk_norm=bool(conf.get("qk_norm")),
            qkv_bias=bool(conf.get("qkv_bias"))),
        compression=CompressionConfig(
            enabled=True, block_attn=conf["circulant_block"]["attn"],
            block_ffn=conf["circulant_block"]["ffn"],
            block_embed=conf["circulant_block"]["head"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=served["activation_dtype"], param_dtype="float32")


def build_engine(conf: dict, cell: dict, params, max_seq: int, device):
    """The continuous engine on one device, with the launcher's defaults
    (fused paged attention, offline spectral weights, greedy decode,
    telemetry on) and the cell's slots and pool."""
    from repro.launch import mesh as mesh_lib
    from repro.obs import Obs, resolve_hardware
    from repro.quant import QuantPolicy
    from repro.serve.engine import ContinuousEngine
    served = conf["served"]
    return ContinuousEngine(
        arch_config(conf), params, max_slots=cell["max_slots"],
        max_seq=max_seq, page_size=served["page_size"],
        num_pages=cell["num_pages"], decode_chunk=served["decode_chunk"],
        admission=served["admission"],
        mesh=mesh_lib.make_device_mesh(device),
        quant=QuantPolicy(kv_dtype=DTYPES[served["kv_pool_dtype"]]),
        obs=Obs(hardware=resolve_hardware("auto")))


def request(index: int, prompt, max_new_tokens: int):
    from repro.serve.engine import Request
    return Request(prompt=prompt, max_new_tokens=max_new_tokens, id=index)


def enable_compile_cache(path: str) -> str:
    """JAX's persistent cache at ``path``, handed to the program's own
    cache set-up through ``JAX_COMPILATION_CACHE_DIR``, with every program
    cached, the small ones too."""
    from repro.launch.cache import ENV_VAR
    from repro.launch.cache import enable_compile_cache as enable
    os.environ[ENV_VAR] = path
    enable()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles and their seconds, persistent-cache hits and the
    seconds spent loading them, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_load_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
