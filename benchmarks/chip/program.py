"""The benchmark's one door into the system under test: build its
configuration from a configuration file, build the continuous engine the
launcher serves with, count its compiles, read its metrics over a window,
and put a trace's device time under its named scopes.  Nothing else of
the benchmark imports the program."""
from __future__ import annotations

import os

import jax

import arch

DTYPES = {"float32": "f32", "bfloat16": "bf16", "int8": "int8"}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, from the
    fields that the module of its architecture gives (``arch.py``)."""
    from repro.configs.base import (ArchConfig, AttentionConfig,
                                    CompressionConfig, MoEConfig)
    served = conf["served"]
    if served["param_dtype"] != "float32":
        raise ValueError("the program keeps its parameters in float32")
    fields = dict(arch.module(conf).program_fields(conf))
    for key, cls in (("attention", AttentionConfig), ("moe", MoEConfig),
                     ("compression", CompressionConfig)):
        if key in fields:
            fields[key] = cls(**fields[key])
    return ArchConfig(**fields, dtype=served["activation_dtype"],
                      param_dtype="float32")


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


# ---------------------------------------------------------------------------
# The program's metrics over a window, and its device time by scope
# ---------------------------------------------------------------------------
def metric_marks(engine) -> tuple:
    """Where each histogram of the engine's registry stands, and its
    counters' values: hand them to ``metrics_since``."""
    reg = engine.obs.registry
    return ({name: m.mark() for name, m in reg.items()
             if hasattr(m, "values_since")},
            reg.snapshot())


def metrics_since(engine, marks) -> tuple:
    """(every histogram's values observed since ``marks``, every
    counter's increase since then), by the registry's flat names."""
    reg = engine.obs.registry
    at, before = marks
    hists = {name: m.values_since(at.get(name, 0))
             for name, m in reg.items() if hasattr(m, "values_since")}
    return hists, reg.delta(reg.snapshot(), before)


def op_scopes(engine) -> list:
    """The op->scope map of every program the engine compiled; take it
    before the engine is freed."""
    return list(engine.op_scopes().values())


def scope_seconds(xplane_path: str, scopes: list) -> dict:
    """Leaf-op device seconds of a trace's programs by named scope, per
    program (``repro.obs.scopes.device_seconds``)."""
    from repro.obs.scopes import device_seconds_file
    return device_seconds_file(xplane_path, scopes)
