"""A configuration's architecture comes from its module under
``benchmarks/chip/models/``: a new architecture is new files, never an
edit to a file of the harness."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import arch
import program
import weights
from bench_tiny import TINY_MIX, tiny_cell, tiny_conf

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
CHIP = os.path.join(ROOT, "benchmarks", "chip")

STUB = '''"""A new architecture for the test: Qwen's mathematics under a model
type of its own."""
from models.qwen import *  # noqa: F401,F403

MODEL_TYPES = ("stub",)
'''

DRIVE = '''import json, pathlib, sys
root, chip, src = sys.argv[1:4]
sys.path[:0] = [chip, src]
import run
cell = run.load_cell("stub.chat", root=pathlib.Path(root))
sys.exit(run.run(cell, seed=2**33 + 3, seconds=1.5, trace=False,
                 require_tpu=False))
'''


def _files(top):
    return {os.path.relpath(os.path.join(d, f), top)
            for d, _, fs in os.walk(top) for f in fs
            if "__pycache__" not in d}


def test_a_new_model_type_is_served_from_new_files(tmp_path):
    """A copy of the harness, with a stub module under a new model type, a
    configuration, a traffic mix and a cell that use it, and a
    BENCHMARK.json that names them: a whole run on the CPU is correct."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", ".jax_cache"))
    before = _files(chip)
    (chip / "models" / "stub.py").write_text(STUB)
    conf = dict(tiny_conf(qk_norm=True, qkv_bias=False), name="stub",
                model_type="stub")
    (chip / "configs" / "stub.json").write_text(json.dumps(conf))
    (chip / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    cell = tiny_cell()
    (chip / "cells" / "stub.chat.json").write_text(json.dumps(cell.params))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "stub", "source": "test", "reduced": [],
                         "file": "benchmarks/chip/configs/stub.json",
                         "why": "test"}]
    bench["workloads"] = [{"name": "stub.chat", "config": "stub",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert _files(chip) - before == {
        "models/stub.py", "configs/stub.json", "traffic/tiny.json",
        "cells/stub.chat.json"}
    for rel in before:
        with open(os.path.join(CHIP, rel), "rb") as a, \
                open(chip / rel, "rb") as b:
            assert a.read() == b.read(), rel

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(tmp_path), str(chip),
         os.path.join(ROOT, "src")],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"]["served_logit_gap"]["value"] <= \
        line["check"]["served_logit_gap"]["limit"]


def test_make_params_draws_any_tree(monkeypatch):
    """Two segments and a leaf beside them (an untied head): every leaf
    drawn at its shape and scale, each from its own key."""
    tree = {"embed": {"table": (64, 16)}, "lm_head": {"w": (16, 64)},
            "final_norm": {"scale": (16,)},
            "segments": [({"mlp": {"wc": (1, 4, 4, 4)}},),
                         ({"mlp": {"wc": (3, 4, 4, 4)},
                           "router": {"w": (3, 16, 8)}},)]}
    std = {"table": 1.0, "w": 2.0, "scale": 0.1, "wc": 0.5}

    class Stub:
        @staticmethod
        def shapes_tree(conf):
            return tree

        @staticmethod
        def leaf_std(path, shape):
            return std[path[-1]]

    monkeypatch.setattr(arch, "module", lambda conf: Stub)
    params = weights.make_params({"model_type": "stub"}, 2**33 + 1)
    got = jax.tree_util.tree_map(lambda x: x.shape, params)
    assert got == jax.tree_util.tree_map(
        lambda s: s, tree, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert len(leaves) == 6
    firsts = set()
    for path, x in leaves:
        x = np.asarray(x)
        assert x.dtype == np.float32
        want = std[path[-1].key]
        assert 0.5 * want < x.std() < 1.5 * want, (path, x.std())
        firsts.add(float(x.ravel()[0]))
    assert len(firsts) == 6
    again = weights.make_params({"model_type": "stub"}, 2**33 + 1)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))


def test_an_unread_key_is_refused():
    conf = dict(tiny_conf(True, False), sliding_window=4096)
    with pytest.raises(ValueError, match="sliding_window"):
        arch.module(conf)
    with pytest.raises(ValueError, match="sliding_window"):
        weights.make_params(conf, 1)


def test_an_unknown_model_type_is_refused():
    with pytest.raises(ValueError, match=r"'mamba'.*'qwen2', 'qwen3'"):
        arch.module(dict(tiny_conf(True, False), model_type="mamba"))


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("use_sliding_window", True), ("tie_word_embeddings", False)])
def test_qwen_refuses_what_it_does_not_compute(key, value):
    conf = dict(tiny_conf(True, False), **{key: value})
    with pytest.raises(ValueError, match=key):
        program.arch_config(conf)
    with pytest.raises(ValueError, match=key):
        weights.make_params(conf, 1)


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen2.5-3b"])
def test_config_files_are_read_whole(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        conf = json.load(f)
    assert arch.module(conf).__name__ == "models.qwen"
    cfg = program.arch_config(conf)
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (
        conf["num_hidden_layers"], conf["hidden_size"], conf["vocab_size"])
    assert cfg.attention.qk_norm == (name == "qwen3-4b")
    assert cfg.attention.qkv_bias == (name == "qwen2.5-3b")

