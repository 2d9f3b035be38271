"""Record the engine trace with program spans that tests/bench reads.

    python benchmarks/chip/testdata/record_spans.py   # on a TPU

Builds the continuous engine for ``configs/qwen3-4b.json`` cut to 2
layers (published widths, weights from seed 0), warms its programs, then
serves 4 requests (prompts 100-700 tokens, 12-16 new tokens, 4 slots)
inside one ``bench.window`` span, each engine step in a ``bench.step``
span.  Writes ``testdata/spans.xplane.pb`` and ``testdata/
spans.scopes.json``, the engine's op->scope map of each compiled program
(``ContinuousEngine.op_scopes()``).
"""
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.join(HERE, "..")
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "..", "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import program  # noqa: E402
import weights  # noqa: E402

PROMPTS = (100, 400, 700, 230)
NEW_TOKENS = (12, 16, 12, 16)


def main():
    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    with open(os.path.join(CHIP, "configs", "qwen3-4b.json")) as f:
        conf = json.load(f)
    conf["num_hidden_layers"] = 2
    device = jax.devices()[0]
    params = weights.make_params(conf, 0)
    engine = program.build_engine(conf, {"max_slots": 4, "num_pages": 40},
                                  params, 1024, device)
    rng = np.random.default_rng(0)
    reqs = [program.request(i, rng.integers(1, conf["vocab_size"], size=s)
                            .astype(np.int32), n)
            for i, (s, n) in enumerate(zip(PROMPTS, NEW_TOKENS))]
    engine.generate(reqs)                    # compiles every program
    engine.reset_serve_clock()
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    for i, r in enumerate(reqs):
        engine.submit(r, 0.02 * i)
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        while not engine.scheduler.idle:
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "spans.xplane.pb"))
    shutil.rmtree(logdir)
    scopes = engine.op_scopes()
    with open(os.path.join(HERE, "spans.scopes.json"), "w") as f:
        json.dump(scopes, f, sort_keys=True)
    print(os.path.getsize(os.path.join(HERE, "spans.xplane.pb")),
          os.path.getsize(os.path.join(HERE, "spans.scopes.json")))


if __name__ == "__main__":
    main()
