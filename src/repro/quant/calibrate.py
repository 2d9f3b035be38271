"""Absmax calibration + fixed-point parity harness.

The paper's accuracy claim for the hardware half is that 12-16-bit fixed
point costs near-zero accuracy ONCE WEIGHTS ARE IN THE FFT DOMAIN; the
reproduction's check of that claim has two parts:

* ``weight_absmax_report`` — the offline calibration pass: per serving
  cache, the absmax / per-block-row scale statistics the codec derives
  (absmax quantization of static weights needs no activation data — the
  "calibration" is reading the weights; this reports what it read, plus
  the bytes the quantized planes will occupy).
* ``parity_report`` / ``servable_parity_sweep`` — the accuracy harness:
  per arch, TEACHER-FORCED decode of the quantized serving stack (int8 KV
  pool and/or fixed-point weight planes) against the f32 dense-cache
  oracle.  Both paths consume the ORACLE's greedy token each step, so the
  metrics measure per-step decision fidelity without compounding
  divergence: ``max_logit_drift`` (worst absolute logit delta over all
  steps) and ``greedy_agreement`` (fraction of steps whose argmax
  matches, prefill's first token included).  Free-running engine-level
  token identity lives in tests/test_quant.py; the methodology note is
  docs/quantization.md.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.registry import build_model
from ..serve import decode as dec
from ..serve import kvcache as kvc
from ..serve.params import precompute_serving_params
from .codec import QuantPolicy

_PLANES = ("wr", "wi", "ws1", "ws2")


# ---------------------------------------------------------------------------
# Offline calibration report
# ---------------------------------------------------------------------------
def weight_absmax_report(params) -> Dict[str, Dict]:
    """Per serving-cache absmax/scale statistics (the calibration pass).

    Walks a precomputed (and possibly already-quantized) parameter tree;
    for every ``*_cache`` dict reports, per plane: the global absmax, the
    largest and smallest per-block-row scale, and the payload bytes.  On a
    quantized tree the scales are read back rather than re-derived.
    """
    report: Dict[str, Dict] = {}

    def walk(path, node):
        if isinstance(node, dict):
            if "wr" in node:
                entry = {}
                for name in _PLANES:
                    if name not in node:
                        continue
                    plane = node[name]
                    stats = {"bytes": int(plane.size)
                             * np.dtype(plane.dtype).itemsize}
                    if name + "_s" in node:                # quantized tree
                        # uint8 marks int4-packed planes: scale = absmax/7
                        qmax = 7.0 if plane.dtype == np.uint8 else 127.0
                        s = np.asarray(node[name + "_s"], np.float64)
                        stats.update(scale_max=float(s.max()),
                                     scale_min=float(s.min()),
                                     absmax=float(s.max() * qmax))
                    else:
                        a = np.abs(np.asarray(plane, np.float64))
                        rows = a.max(axis=(-2, -1))
                        stats.update(absmax=float(a.max()),
                                     scale_max=float(rows.max() / 127.0),
                                     scale_min=float(rows.min() / 127.0))
                    entry[name] = stats
                report["/".join(path)] = entry
                return
            for k, v in node.items():
                walk(path + (str(k),), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(path + (str(i),), v)

    walk((), params)
    return report


# ---------------------------------------------------------------------------
# Teacher-forced parity harness
# ---------------------------------------------------------------------------
def _prompt_batch(cfg: ArchConfig, toks: np.ndarray) -> Dict:
    batch = {"tokens": jnp.asarray(toks[None])}
    if cfg.frontend == "vision_stub":
        batch["patches"] = jnp.zeros(
            (1, cfg.num_patches, cfg.d_model), jnp.float32)
    return batch


class ParityRunner:
    """Reusable teacher-forced parity harness over PRECOMPUTED params.

    Holds the model and jitted oracle/quantized step functions so jit
    caches survive across prompts — the online shadow-oracle sampler
    (``obs/health.ShadowOracle``) replays many finished requests through
    one runner; ``parity_report`` wraps a single-shot run.  Distinct
    prompt/budget sizes recompile per page-count bucket, same as the
    serving stack.
    """

    def __init__(self, cfg: ArchConfig, params_o, params_q, *,
                 policy: QuantPolicy, page_size: int = 4):
        self.cfg = cfg
        self.policy = policy
        self.page_size = int(page_size)
        self.params_o = params_o
        self.params_q = params_q
        self.model = build_model(cfg)
        self._step_o = jax.jit(
            lambda p, t, c, pos: self.model.decode_step(p, t, c, pos))
        self._step_q = jax.jit(
            lambda p, t, c, pos, tab: self.model.decode_step(
                p, t, c, pos, block_table=tab))
        self._prefills: Dict[int, object] = {}

    def _prefill(self, n_pages: int):
        fn = self._prefills.get(n_pages)
        if fn is None:
            fn = dec.make_prefill_pack_step(self.cfg, n_pages,
                                            self.page_size)
            self._prefills[n_pages] = fn
        return fn

    def run(self, prompt, new_tokens: int) -> Dict:
        """Teacher-forced decode of ``new_tokens`` steps on one prompt;
        both paths consume the ORACLE's greedy token each step.  Returns
        ``steps`` / ``greedy_agreement`` / ``max_logit_drift``, and the
        oracle logits' ``oracle_logit_absmax`` (the scale drift is read
        against)."""
        prompt = np.asarray(prompt, np.int32)
        S = len(prompt)
        new_tokens = max(int(new_tokens), 1)
        model, cfg, page_size = self.model, self.cfg, self.page_size

        # oracle: dense f32 cache
        cache = model.init_cache(1, S + new_tokens, dtype=jnp.float32)
        logits, cache = model.prefill(self.params_o,
                                      _prompt_batch(cfg, prompt), cache)
        tok = int(jnp.argmax(logits[0, -1]))

        # quantized: paged pool, pages 1..maxp of a minimal pool
        maxp = kvc.pages_for(S + new_tokens, page_size)
        pool = kvc.build_pool(cfg, maxp + 1, page_size, self.policy)
        table = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
        n_pages = kvc.pages_for(S, page_size)
        spad = n_pages * page_size
        padded = np.zeros(spad, np.int32)
        padded[:S] = prompt
        first_q, _ok, pool, _stats = self._prefill(n_pages)(
            self.params_q, _prompt_batch(cfg, padded), pool,
            table[0, :n_pages], jnp.int32(S))

        agree = [int(first_q) == tok]
        drift = 0.0
        absmax = float(jnp.max(jnp.abs(logits[0, -1])))
        for j in range(new_tokens - 1):
            pos = S + j
            lo, cache = self._step_o(self.params_o,
                                     jnp.asarray([[tok]], jnp.int32),
                                     cache, jnp.int32(pos))
            lq, pool = self._step_q(self.params_q,
                                    jnp.asarray([[tok]], jnp.int32), pool,
                                    jnp.asarray([pos], jnp.int32), table)
            lo32 = np.asarray(lo[0, -1], np.float32)
            lq32 = np.asarray(lq[0, -1], np.float32)
            drift = max(drift, float(np.abs(lq32 - lo32).max()))
            absmax = max(absmax, float(np.abs(lo32).max()))
            agree.append(int(lq32.argmax()) == int(lo32.argmax()))
            tok = int(lo32.argmax())           # teacher forcing: oracle token
        return {"steps": len(agree),
                "greedy_agreement": float(np.mean(agree)),
                "max_logit_drift": drift,
                "oracle_logit_absmax": absmax}


def parity_report(cfg: ArchConfig, params, *, policy: QuantPolicy,
                  prompt_len: int = 20, new_tokens: int = 16,
                  page_size: int = 4, seed: int = 0) -> Dict:
    """Quantized serving stack vs the f32 dense-cache oracle, one arch.

    Runs B=1 teacher-forced decode: the oracle (f32 planes, f32 dense
    cache) picks every input token greedily; the quantized path (pool per
    ``policy.kv_dtype`` + planes per ``policy.quant_weights``) sees the
    SAME tokens at the same positions through the real paged machinery
    (prefill-pack + block-table decode steps).  Returns ``max_logit_drift``
    (max |logits_q - logits_f32| over every compared step),
    ``greedy_agreement`` in [0, 1], and ``steps``.  The same harness
    (``ParityRunner``) backs the ONLINE shadow-oracle sampling in
    ``obs/health.py`` — one definition of agreement/drift offline and on.
    """
    rng = np.random.RandomState(seed)
    prompt = rng.randint(1, cfg.vocab_size, size=prompt_len).astype(np.int32)
    params_o = precompute_serving_params(params, cfg)
    params_q = precompute_serving_params(params, cfg, policy)
    runner = ParityRunner(cfg, params_o, params_q, policy=policy,
                          page_size=page_size)
    out = {"arch": cfg.name, "policy": policy.describe()}
    out.update(runner.run(prompt, new_tokens))
    return out


def servable_parity_sweep(policy: QuantPolicy, *,
                          archs: Optional[Sequence[str]] = None,
                          prompt_len: int = 20, new_tokens: int = 16,
                          page_size: int = 4, seed: int = 0) -> List[Dict]:
    """``parity_report`` over every continuous-servable registry arch
    (smoke configs, f32 activations so quantization is the only delta)."""
    from ..configs.registry import ARCH_IDS, get_smoke_config
    if archs is None:
        archs = [a for a in ARCH_IDS
                 if not kvc.servable_reasons(get_smoke_config(a))]
    out = []
    for arch in archs:
        cfg = get_smoke_config(arch).replace(dtype="float32")
        model_params = build_model(cfg).init(jax.random.PRNGKey(0))
        out.append(parity_report(cfg, model_params, policy=policy,
                                 prompt_len=prompt_len,
                                 new_tokens=new_tokens,
                                 page_size=page_size, seed=seed))
    return out
