"""The comparison that decides ``correct``.

After the window has closed and the engine is freed, a sample of the
requests that the engine finished, drawn from the seed and holding the one
with the most served tokens, is run through the plain reference
(``reference.py``) once: each prompt with its served tokens.  For every
served token the reference gives the gap by which that token's logit lies
below the reference's best logit at its position (0 where the program
picked the reference's greedy token).  The widest gap is compared with
the cell's limit (``cells/<workload>.json``, ``check``), and every request
that arrived has to have finished with all the tokens it asked for.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

import reference

SAMPLE_SALT = 0x5EED


@dataclasses.dataclass
class Verdict:
    readings: List[tuple]         # (name, value, limit)
    checked_requests: int = 0
    checked_tokens: int = 0

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.readings)

    def numbers(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.readings}

    def lines(self) -> List[str]:
        return ([f"checked {self.checked_tokens} served tokens of "
                 f"{self.checked_requests} requests against the reference"]
                + [f"check {n}: {v} (limit {lim})"
                   for n, v, lim in self.readings])


def sample(served, n: int, seed: int):
    """The finished request with the most tokens, and ``n - 1`` others
    drawn from the seed."""
    done = [s for s in served if s.finished and s.tokens]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.tokens), -s.index))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(seed ^ SAMPLE_SALT)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def pack(reqs, n_rows: int, max_seq: int, max_out: int):
    """Token rows (prompt + served tokens but the last), the positions
    whose logits chose each served token, the served tokens, and a mask.
    Shapes are fixed by the cell, so the reference compiles once."""
    tokens = np.zeros((n_rows, max_seq), np.int32)
    pos = np.zeros((n_rows, max_out), np.int32)
    ids = np.zeros((n_rows, max_out, 1), np.int32)
    mask = np.zeros((n_rows, max_out), bool)
    for b, s in enumerate(reqs):
        S, out = len(s.prompt), np.asarray(s.tokens, np.int32)
        seq = np.concatenate([s.prompt, out[:-1]])
        tokens[b, :len(seq)] = seq
        pos[b, :len(out)] = S - 1 + np.arange(len(out))
        ids[b, :len(out), 0] = out
        mask[b, :len(out)] = True
    return tokens, pos, ids, mask


def served_gaps(params, conf: dict, packed, precision: str = "f32"):
    """Per served token: the reference's best logit minus the served
    token's (``precision`` "f32"), or, for the control ("fp8"), the f32
    reference's best minus its logit of the token that the fp8 pass puts
    first.  Returns the gaps of the masked positions."""
    tokens, pos, ids, mask = packed
    if precision == "f32":
        best, _, at = reference.logit_rows(params, conf, tokens, pos, ids)
    else:
        _, top, _ = reference.logit_rows(params, conf, tokens, pos, ids,
                                         precision=precision)
        best, _, at = reference.logit_rows(params, conf, tokens, pos,
                                           np.asarray(top)[..., None])
    gap = np.asarray(best) - np.asarray(at)[..., 0]
    return gap[mask]


def check(cell, params, served, seed: int,
          precision: str = "f32") -> Verdict:
    """The verdict on a run.  ``precision`` "fp8" judges the control in
    the program's place: the same requests and limits, with the gaps of
    the tokens that the reference at fp8 puts first."""
    unfinished = sum(1 for s in served if not s.finished)
    short = sum(1 for s in served if s.finished
                and len(s.tokens) != s.max_new_tokens)
    reqs = sample(served, cell.params["n_check"], seed)
    gap = 1e9                     # nothing finished: nothing to check
    checked = 0
    if reqs:
        packed = pack(reqs, cell.params["n_check"],
                      cell.max_seq, int(cell.mix["output"]["max"]))
        gaps = served_gaps(params, cell.conf, packed, precision)
        gap, checked = float(gaps.max()), int(gaps.size)
    return Verdict([("unfinished_requests", unfinished, 0),
                    ("wrong_length_requests", short, 0),
                    ("served_logit_gap", gap,
                     float(cell.params["check"]["served_logit_gap"]))],
                   checked_requests=len(reqs), checked_tokens=checked)
