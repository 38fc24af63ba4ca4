"""One general traffic generator. A mix is a data file of parameters under
``benchmarks/traffic/``; nothing here knows a mix by name.

Serving mixes (``"kind": "requests"``) and training mixes (``"kind":
"batches"``) are both made from ``--seed``. So that runs on different seeds do
the same work, the SCHEDULE of a mix (due time, prompt length and output
length of every request, in order) is the mix's own; the run's seed
gives the token contents (and, elsewhere, the weights). A seeded ORDER was
tried first and is not used: a request lives for a good part of a window here,
so which of the long ones arrive early decides how many tokens fall inside the
window, and runs on different seeds differed by 9-12 % where two runs of one
seed differed by 0.3 % (my chip runs, PR 22).

The schedule is STRATIFIED, not one random draw: the ``n`` lengths of a window
are the distribution's quantiles at ``(i + 0.5) / n`` (so the share of clipped
or long prompts is the distribution's own whatever ``n`` is), and only the
ORDER of those and the Poisson gaps come from the mix's ``shape_seed``. A ramp before
the window (``ramp_s``, due times below 0) is a stratum of its own at the same
rate, so the window's schedule does not depend on it.

Adapted from ``paddle_tpu/serving/loadgen.py`` (``poisson_arrivals``): that
generator draws sizes uniformly from the run seed. Shared prefixes (documents
asked for again) come with the cell that needs them (PERF.md, Open questions).
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")


def load_mix(name: str) -> Dict[str, Any]:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Request:
    due_s: float  # seconds from the start of the window
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def _lengths(rng: np.random.Generator, spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole numbers: the length distribution's quantiles at
    ``(i + 0.5) / n``, clipped to [min, max], in an order from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in q])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        raw = spec["min"] - 0.5 + q * (spec["max"] - spec["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return rng.permutation(np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64))


def _due_times(rng: np.random.Generator, arrivals: Dict[str, Any], n: int, start: float,
               seconds: float) -> np.ndarray:
    """``n`` due times inside (start, start + seconds): gaps of the arrival
    process, scaled so that the last falls ``1 / (n + 1)`` short of the end."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    gaps = rng.exponential(1.0, n)
    return start + np.cumsum(gaps * (seconds * n / (n + 1)) / gaps.sum())


def _stratum(rng: np.random.Generator, mix: Dict[str, Any], n: int, start: float, seconds: float):
    """Due time, prompt length and output length of each of ``n`` requests
    due inside (start, start + seconds)."""
    prompt_len = _lengths(rng, mix["prompt"], n)
    out_len = _lengths(rng, mix["output"], n)
    return _due_times(rng, mix["arrivals"], n, start, seconds), prompt_len, out_len


def make_requests(mix: Dict[str, Any], rate_rps: float, seconds: float, vocab_size: int,
                  seed: int, ramp_s: float = 0.0) -> List[Request]:
    """The requests due in a window of ``seconds`` at ``rate_rps`` (due times
    above 0), after those of a ramp of ``ramp_s`` before it (due times below 0)."""
    if mix["kind"] != "requests":
        raise ValueError(f"mix kind {mix['kind']!r} is not 'requests'")
    shape = np.random.default_rng(int(mix.get("shape_seed", 0)))
    parts = [_stratum(shape, mix, max(1, int(round(rate_rps * seconds))), 0.0, seconds)]
    if ramp_s > 0:
        parts.insert(0, _stratum(shape, mix, max(1, int(round(rate_rps * ramp_s))), -ramp_s, ramp_s))
    due, prompt_len, out_len = (np.concatenate(x) for x in zip(*parts))

    rng = np.random.default_rng(int(seed))  # contents only: the schedule is the mix's own
    return [Request(float(due[i]), rng.integers(1, vocab_size, int(prompt_len[i]), dtype=np.int64).astype(np.int32),
                    int(out_len[i])) for i in range(len(due))]


def describe(requests: List[Request]) -> Dict[str, Any]:
    """The window's requests (due at or after 0); the ramp's are counted only."""
    window = [r for r in requests if r.due_s >= 0]
    p = np.array([len(r.prompt) for r in window])
    o = np.array([r.max_new_tokens for r in window])
    return {
        "requests": len(window), "ramp_requests": len(requests) - len(window),
        "prompt_tokens": {"min": int(p.min()), "median": float(np.median(p)), "max": int(p.max()),
                          "sum": int(p.sum())},
        "output_tokens": {"min": int(o.min()), "median": float(np.median(o)), "max": int(o.max()),
                          "sum": int(o.sum())},
        "first_due_s": requests[0].due_s, "last_due_s": requests[-1].due_s,
    }


class BatchStream:
    """Training batches from the seed: batch ``k`` is a function of (seed, k)
    alone. ``tokens [B, S + 1]`` uniform over the vocabulary; inputs are the
    first ``S`` columns and labels the last ``S`` (next-token prediction), so
    every row differs."""

    def __init__(self, mix: Dict[str, Any], batch: int, vocab_size: int, seed: int) -> None:
        if mix["kind"] != "batches":
            raise ValueError(f"mix kind {mix['kind']!r} is not 'batches'")
        self.batch, self.seq, self.vocab, self.seed = int(batch), int(mix["sequence_length"]), int(vocab_size), int(seed)

    def get(self, k: int):
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, self.seed >> 32, int(k)])
        t = rng.integers(0, self.vocab, (self.batch, self.seq + 1), dtype=np.int64).astype(np.int32)
        return t[:, :-1], t[:, 1:]
