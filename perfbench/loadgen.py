"""Seeded traffic: ONE general generator that reads a traffic mix's data file.

A traffic mix is ``perfbench/traffic/<name>.json``; a cell's own numbers
(the rate found by the knee sweep, a client count) are ``params`` from
``perfbench/cells/<cell>.json``, and the mix refers to them by name
(``{"param": "rate_rps"}``). The program under test sees only the generated
prompts and budgets.

What a mix can say (every key the generator reads is listed here):

  loop            "open" (arrivals on a schedule, whatever the server does),
                  "batch" (a fixed batch through a closed loop: each client
                  sends its next request when its previous one completed,
                  until the batch is done) or "train" (see harness.py)
  warmup_requests requests served and drained before anything else, so that
                  the server's programs are compiled (default 1)
  warmup_s        open loop: seconds of the same traffic before the window
  requests_per_s  batch: number | {"param": name}; the batch holds
                  round(requests_per_s x seconds) requests, sized so that the
                  batch takes about ``seconds`` on the system as it is
  arrivals        open loop: {"process": "poisson" | "regular",
                  "rate": number | {"param": name},
                  "count": "fixed" | "random",
                  "rate_profile": [[seconds, relative_rate], ...]}
                  "fixed" is a Poisson process conditioned on its count
                  (uniform order statistics): every seed offers the same
                  number of requests, so runs differ by WHEN, not by how much.
                  ``rate_profile`` repeats cyclically from t=0 and is
                  normalised to the mean rate (on/off bursts).
  clients         batch: callers in the closed loop, number | {"param": name}
  prompt_tokens   {"dist": "lognormal", "median", "sigma", "min", "max"} |
  output_tokens   {"dist": "uniform", "min", "max"} | {"dist": "fixed", "value"}
  length_sampling {"kind": "iid"} | {"kind": "stratified", "block": n}:
                  stratified draws each block of n lengths from the n
                  equal-probability strata of the distribution in a seeded
                  order, so every seed carries (nearly) the same multiset of
                  lengths — a fixed amount of work drawn from the seed
  shared_prefix   optional {"tokens", "groups", "share"}: that share of
                  requests start with one of ``groups`` fixed prefixes, put
                  in front of the drawn (unshared) prompt
  schedule_seed   optional: draw WHEN requests arrive, how long they are and
                  which share a prefix from this number instead of the run's
                  seed, which then draws only the token values (and the
                  weights). A replayed schedule: at tens of requests per
                  window, the order of arrivals and lengths alone moves a
                  latency median by more than any bound could allow (PERF.md)
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()
# independent random streams, so that changing one parameter of a mix does
# not shift the draws of another
_STREAMS = {"prompt_len": 1, "output_len": 2, "arrivals": 3, "sharing": 5,
            "tokens": 4, "prefixes": 6}
_CONTENT = ("tokens", "prefixes")       # always from the run's seed
_WARMUP = 100                           # the warm-up requests' own streams


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray              # int32 token ids in [1, vocab)
    max_new_tokens: int
    due_s: Optional[float] = None   # open loop: seconds from the window's
    #                                 start (negative: warm-up traffic)


def resolve(value, params: Dict):
    """A number, or ``{"param": name}`` looked up in the cell's params."""
    if isinstance(value, dict):
        name = value["param"]
        if name not in params:
            raise KeyError(f"traffic mix needs the cell parameter {name!r}; "
                           f"the cell gives {sorted(params)}")
        return params[name]
    return value


def quantile(spec: Dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths at probabilities ``u`` of the distribution ``spec``."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(len(u), int(spec["value"]), np.int64)
    if dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(min(max(p, 1e-12), 1 - 1e-12))
                      for p in u])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def uniforms(rng: np.random.Generator, n: int, sampling: Dict) -> np.ndarray:
    """``n`` probabilities in (0, 1): independent, or stratified by block."""
    kind = sampling.get("kind", "iid")
    if kind == "iid":
        return rng.random(n)
    if kind != "stratified":
        raise ValueError(f"unknown length_sampling kind {kind!r}")
    block = int(sampling["block"])
    out = []
    for start in range(0, n, block):
        b = min(block, n - start)
        out.append((rng.permutation(b) + rng.random(b)) / b)
    return np.concatenate(out) if out else np.zeros((0,))


def arrival_times(spec: Dict, rate: float, start_s: float, end_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in [start_s, end_s) at mean ``rate`` per second."""
    span = end_s - start_s
    if span <= 0 or rate <= 0:
        return np.zeros((0,))
    mean = rate * span
    count = spec.get("count", "fixed")
    if count == "fixed":
        n = int(round(mean))
    elif count == "random":
        n = int(rng.poisson(mean))
    else:
        raise ValueError(f"unknown arrivals count {count!r}")
    process = spec.get("process", "poisson")
    if process == "poisson":
        u = np.sort(rng.random(n))
    elif process == "regular":
        u = (np.arange(n) + 0.5) / max(n, 1)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    profile = spec.get("rate_profile")
    if not profile:
        return start_s + u * span
    # piecewise-constant relative rate, cyclic from t=0: invert its
    # cumulative intensity over [start_s, end_s)
    period = sum(d for d, _ in profile)
    edges, rates = [start_s], []
    t = start_s
    while t < end_s:
        phase = t % period
        acc = 0.0
        for dur, rel in profile:
            if phase < acc + dur:
                nxt = min(t + (acc + dur - phase), end_s)
                edges.append(nxt)
                rates.append(rel)
                t = nxt
                break
            acc += dur
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * np.array(rates))])
    if cum[-1] <= 0:
        raise ValueError("rate_profile has no arrivals in the interval")
    return np.interp(u * cum[-1], cum, np.array(edges))


class Traffic:
    """The requests of one run: ``schedule()`` for an open loop,
    ``batch()`` for a batch. Same (mix, params, vocab, seed, seconds) ->
    same requests."""

    def __init__(self, mix: Dict, params: Dict, vocab_size: int, seed: int,
                 seconds: float):
        self.mix = mix
        self.loop = mix["loop"]
        self.warmup_s = float(mix.get("warmup_s", 0.0))
        self.seconds = float(seconds)
        self._params = params
        self._vocab = int(vocab_size)
        self._seed = int(seed)
        self._schedule_seed = int(mix.get("schedule_seed", seed))
        self._sampling = mix.get("length_sampling", {"kind": "iid"})
        share = mix.get("shared_prefix")
        self._share = share
        if share:
            rng = self._rng("prefixes")
            self._prefixes = rng.integers(
                1, self._vocab, (int(share["groups"]), int(share["tokens"])),
                dtype=np.int32)

    def _rng(self, stream: str, offset: int = 0) -> np.random.Generator:
        seed = self._seed if stream in _CONTENT else self._schedule_seed
        return np.random.default_rng([seed, _STREAMS[stream] + offset])

    def _rngs(self, offset: int = 0) -> Dict:
        return {k: self._rng(k, offset) for k in
                ("prompt_len", "output_len", "tokens", "sharing")}

    @property
    def clients(self) -> int:
        return int(resolve(self.mix["clients"], self._params))

    @property
    def rate(self) -> float:
        return float(resolve(self.mix["arrivals"]["rate"], self._params))

    def _requests(self, n: int, first_index: int, rngs: Dict) -> List[Request]:
        p_len = quantile(self.mix["prompt_tokens"],
                         uniforms(rngs["prompt_len"], n, self._sampling))
        o_len = quantile(self.mix["output_tokens"],
                         uniforms(rngs["output_len"], n, self._sampling))
        out = []
        for i in range(n):
            prompt = rngs["tokens"].integers(1, self._vocab, int(p_len[i]),
                                             dtype=np.int32)
            if self._share and rngs["sharing"].random() < self._share["share"]:
                g = int(rngs["sharing"].integers(len(self._prefixes)))
                prompt = np.concatenate([self._prefixes[g], prompt])
            out.append(Request(first_index + i, prompt, int(o_len[i])))
        return out

    def schedule(self) -> List[Request]:
        """Open loop: every request of warm-up and window with its due time,
        in due order. The window's requests are those with ``due_s >= 0``."""
        spec = self.mix["arrivals"]
        rng = self._rng("arrivals")
        due = np.concatenate([
            arrival_times(spec, self.rate, -self.warmup_s, 0.0, rng),
            arrival_times(spec, self.rate, 0.0, self.seconds, rng)])
        reqs = self._requests(len(due), 0, self._rngs())
        return [dataclasses.replace(r, due_s=float(t))
                for r, t in zip(reqs, due)]

    def warmup(self) -> List[Request]:
        """The requests served and drained before the clock starts (indices
        below zero, streams of their own)."""
        n = int(self.mix.get("warmup_requests", 1))
        return self._requests(n, -n, self._rngs(_WARMUP))

    def batch(self) -> List[Request]:
        """Batch loop: the batch, sized by the window's length."""
        rate = float(resolve(self.mix["requests_per_s"], self._params))
        n = max(1, int(round(rate * self.seconds)))
        return self._requests(n, 0, self._rngs())
