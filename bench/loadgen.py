"""Traffic for the benchmark: one general generator driven by a data file.

A traffic file (``bench/traffic/<name>.json``) names the arrival process,
its rate or client count, the prompt and output length mixtures and the
per-request sampling. Everything is drawn from one seed.

The mixtures and the bursty arrivals are a copy of the program's
``serve/loadgen.py`` (weighted ``(weight, lo, hi)`` uniform components;
clumps of arrivals), kept here so that a change to the program cannot
change the load it is measured under. Four things are added:

- stratified draws: every seed gets the same multiset of lengths and,
  for Poisson arrivals, of inter-arrival gaps, in its own order. A
  random draw changes the amount of work from seed to seed, and that
  spread would read as noise; the stratified draw takes each length and
  gap at evenly spaced quantiles of its distribution and lets the seed
  choose only the order. Bursty arrivals keep the copied draw of times.
- a closed loop (``arrival: "closed"``): ``clients`` callers that each
  send their next request when the previous one has finished.
- a staggered start for the closed loop: each client's first request
  carries a prior progress, part of its drawn output already in its
  prompt, so that the window opens on contexts of every age, as in the
  loop's steady state, and not on a batch that all started together.
- ``schedule_seed``: where a traffic file sets it, the sizes and the
  arrival times come from that seed, the same for every run, and the
  run's seed makes only the prompts' tokens. A tail over a few dozen
  requests is set by their order, so a per-run order spreads the p90
  across seeds by more than any bound the benchmark may set.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

# ((weight, lo, hi), ...): uniform ints in [lo, hi] per component
Mixture = Tuple[Tuple[float, int, int], ...]


@dataclass(frozen=True)
class Traffic:
    arrival: str                   # "poisson" | "bursty" | "closed"
    prompt_lens: Mixture
    output_lens: Mixture
    rate: float = 0.0              # open loop: mean requests per second
    burst: float = 8.0             # bursty: mean clump size
    clients: int = 0               # closed loop: concurrent callers
    lead_seconds: float = 0.0      # open loop: traffic before the window
    temperature: float = 0.0
    top_p: float = 1.0
    greedy_every: int = 1          # request i is greedy when i % k == 0
    schedule_seed: Optional[int] = None   # sizes and arrival times drawn
    #                                from this seed instead of the run's


def traffic_from_dict(d: dict) -> Traffic:
    keys = set(Traffic.__dataclass_fields__)
    unknown = set(d) - keys - {"why", "engine", "limits", "check"}
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    kw = {k: v for k, v in d.items() if k in keys}
    for k in ("prompt_lens", "output_lens"):
        kw[k] = tuple((float(w), int(lo), int(hi)) for w, lo, hi in kw[k])
    return Traffic(**kw)


# -- the copied arithmetic (serve/loadgen.py) ------------------------------

BURST_SPREAD = 1e-3                # bursty: spacing inside a clump (s)


def bursty_times(rng: np.random.Generator, rate: float, n: int,
                 t0: float = 0.0, burst: float = 8.0) -> np.ndarray:
    """``n`` arrival times in clumps of mean size ``burst`` at a mean rate
    of ``rate``: exponential gaps between clumps, geometric clump sizes."""
    times: List[float] = []
    t = t0
    while len(times) < n:
        t += rng.exponential(burst / rate)              # clump gap
        size = int(rng.geometric(1.0 / max(burst, 1.0)))
        for k in range(min(size, n - len(times))):
            times.append(t + k * BURST_SPREAD)
        t = times[-1]
    return np.asarray(times[:n])


# -- stratified draws -------------------------------------------------------

def mixture_quantile(mix: Mixture, u: float) -> int:
    """Inverse CDF of a mixture of discrete uniforms at ``u`` in (0, 1)."""
    w = np.asarray([m[0] for m in mix], float)
    w = w / w.sum()
    acc = 0.0
    for j, ((_, lo, hi), wk) in enumerate(zip(mix, w)):
        if u < acc + wk or j == len(mix) - 1:
            frac = min(max((u - acc) / wk, 0.0), 1.0 - 1e-12)
            return int(lo + math.floor(frac * (hi - lo + 1)))
        acc += wk
    raise AssertionError("unreachable")


def stratified_lens(rng: np.random.Generator, mix: Mixture,
                    n: int) -> np.ndarray:
    """``n`` lengths at the mixture's quantiles (i + 0.5) / n, shuffled."""
    vals = np.asarray([mixture_quantile(mix, (i + 0.5) / n)
                       for i in range(n)], np.int64)
    return rng.permutation(vals)


def stratified_times(rng: np.random.Generator, rate: float, n: int,
                     span: float) -> np.ndarray:
    """``n`` arrival times in ``[0, span)``: exponential gaps at evenly
    spaced quantiles, shuffled, then scaled so that all ``n`` fall inside
    the span (the mean rate is then n / span, which the caller sets to
    ``rate``)."""
    if n <= 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    t = np.cumsum(gaps)
    return t * (span * (n - 0.5) / n) / t[-1]


# -- requests ---------------------------------------------------------------

@dataclass
class Planned:
    """One request as the generator plans it, before it is sent."""
    index: int
    due: float                     # seconds after the traffic starts;
    #                                closed loop: nan until a client sends it
    prompt: np.ndarray
    max_new: int
    greedy: bool
    prior: int = 0                 # closed loop: tokens of prior progress
    #                                at the end of the prompt


def _lens(rng, t: Traffic, n: int) -> Tuple[np.ndarray, np.ndarray]:
    return (stratified_lens(rng, t.prompt_lens, n),
            stratified_lens(rng, t.output_lens, n))


def plan(t: Traffic, seed: int, seconds: float, vocab: int,
         n_closed: int = 0) -> List[Planned]:
    """The requests of one run, in send order.

    Open loop: the requests due in ``[0, lead_seconds + seconds)``; the
    window is ``[lead_seconds, lead_seconds + seconds)``. For Poisson
    arrivals lead-in and window each hold ``round(rate * length)``
    requests. Closed loop: ``n_closed`` requests that the clients take in
    order; the first ``clients`` are the clients' staggered first
    requests (``stagger``).
    """
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    tok_rng = rng
    if t.schedule_seed is not None:
        # one schedule for every run: the run's seed makes the prompts'
        # tokens (and, in the harness, the weights and sampling keys)
        rng = np.random.default_rng(
            np.random.SeedSequence(int(t.schedule_seed)))
    if t.arrival == "closed":
        # the first requests and the rest are each their own multiset
        blocks = (min(t.clients, n_closed), n_closed - min(t.clients,
                                                           n_closed))
        times = np.full(n_closed, np.nan)
    elif t.arrival == "poisson":
        # lead-in and window are each their own multiset, so that every
        # seed's window holds the same requests
        blocks = (int(round(t.rate * t.lead_seconds)),
                  max(1, int(round(t.rate * seconds))))
        times = np.concatenate([
            stratified_times(rng, t.rate, blocks[0], t.lead_seconds),
            t.lead_seconds + stratified_times(rng, t.rate, blocks[1],
                                              seconds)])
    elif t.arrival == "bursty":
        horizon = t.lead_seconds + seconds
        times = bursty_times(rng, t.rate, int(t.rate * horizon * 2 + 16),
                             burst=t.burst)
        times = times[times < horizon]
        blocks = (len(times),)
    else:
        raise ValueError(f"unknown arrival process {t.arrival!r}")
    lens = [_lens(rng, t, k) for k in blocks]
    plens = np.concatenate([x[0] for x in lens]).astype(np.int64)
    olens = np.concatenate([x[1] for x in lens]).astype(np.int64)
    prior = np.zeros(len(times), np.int64)
    if t.arrival == "closed":
        prior[:blocks[0]] = stagger(rng, olens[:blocks[0]])
    return _requests(tok_rng, t, times, plens, olens, prior, vocab)


def stagger(rng: np.random.Generator, olens: np.ndarray) -> np.ndarray:
    """Prior progress of the closed loop's first requests: request i has
    served ``floor(u_i * olens[i])`` of its tokens, with the ``u_i`` at
    evenly spaced quantiles of (0, 1), shuffled, so that the clients'
    contexts are spread over every age of a request, as in the loop's
    steady state (where a request in flight is at a uniform point of
    its life), and every seed gets the same spread."""
    n = len(olens)
    u = rng.permutation((np.arange(n) + 0.5) / n)
    return np.minimum(np.floor(u * olens).astype(np.int64), olens - 1)


def _requests(rng, t: Traffic, times, plens, olens, prior,
              vocab: int) -> List[Planned]:
    k = max(1, int(t.greedy_every))
    out = []
    for i in range(len(times)):
        n = int(plens[i] + prior[i])
        prompt = rng.integers(1, vocab, size=n).astype(np.int32)
        out.append(Planned(i, float(times[i]), prompt,
                           int(olens[i] - prior[i]),
                           greedy=(t.temperature <= 0 or i % k == 0),
                           prior=int(prior[i])))
    return out


def max_output(t: Traffic) -> int:
    return max(hi for _, _, hi in t.output_lens)


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile of ``values`` with linear interpolation (numpy's
    default), as the benchmark reports tails."""
    return float(np.quantile(np.asarray(values, float), q))
