"""One general traffic generator, driven by a mix's data file.

Built to repeat (ISSUE 23): PR 22's paced cell was refused because its runs
spread by 9% of a median, and part of that was the generator's own noise.

- *Fixed composition.* The lengths of a run are the points of a fixed
  quantile grid of the mix's distributions, so every seed offers the same
  multiset of (prompt, output) lengths and the same token totals. The seed
  permutes their order, draws the token ids and orders the arrival gaps
  (open loop) or the clients' starts (closed loop).
- *Balanced order.* The permutation deals the sorted grid into strata and
  puts one item of each stratum into every block of consecutive requests,
  so any stretch of a run carries nearly the same work, whichever seed.
- *Arrivals of a fixed set of gaps.* An open-loop run sends exactly
  ``round(rate * seconds)`` requests; the gaps between them are the quantile
  grid of the distribution the mix names under ``gaps`` (exponential, a
  Poisson process's gaps, unless it says otherwise; ``gamma`` with a ``cv``
  for bursts), the same multiset on every seed, in seeded order.
- *A ramp before the window*, from the same process, so that the window
  opens on slots and cache in their steady state.

Jax-free: the process that generates load never touches a device.
(``modal_examples_tpu/fleet/loadgen.py`` is the program's own generator;
what was sound there, seeded draws and due-time scheduling, is copied in
spirit, and nothing is imported.)
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

from tokenizer import FIRST_PLAIN_ID

_NORMAL = NormalDist()


@dataclasses.dataclass
class RequestSpec:
    rid: int
    phase: str  # "ramp" | "window": by when it is due (open) or offered (closed)
    due_s: float | None  # seconds from the window's opening; None in a closed loop
    prompt_ids: list[int]
    max_tokens: int
    temperature: float
    session: int = -1
    turn: int = 0
    system_len: int = 0  # leading tokens sent as the system message


def quantile_grid(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-point quantiles of a truncated log-normal
    (``median``, ``sigma`` of the log, clipped to ``min``..``max``), or of a
    uniform range. The same for every seed."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist.get("dist", "lognormal") == "uniform":
            x = lo + u * (hi - lo)
        else:
            mu, sigma = math.log(dist["median"]), float(dist["sigma"])
            # quantiles of the log-normal truncated to [lo, hi]
            a = _NORMAL.cdf((math.log(lo) - mu) / sigma)
            b = _NORMAL.cdf((math.log(hi) - mu) / sigma)
            x = math.exp(mu + sigma * _NORMAL.inv_cdf(a + u * (b - a)))
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def balanced_order(n: int, block: int, rng: np.random.Generator) -> list[int]:
    """A permutation of ``range(n)`` (ranks of a sorted grid) in which every
    run of ``block`` consecutive places holds one rank from each of ``block``
    strata of neighbouring ranks."""
    block = max(1, min(block, n))
    n_blocks = math.ceil(n / block)
    blocks: list[list[int]] = [[] for _ in range(n_blocks)]
    for s in range(block):
        stratum = list(range(s * n_blocks, min(n, (s + 1) * n_blocks)))
        places = rng.permutation(n_blocks)[: len(stratum)]
        for rank, b in zip(stratum, places):
            blocks[int(b)].append(rank)
    order = []
    for b in blocks:
        order.extend(int(b[i]) for i in rng.permutation(len(b)))
    return order


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return rng.integers(FIRST_PLAIN_ID, vocab, size=n).tolist()


def _temperature(mix: dict, index: int) -> float:
    """Every ``greedy_every``-th request is greedy: the output check reads
    greedy tokens only, and the rest sample as the mix says."""
    k = int(mix.get("greedy_every", 0))
    return 0.0 if k and index % k == 0 else float(mix["temperature"])


def gap_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` gaps at the mid-point quantiles of the mix's gap distribution,
    in ascending order and any unit (the caller scales them): exponential,
    or gamma with coefficient of variation ``cv`` (over 1: bursts)."""
    dist = spec.get("dist", "exponential")
    if dist == "exponential":
        return np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    if dist == "gamma":
        from scipy.special import gammaincinv

        return gammaincinv(float(spec["cv"]) ** -2.0, (np.arange(n) + 0.5) / n)
    raise ValueError(f"no gap distribution {dist!r}")


def _arrivals(rng, n: int, start: float, length: float, block: int, spec: dict) -> list[float]:
    """``n`` arrival times on ``[start, start + length)`` whose gaps are the
    fixed quantile grid of the mix's gap distribution, scaled to fill the
    stretch exactly: the same multiset on every seed, in a seeded balanced
    order. (Uniform draws, a Poisson process given only its count, left runs
    that differed by 5% in every latency at once: how many arrivals fell
    into one scheduler tick, and so how many prefill calls a run made,
    changed with the seed.)"""
    if n == 0:
        return []
    gaps = gap_grid(spec, n)
    gaps = gaps[balanced_order(n, block, rng)] * (length / gaps.sum())
    offset = rng.random() * gaps[-1]  # the last gap wraps round to the start
    return (start + offset + np.concatenate([[0.0], np.cumsum(gaps[:-1])])).tolist()


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[RequestSpec]:
    """Requests of an open-loop mix: the ramp's, then the window's."""
    rate = float(mix["rate_rps"])
    rng = np.random.default_rng([int(seed), 1])
    shared = mix.get("shared_prefix") or {"tokens": 0, "variants": 1}
    systems = [
        _tokens(rng, int(shared["tokens"]), vocab)
        for _ in range(int(shared["variants"]))
    ]
    out: list[RequestSpec] = []
    for phase, start, length in (
        ("ramp", -float(mix["ramp_s"]), float(mix["ramp_s"])),
        ("window", 0.0, float(seconds)),
    ):
        n = int(round(rate * length))
        prompts = quantile_grid(mix["prompt"], n)
        outputs = quantile_grid(mix["output"], n)
        block = int(mix.get("balance_block", 8))
        p_order = balanced_order(n, block, rng)
        o_order = balanced_order(n, block, rng)
        dues = _arrivals(rng, n, start, length, block, mix.get("gaps") or {})
        for i in range(n):
            n_prompt = prompts[p_order[i]]
            system = systems[int(rng.integers(len(systems)))]
            body = _tokens(rng, max(0, n_prompt - len(system)), vocab)
            out.append(RequestSpec(
                rid=len(out), phase=phase, due_s=dues[i],
                prompt_ids=(system + body)[:n_prompt],
                max_tokens=outputs[o_order[i]],
                temperature=_temperature(mix, len(out)),
                system_len=min(len(system), n_prompt),
            ))
    return out


def closed_loop(mix: dict, seed: int, vocab: int) -> list[list[RequestSpec]]:
    """Sessions of a closed-loop mix, in the order clients take them: one
    document asked ``turns`` questions in turn. More sessions than a window
    can finish, all from one fixed grid; the seed orders documents, questions
    and answers (balanced, so any stretch of sessions carries nearly the same
    work) and draws the token ids."""
    sess = mix["session"]
    turns, n_sessions, pool = int(sess["turns"]), int(sess["sessions"]), int(sess["pool"])
    rng = np.random.default_rng([int(seed), 2])
    doc_lens = quantile_grid(sess["document"], pool)
    block = int(mix.get("balance_block", 8))
    # each round of `pool` sessions asks every document once, in balanced order
    doc_order: list[int] = []
    while len(doc_order) < n_sessions:
        doc_order.extend(balanced_order(pool, block, rng))
    n_req = n_sessions * turns
    q_lens = quantile_grid(sess["question"], n_req)
    a_lens = quantile_grid(sess["answer"], n_req)
    q_order = balanced_order(n_req, block, rng)
    a_order = balanced_order(n_req, block, rng)
    docs = [_tokens(rng, n, vocab) for n in doc_lens]
    sessions = []
    for s in range(n_sessions):
        doc = docs[doc_order[s]]
        reqs = []
        for t in range(turns):
            i = s * turns + t
            question = _tokens(rng, q_lens[q_order[i]], vocab)
            reqs.append(RequestSpec(
                rid=i, phase="window", due_s=None,
                prompt_ids=doc + question, max_tokens=a_lens[a_order[i]],
                temperature=_temperature(mix, i), session=s, turn=t,
            ))
        sessions.append(reqs)
    return sessions


def client_starts(mix: dict, seed: int) -> list[float]:
    """When each closed-loop client sends its first request, in seconds
    from the start of the ramp: an even grid over ``stagger_s`` in seeded
    order, so the clients do not march in step."""
    n = int(mix["clients"])
    order = np.random.default_rng([int(seed), 3]).permutation(n)
    stagger = float(mix.get("stagger_s", 0.0))
    return [float(stagger * k / n) for k in order]


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """Every prompt length (BOS included) the mix can send: what the warm-up
    has to cover. Independent of the seed by construction."""
    if mix["loop"] == "open":
        rate = float(mix["rate_rps"])
        return [
            1 + p
            for length in (float(seconds), float(mix["ramp_s"]))
            for p in quantile_grid(mix["prompt"], int(round(rate * length)))
        ]
    sess = mix["session"]
    docs = set(quantile_grid(sess["document"], int(sess["pool"])))
    n_req = int(sess["sessions"]) * int(sess["turns"])
    questions = set(quantile_grid(sess["question"], n_req))
    return [1 + d + q for d in docs for q in questions]
