"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<name>.json``); everything below is drawn from ``--seed``.

Serving mixes (``generator``: ``open_loop`` or ``backlog``)::

    {"generator": "open_loop",
     "arrivals": {"process": "poisson", "rate_per_s": 24.0,
                  "fixed_count": true},
       # or {"process": "gamma", "rate_per_s": r, "cv": 3.0} for bursts;
       # a backlog mix has no arrivals: the queue is kept topped up.
       # "fixed_count": the Poisson process conditioned on its count:
       # exactly round(rate * horizon) arrivals, uniform over the horizon
       # (the same local burstiness; the offered work no longer varies
       # from seed to seed with the count's own Poisson noise)
     "prompt": {"shared_prefix": {"count": 32, "tokens": 256, "zipf_s": 1.0},
                "unique": {"median": 192, "sigma": 0.6, "min": 32, "max": 640}},
     "output": {"median": 48, "sigma": 0.6, "min": 8, "max": 128},
     "max_total": 1024,
     "sampling": {"temperature": 0.0, "top_k": null}}

Lengths are log-normal (``median``, ``sigma`` of the underlying normal),
rounded and clipped to [min, max]; where prompt + output would pass
``max_total`` the output is cut. A shared prefix is one of ``count`` fixed
token strings, picked with Zipf(``zipf_s``) popularity; the unique part is
fresh random tokens, so two requests share nothing but a prefix.

Training mixes (``generator``: ``train_steps``) carry the job's arguments
and a fixed number of ``steps`` (one number, one compiled program);
the batches come from the program's own synthetic input path, whose
arithmetic is copied in :func:`train_batch` for the reference check.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    due_s: Optional[float]      # seconds after the stream's start; None
    #                             for a backlog (due when a slot wants it)
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    top_k: Optional[int]
    seed: int
    prefix_id: Optional[int] = None


def _lognormal(rng, spec: dict) -> int:
    x = float(np.exp(rng.normal(np.log(spec["median"]), spec["sigma"])))
    return int(min(max(round(x), spec["min"]), spec["max"]))


def _zipf_weights(count: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return w / w.sum()


def shared_prefixes(traffic: dict, seed: int, vocab: int) -> List[List[int]]:
    """The mix's fixed system prompts (empty when it shares nothing)."""
    spec = traffic["prompt"].get("shared_prefix")
    if not spec:
        return []
    rng = np.random.default_rng([seed, 0x5EED])
    return [rng.integers(0, vocab, spec["tokens"]).tolist()
            for _ in range(spec["count"])]


def request_stream(traffic: dict, seed: int, vocab: int,
                   rate_per_s: Optional[float] = None,
                   horizon_s: Optional[float] = None,
                   prefix_seed: Optional[int] = None) -> Iterator[Req]:
    """Seeded stream of requests, endless unless the arrivals have a
    fixed count (then it ends after ``horizon_s``). ``rate_per_s``
    overrides the file's rate (the knee sweep offers several);
    ``prefix_seed`` names the run whose shared prefixes a second stream
    (the stationary fill) must share."""
    rng = np.random.default_rng([seed, 0xA11])
    prefixes = shared_prefixes(
        traffic, seed if prefix_seed is None else prefix_seed, vocab)
    pspec = traffic["prompt"].get("shared_prefix")
    weights = _zipf_weights(pspec["count"], pspec["zipf_s"]) if pspec else None
    arrivals = traffic.get("arrivals")
    if arrivals and rate_per_s is not None:
        arrivals = {**arrivals, "rate_per_s": rate_per_s}
    sampling = traffic.get("sampling", {})
    fixed = None
    if arrivals and arrivals.get("fixed_count") and horizon_s is not None:
        n = int(round(arrivals["rate_per_s"] * horizon_s))
        fixed = np.sort(rng.uniform(0.0, horizon_s, n)).tolist()
    t = 0.0
    index = 0
    while True:
        due = None
        if fixed is not None:
            if index >= len(fixed):
                return
            due = fixed[index]
        elif arrivals:
            mean = 1.0 / arrivals["rate_per_s"]
            if arrivals["process"] == "poisson":
                t += float(rng.exponential(mean))
            elif arrivals["process"] == "gamma":
                shape = 1.0 / arrivals["cv"] ** 2
                t += float(rng.gamma(shape, mean / shape))
            else:
                raise ValueError(f"unknown arrival process {arrivals!r}")
            due = t
        prompt, prefix_id = [], None
        if prefixes:
            prefix_id = int(rng.choice(len(prefixes), p=weights))
            prompt = list(prefixes[prefix_id])
        n_unique = _lognormal(rng, traffic["prompt"]["unique"])
        prompt += rng.integers(0, vocab, n_unique).tolist()
        out = _lognormal(rng, traffic["output"])
        limit = traffic["max_total"]
        if len(prompt) >= limit:
            prompt = prompt[:limit - 1]
        out = max(1, min(out, limit - len(prompt)))
        yield Req(index=index, due_s=due, prompt=prompt, max_new_tokens=out,
                  temperature=float(sampling.get("temperature", 0.0)),
                  top_k=sampling.get("top_k"),
                  seed=int(rng.integers(0, 2 ** 31 - 1)),
                  prefix_id=prefix_id)
        index += 1


def stationary_fill(traffic: dict, seed: int, vocab: int, slots: int
                    ) -> List[Req]:
    """``slots`` requests caught mid-life, for a mix's first fill:
    each is a drawn request that has already produced a share of its
    output, so its prompt is the drawn prompt plus that many random
    'already generated' tokens and its budget is the rest. Slot occupancy
    and resident context are then those of the steady state from the first
    second, and the completions are spread over a lifetime instead of
    arriving together. (A slot seen at a random instant holds a request
    drawn with probability proportional to its length; the fill draws
    twice as many as it needs and keeps ``slots`` of them with that
    weight. The shares are stratified, one in each ``1/slots`` of [0, 1):
    how many requests end inside a window is then nearly the same for
    every seed, which independent draws would make a Poisson count.)"""
    if slots <= 0:
        return []
    rng = np.random.default_rng([seed, 0xF111])
    stream = request_stream(traffic, seed + 1_000_003, vocab,
                            prefix_seed=seed)
    pool = [next(stream) for _ in range(2 * slots)]
    w = np.array([r.max_new_tokens for r in pool], float)
    picks = rng.choice(len(pool), size=slots, replace=False, p=w / w.sum())
    shares = (rng.permutation(slots) + rng.random(slots)) / slots
    out = []
    for i, k in enumerate(picks):
        r = pool[int(k)]
        done = min(int(shares[i] * r.max_new_tokens), r.max_new_tokens - 1)
        out.append(dataclasses.replace(
            r, index=-(i + 1), due_s=None,
            prompt=r.prompt + rng.integers(0, vocab, done).tolist(),
            max_new_tokens=r.max_new_tokens - done))
    return out


def train_batch(job: dict, batch_size: int) -> dict:
    """The first batch of the program's synthetic input path, recomputed
    here (``nezha_tpu/data/synthetic.py``: ``RandomState(0)``, a pool of
    four fixed batches) so the reference sees what the first step saw. The
    program's synthetic path takes no seed; the weights do."""
    r = np.random.RandomState(0)
    if job["data"] == "synthetic_tokens":
        return {"tokens": r.randint(
            0, job["vocab_size"],
            size=(batch_size, job["seq_len"] + 1)).astype(np.int32)}
    if job["data"] == "synthetic_mlm":
        tokens = r.randint(0, job["vocab_size"],
                           size=(batch_size, job["seq_len"])).astype(np.int32)
        labels = np.full_like(tokens, -100)
        mask = r.rand(batch_size, job["seq_len"]) < job["mask_rate"]
        labels[mask] = tokens[mask]
        tokens = tokens.copy()
        tokens[mask] = job["mask_token"]
        return {"tokens": tokens, "labels": labels,
                "segment_ids": np.zeros_like(tokens)}
    raise ValueError(f"unknown train data {job['data']!r}")
