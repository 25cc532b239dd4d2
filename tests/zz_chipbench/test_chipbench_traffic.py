"""The traffic generator: deterministic in the seed, and the lengths it
says it draws."""

import json
import os

import numpy as np

from chipbench import manifest, traffic


def _mix(name):
    with open(os.path.join(manifest.ROOT, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _take(stream, n):
    return [next(stream) for _ in range(n)]


def test_same_seed_same_requests_other_seed_others():
    mix = _mix("chat-prefix")
    a = _take(traffic.request_stream(mix, 3, 50257), 50)
    b = _take(traffic.request_stream(mix, 3, 50257), 50)
    c = _take(traffic.request_stream(mix, 4, 50257), 50)
    assert [(r.due_s, r.prompt, r.max_new_tokens, r.seed) for r in a] == \
           [(r.due_s, r.prompt, r.max_new_tokens, r.seed) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_chat_prefix_lengths_sharing_and_arrivals():
    mix = _mix("chat-prefix")
    reqs = _take(traffic.request_stream(mix, 0, 50257, rate_per_s=10.0), 4000)
    spec = mix["prompt"]
    n_prefix = spec["shared_prefix"]["tokens"]
    unique = np.array([len(r.prompt) - n_prefix for r in reqs])
    out = np.array([r.max_new_tokens for r in reqs])
    assert unique.min() >= spec["unique"]["min"]
    assert unique.max() <= spec["unique"]["max"]
    assert abs(np.median(unique) - spec["unique"]["median"]) < 12
    assert out.min() >= mix["output"]["min"] and out.max() <= mix["output"]["max"]
    assert abs(np.median(out) - mix["output"]["median"]) < 4
    assert all(len(r.prompt) + r.max_new_tokens <= mix["max_total"] for r in reqs)
    # more than half of the prompt tokens are shareable
    assert n_prefix * len(reqs) > 0.5 * sum(len(r.prompt) for r in reqs)
    # Zipf(1) over 32 prefixes: the first is ~1/H(32) = 24.6% of requests
    prefixes = traffic.shared_prefixes(mix, 0, 50257)
    assert len(prefixes) == 32 and len(prefixes[0]) == n_prefix
    share = np.mean([r.prefix_id == 0 for r in reqs])
    assert 0.21 < share < 0.28
    assert all(r.prompt[:n_prefix] == prefixes[r.prefix_id] for r in reqs[:50])
    # Poisson at 10/s: mean gap 100 ms, cv 1
    gaps = np.diff([r.due_s for r in reqs])
    assert abs(gaps.mean() - 0.1) < 0.006
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.08


def test_batch_gen_is_unshared_backlog_with_stationary_fill():
    mix = _mix("batch-gen")
    reqs = _take(traffic.request_stream(mix, 0, 50257), 2000)
    assert all(r.due_s is None and r.prefix_id is None for r in reqs)
    assert all(r.temperature == 0.8 and r.top_k == 40 for r in reqs)
    prompts = np.array([len(r.prompt) for r in reqs])
    out = np.array([r.max_new_tokens for r in reqs])
    assert prompts.min() >= 32 and prompts.max() <= 384
    assert out.min() >= 128 and out.max() <= 640
    assert abs(np.median(prompts) - 128) < 10 and abs(np.median(out) - 384) < 15
    assert len({tuple(r.prompt[:16]) for r in reqs}) == len(reqs)  # unshared
    fill = traffic.stationary_fill(mix, 0, 50257, 256)
    assert len(fill) == 256
    assert [r.prompt for r in fill] == \
           [r.prompt for r in traffic.stationary_fill(mix, 0, 50257, 256)]
    # caught mid-life: about half the output is left, the rest is context
    left = np.mean([r.max_new_tokens for r in fill])
    assert 0.35 * out.mean() < left < 0.7 * out.mean()
    assert np.mean([len(r.prompt) for r in fill]) > prompts.mean() + 100
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in fill)


def test_train_batch_is_the_programs_first_synthetic_batch():
    from nezha_tpu.data import synthetic

    job = {"data": "synthetic_tokens", "vocab_size": 512, "seq_len": 64}
    want = next(synthetic.synthetic_token_batches(4, seq_len=64,
                                                  vocab_size=512))
    assert (traffic.train_batch(job, 4)["tokens"] == want["tokens"]).all()
    job = {"data": "synthetic_mlm", "vocab_size": 512, "seq_len": 64,
           "mask_rate": 0.15, "mask_token": 1}
    want = next(synthetic.synthetic_mlm_batches(4, seq_len=64, vocab_size=512,
                                                mask_token=1))
    got = traffic.train_batch(job, 4)
    assert set(got) == set(want)
    assert all((got[k] == want[k]).all() for k in want)
