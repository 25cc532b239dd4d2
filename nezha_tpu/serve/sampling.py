"""Per-row sampling for the batched decode step.

``models/generate._sample`` keys its compiled program on PYTHON-level
sampling params — fine for one-shot batch decode, fatal for serving,
where every recompile stalls the whole batch. Here temperature / top-k /
top-p arrive as TRACED ``[B]`` arrays, so one compiled step serves every
mix of requests:

- temperature ``<= 0`` selects greedy argmax for that row (no RNG
  consumed — a greedy row's tokens are bit-identical whatever its batch
  neighbors sample);
- top-k cannot be traced through ``lax.top_k`` (its k is static), so the
  step always extracts the static ``k_max`` largest logits (config cap)
  and masks by PER-ROW k via rank comparison against the row's k-th
  value; ``top_k <= 0`` disables truncation for the row, and per-row k is
  clamped to ``[1, k_max]``;
- top-p is the same exclusive-cumsum nucleus as ``_sample`` with p
  broadcast per row (``p >= 1`` keeps everything, ``p <= 0`` degrades to
  argmax via the rank-0 term — never an empty nucleus), read off the
  ``k_max`` head top-k already extracted: the vocabulary is sorted only
  in a step where some row's nucleus is wider than the head
  (:func:`filter_logits_and_flag`);
- rows draw from their OWN PRNG key (vmapped categorical), so sampling
  rows are also isolated: a request's token sequence depends only on its
  seed and its step count, never on who shares the batch.

:func:`split_and_sample` packages one decode step's sampling move —
split every row's key, sample from the carried logits — for the
engine's block-decode scan body: the caller advances a row's key only
when the token is actually EMITTED, so a request's RNG stream depends
on its seed and emitted-token count alone, never on the decode horizon
or its batch neighbors (horizon=1 and horizon=8 sample identical
sequences).

The speculative-decoding kernels live here too (the engine's
draft→verify→accept window composes them): :func:`filter_logits` is the
ONE per-row temperature/top-k/top-p truncation both the classic sampler
and the speculative accept test apply — the rejection test is lossless
for any proposal distribution, but a draft proposal outside the
target's truncated support has p = 0 and always rejects, so the draft
proposes from the same filtered support to keep accept rates at the
draft's actual fidelity; :func:`accept_mask` is the per-position accept
decision (greedy: exact match against the target argmax; sampled: the
standard ``u·q ≤ p`` rejection test); :func:`residual_logits` is the
rejection-resample distribution ``norm(max(p − q, 0))`` in log space —
the engine carries it as the row's next sampling distribution (flagged
``residual``), so the token emitted after a rejection is drawn from
exactly the residual the lossless-speculative-sampling theorem
requires, one window later.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def finite_rows(logits) -> jax.Array:
    """``[B, V]`` logits -> ``[B]`` bool: True where every entry in the
    row is finite. The decode step's NaN/inf tripwire: computed in-program
    (two cheap reductions against a forward pass) on both the carried-in
    logits and the fresh row, so the host learns which rows went bad
    without an extra device round-trip — the scheduler retires those
    requests with ``FinishReason.ERROR`` instead of decoding garbage
    forever or killing the batch."""
    return jnp.isfinite(logits).all(axis=-1)


def _sorted_nucleus_threshold(scaled, top_p) -> jax.Array:
    """``[B, V]`` top-k survivors (the rest at ``-inf``) -> ``[B, 1]``
    nucleus threshold by a sort of the whole row: the smallest value
    whose exclusive cumulative mass is under ``top_p``. What a row costs
    when its nucleus is wider than the ``k_max`` head (the second branch
    of :func:`filter_logits_and_flag`'s ``cond``)."""
    sorted_logits = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    rank = lax.broadcasted_iota(jnp.int32, sorted_logits.shape, 1)
    keep = (exclusive_cum < top_p[:, None]) | (rank == 0)
    return jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                   keepdims=True)


def filter_logits_and_flag(logits, temperature, top_k, top_p,
                           k_max: int):
    """:func:`filter_logits` plus a scalar bool: whether this call ran
    the full-vocabulary sort (the step program returns it; the engine
    counts ``serve.sampling.full_sort_steps_total``).

    The ``k_max`` largest scaled logits ``lax.top_k`` returns (the
    "head", in descending order) are, once the row's top-k rule is
    applied to them, the first ``k_max`` entries of the sorted
    survivors, so the nucleus threshold is read off their exclusive
    cumulative mass; the normaliser is one ``exp``-sum over ALL the
    row's survivors, which counts ties at the k-th value that lie
    outside the head. That is exact unless every head entry is inside
    the nucleus AND survivors lie below the head's last value (top-k
    off, a flat distribution): only such a row needs the sort, and one
    ``lax.cond`` on "any row needs it" runs it. ``top_p >= 1`` is no
    nucleus at all."""
    b, v = logits.shape
    if not 1 <= k_max <= v:
        raise ValueError(f"k_max must be in [1, {v}], got {k_max}")
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

    # Per-row top-k under the static cap: the k_max'th-largest values are
    # computed once; each row thresholds at its own (clamped) k-th value.
    head = lax.top_k(scaled, k_max)[0]                        # [B, k_max]
    k_eff = jnp.clip(top_k, 1, k_max)
    kth = jnp.take_along_axis(head, (k_eff - 1)[:, None], axis=1)
    apply_k = (top_k > 0)[:, None]
    scaled = jnp.where(apply_k & (scaled < kth), -jnp.inf, scaled)
    head = jnp.where(apply_k & (head < kth), -jnp.inf, head)

    # Per-row nucleus from the head (same construction as
    # generate._sample over the sorted row, p per row).
    norm = jnp.sum(jnp.exp(scaled - head[:, :1]), axis=-1, keepdims=True)
    probs = jnp.exp(head - head[:, :1]) / norm
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    rank = lax.broadcasted_iota(jnp.int32, head.shape, 1)
    keep = (exclusive_cum < top_p[:, None]) | (rank == 0)
    threshold = jnp.min(jnp.where(keep, head, jnp.inf), axis=-1,
                        keepdims=True)

    nucleus = top_p < 1.0
    below_head = ((scaled < head[:, -1:]) & (scaled > -jnp.inf)).any(axis=-1)
    wide = nucleus & keep[:, -1] & below_head
    full_sort = wide.any()
    sorted_threshold = lax.cond(
        full_sort, _sorted_nucleus_threshold,
        lambda s, p: jnp.full((b, 1), -jnp.inf, s.dtype), scaled, top_p)
    threshold = jnp.where(wide[:, None], sorted_threshold, threshold)
    threshold = jnp.where(nucleus[:, None], threshold, -jnp.inf)
    return jnp.where(scaled < threshold, -jnp.inf, scaled), full_sort


def filter_logits(logits, temperature, top_k, top_p,
                  k_max: int) -> jax.Array:
    """The per-row temperature/top-k/top-p truncation, factored out of
    :func:`sample_tokens` so the speculative accept test can apply the
    IDENTICAL filtering to draft and target logits: ``[B, V]`` logits ->
    ``[B, V]`` scaled logits with truncated entries at ``-inf``.
    Sampling from the result (``categorical``) is exactly what
    :func:`sample_tokens` does for non-greedy rows."""
    return filter_logits_and_flag(logits, temperature, top_k, top_p,
                                  k_max)[0]


def sample_tokens_and_flag(logits, keys, temperature, top_k, top_p,
                           k_max: int):
    """:func:`sample_tokens` plus :func:`filter_logits_and_flag`'s
    scalar: ``(token ids [B], full_sort)``."""
    greedy = temperature <= 0.0
    scaled, full_sort = filter_logits_and_flag(logits, temperature, top_k,
                                               top_p, k_max)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    tok = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
    return tok.astype(jnp.int32), full_sort


def sample_tokens(logits, keys, temperature, top_k, top_p,
                  k_max: int) -> jax.Array:
    """logits ``[B, V]``, keys ``[B, 2]`` (one PRNG key per row),
    temperature/top_p ``[B]`` float, top_k ``[B]`` int (``<= 0`` = off),
    ``k_max`` static int (``1 <= k_max <= V``) -> token ids ``[B]``.
    """
    return sample_tokens_and_flag(logits, keys, temperature, top_k, top_p,
                                  k_max)[0]


def split_and_sample(keys, logits, temperature, top_k, top_p,
                     k_max: int):
    """One decode step's sampling move: split every row's PRNG key and
    sample from the carried logits. ``keys`` ``[B, 2]`` -> ``(next_keys
    [B, 2], tokens [B], full_sort)``, the last as
    :func:`filter_logits_and_flag` gives it. The caller commits
    ``next_keys`` only for rows whose token is actually emitted — that
    is what keeps a request's RNG stream a function of (seed, emitted
    count) alone, so the same request samples bit-identical tokens at
    any decode horizon and next to any batch mix."""
    splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    tok, full_sort = sample_tokens_and_flag(
        logits, splits[:, 1], temperature, top_k, top_p, k_max)
    return splits[:, 0], tok, full_sort


# ------------------------------------------------- speculative decoding
def categorical_rows(keys, logits) -> jax.Array:
    """Per-row categorical draw: ``keys [B, 2]``, ``logits [B, V]`` ->
    ``[B]`` int32. Used for the residual-distribution resample, whose
    logits are ALREADY filtered log-probabilities — re-applying the
    temperature/top-k/top-p filter there would distort the lossless
    rejection-sampling law."""
    return jax.vmap(jax.random.categorical)(keys, logits).astype(
        jnp.int32)


def accept_mask(draft_tokens, p_probs, q_probs, u, greedy,
                target_argmax) -> jax.Array:
    """Per-position speculative accept decision.

    ``draft_tokens [B, K]`` (the k proposed tokens), ``p_probs`` /
    ``q_probs [B, K, V]`` (target / draft distributions at each
    position, BOTH filtered by :func:`filter_logits` with the row's own
    sampling params), ``u [B, K]`` uniforms, ``greedy [B]`` bool,
    ``target_argmax [B, K]`` (per-position argmax of the UNfiltered
    target logits) -> ``[B, K]`` bool accepts.

    Greedy rows accept exactly the tokens classic greedy would have
    emitted (``draft == argmax(p)``) — the bit-identity half of the
    parity gate. Sampled rows run the standard rejection test
    ``u · q(d) < p(d)`` (accept with probability ``min(1, p/q)``; the
    STRICT inequality matters — ``jax.random.uniform`` can return
    exactly 0, and ``0 · q <= 0`` would accept a token the target's
    truncated distribution assigns ZERO probability, an output classic
    sampling could never emit). A draft distribution that went
    non-finite fails the test DETERMINISTICALLY (no ``u`` involved),
    which keeps the emitted stream unbiased: the position simply falls
    back to a fresh sample from the plain target distribution next
    window."""
    psel = jnp.take_along_axis(p_probs, draft_tokens[..., None],
                               axis=2)[..., 0]
    qsel = jnp.take_along_axis(q_probs, draft_tokens[..., None],
                               axis=2)[..., 0]
    q_ok = jnp.isfinite(q_probs).all(axis=-1)
    sampled_acc = q_ok & (u * qsel < psel)
    greedy_acc = draft_tokens == target_argmax
    return jnp.where(greedy[:, None], greedy_acc, sampled_acc)


def residual_logits(p_probs, q_probs) -> jax.Array:
    """The rejection-resample distribution in log space:
    ``log(max(p − q, 0))`` per row (``[B, V]`` each). Sampling
    ``categorical`` from this is the residual draw of standard
    speculative sampling — the engine defers it one window by carrying
    these logits as the row's next sampling distribution. The floor
    guards zero-mass entries from producing ``-inf``: the engine's
    NaN/inf health tripwire (:func:`finite_rows`) runs on the CARRIED
    logits, so a ``-inf`` here would retire the row as poisoned. The
    floor must be a NORMAL fp32 number — XLA's CPU backend flushes
    denormals to zero (``1e-38 -> 0 -> log = -inf``, a bug found by
    driving the real server); ``1e-30`` lands zero-mass entries at
    ~``-69`` in log space, finite yet still zero probability for
    categorical purposes next to any real residual mass."""
    return jnp.log(jnp.maximum(p_probs - q_probs, 0.0) + 1e-30)
