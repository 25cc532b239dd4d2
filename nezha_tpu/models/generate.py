"""Autoregressive decoding for GPT-2 with a KV cache.

One compiled prefill (whole prompt writes layer caches) + one compiled
decode step reused for every generated token (`lax.scan`, static shapes,
traced position scalar) — the XLA-friendly decode loop: no per-token
recompilation, no growing shapes, cache updates via dynamic_update_slice.
Sampling: greedy, temperature, top-k, and top-p (nucleus).

The scanned step's single-token attention takes the same flash-decode
kernel path as the serving engine (models/gpt2.py routes ``s == 1``
cache attention through ``ops.pallas.flash_decode_attention`` under the
``attn_impl="auto"`` / ``GPT2Config.decode_impl`` resolution), so
training-side eval sampling shares the serving hot-path win; the
composed masked path remains the off-TPU / escape-hatch fallback and is
bit-compatible for greedy decoding (tests pin the parity).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu.models.gpt2 import GPT2


def init_cache(model: GPT2, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> list:
    """Fixed-size per-layer K/V buffers: ``[B, H, max_len, D]`` each."""
    cfg = model.cfg
    d = cfg.hidden_size // cfg.num_heads
    shape = (batch_size, cfg.num_heads, max_len, d)
    return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for _ in range(cfg.num_layers)]


def _caches_from_states(model: GPT2, states: dict, prev: list) -> list:
    return model.caches_from_states(states, prev)


def _sample(logits, rng, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None):
    """logits [B, V] -> token ids [B]. top-k truncation applies before
    top-p nucleus filtering (HF convention when both are set)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None:
        # Clamp to [1, vocab]: lax.top_k rejects k < 1 and k > axis size
        # with an opaque error, and callers (CLI, serving) may hand
        # through user-supplied values. top_k is static, so this is a
        # trace-time Python clamp — no runtime cost.
        top_k = max(1, min(int(top_k), logits.shape[-1]))
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        # Nucleus: keep the smallest prefix of descending-prob tokens whose
        # mass reaches top_p. The explicit rank==0 term keeps the top token
        # even at top_p <= 0 (exclusive-cumsum alone would empty the set
        # there and categorical over all--inf rows silently emits id 0) —
        # top_p -> 0 degrades to argmax, never to an empty set.
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        rank = lax.broadcasted_iota(jnp.int32, sorted_logits.shape,
                                    sorted_logits.ndim - 1)
        keep = (exclusive_cum < top_p) | (rank == 0)
        threshold = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(rng, logits).astype(jnp.int32)


# The jitted programs are built once per (model, sampling config) and
# cached: jax.jit keys on the function object, so closures created inside
# generate() would retrace and recompile on every call. Models hash by
# identity, which is exactly the lifetime of their compiled programs.
@functools.lru_cache(maxsize=64)
def _prefill_fn(model: GPT2):
    @jax.jit
    def prefill(variables, prompt, cache):
        # pos as a STATIC Python 0 (not jnp.int32(0), which traces to a
        # Tracer under jit): Attention.apply's flash-prefill guard only
        # fires when the cache position is statically known to be zero.
        logits, states = model.apply(variables, prompt, training=False,
                                     cache=cache, pos=0,
                                     prefill=True)
        return logits[:, -1, :], _caches_from_states(model, states, cache)

    return prefill


@functools.lru_cache(maxsize=64)
def _decode_fn(model: GPT2, temperature: float, top_k: Optional[int],
               top_p: Optional[float], max_new_tokens: int,
               eos_id: Optional[int] = None,
               pad_id: Optional[int] = None):
    # EOS early-stop keeps static shapes: a finished row keeps decoding
    # (its cache position advances over the pads it feeds itself) but its
    # SAMPLED tokens are masked to pad_id — so the program is the same
    # two compiled pieces whether rows finish early or not.
    pad = eos_id if pad_id is None else pad_id

    def mask_done(tok, done):
        if eos_id is None:
            return tok, done
        tok = jnp.where(done, jnp.int32(pad), tok)
        return tok, done | (tok == eos_id)

    @jax.jit
    def decode(variables, last_logits, cache, pos0, rng):
        def step(carry, _):
            logits, cache, pos, rng, done = carry
            rng, sub = jax.random.split(rng)
            tok = _sample(logits, sub, temperature, top_k, top_p)
            tok, done = mask_done(tok, done)
            out, states = model.apply(variables, tok[:, None],
                                      training=False, cache=cache, pos=pos)
            new_cache = _caches_from_states(model, states, cache)
            return (out[:, -1, :], new_cache, pos + 1, rng, done), tok

        # The last sampled token needs no forward pass (nothing consumes
        # its logits), so scan N-1 steps and sample the final token from
        # the carried logits — N-1 forwards for N tokens.
        done0 = jnp.zeros(last_logits.shape[:1], bool)
        init = (last_logits, cache, pos0, rng, done0)
        (logits, _, _, rng, done), tokens = lax.scan(
            step, init, None, length=max_new_tokens - 1)
        _, sub = jax.random.split(rng)
        final = _sample(logits, sub, temperature, top_k, top_p)
        final, _ = mask_done(final, done)
        tokens = jnp.concatenate([tokens, final[None, :]], axis=0)
        return tokens.T  # [steps, B] -> [B, steps]

    return decode


def generate(model: GPT2, variables: dict, prompt: jax.Array,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             cache_dtype=jnp.bfloat16,
             eos_id: Optional[int] = None,
             pad_id: Optional[int] = None) -> jax.Array:
    """Generate ``[B, prompt_len + max_new_tokens]`` token ids.

    ``temperature=0`` is greedy decoding; otherwise categorical sampling
    (optionally top-k truncated and/or top-p nucleus-filtered). Compiles exactly two programs per
    (model, sampling config, shapes) — prefill and the scanned
    single-token step — reused across calls.

    ``eos_id``: rows that emit it stop — their cache position keeps
    advancing (static shapes) but every subsequent sampled token is
    masked to ``pad_id`` (defaults to ``eos_id``), so output rows read
    ``... eos pad pad``.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, s = prompt.shape
    max_len = s + max_new_tokens
    if max_len > model.cfg.max_positions:
        raise ValueError(
            f"prompt+new = {max_len} exceeds max_positions "
            f"{model.cfg.max_positions}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache = init_cache(model, b, max_len, cache_dtype)
    last_logits, cache = _prefill_fn(model)(variables, prompt, cache)
    new_tokens = _decode_fn(model, temperature, top_k, top_p,
                            max_new_tokens, eos_id, pad_id)(
        variables, last_logits, cache, jnp.int32(s), rng)
    return jnp.concatenate([prompt, new_tokens], axis=1)
