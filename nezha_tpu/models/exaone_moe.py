"""K-EXAONE (``model_type: exaone_moe``): grouped-query attention with
window layers beside global layers, QK-norm, a leading dense layer and
then sigmoid-routed dropless experts with a shared expert.

Per layer ``l``, with ``x`` a token's hidden state (the residual stream is
float32, as in ``mistral4.py``)::

    h = x + Attn_l(RMSNorm(x))
    y = h + MLP(RMSNorm(h))                          # l < first_k_dense_replace
    y = h + Shared(n) + sum_{e in top-k, held} w_e E_e(n),  n = RMSNorm(h)

**Attention.** ``q = n W_q`` -> ``H`` heads of ``D``; ``k = n W_k``, ``v =
n W_v`` -> ``KVH`` heads of ``D``; no biases. ``q_h`` and ``k_g`` are
RMS-normed over the head dimension (one learned ``D``-vector each a
layer), before rotation. A ``sliding_attention`` layer rotates ``q, k``
over all ``D`` dimensions in the half-split form (``ops.rotary.
apply_half_split``, theta 1e6); a ``full_attention`` layer does NOT rotate
(the model card's "global attention: no rotary positional embedding").
Query head ``h`` reads K/V head ``h // (H / KVH)``. Key ``j`` is visible to
query ``i`` iff ``j <= i`` and, on a sliding layer, ``i - j <
sliding_window``. The cache holds ``k`` after norm and rotation, and ``v``,
as lane-dense rows ``[N, bs, KVH*D]``.

**Two cache groups.** A full layer's blocks live in the growing table
(``max_len / bs`` entries a slot); a sliding layer's in a RING of
``ceil(window / bs) + 1`` entries (``serve/slots.py``): position ``p`` is
entry ``(p // bs) % ring``. Decode is the paged kernel
(``ops/pallas/decode_attention.py``: one body, a full-table call and a
ring call) or, off the TPU, its composed form. A prefill chunk at a traced
offset is blocked over keys in ``jax.numpy``: a full layer writes the
chunk and folds the table's key blocks up to its last query with an
online softmax; a sliding layer reads the window before the chunk out of
the ring, attends ``[that | the chunk]`` in bands, and then writes only
what later queries can see. Neither writes a pad (the engine says how
many of the chunk's tokens are real: ``cache["valid"]``).

**Router.** ``s = sigmoid(n W_r)`` over all experts; the chosen are the
largest of ``s + b`` (``b``: the selection bias), the weights ``s`` of the
chosen over their sum, times ``routed_scaling_factor`` (``parallel.expert.
route_top_k``, ``score_func="sigmoid"``).

**The chip's share**, as in ``mistral4.py``: ``experts_held`` (first,
count), ``vocab_held``; the ``full`` preset is the cut
``chipbench/configs/k-exaone-236b.json`` states. The multi-token-
prediction layer is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import nn
from nezha_tpu.models.mistral4 import GatedMLP, _linear, _project_f32
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.ops.pallas import (flash_decode_attention,
                                  paged_attention_composed)
from nezha_tpu.ops.pallas.common import NEG_BIG, pick_block
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy

SLIDING, FULL = "sliding_attention", "full_attention"
# Keys a prefill chunk folds at a time on a full layer (table entries x
# block size): the float32 scores of one fold are H x chunk x this.
_PREFILL_KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    # The published keys (LGAI-EXAONE/K-EXAONE-236B-A23B config.json;
    # ``rope_parameters.rope_theta`` flattened).
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    # layer ``l`` is ``layer_types[l]``: the published pattern, 12 x LLLG
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    sliding_window: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    # The chip's share (the defaults are the whole model).
    experts_held: Tuple[int, int] = (0, 128)
    vocab_held: int = 153600
    # "auto": the paged decode kernel on a TPU backend, composed
    # elsewhere; "kernel" / "xla" force one (ServeConfig.decode_impl).
    decode_impl: str = "auto"

    # What serve.Engine and the pools read of any model's config.
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))


# One chip's share of the stated deployment: the first five layers (the
# dense layer, then a whole sliding x3 / full period), 16 of the 128
# routed experts and an eighth of the vocabulary.
FULL_KW = dict(num_hidden_layers=5, experts_held=(0, 16), vocab_held=19200)
# CPU tests: every mechanism at widths a test can afford.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, num_experts=16, num_experts_per_tok=4,
    experts_held=(0, 4), moe_intermediate_size=32,
    max_position_embeddings=4096)


def _fold(carry, sc, visible, vv):
    """One online-softmax fold of scores ``sc [..., S, L]`` (float32,
    scaled) and values ``vv [b, L, KVH, D]`` into ``(m, l, acc)``."""
    m, l, acc = carry
    sc = jnp.where(visible, sc, NEG_BIG)
    m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
    p = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    acc = acc * corr + jnp.einsum("bkgsl,blkd->bkgsd", p.astype(vv.dtype),
                                  vv, preferred_element_type=jnp.float32)
    return m_new, l * corr + p.sum(axis=-1, keepdims=True), acc


class GQAttention(Module):
    """Grouped-query attention of one layer; ``window`` None: a full
    layer (no rotation), else a sliding layer."""

    def __init__(self, cfg: ExaoneMoeConfig, window: Optional[int],
                 policy: Policy):
        self.cfg, self.window, self.policy = cfg, window, policy
        h, d = cfg.hidden_size, cfg.head_dim
        self.q = _linear(h, cfg.num_attention_heads * d, policy)
        self.k = _linear(h, cfg.num_key_value_heads * d, policy)
        self.v = _linear(h, cfg.num_key_value_heads * d, policy)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.o = _linear(cfg.num_attention_heads * d, h, policy)

    def project(self, variables: Variables, x, positions):
        """-> q [B,S,H,D], k [B,S,KVH,D] (both normed; rotated on a
        sliding layer), v [B,S,KVH,D]. ``positions`` [B|1, S]."""
        c = self.cfg
        b, s, _ = x.shape
        st: dict = {}
        q = run_child(self.q, "q", variables, st, x).reshape(
            b, s, c.num_attention_heads, c.head_dim)
        k = run_child(self.k, "k", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        v = run_child(self.v, "v", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        q = run_child(self.q_norm, "q_norm", variables, st, q)
        k = run_child(self.k_norm, "k_norm", variables, st, k)
        if self.window is not None:
            inv_freq = c.rope_theta ** (
                -jnp.arange(0, c.head_dim, 2, dtype=jnp.float32)
                / c.head_dim)
            q = rotary.apply_half_split(q, positions[..., None], inv_freq)
            k = rotary.apply_half_split(k, positions[..., None], inv_freq)
        return q, k, v

    def _grouped(self, q):
        """[B,S,H,D] -> [B,KVH,G,S,D]: head ``h`` is (h // G, h % G)."""
        c = self.cfg
        b, s = q.shape[:2]
        kvh = c.num_key_value_heads
        return q.reshape(b, s, kvh, c.num_attention_heads // kvh,
                         c.head_dim).transpose(0, 2, 3, 1, 4)

    def _ungrouped(self, o):
        """[B,KVH,G,S,D] -> [B,S,H*D]."""
        b, kvh, g, s, d = o.shape
        return o.transpose(0, 3, 1, 2, 4).reshape(b, s, kvh * g * d)

    def _dense(self, q, k, v):
        """The cache-less forward: every key of the sequence."""
        s = q.shape[1]
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        visible = j <= i
        if self.window is not None:
            visible &= i - j < self.window
        sc = jnp.einsum("bkgsd,blkd->bkgsl", self._grouped(q), k,
                        preferred_element_type=jnp.float32)
        sc = jnp.where(visible, sc * self.cfg.head_dim ** -0.5, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return self._ungrouped(jnp.einsum("bkgsl,blkd->bkgsd", p, v))

    def _decode(self, q, k, v, cache, pos, active):
        """One token a row at its own depth through the paged pool."""
        kp, vp, tab = cache["k"], cache["v"], cache["tables"]
        b = q.shape[0]
        bs, m = kp.shape[1], tab.shape[1]
        if self.window is None:
            pos_w = jnp.minimum(pos, m * bs - 1)
            entry = pos_w // bs
        else:
            pos_w = pos
            entry = (pos // bs) % m
        blk = jnp.take_along_axis(tab, entry[:, None], axis=1)[:, 0]
        off = pos_w % bs
        lengths = pos + 1
        if active is not None:
            # inactive rows write the scratch block and attend nothing
            blk, off = jnp.where(active, blk, 0), jnp.where(active, off, 0)
            lengths = jnp.where(active, lengths, 0)
        kp = kp.at[blk, off, :].set(k.reshape(b, -1).astype(kp.dtype))
        vp = vp.at[blk, off, :].set(v.reshape(b, -1).astype(vp.dtype))
        impl = self.cfg.decode_impl
        attend = (flash_decode_attention if impl == "kernel" or (
            impl == "auto" and jax.default_backend() == "tpu")
            else paged_attention_composed)
        out = attend(q.transpose(0, 2, 1, 3), kp, vp, lengths,
                     block_tables=tab, window=self.window)
        return out.reshape(b, 1, -1), kp, vp

    def _prefill(self, q, k, v, cache, pos):
        """A chunk of ``S`` tokens of one row at the traced offset
        ``pos``; only the first ``cache["valid"]`` are real."""
        kp, vp, tab = cache["k"], cache["v"], cache["tables"]
        b, s = q.shape[:2]
        bs, m = kp.shape[1], tab.shape[1]
        valid = cache.get("valid", s)
        scale = self.cfg.head_dim ** -0.5
        local = jnp.arange(s)
        p_abs = pos + local                                   # [S]
        k_rows = k.reshape(b, s, -1).astype(kp.dtype)
        v_rows = v.reshape(b, s, -1).astype(vp.dtype)
        qg = self._grouped(q)
        if self.window is None:
            # write the real tokens (pads land on the scratch block),
            # then fold the table's key blocks up to the last query
            keep = (local < valid) & (p_abs < m * bs)
            blk = jnp.where(keep[None, :],
                            tab[:, jnp.clip(p_abs // bs, 0, m - 1)], 0)
            off = jnp.where(keep, p_abs % bs, 0)[None, :]
            kp = kp.at[blk, off, :].set(k_rows)
            vp = vp.at[blk, off, :].set(v_rows)
            e = pick_block(m, max(1, _PREFILL_KEY_BLOCK // bs))
            span = e * bs

            def fold(i, carry):
                ent = lax.dynamic_slice_in_dim(tab, i * e, e, axis=1)
                kk = kp[ent].reshape(b, span, *k.shape[2:]).astype(q.dtype)
                vv = vp[ent].reshape(b, span, *v.shape[2:]).astype(q.dtype)
                sc = jnp.einsum("bkgsd,blkd->bkgsl", qg, kk,
                                preferred_element_type=jnp.float32) * scale
                kpos = i * span + jnp.arange(span)
                return _fold(carry, sc, kpos[None, :] <= p_abs[:, None], vv)

            shape = qg.shape[:-1] + (1,)
            init = (jnp.full(shape, NEG_BIG, jnp.float32),
                    jnp.zeros(shape, jnp.float32),
                    jnp.zeros(qg.shape, jnp.float32))
            blocks = jnp.minimum((pos + s + span - 1) // span, m // e)
            _, l, acc = lax.fori_loop(0, blocks, fold, init)
            out = acc / jnp.maximum(l, 1e-30)
        else:
            w = self.window
            # the window before the chunk, out of the ring as it stands
            pp = pos - w + jnp.arange(w)
            ppc = jnp.maximum(pp, 0)
            pblk, poff = tab[:, (ppc // bs) % m], (ppc % bs)[None, :]
            keys = jnp.concatenate([kp[pblk, poff], k_rows], axis=1)
            vals = jnp.concatenate([vp[pblk, poff], v_rows], axis=1)
            # bands: a block of queries sees the keys from ``w`` before
            # its first query to its last one
            qb = w if s % w == 0 else s
            nq = s // qb
            idx = (jnp.arange(nq) * qb)[:, None] + jnp.arange(qb + w)[None]

            def bands(rows, heads_shape):
                """[b, w + S, KVH*D] -> [b, nq, qb + w, KVH, D]: static
                slices (a gather of the same rows costs a DMA a row)."""
                return jnp.stack([rows[:, n * qb:n * qb + qb + w]
                                  for n in range(nq)], axis=1).reshape(
                    b, nq, qb + w, *heads_shape)

            kk, vv = bands(keys, k.shape[2:]), bands(vals, v.shape[2:])
            qn = qg.reshape(*qg.shape[:3], nq, qb, qg.shape[-1])
            sc = jnp.einsum("bkgnqd,bnlkd->bkgnql", qn, kk.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
            q_loc = idx[:, :qb, None]                         # [nq, qb, 1]
            k_loc = idx[:, None, :] - w                       # [nq, 1, L]
            visible = ((k_loc <= q_loc) & (q_loc - k_loc < w)
                       & (k_loc + pos >= 0))
            sc = jnp.where(visible, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgnql,bnlkd->bkgnqd", p, vv.astype(q.dtype),
                             preferred_element_type=jnp.float32)
            out = out.reshape(qg.shape)
            # write what later queries can see: the real tokens in the
            # ring's last ``m`` blocks (the rest, and the pads, land on
            # the scratch block)
            last_blk = (pos + valid - 1) // bs
            keep = (local < valid) & (p_abs // bs > last_blk - m)
            blk = jnp.where(keep[None, :], tab[:, (p_abs // bs) % m], 0)
            off = jnp.where(keep, p_abs % bs, 0)[None, :]
            kp = kp.at[blk, off, :].set(k_rows)
            vp = vp.at[blk, off, :].set(v_rows)
        return self._ungrouped(out.astype(q.dtype)), kp, vp

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        s = x.shape[1]
        per_row = getattr(pos, "ndim", 0) == 1
        if per_row:
            positions = pos[:, None] + jnp.arange(s)[None, :]
        else:
            positions = (0 if pos is None else pos) + jnp.arange(s)[None, :]
        q, k, v = self.project(variables, x, positions)
        states: dict = {}
        if cache is None:
            out = self._dense(q, k, v)
        else:
            if "tables" not in cache:
                raise ValueError(
                    "the grouped-query cache is block-paged only: it takes "
                    "a cache with 'tables' (the serve engine's block tables)")
            if per_row and s > 1:
                raise ValueError(
                    "multi-token steps at per-row positions (speculative "
                    "verify) are not implemented: a ring cannot take back "
                    "a rejected token's write")
            if per_row:
                out, kp, vp = self._decode(q, k, v, cache, pos, active)
            else:
                out, kp, vp = self._prefill(q, k, v, cache, pos)
            states["cache"] = {"k": kp, "v": vp, "tables": cache["tables"]}
        return _project_f32(self.o, variables, "o", out), states


class Block(Module):
    def __init__(self, cfg: ExaoneMoeConfig, layer: int, policy: Policy):
        h = cfg.hidden_size
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.attn = GQAttention(cfg, cfg.window_of(layer), policy)
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.sparse = layer >= cfg.first_k_dense_replace
        if not self.sparse:
            self.mlp = GatedMLP(h, cfg.intermediate_size, policy)
            return
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.num_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            score_func=cfg.scoring_func), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        b, s, h = x.shape
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        x = x.astype(jnp.float32) + a       # float32 residual (mistral4.py)
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, x)
        if not self.sparse:
            return x + run_child(self.mlp, "mlp", variables, states, y), states
        shared = run_child(self.shared, "shared", variables, states, y)
        routed = run_child(
            self.moe, "moe", variables, states, y.reshape(b * s, h),
            active=active if (active is not None and s == 1) else None)
        return x + shared + routed.reshape(b, s, h), states


class ExaoneMoe(Module):
    """Returns logits [B, S, vocab_held] (float32); untied head."""

    def __init__(self, cfg: ExaoneMoeConfig = ExaoneMoeConfig(),
                 policy: Policy = DEFAULT_POLICY):
        if not 1 <= cfg.vocab_held <= cfg.vocab_size:
            raise ValueError(f"vocab_held {cfg.vocab_held} outside the "
                             f"vocabulary of {cfg.vocab_size}")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} query heads are not whole "
                f"groups over {cfg.num_key_value_heads} K/V heads")
        if len(cfg.layer_types) < cfg.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(cfg.layer_types)} layers, "
                f"num_hidden_layers is {cfg.num_hidden_layers}")
        self.cfg = cfg
        self.policy = policy
        self.embed = nn.Embedding(cfg.vocab_held, cfg.hidden_size,
                                  policy=policy)
        self.h = [Block(cfg, i, policy)
                  for i in range(cfg.num_hidden_layers)]
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, policy)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_held, policy)

    def apply(self, variables: Variables, batch, training: bool = False,
              rng=None, cache=None, pos=None, prefill: bool = False,
              active=None):
        tokens = batch["tokens"][:, :-1] if isinstance(batch, dict) else batch
        states: dict = {}
        x = run_child(self.embed, "embed", variables, states, tokens)
        for i, block in enumerate(self.h):
            x = run_child(block, f"h{i}", variables, states, x,
                          cache=None if cache is None else cache[i],
                          pos=pos, prefill=prefill, active=active)
        x = run_child(self.norm, "norm", variables, states, x)
        return _project_f32(self.lm_head, variables, "lm_head", x), states

    # ------------------------------------- what serve.Engine asks a model
    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: a full layer
        in the growing group, a sliding layer in the ring; K and V rows
        ``(block_size, KVH*D)`` either way."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': the int8 pool's scales are per (block, "
                "query head) and its kernels have no grouped-query or "
                "window form")
        c = self.cfg
        kv = (block_size, c.num_key_value_heads * c.head_dim)
        leaves = {"k": (kv, dtype), "v": (kv, dtype)}
        return [("global" if c.window_of(i) is None else "window",
                 c.window_of(i), leaves)
                for i in range(c.num_hidden_layers)]

    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_hidden_layers)]

    def expert_load(self, states: dict):
        """[sparse layers, experts held] int32: pairs computed per held
        expert in this forward pass."""
        return jnp.stack([states[f"h{i}"]["moe"]["load"]
                          for i in self.cfg.sparse_layers])

    def expert_visits(self, states: dict):
        """[sparse layers, 2] int32: the (row tile, expert) visits the
        experts' kernel made in this forward pass and the held experts
        it touched (``ops/pallas/moe_experts.py::visit_plan``)."""
        return jnp.stack([states[f"h{i}"]["moe"]["visits"]
                          for i in self.cfg.sparse_layers])

    def paged_prefill_uses_kernel(self) -> bool:
        return False


def k_exaone(preset: str = "full", policy: Optional[Policy] = None,
             **overrides) -> ExaoneMoe:
    """``full``: one chip's share at the published widths (``FULL_KW``),
    bf16 parameters and compute. ``tiny``: float32, for CPU tests."""
    if preset == "full":
        kw = dict(FULL_KW)
        policy = policy or Policy(jnp.bfloat16, jnp.bfloat16)
    elif preset == "tiny":
        kw = dict(TINY_KW)
        policy = policy or DEFAULT_POLICY
    else:
        raise ValueError(f"unknown preset {preset!r}")
    kw.update(overrides)
    return ExaoneMoe(ExaoneMoeConfig(**kw), policy=policy)
