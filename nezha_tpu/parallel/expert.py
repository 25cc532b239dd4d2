"""Mixture-of-experts with expert parallelism over an ``ep`` mesh axis.

Not attested in the reference (SURVEY.md §0: only DP + ZeRO-1 observed);
included per the build brief (dp/tp/pp/sp/ep are all first-class).

TPU-first design — the Mesh-TensorFlow/Flaxformer dense-dispatch
formulation rather than gather/scatter token shuffling:

- Routing produces *static-shape* one-hot dispatch/combine tensors
  [T, E, C] (top-k gating, fixed capacity C per expert). No dynamic shapes,
  so the whole layer stays inside one XLA program.
- Dispatch, expert compute, and combine are einsums — MXU work, not
  scalar indexing.
- Expert weights are stacked [E, d, f] and sharded over ``ep`` with GSPMD
  PartitionSpecs; XLA's SPMD partitioner inserts the token all-to-alls
  between the dp-sharded token axis and the ep-sharded expert axis (the
  TPU-native equivalent of NCCL all-to-all in GPU MoE stacks).
- Tokens over capacity are *dropped* (standard Switch behavior) and the
  load-balance auxiliary loss keeps the router near-uniform.

:class:`DroplessMoE` is the serving-side layer: no capacity, no dropped
token, SiLU-gated experts, and it is told which of the routed experts it
holds (the chip's share of an expert-parallel host).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nezha_tpu import nn
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, make_variables
from nezha_tpu.ops.pallas.moe_experts import moe_experts
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.5
    aux_loss_weight: float = 0.01


def _top_k_gating(router_logits: jax.Array, top_k: int, num_experts: int,
                  capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Return (dispatch [T,E,C] one-hot, combine [T,E,C], aux_loss scalar)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T,E]
    t = probs.shape[0]

    gate_list, mask_list = [], []
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                       # [T]
        onehot = jax.nn.one_hot(idx, num_experts, dtype=probs.dtype)
        gate_list.append(jnp.sum(probs * onehot, axis=-1))         # [T]
        mask_list.append(onehot)
        remaining = remaining * (1.0 - onehot)

    # Position of each token within its expert's capacity buffer: cumsum of
    # the selection mask over tokens, counting earlier top-k passes first.
    dispatch = jnp.zeros((t, num_experts, capacity), probs.dtype)
    combine = jnp.zeros((t, num_experts, capacity), probs.dtype)
    prior = jnp.zeros((num_experts,), probs.dtype)
    for gate, mask in zip(gate_list, mask_list):
        pos = jnp.cumsum(mask, axis=0) - mask + prior[None, :]     # [T,E]
        in_cap = (pos < capacity) & (mask > 0)
        pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
        onehot_cap = jax.nn.one_hot(pos_clamped, capacity, dtype=probs.dtype)
        sel = onehot_cap * in_cap[..., None] * mask[..., None]     # [T,E,C]
        dispatch = dispatch + sel
        combine = combine + sel * gate[:, None, None]
        prior = prior + jnp.sum(mask, axis=0)

    # Switch-style load-balance loss: E * sum_e fraction_e * router_prob_e.
    frac = jnp.mean(mask_list[0], axis=0)          # top-1 assignment fraction
    prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * prob)
    return dispatch, combine, aux


class MoE(Module):
    """Top-k routed mixture of expert MLPs (GELU two-layer experts).

    ``apply`` returns ``(y, state)`` where ``state['aux_loss']`` carries the
    load-balance loss — add ``cfg.aux_loss_weight * aux_loss`` to the
    training objective.
    """

    def __init__(self, cfg: MoEConfig, policy: Policy = DEFAULT_POLICY,
                 name: Optional[str] = None):
        self.cfg = cfg
        self.policy = policy
        self.router = nn.Linear(cfg.d_model, cfg.num_experts,
                                kernel_init=init_lib.normal(0.02),
                                use_bias=False, policy=policy)

    def init(self, rng: jax.Array) -> Variables:
        cfg = self.cfg
        r_router, r_in, r_out = jax.random.split(rng, 3)
        k_in = init_lib.normal(0.02)(
            r_in, (cfg.num_experts, cfg.d_model, cfg.d_ff), jnp.float32)
        k_out = init_lib.normal(0.02)(
            r_out, (cfg.num_experts, cfg.d_ff, cfg.d_model), jnp.float32)
        return make_variables({
            "router": self.router.init(r_router)["params"],
            "w_in": k_in,
            "w_out": k_out,
        })

    def capacity(self, num_tokens: int) -> int:
        cfg = self.cfg
        return max(1, int(cfg.capacity_factor * cfg.top_k * num_tokens
                          / cfg.num_experts))

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        cfg = self.cfg
        params = variables["params"]
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        num_tokens = b * s
        cap = self.capacity(num_tokens)

        logits, _ = self.router.apply({"params": params["router"], "state": {}},
                                      tokens)
        dispatch, combine, aux = _top_k_gating(
            logits, cfg.top_k, cfg.num_experts, cap)

        compute_dtype = self.policy.compute_dtype
        xin = jnp.einsum("tec,td->ecd", dispatch.astype(compute_dtype),
                         tokens.astype(compute_dtype))
        h = jnp.einsum("ecd,edf->ecf", xin,
                       params["w_in"].astype(compute_dtype))
        h = jax.nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", h,
                         params["w_out"].astype(compute_dtype))
        y = jnp.einsum("tec,ecd->td", combine.astype(compute_dtype), out)
        y = y.reshape(b, s, d).astype(x.dtype)
        return y, {"aux_loss": aux}


@dataclasses.dataclass(frozen=True)
class DroplessMoEConfig:
    """A dropless expert layer that is told which experts it holds.

    ``num_experts`` is the router's width (every routed expert of the
    model, wherever it lives); ``experts_held = (first, count)`` names
    the contiguous ids whose weights are on this chip."""
    d_model: int
    d_ff: int
    num_experts: int
    top_k: int
    experts_held: Tuple[int, int]
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # How the router scores an expert (:func:`route_top_k`): "softmax"
    # over all experts, or "sigmoid" of each logit with a learned
    # per-expert selection bias (``router/bias``, float32).
    score_func: str = "softmax"


SCORE_FUNCS = ("softmax", "sigmoid")


def route_top_k(router_logits: jax.Array, top_k: int, norm_topk_prob: bool,
                scaling: float, score_func: str = "softmax",
                bias=None) -> Tuple[jax.Array, jax.Array]:
    """Scores over ALL experts, then the ``top_k`` largest:
    -> (ids [T, k] int32, weights [T, k] float32). Two score functions
    exist (``SCORE_FUNCS``). ``"softmax"``: the scores are the softmax
    of the logits, and choose and weigh. ``"sigmoid"`` (the DeepSeek-V3
    line's ``scoring_func``): ``s = sigmoid(logits)``; the chosen are
    the largest of ``s + bias`` (``bias`` [E]: the per-expert selection
    bias that balances load without a loss term), and the weights are
    ``s`` of the chosen, WITHOUT the bias. With ``norm_topk_prob`` the
    weights are renormalised over the k chosen (held here or not), then
    scaled."""
    logits = router_logits.astype(jnp.float32)
    if score_func == "softmax":
        weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    elif score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(
            scores if bias is None else scores + bias, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        raise ValueError(f"score_func {score_func!r} not in {SCORE_FUNCS}")
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), weights * scaling


class DroplessMoE(Module):
    """Top-k routed SiLU-gated experts, no capacity and no dropped token.

    The router scores all ``num_experts``; the layer computes the part
    of ``sum_e w_e E_e(x)`` that the experts it HOLDS give, and leaves
    out what the absent ones would add (on an expert-parallel host their
    chips compute it; alone, that partial sum is the result). Token-
    expert pairs are sorted by expert, pairs of absent experts last, and
    ONE kernel call runs gate, up, the activation and down over the held
    groups only (``ops/pallas/moe_experts.py``, ``nezha_moe_experts``: a
    grouped matmul whose row tile, ``d_ff`` tile and window follow the
    static shapes by its ``tile_sizes``; an expert with no row costs no
    weight read and a touched one's weights are read once a call unless a
    row tile's edge cuts its group). Its rounding points are those of the
    three ``jax.lax.ragged_dot`` calls it replaced: ``gate`` and ``up``
    accumulate in float32, ``silu(gate) * up`` is rounded to the compute
    dtype, the down projection accumulates in float32. Shapes are static
    (T x top_k pair rows), so one program serves a prefill chunk (tens of
    rows an expert) and a decode step (a few); no shape keeps
    ``ragged_dot`` (measured: PERF.md section 6, PR 33). ``apply`` returns
    ``(y, {"load": [count] int32, "visits": [2] int32})``: the pairs
    computed per held expert, rows with ``active`` False not counted, and
    the kernel's (row tile, expert) visits beside the held experts it
    touched."""

    def __init__(self, cfg: DroplessMoEConfig, policy: Policy = DEFAULT_POLICY):
        self.cfg = cfg
        self.policy = policy
        first, count = cfg.experts_held
        if not (0 <= first and count >= 1
                and first + count <= cfg.num_experts):
            raise ValueError(
                f"experts_held {cfg.experts_held} outside the "
                f"{cfg.num_experts} routed experts")
        if cfg.score_func not in SCORE_FUNCS:
            raise ValueError(
                f"score_func {cfg.score_func!r} not in {SCORE_FUNCS}")

    def init(self, rng: jax.Array) -> Variables:
        cfg, dtype = self.cfg, self.policy.param_dtype
        held = cfg.experts_held[1]
        r_router, r_gate, r_up, r_down = jax.random.split(rng, 4)
        normal = init_lib.normal(0.02)
        router = {"w": normal(r_router, (cfg.d_model, cfg.num_experts),
                              dtype)}
        if cfg.score_func == "sigmoid":
            # drawn, not zero: selection and weights then differ
            router["bias"] = normal(jax.random.fold_in(r_router, 1),
                                    (cfg.num_experts,), jnp.float32)
        return make_variables({
            "router": router,
            "w_gate": normal(r_gate, (held, cfg.d_model, cfg.d_ff), dtype),
            "w_up": normal(r_up, (held, cfg.d_model, cfg.d_ff), dtype),
            "w_down": normal(r_down, (held, cfg.d_ff, cfg.d_model), dtype),
        })

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              active=None):
        """``x`` [T, d]; ``active`` [T] bool or None."""
        del training, rng
        cfg, p = self.cfg, variables["params"]
        first, held = cfg.experts_held
        cdt = self.policy.compute_dtype
        x = x.astype(cdt)
        t, k = x.shape[0], cfg.top_k
        # Router logits in float32 from compute-dtype operands: the
        # top-k boundary is a discontinuity, so nothing rounds the
        # logits on their way to it.
        logits = jnp.dot(x, p["router"]["w"].astype(cdt),
                         preferred_element_type=jnp.float32)
        ids, weights = route_top_k(logits, k, cfg.norm_topk_prob,
                                   cfg.routed_scaling_factor,
                                   cfg.score_func, p["router"].get("bias"))
        with jax.named_scope("nezha_moe_experts"):
            local = ids - first
            is_held = (local >= 0) & (local < held)
            key = jnp.where(is_held, local, held).reshape(-1)    # [T*k]
            order = jnp.argsort(key)            # stable: by expert, absent last
            token_of = order // k
            sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
            load = sizes if active is None else jnp.bincount(
                jnp.where(jnp.repeat(active, k), key, held),
                length=held + 1)[:held].astype(jnp.int32)
            xs = x[token_of]                                     # [T*k, d]
            out, visits = moe_experts(
                xs, sizes, p["w_gate"].astype(cdt), p["w_up"].astype(cdt),
                p["w_down"].astype(cdt))
            # Back to token order by the inverse permutation (a gather,
            # not a scatter-add); rows of absent experts weigh nothing.
            w_sorted = jnp.where(key[order] < held,
                                 weights.reshape(-1)[order], 0.0)
            out = jnp.where(w_sorted[:, None] > 0, out * w_sorted[:, None], 0.0)
            inverse = jnp.zeros((t * k,), jnp.int32).at[order].set(
                jnp.arange(t * k, dtype=jnp.int32))
            y = out[inverse].reshape(t, k, cfg.d_model).sum(axis=1)
        return y, {"load": load, "visits": visits}      # y is float32


def moe_ep_rules(ep_axis: str = "ep"):
    """GSPMD rules: stacked expert weights shard over ``ep_axis`` on the
    expert axis; the router (and everything else) replicates."""
    return [
        (r".*w_in$", P(ep_axis, None, None)),
        (r".*w_out$", P(ep_axis, None, None)),
    ]


MOE_EP_RULES = moe_ep_rules()


def gpt2_moe_gspmd_rules(tp_rules=None, ep_axis: str = "ep"):
    """First-match GSPMD rule table for the MoE GPT-2 param tree: stacked
    expert weights shard over ``ep_axis``, the router replicates, and the
    dense remainder (attention, dense-block MLPs, embeddings, norms)
    follows ``tp_rules`` — pass ``parallel.GPT2_TP_RULES`` for a
    dp x tp x ep launch (tp=1 degrades gracefully to dp x ep). Strict-mode
    compatible: every MoE-specific leaf is matched here, every dense leaf
    by the appended table."""
    return (moe_ep_rules(ep_axis)  # single source of truth for expert specs
            + [(r".*/mlp/router/w$", P())]
            + list(tp_rules or []))


def shard_moe_params(params: Any, mesh: Mesh, ep_axis: str = "ep") -> Any:
    """Place a MoE param tree per ``moe_ep_rules`` (single source of truth
    with the exported rule table)."""
    from nezha_tpu.parallel.gspmd import param_specs_from_rules

    specs = param_specs_from_rules(params, moe_ep_rules(ep_axis))
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        params, specs)


def dryrun_moe_step(mesh: Mesh, n_experts: int, ep_axis: str = "ep",
                    dp_axis: str = "dp") -> float:
    """One expert-parallel MoE train step on tiny shapes (driver dry-run):
    dp-sharded tokens x ep-sharded experts, full fwd+bwd+SGD update."""
    cfg = MoEConfig(d_model=16, d_ff=32, num_experts=n_experts)
    layer = MoE(cfg)
    variables = layer.init(jax.random.PRNGKey(0))
    params = shard_moe_params(variables["params"], mesh, ep_axis)

    dp = mesh.shape.get(dp_axis, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2 * dp, 8, cfg.d_model))
    x = jax.device_put(x, NamedSharding(mesh, P(dp_axis)))

    def loss_fn(p, x):
        y, st = layer.apply({"params": p, "state": {}}, x)
        return jnp.mean((y - x) ** 2) + cfg.aux_loss_weight * st["aux_loss"]

    @jax.jit
    def step(p, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        p = jax.tree_util.tree_map(lambda w, g: w - 1e-2 * g, p, grads)
        return loss, p

    loss, params = step(params, x)
    jax.block_until_ready(loss)
    return float(loss)
