"""GPT-2 (124M default) — benchmark config 3 (SURVEY.md §0: "GPT-2 124M —
GEMM-heavy transformer; exercises bf16").

TPU-first: pre-LN blocks whose QKV/proj/MLP matmuls are large bf16 GEMMs on
the MXU; attention softmax accumulates fp32; weights tied between the token
embedding and the LM head; causal mask built once per forward (static
shapes). Sequence parallelism hooks: ``attn_impl='ring'``/``'ulysses'``
switch attention to `nezha_tpu.parallel` collectives for long context
(call inside shard_map with the ``sp`` axis).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from nezha_tpu import nn, ops
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, child_rng, child_vars, run_child
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy, bf16_policy


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_positions: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0  # 0 for throughput benchmarking; 0.1 for GPT-2 paper
    # "auto" (default): Pallas flash kernels on TPU backends, composed
    # einsum+softmax elsewhere. Measured on v5e (bf16 fwd+bwd train step,
    # GPT-2 124M B=8 S=1024): flash 102.0k tok/s vs xla 87.0k (+17%) once
    # the kernel dots run in bf16 with tuned blocks; flash also removes the
    # S x S score buffers, so B=32 trains where the xla path OOMs.
    # "auto" | "xla" | "flash" | "flash_shmap" (flash via nested
    # shard_map over tp-sharded heads inside a gspmd trace — auto picks
    # it on TPU when tp divides the heads) | "ring" | "ulysses"
    attn_impl: str = "auto"
    sp_axis: str = "sp"
    # ring/ulysses flash policy: None = auto (flash kernels on TPU,
    # composed elsewhere); True/False force it — the escape hatch back to
    # the composed sp paths on hardware without editing source.
    sp_use_flash: "bool | None" = None
    # Fused LM head: apply() returns {"hidden", "wte"} instead of logits and
    # `lm_loss` computes the CE without materializing fp32 [B,S,V] (1.6 GB
    # at B=8 S=1024). 0 = off (logits API, decode/HF paths). -1 = dense
    # compute-dtype logits with the fp32 upcast fused into logsumexp
    # (fastest on v5e: +3% e2e). >0 = sequence-chunked scan of this many
    # positions (ops.losses.chunked_lm_cross_entropy) — slower (-10% e2e,
    # measured) but peak logit memory drops S/chunk-fold in BOTH dtypes;
    # for very long context / big batch where even bf16 logits blow HBM.
    fused_loss_chunk: int = 0
    # Mixture-of-experts: >0 swaps every `moe_every`-th block's MLP for a
    # top-k routed expert layer (`parallel.expert.MoE`, dense-dispatch,
    # EP-shardable over an "ep" mesh axis). apply() then returns a dict
    # carrying the weighted load-balance aux loss, which `lm_loss` adds.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2  # blocks 1, 3, 5, ... are MoE when moe_every=2
    moe_aux_weight: float = 0.01
    # Single-token KV-cache decode attention (the serving hot path):
    # "auto" (default) runs the Pallas flash-decode kernel
    # (ops/pallas/decode_attention.py — split-K online softmax, per-row
    # lengths skip KV blocks) under the same backend policy as the
    # prefill flash path (compiled on TPU, composed masked attention
    # elsewhere, and wherever attn_impl itself forces "xla");
    # "kernel" forces the kernel (interpret mode off-TPU — the parity-
    # test path); "xla" forces the composed masked path.
    decode_impl: str = "auto"
    # Paged prefill-chunk attention (the serving TTFT path): "auto"
    # (default) runs the Pallas flash-prefill kernel
    # (ops/pallas/prefill_attention.py — online softmax over the block
    # table with per-row start offsets; on int8 pools the block write
    # fuses into the kernel epilogue, replacing the whole
    # _quant_prefill_write gather/requant round trip) under the same
    # backend policy as decode_impl; "kernel" forces it (interpret mode
    # off-TPU — the parity-test path); "xla" forces the composed
    # masked path. Only the paged cache routes here: the whole-cache
    # prefill of models/generate.py keeps the attn_impl-resolved path.
    prefill_impl: str = "auto"
    # "pallas" opts layer norms into the fused kernel (fwd + bwd) on TPU.
    ln_impl: str = "xla"
    # Rematerialize each transformer block in backward (jax.checkpoint):
    # activation memory drops from O(layers) residuals to O(1) per block at
    # ~1/3 extra FLOPs — the long-context / big-batch memory knob (pairs
    # with fused_loss_chunk>0 and --parallel sp). Training-only; the
    # KV-cache decode path never remats.
    remat: bool = False
    # Layer-stacked trunk applied via lax.scan: ONE traced/compiled block
    # program instead of num_layers inlined copies — cuts XLA trace/
    # compile time and per-layer scheduling overhead (the r4 trunk-MFU
    # lever; A/B via experiments/gpt2_tune.py --variants scan). Changes
    # the params layout: blocks live under "h_scan" with a leading
    # [num_layers] dim on every leaf (convert with
    # stack_layer_params/unstack_layer_params). Homogeneous blocks only
    # (incompatible with moe_experts). Decode still runs per-layer so the
    # KV-cache/generate path is unchanged.
    scan_layers: bool = False


def _tp_sharded_flash(q, k, v, mesh, causal: bool = True,
                      kv_lengths=None):
    """Per-device flash attention over head-sharded blocks inside a GSPMD
    trace: heads are embarrassingly parallel over ``tp`` (the Megatron
    qkv column-parallel layout shards [B, H, S, D] on H), so a NESTED
    shard_map runs the Mosaic kernel device-locally — the auto-
    partitioner never sees the custom call, and TP training keeps the
    flash kernel instead of falling back to composed S x S attention.
    ``kv_lengths`` ([B] int32, BERT right-padding) shards with the
    batch."""
    from jax.sharding import PartitionSpec as P

    from nezha_tpu.ops.pallas import flash_attention
    from nezha_tpu.parallel._compat import shard_map

    # Batch over dp (matching the enclosing data-parallel sharding — a
    # None there would make jit all-gather the batch and compute every
    # dp shard redundantly), heads over tp.
    bspec = "dp" if "dp" in mesh.axis_names else None
    spec = P(bspec, "tp", None, None)
    if kv_lengths is None:
        f = shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return f(q, k, v)
    f = shard_map(
        lambda q_, k_, v_, l_: flash_attention(q_, k_, v_, causal=causal,
                                               kv_lengths=l_),
        mesh=mesh, in_specs=(spec, spec, spec, P(bspec)), out_specs=spec)
    return f(q, k, v, kv_lengths)


def _tp_flash_mesh(num_heads: int):
    """The enclosing gspmd mesh when the nested-shard_map flash path is
    usable for ``num_heads`` (TPU backend, a ``tp`` axis that divides the
    heads); None otherwise. (Mosaic inside shard_map compiled and ran on
    a four-chip v5e host in PR 21 — ``chip_smoke.py --chips 4``.)"""
    from nezha_tpu.ops.pallas.common import resolve_interpret
    from nezha_tpu.parallel.gspmd import auto_partitioner_mesh
    mesh = auto_partitioner_mesh()
    if (mesh is not None and "tp" in mesh.axis_names
            and num_heads % mesh.shape["tp"] == 0
            and not resolve_interpret(None)):   # the kernels compile here
        return mesh
    return None


def _resolve_auto_impl(cfg) -> str:
    """THE attn_impl='auto' policy, shared by training and prefill:
    compiled flash on TPU; under a mesh-carrying GSPMD trace, the nested
    shard_map kernel when tp divides the heads; composed XLA otherwise."""
    if _flash_auto_ok():
        return "flash"
    if _tp_flash_mesh(cfg.num_heads) is not None:
        return "flash_shmap"
    return "xla"


def _decode_flash_ok(cfg) -> bool:
    """Whether the single-token decode step takes the flash-decode kernel.

    An explicit config override
    (``decode_impl="kernel"``/``"xla"``), and otherwise the shared
    ``attn_impl`` resolution — the kernel fires exactly where prefill
    flash would (TPU backend, not under the auto-partitioner), so one
    flag set governs the whole attention surface."""
    if cfg.decode_impl == "kernel":
        return True
    if cfg.decode_impl != "auto":
        return False
    impl = cfg.attn_impl
    if impl == "auto":
        return _flash_auto_ok()
    return impl == "flash"


def _decode_flash_shmap_mesh(cfg):
    """The enclosing auto-partitioner mesh when the flash-DECODE kernel
    can run per-shard under a nested ``shard_map`` (the sharded serve
    engine's path, ops/pallas/decode_attention.py
    ``flash_decode_attention_sharded``); None otherwise. Same gates as
    the prefill ``flash_shmap`` idiom — TPU backend, a ``tp`` axis
    dividing the heads — plus the
    decode kernel's own switches (``decode_impl``, the shared
    ``attn_impl`` resolution).
    ``decode_impl="kernel"`` honors the force on ANY backend (interpret
    mode off-TPU, the parity-test path — under the partitioner the raw
    Mosaic call is never an option, so the nested variant IS the forced
    kernel). Otherwise, off-TPU the composed masked path simply
    auto-partitions under the mesh."""
    if cfg.decode_impl == "xla":
        return None
    if cfg.decode_impl == "auto" and cfg.attn_impl not in ("auto",
                                                           "flash"):
        return None
    if cfg.decode_impl == "kernel":
        from nezha_tpu.parallel.gspmd import auto_partitioner_mesh
        mesh = auto_partitioner_mesh()
        if (mesh is not None and "tp" in mesh.axis_names
                and cfg.num_heads % mesh.shape["tp"] == 0):
            return mesh
        return None
    return _tp_flash_mesh(cfg.num_heads)


def _prefill_flash_ok(cfg) -> bool:
    """Whether the paged prefill-chunk branch takes the flash-prefill
    kernel — the same shape as :func:`_decode_flash_ok`: an explicit
    config override (``prefill_impl="kernel"``/``"xla"``), and
    otherwise the shared ``attn_impl`` resolution, so one flag set
    governs the whole attention surface."""
    if cfg.prefill_impl == "kernel":
        return True
    if cfg.prefill_impl != "auto":
        return False
    impl = cfg.attn_impl
    if impl == "auto":
        return _flash_auto_ok()
    return impl == "flash"


def _prefill_flash_shmap_mesh(cfg):
    """The enclosing auto-partitioner mesh when the flash-PREFILL
    kernel can run per-shard under a nested ``shard_map`` (the sharded
    serve engine's path, ops/pallas/prefill_attention.py
    ``flash_prefill_attention_sharded``); None otherwise. Same gates
    as :func:`_decode_flash_shmap_mesh` with ``prefill_impl``
    swapped in:
    ``prefill_impl="kernel"`` honors the force on ANY backend
    (interpret mode off-TPU — under the partitioner the raw Mosaic
    call is never an option, so the nested variant IS the forced
    kernel)."""
    if cfg.prefill_impl == "xla":
        return None
    if cfg.prefill_impl == "auto" and cfg.attn_impl not in ("auto",
                                                            "flash"):
        return None
    if cfg.prefill_impl == "kernel":
        from nezha_tpu.parallel.gspmd import auto_partitioner_mesh
        mesh = auto_partitioner_mesh()
        if (mesh is not None and "tp" in mesh.axis_names
                and cfg.num_heads % mesh.shape["tp"] == 0):
            return mesh
        return None
    return _tp_flash_mesh(cfg.num_heads)


def _flash_auto_ok() -> bool:
    """Whether attn_impl='auto' takes the compiled kernels here: the
    package-wide rule (``ops.pallas.common.use_kernels``)."""
    from nezha_tpu.ops.pallas.common import use_kernels
    return use_kernels("auto")


def _pool_rows(x):
    """Fresh projections ``[b, H, s, D]`` -> pool rows ``[b, s, H*D]``:
    one row a position, all heads side by side in lanes (the layout of
    a paged K/V pool, ``[N, bs, H*D]``)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _pool_view(blocks, heads):
    """Gathered pool blocks ``[b, M, bs, H*D]`` (``pool[tab]``) -> the
    dense per-head view ``[b, H, M*bs, D]`` the composed attention
    takes."""
    b, m, bs, hd = blocks.shape
    return blocks.reshape(b, m * bs, heads, hd // heads).transpose(0, 2, 1, 3)


def _float_chunk_write(pool, tab, pos, new):
    """One prefill chunk's K (or V) ``new [b, H, s, D]`` into a float
    pool at traced offset ``pos``: ONE row scatter through the block
    table (pads beyond the prompt land in the row's own bound blocks
    and are overwritten by decode before any mask attends them)."""
    bs = pool.shape[1]
    m = tab.shape[1]
    ppos = jnp.minimum(pos + jnp.arange(new.shape[2]), m * bs - 1)
    blk = tab[:, jnp.clip(ppos // bs, 0, m - 1)]               # [b, s]
    off = (ppos % bs)[None, :]                                 # [1, s]
    return pool.at[blk, off, :].set(_pool_rows(new).astype(pool.dtype))


def _quant_decode_write(pool, scales, blk, off, row):
    """One decode token's K (or V) into an INT8 block pool at BLOCK
    granularity: gather each row's target block, dequantize, zero the
    stale positions past the write offset (a freshly-bound block holds
    a previous occupant's int8 garbage — letting it into the absmax
    would inflate the new scale and crush the real entries), insert the
    new row, requantize with a fresh per-(block, head) scale, scatter
    block + scale back. Positions below ``off`` are this row's own
    earlier tokens: they re-round only if the block absmax moved
    (unchanged scale round-trips int8 exactly), which is the bounded
    re-quantization error the ``serve.kv.quant_error`` histogram
    samples. ``pool [N,bs,H*D] int8``, ``scales [N,H] f32``,
    ``blk``/``off [B]``, ``row [B,H,D]``."""
    from nezha_tpu.ops import quant
    bs = pool.shape[1]
    heads = scales.shape[1]
    deq = quant.dequantize_kv_rows(pool[blk], scales[blk])   # [B, bs, H*D]
    idx = jnp.arange(bs)
    keep = (idx[None, :] < off[:, None])[:, :, None]
    sel = (idx[None, :] == off[:, None])[:, :, None]
    new = row.astype(jnp.float32).reshape(row.shape[0], 1, -1)
    deq = jnp.where(sel, new, jnp.where(keep, deq, 0.0))
    qn, sn = quant.quantize_kv_rows(deq, heads)
    return pool.at[blk].set(qn), scales.at[blk].set(sn)


def _quant_prefill_write(pool, scales, tab, pos, new, s):
    """One prefill chunk's K (or V) into an INT8 block pool: the chunk
    ``new [b,H,s,D]`` lands at traced offset ``pos`` through the block
    table ``tab [b,M]``. The touched-block window is STATIC
    (``ceil(s/bs)+1`` gathered blocks — ``s`` and ``bs`` are static,
    only ``pos`` is traced); per touched block, positions before the
    chunk keep their dequantized content (earlier chunks / COWed cached
    prefix), chunk positions take the new values, and positions past
    the chunk zero out (previous-occupant garbage must not set the
    scale). Over-covered window rows (the +1 slack when ``pos`` is
    block-aligned) are routed to the scratch block with zero content —
    never to a data block, whose content a duplicate-index scatter
    could otherwise clobber. Returns ``(pool, scales, err)`` with
    ``err`` the max-abs dequant error over the written span — the
    ``serve.kv.quant_error`` sample."""
    from nezha_tpu.ops import quant
    bs = pool.shape[1]
    heads = scales.shape[1]
    m = tab.shape[1]
    t = min((s - 1) // bs + 2, m)
    fb = pos // bs
    tbi_raw = fb + jnp.arange(t)                         # [T]
    touched = tbi_raw <= (pos + s - 1) // bs
    blks = jnp.where(touched[None, :],
                     tab[:, jnp.clip(tbi_raw, 0, m - 1)], 0)   # [b, T]
    deq = quant.dequantize_kv_rows(pool[blks], scales[blks])  # [b,T,bs,HD]
    wpos = tbi_raw[:, None] * bs + jnp.arange(bs)[None, :]     # [T, bs]
    keep = (wpos < pos) & touched[:, None]
    in_chunk = (wpos >= pos) & (wpos < pos + s) & touched[:, None]
    neww = _pool_rows(new).astype(jnp.float32)[
        :, jnp.clip(wpos - pos, 0, s - 1), :]            # [b,T,bs,HD]
    deq = jnp.where(in_chunk[None, :, :, None], neww,
                    jnp.where(keep[None, :, :, None], deq, 0.0))
    qn, sn = quant.quantize_kv_rows(deq, heads)
    err = jnp.max(jnp.abs(jnp.where(
        (keep | in_chunk)[None, :, :, None],
        quant.sanitize(deq) - quant.dequantize_kv_rows(qn, sn), 0.0)))
    return pool.at[blks].set(qn), scales.at[blks].set(sn), err


class Attention(Module):
    def __init__(self, cfg: GPT2Config, policy: Policy):
        h = cfg.hidden_size
        self.cfg = cfg
        self.qkv = nn.Linear(h, 3 * h, kernel_init=init_lib.normal(0.02),
                             policy=policy)
        self.proj = nn.Linear(
            h, h, kernel_init=init_lib.normal(0.02 / (2 * cfg.num_layers) ** 0.5),
            policy=policy)
        self.drop = nn.Dropout(cfg.dropout)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        cfg = self.cfg
        b, s, h = x.shape
        d = h // cfg.num_heads
        states: dict = {}
        qkv = run_child(self.qkv, "qkv", variables, states, x, training=training)
        qkv = qkv.reshape(b, s, 3, cfg.num_heads, d).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]  # each [B, H, S, D]

        if cache is not None and "tables" in cache:
            # PAGED cache (the serve engine's block-paged pool): k/v are
            # block POOLS [N, bs, H*D] (one row a position, all heads in
            # lanes) and cache["tables"] [B, M] maps
            # this row's position p to pool block tables[p // bs] at
            # offset p % bs. Writes are a scatter through the table;
            # attention either runs the flash-decode kernel directly on
            # the pools (block-table gather operand, per-row length
            # skip preserved) or gathers the row's blocks and takes the
            # composed masked path. Non-emitting rows
            # (``active`` False) route their frozen-position pad write
            # to block 0 — the pool's reserved scratch block — so a
            # retired slot can never scribble on a block that was
            # rebound to another request.
            return self._apply_paged(variables, x, q, k, v, cache, pos,
                                     prefill, active, states,
                                     training=training)
        if cache is not None:
            # Incremental decoding over a whole-batch cache
            # (models/generate.py): append this chunk's K/V at `pos` in the
            # fixed-size cache and attend causally over everything written
            # so far. Static shapes throughout — `pos` is a traced scalar,
            # so one compiled program serves every decode step.
            import jax.lax as lax
            if getattr(pos, "ndim", 0) != 0:
                raise ValueError(
                    f"a cache without 'tables' takes one scalar position "
                    f"for the whole batch, got pos of shape "
                    f"{tuple(pos.shape)}: per-row positions are the paged "
                    f"cache's (the serve engine's block tables)")
            zero = jnp.zeros((), jnp.int32)
            k_all = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype),
                (zero, zero, pos, zero))
            v_all = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype),
                (zero, zero, pos, zero))
            use_flash_prefill = False
            if prefill and s > 1:
                # Prefill contract (ADVICE r5): ``prefill=True`` promises
                # the chunk IS the whole cache prefix — flash attends
                # within the chunk only, so a nonzero cache position would
                # silently drop attention to the cached prefix. Honor it
                # statically: only a pos known to be 0 at trace time (a
                # Python/numpy int or a concrete array, as generate.py
                # passes) takes the flash path; a traced or nonzero pos
                # falls back to masked attention over the cache, which is
                # correct at any position.
                from jax.core import Tracer as _Tracer
                try:
                    pos_is_zero = (not isinstance(pos, _Tracer)
                                   and int(pos) == 0)
                except TypeError:  # non-scalar / unconvertible pos
                    pos_is_zero = False
                if pos_is_zero:
                    # Nothing precedes the prompt, so attention is exactly
                    # causal flash over the chunk itself — no [B,H,S,L]
                    # score matrix against the padded cache. Same backend
                    # policy as the training path (shared helper).
                    impl = cfg.attn_impl
                    if impl == "auto":
                        impl = _resolve_auto_impl(cfg)
                    # (flash_shmap applies to the training path; prefill
                    # runs outside the gspmd trace, where auto resolves to
                    # plain flash/xla.)
                    use_flash_prefill = impl == "flash"
            use_decode_kernel = (not prefill and s == 1
                                 and _decode_flash_ok(cfg))
            if use_flash_prefill:
                from nezha_tpu.ops.pallas import flash_attention
                # Arbitrary prompt lengths: pad to a lane multiple so the
                # kernel gets real block sizes (a prime S would degrade
                # _pick_block to 1-wide blocks); padded keys are masked
                # via kv_lengths, padded query rows sliced off.
                pad = (-s) % 128
                if pad:
                    pq, pk, pv = (jnp.pad(t, ((0, 0), (0, 0), (0, pad),
                                              (0, 0)))
                                  for t in (q, k, v))
                    lens = jnp.full((b,), s, jnp.int32)
                    out = flash_attention(pq, pk, pv, causal=True,
                                          kv_lengths=lens)[:, :, :s, :]
                else:
                    out = flash_attention(q, k, v, causal=True)
            elif use_decode_kernel:
                # Single-token decode: the flash-decode kernel attends the
                # one query row over the cache prefix [0, pos]; its
                # lengths operand skips the KV blocks above it.
                from nezha_tpu.ops.pallas import flash_decode_attention
                lengths = jnp.broadcast_to(pos, (b,)) + 1
                if active is not None:
                    lengths = jnp.where(active, lengths, 0)
                out = flash_decode_attention(q, k_all, v_all, lengths)
            else:
                L = k_all.shape[2]
                abs_q = pos + jnp.arange(s)[:, None]  # absolute positions
                attendable = jnp.arange(L)[None, :] <= abs_q
                mask = jnp.where(attendable, 0.0, -jnp.inf).astype(jnp.float32)
                out = ops.dot_product_attention(q, k_all.astype(q.dtype),
                                                v_all.astype(q.dtype),
                                                mask=mask)
            states["cache"] = {"k": k_all, "v": v_all}
            out = out.transpose(0, 2, 1, 3).reshape(b, s, h)
            out = run_child(self.proj, "proj", variables, states, out,
                            training=training)
            return out, states

        impl = cfg.attn_impl
        if impl == "auto":
            # Compiled flash wins on TPU at every training shape measured
            # (S=1024: +10% over xla attention-only, +17% end-to-end;
            # S=2048: +25% attention-only) and is the only path at S>=32k
            # where the S x S score matrix exhausts HBM. Interpret-mode
            # flash (non-TPU backends) is never auto-chosen. Under the
            # GSPMD auto-partitioner (which cannot partition a Mosaic
            # custom call) the kernel still runs when the trace carries
            # its mesh and tp divides the heads — via a nested shard_map
            # over the head axis (_tp_sharded_flash); otherwise composed.
            impl = _resolve_auto_impl(cfg)
        if impl == "ring":
            from nezha_tpu.parallel.ring import ring_attention
            out = ring_attention(q, k, v, cfg.sp_axis, causal=True,
                                 use_flash=cfg.sp_use_flash)
        elif impl == "ulysses":
            from nezha_tpu.parallel.sequence_parallel import ulysses_attention
            out = ulysses_attention(q, k, v, cfg.sp_axis, causal=True,
                                    use_flash=cfg.sp_use_flash)
        elif impl == "flash_shmap":
            from nezha_tpu.parallel.gspmd import auto_partitioner_mesh
            mesh = auto_partitioner_mesh()
            if mesh is None or "tp" not in mesh.axis_names \
                    or cfg.num_heads % mesh.shape["tp"]:
                raise ValueError(
                    f"attn_impl='flash_shmap' needs an enclosing gspmd "
                    f"trace carrying a mesh with a 'tp' axis dividing "
                    f"num_heads={cfg.num_heads} "
                    f"(make_gspmd_train_step or "
                    f"auto_partitioner_scope(mesh=...)); got "
                    f"{mesh and dict(mesh.shape)}")
            out = _tp_sharded_flash(q, k, v, mesh, causal=True)
        elif impl == "flash":
            from nezha_tpu.ops.pallas import flash_attention
            out = flash_attention(q, k, v, causal=True)
        else:
            mask = ops.causal_mask(s, s)
            out = ops.dot_product_attention(q, k, v, mask=mask)

        out = out.transpose(0, 2, 1, 3).reshape(b, s, h)
        out = run_child(self.proj, "proj", variables, states, out,
                        training=training)
        out = run_child(self.drop, "drop", variables, states, out,
                        training=training, rng=rng)
        return out, states


    def _apply_paged(self, variables, x, q, k, v, cache, pos, prefill,
                     active, states, *, training):
        """The block-paged cache path (see ``apply``). ``cache`` is
        ``{"k": [N, bs, H*D], "v": [N, bs, H*D], "tables": [B, M]}``;
        the engine guarantees every position this call writes sits in a
        block the row owns exclusively (ref count 1 — prepare_write
        COWed/bound it), and every position it attends below a row's
        length was genuinely written (prefill order / prefix refs)."""
        cfg = self.cfg
        b, s, h = x.shape
        kp, vp, tab = cache["k"], cache["v"], cache["tables"]
        quant = "k_scale" in cache   # int8 pool: scales ride the cache
        ks_pool = cache.get("k_scale")
        vs_pool = cache.get("v_scale")
        bs_kv = kp.shape[1]
        m = tab.shape[1]
        L = m * bs_kv
        per_row = getattr(pos, "ndim", 0) == 1
        qerr = None
        out_pf = None   # flash-prefill kernel output, when that path ran
        if per_row and s > 1:
            # Speculative verify window: s tokens per row at PER-ROW
            # offsets, scattered through the block table. Positions
            # past the row's bound frontier gather a scratch (0) table
            # entry by construction, and positions past capacity — or
            # any position of a non-emitting row — are routed to
            # scratch explicitly: the PR 7 pad idiom, so a rejected
            # draft position can never scribble on a rebound block.
            ppos = pos[:, None] + jnp.arange(s)[None, :]       # [B, s]
            route = ppos >= L
            if active is not None:
                route = route | ~active[:, None]
            ppos_c = jnp.minimum(ppos, L - 1)
            bi = jnp.clip(ppos_c // bs_kv, 0, m - 1)
            blk = jnp.take_along_axis(tab, bi, axis=1)         # [B, s]
            blk = jnp.where(route, 0, blk)
            off = jnp.where(route, 0, ppos_c % bs_kv)
            if quant:
                # Sequential per-position block requants (the
                # _quant_decode_write move, once per window position):
                # position j+1's gather sees position j's write, so the
                # window lands exactly as k+1 single-token decodes
                # would — the bounded requant error is the same one
                # serve.kv.quant_error samples at prefill.
                k_pool, v_pool = kp, vp
                for j in range(s):
                    k_pool, ks_pool = _quant_decode_write(
                        k_pool, ks_pool, blk[:, j], off[:, j],
                        k[:, :, j, :])
                    v_pool, vs_pool = _quant_decode_write(
                        v_pool, vs_pool, blk[:, j], off[:, j],
                        v[:, :, j, :])
            else:
                k_pool = kp.at[blk, off, :].set(
                    _pool_rows(k).astype(kp.dtype))
                v_pool = vp.at[blk, off, :].set(
                    _pool_rows(v).astype(vp.dtype))
        elif per_row:
            # Decode: one token per row at its own depth. The clamp
            # keeps a capacity-filled row (it is done) writing its pad on
            # its own last position, never past it, and inactive rows
            # write scratch.
            pos_w = jnp.minimum(pos, L - 1)
            bi = jnp.clip(pos_w // bs_kv, 0, m - 1)
            blk = jnp.take_along_axis(tab, bi[:, None], axis=1)[:, 0]
            off = pos_w % bs_kv
            if active is not None:
                blk = jnp.where(active, blk, 0)
                off = jnp.where(active, off, 0)
            if quant:
                # Block-granularity requant (see _quant_decode_write):
                # the row's current block is rewritten whole so its
                # per-(block, head) scale tracks the content absmax.
                k_pool, ks_pool = _quant_decode_write(
                    kp, ks_pool, blk, off, k[:, :, 0, :])
                v_pool, vs_pool = _quant_decode_write(
                    vp, vs_pool, blk, off, v[:, :, 0, :])
            else:
                k_pool = kp.at[blk, off, :].set(
                    k[:, :, 0, :].reshape(b, h).astype(kp.dtype))
                v_pool = vp.at[blk, off, :].set(
                    v[:, :, 0, :].reshape(b, h).astype(vp.dtype))
        else:
            # Prefill chunk at a traced scalar offset. The flash-
            # prefill kernel (prefill_impl resolution, mirroring
            # decode_impl) attends the cached prefix through the block
            # table with the chunk's own K/V folded causally from the
            # fresh operands, ONE program for every start offset — and
            # on int8 pools it fuses the whole block write
            # (_quant_prefill_write's gather→dequant→insert→requant→
            # scatter chain) into its epilogue, stale-position zeroing
            # and the qerr sample included. Composed fallback: scatter
            # the s tokens through the table (pads beyond the prompt
            # land in the row's own bound blocks and are overwritten by
            # decode before any mask attends them), then masked
            # attention over the gathered pool.
            # Sequence-sharded prefill: a trace-time scope the sharded
            # engine enters while tracing its bucket programs
            # (prefill_mode="sequence"). The sys.modules probe keeps
            # the check free unless the seq-prefill module was ever
            # imported — single-device serving never pays for it.
            import sys as _sys
            _spm = _sys.modules.get(
                "nezha_tpu.serve.sharded.seq_prefill")
            _sp = (_spm.seq_prefill_params()
                   if _spm is not None else None)
            use_pf = False
            pf_mesh = None
            if _sp is None:
                use_pf = _prefill_flash_ok(cfg)
                from nezha_tpu.parallel.gspmd import (
                    under_auto_partitioner)
                if under_auto_partitioner():
                    # Same move as decode below: the raw Mosaic call
                    # can never be handed to the auto-partitioner —
                    # the nested-shard_map variant runs it per head
                    # shard, or the composed path partitions.
                    use_pf = False
                    pf_mesh = _prefill_flash_shmap_mesh(cfg)
            if _sp is not None:
                # The nested shard_map owns BOTH the pool write and
                # the chunk attention; the kernel-vs-composed choice
                # mirrors prefill_impl exactly (the shmap-mesh
                # resolver is backend-aware).
                starts = jnp.broadcast_to(
                    jnp.asarray(pos, jnp.int32), (b,))
                use_k = _prefill_flash_shmap_mesh(cfg) is not None
                (out_pf, k_pool, v_pool, ks_n, vs_n,
                 qerr) = _spm.seq_prefill_attention(
                    q, k, v, kp, vp, tab, starts, mesh=_sp.mesh,
                    variant=_sp.variant, use_kernel=use_k,
                    block_scales=((ks_pool, vs_pool) if quant
                                  else None))
                if quant:
                    ks_pool, vs_pool = ks_n, vs_n
            elif use_pf or pf_mesh is not None:
                from nezha_tpu.ops.pallas import (
                    flash_prefill_attention,
                    flash_prefill_attention_sharded,
                )
                starts = jnp.broadcast_to(
                    jnp.asarray(pos, jnp.int32), (b,))
                if quant:
                    if pf_mesh is not None:
                        (out_pf, k_pool, v_pool, ks_pool, vs_pool,
                         qerr) = flash_prefill_attention_sharded(
                            q, k, v, kp, vp, tab, starts, pf_mesh,
                            block_scales=(ks_pool, vs_pool))
                    else:
                        (out_pf, k_pool, v_pool, ks_pool, vs_pool,
                         qerr) = flash_prefill_attention(
                            q, k, v, kp, vp, tab, starts,
                            block_scales=(ks_pool, vs_pool))
                else:
                    # Float pools keep the one-scatter chunk write (it
                    # is already a single cheap XLA op); the kernel
                    # reads only prefix positions plus the fresh
                    # operands, so write and attention commute.
                    k_pool = _float_chunk_write(kp, tab, pos, k)
                    v_pool = _float_chunk_write(vp, tab, pos, v)
                    if pf_mesh is not None:
                        out_pf = flash_prefill_attention_sharded(
                            q, k, v, kp, vp, tab, starts, pf_mesh)
                    else:
                        out_pf = flash_prefill_attention(
                            q, k, v, kp, vp, tab, starts)
            elif quant:
                k_pool, ks_pool, ek = _quant_prefill_write(
                    kp, ks_pool, tab, pos, k, s)
                v_pool, vs_pool, ev = _quant_prefill_write(
                    vp, vs_pool, tab, pos, v, s)
                qerr = jnp.maximum(ek, ev)
            else:
                k_pool = _float_chunk_write(kp, tab, pos, k)
                v_pool = _float_chunk_write(vp, tab, pos, v)
        use_decode_kernel = (not prefill and s == 1 and per_row
                             and _decode_flash_ok(cfg))
        shmap_mesh = None
        if not prefill and s == 1 and per_row:
            from nezha_tpu.parallel.gspmd import under_auto_partitioner
            if under_auto_partitioner():
                # Under the sharded serve engine's auto-partitioner
                # trace the RAW kernel is never an option — a Mosaic
                # custom call cannot be handed to the partitioner,
                # forced decode_impl="kernel" included. The nested-
                # shard_map variant runs it PER SHARD on each device's
                # head slice (block tables replicated, the training-
                # side flash_shmap idiom on the decode path); when the
                # mesh can't host it, the composed path partitions.
                use_decode_kernel = False
                shmap_mesh = _decode_flash_shmap_mesh(cfg)
        if out_pf is not None:
            # The flash-prefill kernel already produced the chunk's
            # attention (and, on int8 pools, the fused write above).
            out = out_pf
        elif use_decode_kernel or shmap_mesh is not None:
            # The kernel takes the POOLS + table directly: it copies
            # and folds only the table entries below a row's own length
            # (all heads of an entry at once; a loop over the row's own
            # entries where the pool's rows are whole 128-lane tiles, a
            # grid of rows x table entries / c on a head shard), and an
            # inactive row folds nothing. Int8 pools
            # add the [N, H] scale operands and the kernel dequantizes
            # inside its block loop — the int8 cache never round-trips
            # through a dense bf16 view.
            lengths = pos + 1
            if active is not None:
                lengths = jnp.where(active, lengths, 0)
            if shmap_mesh is not None:
                from nezha_tpu.ops.pallas import (
                    flash_decode_attention_sharded)
                out = flash_decode_attention_sharded(
                    q, k_pool, v_pool, lengths, shmap_mesh,
                    block_tables=tab,
                    block_scales=((ks_pool, vs_pool) if quant
                                  else None))
            else:
                from nezha_tpu.ops.pallas import flash_decode_attention
                out = flash_decode_attention(
                    q, k_pool, v_pool, lengths, block_tables=tab,
                    block_scales=((ks_pool, vs_pool) if quant
                                  else None))
        else:
            # Composed path: gather the rows' blocks ([b, M, bs, H*D])
            # into the per-head [b, H, L, D] view and run masked
            # attention over it (unbound table entries gather scratch —
            # always masked, since they sit at/past the row's length).
            # Int8 pools dequantize the gathered blocks with the SAME
            # expression as the kernel's in-loop dequant
            # (ops.quant.dequantize_kv_rows), so decode_impl="xla"
            # stays a faithful reference for the quantized cache.
            if quant:
                from nezha_tpu.ops.quant import dequantize_kv_rows
                k_all = dequantize_kv_rows(k_pool[tab], ks_pool[tab],
                                           q.dtype)
                v_all = dequantize_kv_rows(v_pool[tab], vs_pool[tab],
                                           q.dtype)
            else:
                k_all, v_all = k_pool[tab], v_pool[tab]
            k_all = _pool_view(k_all, cfg.num_heads)
            v_all = _pool_view(v_all, cfg.num_heads)
            if per_row:
                abs_q = pos[:, None] + jnp.arange(s)[None, :]
                attendable = (jnp.arange(L)[None, None, :]
                              <= abs_q[:, :, None])[:, None, :, :]
            else:
                abs_q = pos + jnp.arange(s)[:, None]
                attendable = jnp.arange(L)[None, :] <= abs_q
            mask = jnp.where(attendable, 0.0, -jnp.inf).astype(jnp.float32)
            out = ops.dot_product_attention(q, k_all.astype(q.dtype),
                                            v_all.astype(q.dtype),
                                            mask=mask)
        new_cache = {"k": k_pool, "v": v_pool, "tables": tab}
        if quant:
            new_cache["k_scale"] = ks_pool
            new_cache["v_scale"] = vs_pool
            if qerr is not None:
                # Per-chunk max-abs dequant error, harvested by the
                # engine's prefill program into serve.kv.quant_error
                # (a per-forward value, not running state — the engine
                # strips it before rebinding caches).
                new_cache["qerr"] = qerr
        states["cache"] = new_cache
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h)
        out = run_child(self.proj, "proj", variables, states, out,
                        training=training)
        return out, states


class MLPBlock(Module):
    def __init__(self, cfg: GPT2Config, policy: Policy):
        h, m = cfg.hidden_size, cfg.hidden_size * cfg.mlp_ratio
        self.fc = nn.Linear(h, m, kernel_init=init_lib.normal(0.02),
                            policy=policy)
        self.proj = nn.Linear(
            m, h, kernel_init=init_lib.normal(0.02 / (2 * cfg.num_layers) ** 0.5),
            policy=policy)
        self.drop = nn.Dropout(cfg.dropout)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        states: dict = {}
        x = run_child(self.fc, "fc", variables, states, x, training=training)
        x = ops.gelu(x)
        x = run_child(self.proj, "proj", variables, states, x, training=training)
        x = run_child(self.drop, "drop", variables, states, x,
                      training=training, rng=rng)
        return x, states


class Block(Module):
    def __init__(self, cfg: GPT2Config, policy: Policy, use_moe: bool = False):
        h = cfg.hidden_size
        self.ln_1 = nn.LayerNorm(h, policy=policy, impl=cfg.ln_impl)
        self.attn = Attention(cfg, policy)
        self.ln_2 = nn.LayerNorm(h, policy=policy, impl=cfg.ln_impl)
        if use_moe:
            from nezha_tpu.parallel.expert import MoE, MoEConfig
            self.mlp = MoE(MoEConfig(
                d_model=h, d_ff=h * cfg.mlp_ratio,
                num_experts=cfg.moe_experts, top_k=cfg.moe_top_k),
                policy=policy)
        else:
            self.mlp = MLPBlock(cfg, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        y = run_child(self.ln_1, "ln_1", variables, states, x, training=training)
        y = run_child(self.attn, "attn", variables, states, y,
                      training=training, rng=rng, cache=cache, pos=pos,
                      prefill=prefill, active=active)
        x = x + y
        y = run_child(self.ln_2, "ln_2", variables, states, x, training=training)
        y = run_child(self.mlp, "mlp", variables, states, y,
                      training=training, rng=rng)
        return x + y, states


class ScannedBlocks(Module):
    """``num_layers`` homogeneous :class:`Block`s with layer-stacked
    parameters, applied via ``lax.scan``.

    Every param leaf carries a leading ``[num_layers]`` dim; the scan body
    slices one layer per iteration, so XLA compiles ONE block program
    (reference inlines per-layer graph nodes — SURVEY.md §1; on TPU the
    unrolled trace costs compile time and inter-layer scheduling, which is
    what this removes). Per-layer dropout RNGs are pre-split outside the
    scan with the SAME ``h{i}`` derivation as the unrolled trunk, so the
    two layouts are bit-identical in expectation and in tests.
    """

    _init_with_parent_rng = True  # layer keys derive from GPT2's rng

    def __init__(self, cfg: GPT2Config, policy: Policy):
        self.cfg = cfg
        self.policy = policy
        # Template holding the single-block structure; its params are
        # never used directly (init stacks per-layer inits instead).
        self.block = Block(cfg, policy)

    def init(self, rng: jax.Array) -> Variables:
        from nezha_tpu.nn.module import scan_stack_init
        return scan_stack_init(self.block, rng, self.cfg.num_layers, "h")

    def apply(self, variables: Variables, x, training: bool = False,
              rng=None, pos=None):
        from nezha_tpu.nn.module import scan_stack_apply
        x = scan_stack_apply(self.block, variables["params"], x,
                             self.cfg.num_layers, "h", rng=rng,
                             remat=self.cfg.remat and training,
                             training=training, pos=pos)
        return x, {}


def stack_layer_params(params: dict, num_layers: int) -> dict:
    """Unrolled GPT-2 params (``h0`` .. ``h{L-1}``) -> scan layout
    (``h_scan`` with a leading layer dim). Non-trunk entries pass through."""
    from nezha_tpu.nn.module import stack_prefixed_params
    return stack_prefixed_params(params, "h", num_layers, "h_scan")


def unstack_layer_params(params: dict, num_layers: int) -> dict:
    """Scan-layout GPT-2 params -> unrolled ``h{i}`` layout (checkpoint/HF
    interchange, tensor-parallel rule tables)."""
    from nezha_tpu.nn.module import unstack_prefixed_params
    return unstack_prefixed_params(params, "h", num_layers, "h_scan")


class GPT2(Module):
    """Returns LM logits [B, S, vocab]; weight-tied head.

    ``batch`` may be {"tokens": [B, S+1]} (inputs are tokens[:, :-1] — the
    LM-loss convention used by `lm_loss`) or a raw [B, S] int array.
    """

    def __init__(self, cfg: GPT2Config = GPT2Config(),
                 policy: Policy = DEFAULT_POLICY):
        self.cfg = cfg
        self.policy = policy
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size, policy=policy)
        self.wpe = nn.Embedding(cfg.max_positions, cfg.hidden_size,
                                embedding_init=init_lib.normal(0.01),
                                policy=policy)
        self.drop = nn.Dropout(cfg.dropout)
        if cfg.scan_layers:
            if cfg.moe_experts:
                raise ValueError(
                    "scan_layers requires homogeneous blocks; "
                    "incompatible with moe_experts")
            self.h_scan = ScannedBlocks(cfg, policy)
            self.h = []
        else:
            self.h = [Block(cfg, policy,
                            use_moe=bool(cfg.moe_experts)
                            and i % cfg.moe_every == cfg.moe_every - 1)
                      for i in range(cfg.num_layers)]
        self.ln_f = nn.LayerNorm(cfg.hidden_size, policy=policy,
                          impl=cfg.ln_impl)

    def apply(self, variables: Variables, batch, training: bool = False,
              rng=None, cache=None, pos=None, prefill: bool = False,
              active=None):
        # ``active`` ([B] bool, decode-with-cache only) marks rows whose
        # output is consumed. For single-token serving steps that is the
        # engine's occupancy mask; inside a decode-horizon scan it is the
        # per-scan-step ``active ∧ ¬done ∧ ok`` emit mask, so rows that
        # hit EOS / budget / a NaN freeze mid-block stop doing attention
        # work exactly like empty slots. It is advisory: the
        # flash-decode kernel skips ALL work for non-emitting rows
        # (length 0); the composed path ignores it (garbage rows are
        # masked by the engine's ``where(emit, ...)`` either way).
        if isinstance(batch, dict):
            tokens = batch["tokens"][:, :-1]
        else:
            tokens = batch
        states: dict = {}
        s = tokens.shape[1]
        if s > self.cfg.max_positions:
            # Without this, the position-embedding gather silently clamps.
            raise ValueError(
                f"sequence length {s} exceeds max_positions "
                f"{self.cfg.max_positions}")
        # ``pos`` without a cache = a global position offset: the sequence-
        # parallel train step passes each shard's offset so position
        # embeddings (and ring attention's causal mask) see global positions.
        # A [B] pos vector (serve decode) offsets each row independently.
        offset = 0 if pos is None else pos
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None] + jnp.arange(s)[None, :]
        else:
            positions = offset + jnp.arange(s)[None, :]
        x = run_child(self.wte, "wte", variables, states, tokens,
                      training=training)
        x = x + run_child(self.wpe, "wpe", variables, states, positions,
                          training=training)
        x = run_child(self.drop, "drop", variables, states, x,
                      training=training, rng=rng)
        if self.cfg.scan_layers:
            if cache is None:
                # rng passed RAW (not via run_child): ScannedBlocks does
                # the per-layer ``h{i}`` derivation itself so dropout keys
                # match the unrolled trunk exactly.
                x, _ = self.h_scan.apply(
                    child_vars(variables, "h_scan"), x,
                    training=training, rng=rng, pos=pos)
            else:
                # Decode: per-layer slices of the stacked params, states
                # emitted under the unrolled ``h{i}`` names so the
                # generate/KV-cache plumbing is layout-agnostic.
                stacked = child_vars(variables, "h_scan")["params"]
                for i in range(self.cfg.num_layers):
                    lvars = {"params": jax.tree_util.tree_map(
                        lambda p, i=i: p[i], stacked), "state": {}}
                    x, st = self.h_scan.block.apply(
                        lvars, x, training=training,
                        rng=child_rng(rng, f"h{i}"), cache=cache[i],
                        pos=pos, prefill=prefill, active=active)
                    if st:
                        states[f"h{i}"] = st
        # (With scan_layers, self.h is empty — the loop below is a no-op
        # and the shared ln_f/aux/head tail runs for both layouts.)
        remat = self.cfg.remat and training and cache is None
        for i, block in enumerate(self.h):
            if remat:
                # Save only each block's input; recompute its internals in
                # backward. rng/pos ride through as traced args so dropout
                # keys replay identically in the recompute.
                name = f"h{i}"

                def block_fn(bvars, xx, block=block):
                    return block.apply(bvars, xx, training=True,
                                       rng=child_rng(rng, name), pos=pos)

                x, st = jax.checkpoint(block_fn)(
                    child_vars(variables, name), x)
                if st:
                    states[name] = st
            else:
                x = run_child(block, f"h{i}", variables, states, x,
                              training=training, rng=rng,
                              cache=None if cache is None else cache[i],
                              pos=pos, prefill=prefill, active=active)
        x = run_child(self.ln_f, "ln_f", variables, states, x,
                      training=training)
        # MoE blocks report their load-balance losses through child state;
        # harvest them OUT of the state tree (they're per-forward values,
        # not running state — leaving them in would change the TrainState
        # pytree structure between steps) and surface the weighted sum so
        # lm_loss can add it to the objective.
        aux = None
        if self.cfg.moe_experts and cache is None:
            terms = []
            for i in range(self.cfg.num_layers):
                blk = states.get(f"h{i}")
                if blk and "aux_loss" in blk.get("mlp", {}):
                    mlp_state = dict(blk["mlp"])
                    terms.append(mlp_state.pop("aux_loss"))
                    if mlp_state:
                        blk["mlp"] = mlp_state
                    else:
                        del blk["mlp"]
                    if not blk:
                        del states[f"h{i}"]
            if terms:
                aux = self.cfg.moe_aux_weight * sum(terms)
        if self.cfg.fused_loss_chunk and cache is None:
            # Defer the LM head to the loss: hand back the final hidden
            # states + the tied table so chunked_lm_cross_entropy computes
            # logits blockwise (grads flow to wte through this dict; "chunk"
            # is a static python int — it never crosses a jit boundary).
            wte = child_vars(variables, "wte")["params"]["embedding"]
            out = {"hidden": x, "wte": wte,
                   "chunk": self.cfg.fused_loss_chunk}
            if aux is not None:
                out["aux_loss"] = aux
            return out, states
        logits = self.wte.attend(child_vars(variables, "wte"), x)
        logits = jnp.asarray(logits, jnp.float32)
        if aux is not None:
            return {"logits": logits, "aux_loss": aux}, states
        return logits, states


    # ------------------------------------- what serve.Engine asks a model
    # No recurrent-state layers, no hyper-connection maps, no block
    # selection (``models.decoder.DecoderLM`` has the three counts).
    ssm_layers = 0
    mhc_sublayers = 0
    sparse_attention_layers = 0

    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: the cache
        group the layer's blocks live in (every layer here: the growing
        group, no window) and the leaves of one pool block, name ->
        (trailing shape, dtype); ``PagedSlotPool`` allocates
        ``[num_blocks, ...]`` of each. K and V are LANE-DENSE rows: ``(block_size, H*D)``, a
        position's heads side by side in lanes (head ``h`` in lanes
        ``h*D .. (h+1)*D``), so the pool ``[N, bs, H*D]`` is whole
        128-lane tiles in the device's own row-major layout and no
        program copies it between layouts. With ``quantized``, int8 rows
        plus one float32 absmax scale per (block, head)."""
        cfg = self.cfg
        kv = (block_size, cfg.hidden_size)
        if quantized:
            leaves = {"k": (kv, jnp.int8), "v": (kv, jnp.int8),
                      "k_scale": ((cfg.num_heads,), jnp.float32),
                      "v_scale": ((cfg.num_heads,), jnp.float32)}
        else:
            leaves = {"k": (kv, dtype), "v": (kv, dtype)}
        return [("global", None, leaves)] * cfg.num_layers

    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_layers)]

    def expert_load(self, states: dict):
        """No serving-side expert layer: nothing to count."""
        return None

    def paged_prefill_uses_kernel(self) -> bool:
        return _prefill_flash_ok(self.cfg)


def gpt2_124m(policy: Policy | None = None, **overrides) -> GPT2:
    cfg = GPT2Config(**overrides)
    return GPT2(cfg, policy=policy or bf16_policy())


def lm_loss(out, batch):
    """Next-token CE over {"tokens": [B, S+1]} batches.

    ``out`` is either dense logits or the fused-head dict (see
    ``GPT2Config.fused_loss_chunk``)."""
    targets = batch["tokens"][:, 1:]
    from nezha_tpu.ops.losses import lm_objective
    return lm_objective(out, targets)
