"""The tensor-sharded serve engine: one replica, M devices, one mesh.

:class:`ShardedEngine` turns the single-device continuous-batching
engine (serve/engine.py) into an M-device tensor-parallel engine under
a 1xM device mesh (axis name ``tp`` — the same axis the training-side
GSPMD stack and the nested-``shard_map`` flash idiom already key on):

- **parameters** are committed Megatron-style per the serve rule table
  (:func:`~nezha_tpu.serve.sharded.reshard.serve_tp_rules` — column-
  parallel qkv/fc, row-parallel proj, the training table verbatim);
- **paged K/V pools and per-block scales** are committed HEAD-sharded
  (:class:`~nezha_tpu.serve.sharded.pool.ShardedPagedSlotPool`) — one
  logical pool, M physical shards, while block tables, the free list,
  ref counts, and the prefix trie stay host-side and layout-identical
  to PR 7;
- **programs** are EXACTLY the engine's frozen set — the same
  ``_build_prefill`` / ``_build_step`` / ``_build_spec_step`` closures,
  untouched — reached through the two subsystem hooks: pools built
  sharded, and every program trace wrapped in
  ``auto_partitioner_scope(mesh)`` so XLA's SPMD partitioner lays the
  collectives (attention stays embarrassingly head-parallel; one
  reduce per row-parallel proj) onto the mesh, model code skips Mosaic
  kernels the partitioner cannot split, and — on TPU — the decode
  attention drops into ``flash_decode_attention`` per-shard via a
  nested ``shard_map`` over the head axis with the scalar-prefetched
  block tables replicated (ops/pallas/decode_attention.py).

The frozen program contract is preserved PER MESH: steady state is
still ``1 step + len(all_prefill_buckets)`` executor entries with misses
frozen after warmup — the executor keys on function identity + shapes,
and the wrapped closures are built once per engine. Greedy outputs are
bit-identical to the single-device engine on a fitting config
(attention partitions per head; the per-proj reduces are the only
cross-device math), which the ``sharded_serve`` bench suite and
tests/test_sharded.py pin.

Composition: ``nezha-serve --replicas N --mesh M`` gives N routed
replicas x M-device meshes — the router/supervisor never sees the mesh
(a sharded replica answers the same HTTP surface), so the two scale
axes multiply without new protocol. Migration composes too:
``export_block_payload`` gathers the head shards into the full-head
int8+scales wire payload (gather-on-export), and installs scatter back
into whatever mesh the destination runs.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax

from jax.sharding import NamedSharding, PartitionSpec as P

from nezha_tpu import faults, obs
from nezha_tpu.parallel.gspmd import auto_partitioner_scope
from nezha_tpu.parallel.mesh import make_mesh
from nezha_tpu.serve.engine import Engine, ServeConfig
from nezha_tpu.serve.sharded.pool import (
    ShardedPagedSlotPool,
    head_sharding,
)
from nezha_tpu.serve.sharded.reshard import (place_variables,
                                             serve_tp_rules)


class ShardedEngine(Engine):
    """The M-device tensor-parallel serve engine. Drop-in for
    :class:`~nezha_tpu.serve.engine.Engine` everywhere the scheduler,
    migration, and front ends are concerned — the mesh is an internal
    axis, not a protocol change. ``mesh_devices=1`` is a valid
    degenerate mesh (useful for A/B parity runs on one device)."""

    _seq_prefill_capable = True

    def __init__(self, model, variables, cfg: ServeConfig = ServeConfig(),
                 *, mesh_devices: int, devices: Optional[Sequence] = None,
                 rules=None, draft_model=None, draft_variables=None):
        m = int(mesh_devices)
        if m < 1:
            raise ValueError(f"mesh_devices must be >= 1, got {m}")
        # Sequence-sharded prefill (PR 20); prefill_mode="replicated"
        # is the way back: the long buckets keep serving the same
        # prompts, only the chunk attention stops sharding over the
        # sequence axis.
        self._seq_active = cfg.prefill_mode == "sequence"
        self._seq_variant = None
        if self._seq_active:
            if m < 2:
                raise ValueError(
                    "prefill_mode='sequence' requires mesh_devices > 1 "
                    "— there is no sequence axis to shard over on a "
                    "degenerate 1-device mesh")
            bad = [w for w in cfg.all_prefill_buckets if w % m]
            if bad:
                raise ValueError(
                    f"prefill_mode='sequence' needs every prefill "
                    f"bucket width divisible by mesh_devices={m}; "
                    f"offending buckets: {bad} (size prefill_buckets/"
                    f"long_prefill_buckets accordingly)")
            # "auto" resolves to ulysses: the engine's head-
            # divisibility requirement above guarantees H % M == 0,
            # and ulysses is the bitwise-parity layout (RUNBOOK §8).
            self._seq_variant = ("ulysses"
                                 if cfg.seq_prefill_variant == "auto"
                                 else cfg.seq_prefill_variant)
        avail = list(devices) if devices is not None else jax.devices()
        if m > len(avail):
            raise ValueError(
                f"mesh_devices={m} but only {len(avail)} device(s) "
                f"visible (force host devices with "
                f"--xla_force_host_platform_device_count on CPU)")
        if model.cfg.num_heads % m:
            raise ValueError(
                f"num_heads={model.cfg.num_heads} not divisible by "
                f"mesh_devices={m} — K/V pools shard on the head axis")
        # The 1xM serve mesh: one replica, M tensor shards. Axis name
        # 'tp' on purpose — the training rule table and the nested
        # shard_map kernel paths key on it.
        self.mesh = make_mesh({"tp": m}, devices=avail[:m])
        self.mesh_devices = m
        self._rules = (rules if rules is not None
                       else serve_tp_rules(model.cfg, m))
        variables = place_variables(variables, self.mesh, self._rules)
        if draft_variables is not None and draft_model is not None:
            draft_variables = place_variables(
                draft_variables, self.mesh,
                serve_tp_rules(draft_model.cfg, m))
        # Output-sharding pins for the program wrapper (created BEFORE
        # super().__init__, which builds the programs through the
        # hooks): cache pytrees stay head-sharded (each leaf's LAST
        # axis over tp: the lanes of the [N, bs, H*D] K/V rows, the
        # heads of the [N, H] scales — pool.head_sharding), everything
        # else replicates.
        self._rep_out = NamedSharding(self.mesh, P())
        # super().__init__ builds pools and programs through the two
        # subsystem hooks below; a self-draft built inside it SHARES
        # the placed target leaves, so its params arrive sharded free.
        super().__init__(model, variables, cfg, draft_model=draft_model,
                         draft_variables=draft_variables)
        # Commit the per-row engine state (logits, positions, keys,
        # sampling params, completion state) to the mesh REPLICATED at
        # construction: combined with the wrapper's output constraints
        # below, every dispatch of a program sees one stable sharding
        # signature — without this, the first trace keys on
        # uncommitted zeros and the second dispatch pays a hidden
        # whole-program recompile (measured: ~100x one prefill's cost).
        for name in ("last_logits", "positions", "keys", "temps",
                     "top_ks", "top_ps", "eos_ids", "budgets"):
            setattr(self, name,
                    jax.device_put(getattr(self, name), self._rep_out))
        if self.spec is not None:
            self.residual = jax.device_put(self.residual, self._rep_out)
        obs.gauge("serve.mesh.devices").set(m)
        # How many sequence shards each prefill chunk spreads over
        # (0 = replicated prefill). Re-pinned per prefill call, like
        # kernel_active — bench harnesses reset the registry after
        # warmup.
        obs.gauge("serve.prefill.seq_shards").set(
            float(m) if self._seq_active else 0.0)
        # Trace-shape estimate of the cross-shard collective payload
        # per TOKEN through the target model: the SPMD partitioner
        # inserts one activation reduce after each row-parallel proj
        # (attention out + MLP out -> 2 per layer), fp32-width — the
        # same trace-time accounting idiom PR 1 uses for the training
        # collectives. 0 on a degenerate 1-device mesh.
        c = model.cfg
        self._coll_bytes_per_token = (
            0 if m == 1 else 2 * c.num_layers * c.hidden_size * 4)

    # ----------------------------------------------- subsystem hooks
    def _make_paged_pool(self, model, *, num_blocks, prefix_cache,
                         eviction, quantized, host_blocks=0):
        cfg = self.cfg
        return ShardedPagedSlotPool(
            model, cfg.max_batch_size, cfg.max_len, cfg.cache_dtype,
            mesh=self.mesh, block_size=cfg.kv_block_size,
            num_blocks=num_blocks, prefix_cache=prefix_cache,
            eviction=eviction, quantized=quantized,
            host_blocks=host_blocks)

    def _wrap_program(self, fn):
        """Every frozen program traces under the auto-partitioner scope
        carrying the serve mesh: model code sees the mesh (TPU decode
        attention drops to the per-shard nested-shard_map kernel;
        Mosaic is never handed to the partitioner raw) and XLA inserts
        the collectives. One wrapper per built program, created once in
        ``__init__`` — the executor keys on the wrapper's identity, so
        the frozen-program contract counts exactly as before.

        Outputs are sharding-PINNED: cache pytrees (the list-shaped
        elements — target and draft caches alike) stay head-sharded,
        every other output replicates. Pinning is what makes each
        program's input signature a FIXED POINT — its state outputs
        feed the next dispatch with the same shardings the first trace
        committed, so no dispatch after the first ever recompiles
        (the per-mesh frozen-program contract, at the jit level as
        well as the executor level)."""
        mesh = self.mesh
        rep_out = self._rep_out

        def pin(out):
            if isinstance(out, list):       # a per-layer caches list
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, head_sharding(mesh, x.ndim)), out)
            if isinstance(out, tuple):
                return tuple(pin(o) for o in out)
            return jax.lax.with_sharding_constraint(out, rep_out)

        def sharded_program(*args):
            with auto_partitioner_scope(mesh):
                return pin(fn(*args))

        return sharded_program

    def _wrap_prefill_program(self, fn):
        """In sequence mode the bucket programs trace with the
        seq-prefill scope nested inside the partitioner scope, so the
        model's prefill-chunk branch builds the nested sequence-sharded
        shard_map (serve/sharded/seq_prefill.py — importing it here is
        also what arms the model's ``sys.modules`` probe). Step/decode
        programs never come through this hook and stay untouched;
        replicated mode is the plain :meth:`_wrap_program`, byte for
        byte."""
        if not self._seq_active:
            return self._wrap_program(fn)
        from nezha_tpu.serve.sharded import seq_prefill

        inner = self._wrap_program(fn)
        mesh, variant = self.mesh, self._seq_variant

        def seq_program(*args):
            with seq_prefill.seq_prefill_scope(mesh, variant):
                return inner(*args)

        return seq_program

    # ------------------------------------------------------- dispatch
    def prefill(self, slot: int, tokens, **kwargs) -> None:
        if self._seq_active:
            # Chunk-retirement drill point for sequence mode: a seeded
            # fault here must retire ONLY the victim request with zero
            # slot/block/scale leaks on every shard (tests/chaos).
            faults.point("serve.prefill.seq")
            obs.gauge("serve.prefill.seq_shards").set(
                float(self.mesh_devices))
            with obs.span("serve.prefill.seq_s"):
                super().prefill(slot, tokens, **kwargs)
            if self._seq_variant == "ring":
                # One ring rotation per chunk: every shard's block
                # travels the full ring, world hops per chunk program.
                obs.counter("serve.prefill.ring_hops_total").inc(
                    self.mesh_devices * self.last_prefill_chunks)
        else:
            super().prefill(slot, tokens, **kwargs)
        if self._coll_bytes_per_token:
            # The tokens the compiled chunks ACTUALLY pushed through
            # the target model (bucket pads included, a prefix hit's
            # cached span excluded — the base prefill records it).
            obs.counter("serve.mesh.collective_bytes").inc(
                self.last_prefill_tokens
                * self._coll_bytes_per_token)

    def step(self, active: np.ndarray):
        out = super().step(active)
        if self._coll_bytes_per_token:
            obs.counter("serve.mesh.collective_bytes").inc(
                self.cfg.max_batch_size * self.tokens_per_dispatch
                * self._coll_bytes_per_token)
        return out

    # ----------------------------------------------------- accounting
    def memory_report(self) -> dict:
        """Exact per-device vs logical byte accounting — the proof
        instrument for "``--mesh M`` serves a config whose KV + params
        exceed a single device's budget". Params are summed from the
        committed leaves' addressable shards on the mesh's first device
        (replicated leaves count full size there — honest: each device
        really holds them); KV is the pools' CAPACITY (all blocks), the
        number a budget must provision for, not the instantaneous
        ``bytes_resident``."""
        dev0 = self.mesh.devices.flat[0]

        def dev_bytes(tree):
            total = shard = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                if not isinstance(leaf, jax.Array):
                    continue
                total += leaf.nbytes
                shard += sum(s.data.nbytes
                             for s in leaf.addressable_shards
                             if s.device == dev0)
            return total, shard

        p_total, p_shard = dev_bytes(self.variables)
        pools = [self.pool.caches]
        if self.draft_pool is not None:
            pools.append(self.draft_pool.caches)
        k_total = k_shard = 0
        for caches in pools:
            t, s = dev_bytes(caches)
            k_total += t
            k_shard += s
        return {
            "mesh_devices": self.mesh_devices,
            "params_bytes": p_total,
            "params_bytes_per_device": p_shard,
            "kv_capacity_bytes": k_total,
            "kv_capacity_bytes_per_device": k_shard,
            "bytes_total": p_total + k_total,
            "bytes_per_device": p_shard + k_shard,
        }
