"""Head-sharded paged KV pool: one logical pool, M physical shards.

:class:`ShardedPagedSlotPool` is the PR 7 block-paged pool laid out
across a serve mesh: every per-layer K/V buffer
(``[num_blocks, block_size, H*D]``, lane-dense rows) and per-block
scale row (``[num_blocks, H]``, int8 pools) is committed to the mesh
with its LAST axis — the one the heads lie along: lanes for K/V
(head ``h`` in lanes ``h*D .. (h+1)*D``, so a shard is ``H / M``
contiguous heads), heads for scales — partitioned over ``tp``: block
``b`` exists on every device, each device holding its ``H / M`` head
slice of it. Everything
host-side is **deliberately unchanged and unsharded**: the free list,
ref counts, per-slot block tables, bound counts, and the prefix trie
are exactly PR 7's single bookkeeping state, because a block is a
LOGICAL unit — binding, COW, eviction, and the write-at-ref==1
invariant are decisions about block *identities*, which are mesh-
invariant. The ``mesh-host-side-tables`` lint rule pins the other
direction of that split: none of this host state may ever be mutated
from inside a ``shard_map``-lowered body.

What this buys:

- capacity scales with M — ``bytes_resident`` is the logical total,
  :attr:`bytes_resident_per_shard` what each device actually holds
  (the acceptance instrument for "a model whose KV exceeds one
  device's budget serves on ``--mesh M``");
- the COW / gather / scatter device ops (slots.py module jits) work
  verbatim: they are leading-axis (block-indexed) ops over the caches
  pytree, so XLA partitions them trivially along the untouched last
  axis, and a donated rewrite stays a per-shard rewrite;
- migration is GATHER-ON-EXPORT: ``export_block_payload`` already
  converts the gathered blocks to host arrays, which assembles the
  full-head wire payload from the shards — the int8+scales wire format
  (and the installer on any mesh size) is unchanged. A per-shard pull
  protocol is the noted follow-up.

``leak_check`` extends the PR 7 oracle per shard: besides the ref-count
books, every cache leaf must still be partitioned over ``tp`` (a
program or maintenance op that silently replicated the pool would
multiply resident bytes by M — exactly the regression the sharded
engine exists to prevent).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import jax.numpy as jnp

from nezha_tpu.serve.slots import PagedSlotPool


def head_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """The sharding of a block-indexed cache leaf of rank ``ndim``: its
    LAST axis over ``tp``, the rest replicated. One rule serves both
    leaf ranks: ``[N, bs, H*D]`` K/V rows (heads lie along the lanes)
    and ``[N, H]`` scale rows."""
    return NamedSharding(mesh, P(*([None] * (ndim - 1)), "tp"))


class ShardedPagedSlotPool(PagedSlotPool):
    """PR 7's paged pool with device state committed head-sharded over
    a serve mesh (axis name ``tp``). Host bookkeeping is inherited
    UNCHANGED — one logical pool, M physical shards."""

    def __init__(self, model, capacity: int, max_len: int,
                 dtype=jnp.bfloat16, *, mesh: Mesh,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache: bool = True, eviction: str = "lru",
                 quantized: bool = False, host_blocks: int = 0):
        if "tp" not in mesh.axis_names:
            raise ValueError(
                f"serve mesh must carry a 'tp' axis, got "
                f"{mesh.axis_names}")
        tp = int(mesh.shape["tp"])
        if model.cfg.num_heads % tp:
            raise ValueError(
                f"num_heads={model.cfg.num_heads} not divisible by the "
                f"mesh's tp={tp} — the KV pools shard on the head axis")
        # The host tier composes unchanged: demotion is the migration
        # export gather (gather-on-export assembles full heads from
        # the shards) and promotion the migration install scatter
        # (XLA partitions the leading-axis write along the untouched
        # last axis), so one host payload format serves every mesh.
        super().__init__(model, capacity, max_len, dtype,
                         block_size=block_size, num_blocks=num_blocks,
                         prefix_cache=prefix_cache, eviction=eviction,
                         quantized=quantized, host_blocks=host_blocks)
        self.mesh = mesh
        self.caches = self._place(self.caches)

    def _place(self, caches):
        """Commit every block-indexed leaf to the head sharding
        (:func:`head_sharding`)."""
        return [{k: jax.device_put(v, head_sharding(self.mesh, v.ndim))
                 for k, v in layer.items()} for layer in caches]

    # ------------------------------------------------------ accounting
    @property
    def shard_devices(self) -> int:
        """Mesh size M — how many physical shards the logical pool has."""
        return int(self.mesh.shape["tp"])

    @property
    def bytes_resident_per_shard(self) -> int:
        """Device bytes ONE shard holds for the resident blocks: the
        head axis divides exactly (validated at construction), so each
        device carries ``bytes_resident / M``. This is the number the
        per-device memory budget is judged against — and the reason a
        config whose logical pool exceeds one device fits under
        ``--mesh M``."""
        return self.bytes_resident // self.shard_devices

    # -------------------------------------------------------- invariants
    def leak_check(self) -> None:
        """PR 7's ref-count oracle, extended per shard: every cache
        leaf must still be PARTITIONED over the mesh's tp axis. A
        maintenance path that rebuilt the caches tree without the
        sharding (or a program whose output XLA chose to replicate)
        would silently multiply resident device bytes by M — a leak in
        the capacity dimension this subsystem exists to scale."""
        super().leak_check()
        if self.shard_devices > 1:
            for li, layer in enumerate(self.caches):
                for key, leaf in layer.items():
                    sh = getattr(leaf, "sharding", None)
                    if sh is None or sh.is_fully_replicated:
                        raise AssertionError(
                            f"layer {li} {key!r} pool leaf lost its "
                            f"head sharding (fully replicated across "
                            f"the {self.shard_devices}-device mesh) — "
                            f"resident bytes silently multiplied")
