"""Disaggregated memory pools: the engine-level objects.

``KVCachePool`` owns a device, every colocated model's *non-FFN* params,
and the shared physical KV page pool (virtualizer) — the SINGLE KV
allocation serving every colocated model's decode.  ``WeightsPool`` owns
another device and the consolidated FFN/MoE weights of ALL colocated
models — since PR 2 as ONE demand-managed slab arena
(``repro.core.weight_pool.WeightArena``): master copies stay on the host,
models are activated into / evicted from the arena, and device FFN bytes
are fixed by ``slot_budget`` alone regardless of the colocation count —
the weights-side twin of the KV pool's ``page_budget`` claim.  Hidden
states are the only tensors that cross between the pools (``transfer``),
matching the paper's NVSHMEM boundary.

On a one-device host both pools may map to the same device — the data-path
structure (split params, explicit transfers, page accounting) is identical;
on the production mesh the same roles are expressed by the ``crosspool``
sharding strategy inside one SPMD program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import split_exec
from repro.core.virtualizer import (DEFAULT_PAGE_BYTES, KVVirtualizer,
                                    ModelView)
from repro.core.weight_pool import (DEFAULT_SLAB_BYTES, ModelArenaView,
                                    OutOfSlabsError, WeightArena)


@dataclass
class PooledModel:
    cfg: ModelConfig
    kv_params: Dict            # embeddings, norms, attention (KV pool device)
    # HOST master FFN tree (numpy leaves) — fused-fallback families only;
    # split models' single host master is the arena's packed slab form
    w_params: Optional[Dict]
    view: ModelView            # how this model types the shared pages
    # how this model's FFN tree maps onto arena slabs (None for fused
    # fallback families, which never read weights through the arena)
    w_view: Optional[ModelArenaView]
    # the ONE shared weights arena (same object for every pooled model)
    arena: Optional[WeightArena]
    # None for fused-fallback families (SSM/hybrid/enc-dec/SWA)
    stage_fns: Optional[split_exec.StageFns]


class WeightsPool:
    """Consolidated FFN weights of all colocated cold models.

    Device side: ONE slab arena sized by ``slot_budget``.  Host side: the
    packed master slabs for arena (split-execution) models — stored ONCE,
    in upload-ready form — plus plain FFN trees (numpy leaves) for the
    fused-fallback families the arena never serves.
    """

    def __init__(self, device, *, slab_bytes: int = DEFAULT_SLAB_BYTES):
        self.device = device
        self.arena = WeightArena(slab_bytes=slab_bytes, device=device)
        # host master trees of the fallback families only (split models'
        # single host copy is the packed arena.host_slabs)
        self.ffn_params: Dict[str, Dict] = {}

    def add_model(self, name: str, cfg: ModelConfig, w_params: Dict) -> None:
        host = jax.tree.map(np.asarray, w_params)
        if split_exec.supports_split(cfg):
            self.arena.add_model(name, cfg, host)
        else:
            self.ffn_params[name] = host

    def finalize(self, slot_budget: Optional[int] = None, *,
                 allocate: bool = True) -> None:
        self.arena.finalize(slot_budget, allocate=allocate)

    def total_bytes(self) -> int:
        """DEVICE weights-pool bytes: the arena, fixed by slot_budget."""
        return self.arena.device_bytes()

    def resize(self, slot_budget: int):
        """Elastic entry: live-resize the arena (DESIGN.md §8)."""
        return self.arena.resize(slot_budget)

    def host_master_bytes(self) -> int:
        return sum(
            leaf.size * leaf.dtype.itemsize
            for tree in self.ffn_params.values()
            for leaf in jax.tree.leaves(tree))


class KVCachePool:
    """Attention-side pool: non-FFN params + the shared paged KV space."""

    def __init__(self, device, models: Dict[str, ModelConfig], *,
                 page_budget: int, page_bytes: int = DEFAULT_PAGE_BYTES,
                 pool_dtype=jnp.bfloat16,
                 allocate_device_pool: bool = True):
        self.device = device
        self.attn_params: Dict[str, Dict] = {}
        self.virtualizer = KVVirtualizer(
            models, page_budget=page_budget, page_bytes=page_bytes,
            dtype=pool_dtype, allocate_device_pool=allocate_device_pool,
            device=device)

    def add_model(self, name: str, kv_params: Dict) -> None:
        self.attn_params[name] = jax.device_put(kv_params, self.device)

    def resize(self, page_budget: int, protected=()):
        """Elastic entry: live-resize the shared page pool (DESIGN.md §8)."""
        return self.virtualizer.resize(page_budget, protected=protected)

    def total_param_bytes(self) -> int:
        return sum(
            leaf.size * leaf.dtype.itemsize
            for tree in self.attn_params.values()
            for leaf in jax.tree.leaves(tree))


def transfer(x: jax.Array, device) -> jax.Array:
    """The pool boundary: explicit async hidden-state transfer, issued
    inside a ``cp.transfer`` profiler span while a JAX profiler session
    records (the host-driven pipeline's exposed transfer time)."""
    if not TraceAnnotation.is_enabled():
        return jax.device_put(x, device)
    with TraceAnnotation("cp.transfer", bytes=x.nbytes):
        return jax.device_put(x, device)


def build_pools(models: Dict[str, ModelConfig],
                params: Union[Dict[str, Dict], Callable[[str], Dict]], *,
                kv_device=None, w_device=None, page_budget: int,
                page_bytes: int = DEFAULT_PAGE_BYTES,
                pool_dtype=jnp.bfloat16,
                allocate_device_pool: bool = True,
                slot_budget: Optional[int] = None,
                slab_bytes: int = DEFAULT_SLAB_BYTES,
                arena_device=None,
                allocate_device_arena: Optional[bool] = None,
                activate_resident: bool = True,
                ):
    """Split every model's params across the two pools.

    Models that support split execution get paged :class:`StageFns`
    compiled against the virtualizer's page geometry AND the arena's slab
    geometry; fused-fallback families get ``stage_fns=None`` and keep
    serving through their dense per-model caches.

    ``slot_budget=None`` sizes the arena so every split model fits
    resident at once (the PR-1-equivalent all-resident working set); a
    smaller budget turns activation into demand paging with LRU eviction
    of idle models.  ``activate_resident`` eagerly activates models in
    registration order until the budget is full — remaining models are
    activated on demand by the engine.

    ``params`` maps a model name to its full param tree, or is a callable
    that builds it: models are split one at a time, so with a callable
    only one full tree is alive at once.
    """
    devs = jax.devices()
    kv_device = kv_device or devs[0]
    w_device = w_device or devs[-1]
    kv_pool = KVCachePool(kv_device, models, page_budget=page_budget,
                          page_bytes=page_bytes, pool_dtype=pool_dtype,
                          allocate_device_pool=allocate_device_pool)
    w_pool = WeightsPool(arena_device or w_device, slab_bytes=slab_bytes)
    tree_of = params if callable(params) else params.__getitem__
    for name, cfg in models.items():
        kv_tree, w_tree = split_exec.split_params(tree_of(name), cfg)
        kv_pool.add_model(name, kv_tree)
        w_pool.add_model(name, cfg, w_tree)
    if allocate_device_arena is None:
        allocate_device_arena = allocate_device_pool
    w_pool.finalize(slot_budget, allocate=allocate_device_arena)
    if activate_resident:
        for name in w_pool.arena.views:
            try:
                w_pool.arena.activate(name)
            except OutOfSlabsError:
                break                      # the rest activate on demand
    pooled: Dict[str, PooledModel] = {}
    for name, cfg in models.items():
        view = kv_pool.virtualizer.views[name]
        w_view = w_pool.arena.views.get(name)
        stage_fns = (split_exec.make_stage_fns(cfg, view, w_view)
                     if split_exec.supports_split(cfg) else None)
        pooled[name] = PooledModel(
            cfg=cfg,
            kv_params=kv_pool.attn_params[name],
            w_params=w_pool.ffn_params.get(name),
            view=view,
            w_view=w_view,
            arena=w_pool.arena,
            stage_fns=stage_fns,
        )
    return kv_pool, w_pool, pooled
