"""Weights-pool virtualizer: an expert-slab arena for cold models' FFN.

The KV side of the paper virtualizes cache memory behind ONE shared page
pool (``repro.core.virtualizer``).  This module is its weights-side twin
(DESIGN.md §5): device FFN/MoE bytes for every colocated cold model come
out of ONE pre-allocated **arena** of fixed-size slabs, and "loading a
model" is slot-table bookkeeping plus an async host->device upload — not a
per-model ``device_put`` that scales with the colocation count.

  * the arena is an untyped array of 16-bit words ``[slot_budget,
    slab_rows, 128]``: heterogeneous models — bf16 experts, f32 routers —
    share the same physical slabs and are reconstructed bit-exactly by
    in-program same-width bitcasts, the weights analogue of the KV pool's
    untyped pages;
  * every model's FFN tree is decomposed into per-layer **slab units**:
    one unit per expert (``wg``/``wu``/``wd`` of one expert of one layer)
    plus one "rest" unit per layer (router, shared experts, or the whole
    dense MLP).  A unit occupies ``ceil(unit_rows / slab_rows)`` slabs;
  * **slow path** (host, per activation): ``activate`` / ``evict`` move
    slab ids between the free list and per-model slot tables.  Mapping is
    ATOMIC — eviction candidates are planned first and the slab count is
    taken in one step, so ``OutOfSlabsError`` leaves the arena untouched
    (same rule as ``KVVirtualizer.register_request``);
  * **fast path** (device, per layer): ``ffn_stage`` gathers one layer's
    slab rows through the model's slot table (``ModelArenaView
    .unpack_layer``) and bitcasts their words back into expert/MLP weight
    tensors — weights are read through a table exactly like KV pages;
  * master copies stay HOST-resident (packed slab form), so eviction is
    free (weights are read-only) and re-activation re-uploads the same
    bytes: an evict/re-activate round trip is bit-for-bit invisible;
  * uploads are per-layer scatters, so the layer-wise pipeline can
    prefetch layer L+1's slabs while layer L's attention runs
    (``prefetch_layer``) — the paper's transfer-hiding scheduler extended
    from hidden states to weights.

Idle models are evicted clock/LRU under pressure; models with in-flight
requests are pinned and never evicted (the weights analogue of "active
pages are never revoked", paper §3.1).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.errors import check
from repro.models.moe import EXPERT_STACKED_LEAVES

#: Slab granularity of the weights arena.  Weights move in whole-expert
#: units (tens of MB at paper scale), so the slab is far coarser than the
#: 16 KiB KV page: 512 KiB keeps per-expert internal fragmentation under a
#: slab per unit while the slot table stays short.  It is also the largest
#: slab (2048 rows of the arena's 16-bit words) the TPU compiler gathers
#: as one slice: it splits a larger one in halves and joins them through a
#: layer-sized pad-and-add copy, which doubles the FFN stage's scratch.
DEFAULT_SLAB_BYTES = 1 << 19


class OutOfSlabsError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Static layout: how one model's FFN tree maps onto slabs
# ---------------------------------------------------------------------------

#: Storage of the arena: 16-bit words in rows of ``ARENA_LANES``, so a
#: slab is ``[slab_rows, ARENA_LANES]`` and the slabs of one unit stack
#: along the ROW axis.  Unpacking then only splits and slices row axes and
#: bitcasts words of the same width; it never folds rows into the minor
#: axis or builds a minor axis of an itemsize.  (On a TPU either relayout
#: pads or shuffles whole tiles: a byte-level unpack of one 64-expert
#: layer took minutes to compile and gigabytes of scratch.)  A 16-bit
#: leaf (bf16) is a bitcast of its words; a 32-bit leaf (the f32 router,
#: f32 models) is stored as two word PLANES — every element's low half,
#: then every high half, each padded to whole rows — joined by a shift
#: and an or.
ARENA_WORD = np.uint16
ARENA_LANES = 128
_WORD_BYTES = np.dtype(ARENA_WORD).itemsize
_ROW_BYTES = ARENA_LANES * _WORD_BYTES


@dataclass(frozen=True)
class LeafSpec:
    """One weight tensor inside a slab unit."""

    path: Tuple[str, ...]          # e.g. ("moe", "wg") / ("mlp", "wd")
    dtype: jnp.dtype
    shape: Tuple[int, ...]         # per-unit shape (no layer/expert axes)
    offset: int                    # first row inside the unit
    plane_rows: int                # rows of one plane

    @property
    def planes(self) -> int:
        return self.dtype.itemsize // _WORD_BYTES

    @property
    def rows(self) -> int:
        return self.planes * self.plane_rows


@dataclass(frozen=True)
class UnitSpec:
    """A fixed-size allocation unit: one expert, or one layer's rest."""

    kind: str                      # "expert" | "rest"
    count: int                     # units of this kind per layer (E or 1)
    leaves: Tuple[LeafSpec, ...]
    unit_rows: int
    slabs_per_unit: int
    slab_offset: int               # first slab of this kind in a layer row


def _leaf_paths(tree: Dict, prefix: Tuple[str, ...] = ()) -> List[Tuple[Tuple[str, ...], np.ndarray]]:
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(_leaf_paths(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), v))
    return out


def _is_expert_leaf(path: Tuple[str, ...], cfg: ModelConfig) -> bool:
    """Leaves stacked over the expert axis: moe/{wg,wu,wd} [L,E,...]."""
    return (cfg.is_moe and len(path) == 2 and path[0] == "moe"
            and path[1] in EXPERT_STACKED_LEAVES)


def _leaf_rows(n_elems: int, itemsize: int) -> int:
    """Arena rows one leaf occupies: each plane padded to whole rows."""
    return (itemsize // _WORD_BYTES) * math.ceil(n_elems / ARENA_LANES)


def _build_specs(kind: str, leaves: Sequence[Tuple[Tuple[str, ...], np.ndarray]],
                 count: int, per_unit_axes: int, slab_rows: int,
                 slab_offset: int) -> Optional[UnitSpec]:
    """Lay ``leaves`` out back-to-back inside one unit.

    ``per_unit_axes`` is how many leading axes (layer, expert) to strip
    from the stacked array shape to get the per-unit tensor shape.  Only
    shapes and dtypes are read, so ``jax.ShapeDtypeStruct`` leaves work.
    """
    if not leaves:
        return None
    specs, off = [], 0
    for path, arr in leaves:
        shape = tuple(arr.shape[per_unit_axes:])
        dt = jnp.dtype(arr.dtype)
        check(dt.itemsize in (_WORD_BYTES, 2 * _WORD_BYTES),
              f"arena leaf {'/'.join(path)} has {dt}; only 16- and 32-bit "
              f"weights are stored")
        leaf = LeafSpec(path, dt, shape, off,
                        math.ceil(math.prod(shape) / ARENA_LANES))
        specs.append(leaf)
        off += leaf.rows
    return UnitSpec(kind, count, tuple(specs), off,
                    max(1, math.ceil(off / slab_rows)), slab_offset)


def _from_words(raw: jax.Array, leaf: LeafSpec) -> jax.Array:
    """[count, rows, ARENA_LANES] words -> leaf.dtype [count, *leaf.shape]
    (value-exact)."""
    if leaf.planes == 1:
        val = jax.lax.bitcast_convert_type(raw, leaf.dtype)
    else:
        lo = raw[:, :leaf.plane_rows].astype(jnp.uint32)
        hi = raw[:, leaf.plane_rows:].astype(jnp.uint32)
        val = jax.lax.bitcast_convert_type(lo | (hi << 16), leaf.dtype)
    n = math.prod(leaf.shape)
    if n % ARENA_LANES:                 # only tiny leaves pad their last row
        val = val.reshape(val.shape[0], -1)[:, :n]
    return val.reshape((val.shape[0],) + leaf.shape)


def _to_words(arr: np.ndarray, leaf: LeafSpec) -> np.ndarray:
    """Host inverse of :func:`_from_words`: [..., n] -> [..., rows, LANES]."""
    w = arr.view(ARENA_WORD).reshape(arr.shape[:-1] + (-1, leaf.planes))
    w = np.moveaxis(w, -1, -2)                        # [..., planes, n]
    pad = leaf.plane_rows * ARENA_LANES - w.shape[-1]
    w = np.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, pad)])
    return w.reshape(arr.shape[:-1] + (leaf.rows, ARENA_LANES))


@dataclass
class ModelArenaView:
    """Static slab geometry of one model + the in-program unpacker.

    The layout is identical for every layer, so a model's residency is a
    ``[n_layers, slabs_per_layer]`` slot table and ``unpack_layer`` is a
    gather per leaf + static row slicing/bitcasting compiled into the FFN
    stage.
    """

    name: str
    n_layers: int
    units: Tuple[UnitSpec, ...]
    slabs_per_layer: int
    slab_rows: int

    @property
    def total_slabs(self) -> int:
        return self.n_layers * self.slabs_per_layer

    def unpack_layer(self, arena: jax.Array, row: jax.Array) -> Dict:
        """Rebuild one layer's FFN param tree from the arena.

        ``arena``: [slot_budget, slab_rows, ARENA_LANES] ARENA_WORD;
        ``row``: [slabs_per_layer] int32 slab ids.  Per leaf, ONE gather
        of just the slabs its rows touch (in every unit of its kind), then
        a static row slice and a same-width bitcast — bit-for-bit the
        packed host words.  Gathering per leaf, not per layer, lets each
        leaf's rows feed its consumer without a second layer-sized copy.
        """
        with jax.named_scope("unpack"):
            return self._unpack(arena, row)

    def _unpack(self, arena: jax.Array, row: jax.Array) -> Dict:
        out: Dict = {}
        for u in self.units:
            unit_base = (u.slab_offset
                         + u.slabs_per_unit * np.arange(u.count)[:, None])
            for leaf in u.leaves:
                s0 = leaf.offset // self.slab_rows
                s1 = -(-(leaf.offset + leaf.rows) // self.slab_rows)
                raw = arena[row[unit_base + np.arange(s0, s1)]]
                raw = raw.reshape(u.count, (s1 - s0) * self.slab_rows,
                                  ARENA_LANES)
                start = leaf.offset - s0 * self.slab_rows
                val = _from_words(jax.lax.slice_in_dim(
                    raw, start, start + leaf.rows, axis=1), leaf)
                # expert units keep their stacked [E, ...] axis even when
                # E == 1 (apply_moe expects the init_moe layout); rest
                # units are per-layer tensors with no unit axis
                if u.kind != "expert":
                    val = val[0]
                dst = out
                for k in leaf.path[:-1]:
                    dst = dst.setdefault(k, {})
                dst[leaf.path[-1]] = val
        return out


def build_view(name: str, cfg: ModelConfig, w_tree: Dict, *,
               slab_bytes: int) -> ModelArenaView:
    """Static slab geometry of a split FFN tree.

    ``w_tree`` is ``split_exec.split_params``' weights-pool half with
    layer-stacked leaves.  Only leaf shapes and dtypes are read, so a tree
    from ``jax.eval_shape`` gives the geometry without any weights.
    """
    check(slab_bytes % _ROW_BYTES == 0,
          f"slab_bytes {slab_bytes} is not a whole number of "
          f"{_ROW_BYTES}-byte arena rows")
    slab_rows = slab_bytes // _ROW_BYTES
    layer_leaves = _leaf_paths(w_tree["layers"])
    n_layers = layer_leaves[0][1].shape[0]
    expert = [(p, a) for p, a in layer_leaves if _is_expert_leaf(p, cfg)]
    rest = [(p, a) for p, a in layer_leaves if not _is_expert_leaf(p, cfg)]

    units: List[UnitSpec] = []
    off = 0
    eu = _build_specs("expert", expert, cfg.n_experts, 2, slab_rows, off)
    if eu is not None:
        units.append(eu)
        off += eu.count * eu.slabs_per_unit
    ru = _build_specs("rest", rest, 1, 1, slab_rows, off)
    if ru is not None:
        units.append(ru)
        off += ru.slabs_per_unit
    return ModelArenaView(name, n_layers, tuple(units), off, slab_rows)


def pack_slabs(view: ModelArenaView, w_tree: Dict) -> np.ndarray:
    """The packed host master ``[n_layers, slabs_per_layer, slab_rows,
    ARENA_LANES]`` ARENA_WORD of ``w_tree`` (host numpy leaves) — the
    HOST-resident source every (re-)upload scatters from."""
    n_layers = view.n_layers
    slabs = np.zeros((n_layers, view.slabs_per_layer, view.slab_rows,
                      ARENA_LANES), ARENA_WORD)
    by_path = dict(_leaf_paths(w_tree["layers"]))
    for u in view.units:
        span = slabs[:, u.slab_offset:
                     u.slab_offset + u.count * u.slabs_per_unit]
        span = span.reshape(n_layers, u.count,
                            u.slabs_per_unit * view.slab_rows, ARENA_LANES)
        for leaf in u.leaves:
            arr = np.ascontiguousarray(by_path[leaf.path])
            span[:, :, leaf.offset:leaf.offset + leaf.rows] = _to_words(
                arr.reshape(n_layers, u.count, -1), leaf)
    return slabs


# ---------------------------------------------------------------------------
# Analytic accounting (planner / Table 1 — no weights needed)
# ---------------------------------------------------------------------------

def _cfg_itemsize(cfg: ModelConfig) -> int:
    return 4 if cfg.dtype == "float32" else 2


def slabs_for_config(cfg: ModelConfig, slab_bytes: int = DEFAULT_SLAB_BYTES
                     ) -> int:
    """Arena slabs a fully resident model needs, from the config alone.

    Mirrors :func:`build_view` geometry: per layer, E expert units (3
    matrices each) + one rest unit (router [+ shared experts], or the
    whole dense MLP), every leaf padded to whole arena rows.
    """
    d, isz = cfg.d_model, _cfg_itemsize(cfg)
    slab_rows = slab_bytes // _ROW_BYTES

    def unit_slabs(*leaves: Tuple[int, int]) -> int:
        rows = sum(_leaf_rows(n, size) for n, size in leaves)
        return math.ceil(rows / slab_rows)

    if cfg.is_moe:
        per_layer = cfg.n_experts * unit_slabs(*[(d * cfg.d_ff, isz)] * 3)
        rest = [(d * cfg.n_experts, 4)]                     # f32 router
        if cfg.n_shared_experts:
            rest += [(d * cfg.n_shared_experts * cfg.d_ff, isz)] * 3
        per_layer += unit_slabs(*rest)
    else:
        n_mats = 3 if cfg.mlp_kind == "swiglu" else 2
        per_layer = unit_slabs(*[(d * cfg.d_ff, isz)] * n_mats)
    return cfg.n_layers * per_layer


def static_ffn_bytes(cfg: ModelConfig) -> int:
    """Per-model-static baseline: the model's full FFN bytes device-resident."""
    return cfg.param_counts()["ffn"] * _cfg_itemsize(cfg)


# ---------------------------------------------------------------------------
# The arena
# ---------------------------------------------------------------------------

@dataclass
class Residency:
    """One resident model's mapping into the arena."""

    slots: np.ndarray              # [n_layers, slabs_per_layer] int32
    uploaded: np.ndarray           # [n_layers] bool (per-layer streaming)
    last_used: int = 0             # LRU clock tick
    rev: int = -1                  # bumped per activation (table cache key)


#: One donated-buffer scatter of packed slab rows into the arena.
_arena_scatter = jax.jit(lambda a, i, r: a.at[i].set(r), donate_argnums=0)

#: One gather of whole slabs: builds the compacted / resized arena.
_arena_gather = jax.jit(lambda a, i: a[i])


class WeightArena:
    """Host-side slab allocator over one device-resident weights arena."""

    def __init__(self, *, slab_bytes: int = DEFAULT_SLAB_BYTES, device=None):
        self.slab_bytes = slab_bytes
        self.slab_rows = slab_bytes // _ROW_BYTES
        self.device = device
        self.slot_budget = 0
        self.arena: Optional[jax.Array] = None
        self.free_list: List[int] = []
        self.views: Dict[str, ModelArenaView] = {}
        self.host_slabs: Dict[str, np.ndarray] = {}
        self.residency: Dict[str, Residency] = {}
        self.pins: Dict[str, int] = {}
        self._clock = 0
        self._rev_counter = 0
        self._table_cache: Dict[str, dict] = {}
        # stats
        self.activations = 0
        self.evictions = 0
        self.layer_uploads = 0
        self.resizes = 0
        # optional observability sink (core.hooks.CoreHooks); every hook
        # fires AFTER the matching stat counter above has been updated
        self.hooks = None

    # ------------------------------------------------------------------
    # registration / allocation
    # ------------------------------------------------------------------
    def add_model(self, name: str, cfg: ModelConfig, w_tree: Dict) -> None:
        """Register a cold model: pack its host master slabs + build the
        static view.  No device memory is touched."""
        view = build_view(name, cfg, w_tree, slab_bytes=self.slab_bytes)
        self.views[name] = view
        self.host_slabs[name] = pack_slabs(view, w_tree)

    def finalize(self, slot_budget: Optional[int] = None, *,
                 allocate: bool = True) -> None:
        """Fix the budget and (optionally) allocate the device arena.

        Default budget = every registered model fully resident — callers
        shrink it to force demand paging of cold models.
        """
        if slot_budget is None:
            slot_budget = max(
                sum(v.total_slabs for v in self.views.values()), 1)
        self.slot_budget = slot_budget
        self.free_list = list(range(slot_budget - 1, -1, -1))
        if allocate:
            self.arena = jnp.zeros(
                (slot_budget, self.slab_rows, ARENA_LANES), ARENA_WORD,
                device=self.device)

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------
    @property
    def free_slabs(self) -> int:
        return len(self.free_list)

    @property
    def resident_slabs(self) -> int:
        return self.slot_budget - len(self.free_list)

    def device_bytes(self) -> int:
        """Device FFN bytes: fixed by ``slot_budget`` alone."""
        return self.slot_budget * self.slab_bytes

    def host_master_bytes(self) -> int:
        return sum(s.nbytes for s in self.host_slabs.values())

    def is_resident(self, name: str) -> bool:
        return name in self.residency

    def pinned_slabs(self) -> int:
        """Slabs the elastic rebalancer can never reclaim: every pinned
        model's full footprint, resident or promised (an admitted cold
        model's pin is taken BEFORE its activation maps slots)."""
        return sum(self.views[n].total_slabs
                   for n in self.pins if n in self.views)

    def min_slot_budget(self) -> int:
        """Smallest budget a shrink may target: pinned footprints, and
        never below the largest registered model (a smaller arena would
        make that model permanently unserviceable — admission fails
        loudly on it)."""
        largest = max((v.total_slabs for v in self.views.values()),
                      default=1)
        return max(self.pinned_slabs(), largest, 1)

    def residency_by_model(self) -> Dict[str, int]:
        """Resident slab count per model — the slab-timeline source for
        flight-recorder snapshots and Perfetto counter tracks."""
        return {name: int(res.slots.size)
                for name, res in self.residency.items()}

    def utilization(self) -> Dict[str, float]:
        return {
            "slot_budget": self.slot_budget,
            "resident_slabs": self.resident_slabs,
            "free_slabs": self.free_slabs,
            "resident_models": len(self.residency),
            "activations": self.activations,
            "evictions": self.evictions,
            "layer_uploads": self.layer_uploads,
            "device_bytes": self.device_bytes(),
            "occupancy": self.resident_slabs / max(self.slot_budget, 1),
            "pinned_slabs": self.pinned_slabs(),
            "resizes": self.resizes,
        }

    # ------------------------------------------------------------------
    # slow path: activate / evict (atomic)
    # ------------------------------------------------------------------
    def _next_rev(self) -> int:
        self._rev_counter += 1
        return self._rev_counter

    def touch(self, name: str) -> None:
        if name in self.residency:
            self._clock += 1
            self.residency[name].last_used = self._clock

    def pin(self, name: str) -> None:
        self.pins[name] = self.pins.get(name, 0) + 1

    def unpin(self, name: str) -> None:
        n = self.pins.get(name, 0) - 1
        if n <= 0:
            self.pins.pop(name, None)
        else:
            self.pins[name] = n
        self.touch(name)

    def _take(self, n: int) -> List[int]:
        """Atomically pop ``n`` slabs: raises BEFORE mutating any state."""
        if n > len(self.free_list):
            raise OutOfSlabsError(
                f"need {n} slabs, {len(self.free_list)} free "
                f"(budget {self.slot_budget})")
        return [self.free_list.pop() for _ in range(n)]

    def _plan_evictions(self, need: int) -> List[str]:
        """LRU victims whose slabs make ``need`` fit — WITHOUT evicting.

        Raises ``OutOfSlabsError`` (no state change) when even evicting
        every unpinned idle model cannot free enough.
        """
        if need <= self.free_slabs:
            return []
        victims: List[str] = []
        would_free = self.free_slabs
        idle = sorted((r.last_used, n) for n, r in self.residency.items()
                      if n not in self.pins)
        for _, n in idle:
            victims.append(n)
            would_free += self.views[n].total_slabs
            if would_free >= need:
                return victims
        raise OutOfSlabsError(
            f"activation needs {need} slabs; only {would_free} reachable "
            f"after evicting all idle models (budget {self.slot_budget}, "
            f"pinned: {sorted(self.pins)})")

    def activate(self, name: str, *, upload: bool = True) -> Residency:
        """Make a cold model resident: map its slabs (evicting idle LRU
        models under pressure) and optionally upload every layer.

        Atomic: eviction victims are planned BEFORE any state changes and
        the slab count is taken in one ``_take``, so ``OutOfSlabsError``
        leaves the free list, every residency and all pins untouched.
        ``upload=False`` maps slots only — the pipeline's per-layer
        prefetch (or ``ensure_model_uploaded``) streams the bytes in.
        """
        if name in self.residency:
            self.touch(name)
            return self.residency[name]
        view = self.views[name]
        for victim in self._plan_evictions(view.total_slabs):
            self.evict(victim)
        slabs = self._take(view.total_slabs)
        res = Residency(
            slots=np.asarray(slabs, np.int32).reshape(
                view.n_layers, view.slabs_per_layer),
            uploaded=np.zeros(view.n_layers, bool),
            rev=self._next_rev())
        self.residency[name] = res
        self.activations += 1
        self.touch(name)
        if self.hooks is not None:
            self.hooks.arena_activate(name, view.total_slabs)
        if upload:
            self.ensure_model_uploaded(name)
        return res

    def evict(self, name: str) -> None:
        """Return an idle model's slabs to the free list.

        Master bytes live on the host, so eviction copies nothing back;
        re-activation reproduces the identical weights.
        """
        if name in self.pins:
            raise ValueError(f"cannot evict pinned model {name!r}")
        res = self.residency.pop(name)
        self.free_list.extend(int(s) for s in res.slots.ravel())
        self._table_cache.pop(name, None)
        self.evictions += 1
        if self.hooks is not None:
            self.hooks.arena_evict(name, res.slots.size)

    # ------------------------------------------------------------------
    # elastic boundary: live resize (DESIGN.md §8)
    # ------------------------------------------------------------------
    def resize(self, new_budget: int) -> Dict[str, int]:
        """Grow or shrink ``slot_budget`` at a step boundary.

        Growing copies the arena into the prefix of a larger buffer and
        prepends fresh ids to the (pop-from-the-end) free list, so low
        slabs keep being preferred deterministically.  Shrinking evicts
        idle unpinned models LRU until the survivors fit, then compacts
        every surviving residency into the retained prefix with ONE
        jitted gather and bumps each residency's rev (slot-table caches
        refresh; host masters are untouched, so the moved bytes stay
        bit-exact).  Raises ``OutOfSlabsError`` when pinned residents
        alone exceed the new budget — no state changes beyond completed
        evictions.
        """
        new_budget = int(new_budget)
        check(new_budget >= 1, f"slot budget must be >= 1, got {new_budget}")
        old_budget = self.slot_budget
        if new_budget == old_budget:
            return {"slot_budget": old_budget, "evicted": 0, "moved": 0}
        if new_budget > old_budget:
            if self.arena is not None:
                pad = jnp.zeros(
                    (new_budget - old_budget,) + self.arena.shape[1:],
                    self.arena.dtype, device=self.arena.sharding)
                self.arena = jnp.concatenate([self.arena, pad], axis=0)
            self.free_list = list(range(new_budget - 1, old_budget - 1, -1)) \
                + self.free_list
            self.slot_budget = new_budget
            self.resizes += 1
            if self.hooks is not None:
                self.hooks.arena_resize(old_budget, new_budget, 0, 0)
            return {"slot_budget": new_budget, "evicted": 0, "moved": 0}

        # --- shrink: evict idle LRU until the survivors fit -------------
        evicted = 0
        while self.resident_slabs > new_budget:
            idle = sorted((r.last_used, n) for n, r in self.residency.items()
                          if n not in self.pins)
            if not idle:
                raise OutOfSlabsError(
                    f"cannot shrink arena to {new_budget} slabs: "
                    f"{self.resident_slabs} resident and every resident "
                    f"model is pinned (pinned: {sorted(self.pins)})")
            self.evict(idle[0][1])
            evicted += 1
        # compact survivors into [0, new_budget) in deterministic order
        old_ids: List[int] = []
        for name in sorted(self.residency):
            old_ids.extend(int(s) for s in self.residency[name].slots.ravel())
        k = len(old_ids)
        perm = np.zeros(new_budget, np.int32)
        perm[:k] = np.asarray(old_ids, np.int32) if k else []
        if self.arena is not None:
            self.arena = _arena_gather(self.arena, jnp.asarray(perm))
        next_id = 0
        for name in sorted(self.residency):
            res = self.residency[name]
            n = res.slots.size
            res.slots = np.arange(next_id, next_id + n,
                                  dtype=np.int32).reshape(res.slots.shape)
            res.rev = self._next_rev()
            next_id += n
        self.free_list = list(range(new_budget - 1, k - 1, -1))
        self.slot_budget = new_budget
        self.resizes += 1
        if self.hooks is not None:
            self.hooks.arena_resize(old_budget, new_budget, evicted, k)
        return {"slot_budget": new_budget, "evicted": evicted, "moved": k}

    # ------------------------------------------------------------------
    # uploads (slow path, but overlappable with compute)
    # ------------------------------------------------------------------
    def _upload_layers(self, name: str, layers: np.ndarray) -> None:
        res = self.residency[name]
        if self.arena is not None:
            ids = res.slots[layers].reshape(-1)
            rows = self.host_slabs[name][layers].reshape(
                (-1,) + self.arena.shape[1:])
            self.arena = _arena_scatter(self.arena, jnp.asarray(ids),
                                        jnp.asarray(rows))
        res.uploaded[layers] = True
        self.layer_uploads += len(layers)
        if self.hooks is not None:
            self.hooks.arena_upload(
                name, len(layers) * self.views[name].slabs_per_layer)

    def prefetch_layer(self, name: str, layer: int) -> None:
        """Issue (async) the upload of one layer's slabs; no-op if already
        uploaded or out of range — the pipeline calls this for layer L+1
        while layer L's attention is in flight."""
        res = self.residency.get(name)
        if res is None or layer < 0 or layer >= len(res.uploaded) \
                or res.uploaded[layer]:
            return
        self._upload_layers(name, np.asarray([layer]))

    def ensure_model_uploaded(self, name: str) -> None:
        """Upload every not-yet-streamed layer (one scatter)."""
        res = self.residency[name]
        missing = np.flatnonzero(~res.uploaded)
        if len(missing):
            self._upload_layers(name, missing)

    def acquire(self, name: str) -> Tuple[jax.Array, jax.Array]:
        """(arena buffer, slot table) with ``name`` resident and uploaded —
        the one residency protocol every decode step goes through.

        ``activate`` is a host-side no-op (LRU touch) when the model is
        already resident; a cold call activates it on first use.
        """
        self.activate(name)
        self.ensure_model_uploaded(name)
        return self.arena, self.slot_table(name)

    # ------------------------------------------------------------------
    # fast path: device slot tables
    # ------------------------------------------------------------------
    def slot_table(self, name: str) -> jax.Array:
        """[n_layers, slabs_per_layer] int32 device table, cached per
        activation rev (re-activation remaps -> re-upload)."""
        res = self.residency.get(name)
        if res is None:
            raise KeyError(f"model {name!r} is not resident in the arena")
        entry = self._table_cache.get(name)
        if entry is not None and entry["rev"] == res.rev:
            return entry["dev"]
        dev = jnp.asarray(res.slots)
        if self.device is not None:
            dev = jax.device_put(dev, self.device)
        self._table_cache[name] = {"rev": res.rev, "dev": dev}
        return dev
