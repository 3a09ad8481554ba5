"""Logical-axis sharding rules (DP / FSDP / TP / SP / EP / pod hierarchy).

Models annotate activations with *logical* axis names via ``constrain`` and
parameters get specs inferred from their tree paths via ``infer_param_specs``.
A ``ShardingRules`` object maps logical names onto physical mesh axes with
per-dimension divisibility fallback (a logical axis that does not divide the
dim is silently replicated — e.g. 8 q-heads on a 16-way model axis).

The rules are a module-level context so model code stays mesh-agnostic; the
launcher (or a test) activates rules around tracing:

    with sharding.use_rules(rules):
        lowered = jax.jit(train_step, ...).lower(...)

Default schemes:
  - single-pod (data, model):  batch/seq -> data (DP/SP), heads/mlp/vocab/
    experts -> model (TP/EP), param d_model dim -> data (FSDP/ZeRO-3).
  - multi-pod (pod, data, model): batch -> (pod, data) so gradient
    all-reduce is hierarchical, while FSDP param gathers stay *intra-pod*
    (the pod axis never appears in param specs — cross-pod links only carry
    gradient reductions, the distributed-optimization trick that makes
    1000+-node scaling viable).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    # logical axis name -> tuple of mesh axis names (or None = replicate)
    rules: dict

    def mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        axes = self.rules.get(logical)
        if axes is None:
            return None
        return tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)

    def axis_size(self, axes) -> int:
        return math.prod(self.mesh.shape[a] for a in axes)

    def spec_for_shape(self, shape, logical_axes) -> P:
        """PartitionSpec with divisibility fallback per dimension."""
        assert len(shape) == len(logical_axes), (shape, logical_axes)
        out, used = [], set()
        for dim, name in zip(shape, logical_axes):
            axes = self.mesh_axes(name)
            if axes is None or any(a in used for a in axes) \
                    or dim % self.axis_size(axes) != 0:
                out.append(None)
            else:
                out.append(axes[0] if len(axes) == 1 else tuple(axes))
                used.update(axes)
        return P(*out)

    def sharding_for_shape(self, shape, logical_axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for_shape(shape, logical_axes))


# ---------------------------------------------------------------------------
# Default rule tables
# ---------------------------------------------------------------------------


def default_rules(mesh: Mesh, *, seq_sharded: bool = False,
                  serve_params: bool = False) -> ShardingRules:
    """Standard scheme; ``seq_sharded`` turns on sequence parallelism
    (long-context prefill / batch-1 shapes shard seq over the data axis).
    ``serve_params`` switches params to TP-only (replicated over data):
    decode steps then read weights locally instead of all-gathering the
    FSDP shards every step (see EXPERIMENTS.md SSPerf cell B)."""
    multi_pod = "pod" in mesh.shape
    data_axes = ("pod", "data") if multi_pod else ("data",)
    rules = {
        # activations
        "batch": data_axes,
        "seq": data_axes if seq_sharded else None,
        "act_embed": None,          # d_model stays unsharded in activations
        "act_heads": ("model",),
        "act_kv_heads": ("model",),
        "act_mlp": ("model",),
        "act_vocab": ("model",),
        "act_experts": ("model",),
        # parameters
        "embed": None if serve_params else ("data",),  # FSDP dim
        "heads": ("model",),         # TP
        "kv_heads": ("model",),
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),       # EP
        "head_dim": None,
        "conv": None,
        "rnn": ("model",),           # RG-LRU / RWKV channel dim
        "lora": None,
    }
    return ShardingRules(mesh=mesh, rules=rules)


# ---------------------------------------------------------------------------
# Search-plane rules (the distributed HNTL data plane)
# ---------------------------------------------------------------------------


def search_plane_rules(mesh: Mesh, *,
                       grain_axis: str = "model") -> ShardingRules:
    """Logical-axis rules for the grain-sharded search plane.

    The index is partitioned grain-wise: grain panels and routing centroids
    ("grains") and the permuted raw tier + id table ("rows") split along
    ``grain_axis`` (model by default — the index plays the role of
    weights).  Queries are not placed through these rules: they enter as
    plain arrays and `planner.search_stacked_sharded`'s ``batch_axis``
    controls their (optional) data-axis sharding.  An absent mesh axis
    replicates via the usual divisibility/fallback path in
    :meth:`ShardingRules.spec_for_shape`.

    Residency: the sharded plane keeps EVERY shard fully device-resident —
    aggregate HBM scales with the mesh, which is the whole point of
    sharding.  Tiered residency (``VectorStore(device_budget=...)``, the
    disk-backed cold tier) is the single-device answer to the same
    capacity problem and the store rejects combining the two; a future
    per-shard residency mode would give each shard its own budget over the
    grain range :func:`shard_hot_sets` describes.
    """
    on_mesh = grain_axis in mesh.shape
    rules = {
        "grains": (grain_axis,) if on_mesh else None,
        "rows": (grain_axis,) if on_mesh else None,
    }
    return ShardingRules(mesh=mesh, rules=rules)


def search_plane_specs(tree, rules: ShardingRules):
    """PartitionSpec pytree for a search-plane pytree (StackedSegments /
    ShardedStackedSegments / HNTLIndex), from the per-field logical axes
    declared in ``core.types.SEARCH_PLANE_AXES`` (dim 0; trailing dims
    replicated)."""
    from ..core.types import SEARCH_PLANE_AXES  # deferred: no import cycle

    def leaf_spec(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        logical = SEARCH_PLANE_AXES.get(keys[-1]) if keys else None
        axes = (logical,) + (None,) * (leaf.ndim - 1) if leaf.ndim else ()
        return rules.spec_for_shape(leaf.shape, axes)
    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


def shard_search_plane(tree, rules: ShardingRules, *, reuse=None):
    """Place a search-plane pytree on the mesh, each leaf sharded per
    :func:`search_plane_specs` (host numpy leaves go straight to their
    shards — no replicated staging copy).

    ``reuse``: optional ``{field: already-placed leaf}`` — the grain
    maintenance delta path.  A refit-only maintenance epoch rewrites grain
    panels but keeps row ownership (and hence the row permutation) intact,
    so the store passes the previous plane's placed ``raw`` tier and
    ``gid_of_row`` table here; those leaves are swapped in *after*
    placement and never re-staged — only the changed grain rows move to
    the mesh.  Callers are responsible for proving the reused leaves'
    host content is unchanged (see ``store._reusable_row_leaves``).
    """
    reuse = {k: v for k, v in (reuse or {}).items() if v is not None}
    if reuse:
        # strip reused leaves before placement (None = empty pytree node),
        # so zero bytes of theirs are transferred
        stripped = dict.fromkeys(reuse)
        tree = dataclasses.replace(
            tree, gid_of_row=stripped.get("gid_of_row",
                                          tree.gid_of_row),
            index=dataclasses.replace(tree.index,
                                      raw=stripped.get("raw",
                                                       tree.index.raw))
            if "raw" in stripped else tree.index)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(rules.mesh, s),
        search_plane_specs(tree, rules),
        is_leaf=lambda x: isinstance(x, P))
    placed = jax.device_put(tree, shardings)
    if reuse:
        if "raw" in reuse:
            placed = dataclasses.replace(
                placed, index=dataclasses.replace(placed.index,
                                                  raw=reuse["raw"]))
        if "gid_of_row" in reuse:
            placed = dataclasses.replace(placed,
                                         gid_of_row=reuse["gid_of_row"])
    return placed


def shard_plane_field(arr, rules: ShardingRules, field: str, *,
                      dim: int = 0):
    """Place ONE search-plane leaf on the mesh per its declared logical axis.

    The mutation path uses this to swap the per-epoch ``live`` bitmap into
    an already-placed plane (`dataclasses.replace`) without re-staging any
    other leaf: a delete/upsert moves G*cap bools, not the index.

    ``dim``: which dimension carries the logical axis (default 0, like the
    plane leaves).  The multi-tenant serving plane passes dim=1 for its
    [T, G, cap] per-tenant visibility stack — the grain axis must line up
    with the sharded panels while the tenant axis stays replicated.
    """
    from ..core.types import SEARCH_PLANE_AXES  # deferred: no import cycle
    logical = SEARCH_PLANE_AXES.get(field)
    axes = tuple(logical if i == dim else None for i in range(arr.ndim))
    spec = rules.spec_for_shape(arr.shape, axes)
    return jax.device_put(arr, NamedSharding(rules.mesh, spec))


def shard_hot_sets(hot_slots, n_grains: int, n_shards: int):
    """Split a global hot-grain set into per-shard local hot sets.

    The grain-sharded plane partitions grains into ``n_shards`` contiguous
    ranges of ``n_grains // n_shards`` (the dim-0 block partition
    ``NamedSharding`` applies).  Given the tiered residency manager's
    global hot set (``TieredPlane.hot_slots``), return a list of per-shard
    arrays of *local* grain indices — what each shard would keep resident
    under a per-shard device budget.  Today this is an accounting helper
    (the sharded plane is all-resident; see :func:`search_plane_rules`);
    it pins down the partition arithmetic a per-shard residency mode would
    inherit.
    """
    if n_shards <= 0 or n_grains % n_shards != 0:
        raise ValueError(
            f"n_shards must divide n_grains: {n_shards} vs {n_grains}")
    import numpy as np
    hot = np.unique(np.asarray(hot_slots, np.int64))
    if hot.size and (hot[0] < 0 or hot[-1] >= n_grains):
        raise ValueError(f"hot slot out of range [0, {n_grains})")
    per = n_grains // n_shards
    return [hot[(hot // per) == s] - s * per for s in range(n_shards)]


# ---------------------------------------------------------------------------
# Active-rules context (keeps model code mesh-agnostic)
# ---------------------------------------------------------------------------

_ACTIVE: list = []


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    _ACTIVE.append(rules)
    try:
        yield rules
    finally:
        _ACTIVE.pop()


def active_rules() -> Optional[ShardingRules]:
    return _ACTIVE[-1] if _ACTIVE else None


def constrain(x: jax.Array, *logical_axes):
    """Annotate an activation with logical axes; no-op without active rules."""
    rules = active_rules()
    if rules is None:
        return x
    spec = rules.spec_for_shape(x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter spec inference (path + shape conventions)
# ---------------------------------------------------------------------------

# last-key -> logical axes of the *trailing* dims (leading stack dims -> None)
_PARAM_AXES = {
    # attention
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "bq": ("heads", "head_dim"),
    "bk": ("kv_heads", "head_dim"),
    "bv": ("kv_heads", "head_dim"),
    # dense mlp
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    # moe
    "router": ("embed", "experts"),
    "e_gate": ("experts", "embed", "mlp"),
    "e_up": ("experts", "embed", "mlp"),
    "e_down": ("experts", "mlp", "embed"),
    # embeddings
    "embedding": ("vocab", "embed"),
    "lm_head": ("vocab", "embed"),
    "pos_embedding": (None, "embed"),
    # rg-lru / rwkv
    "w_in": ("embed", "rnn"),
    "w_gate_rnn": ("embed", "rnn"),
    "w_out": ("rnn", "embed"),
    "conv_w": ("conv", "rnn"),
    "lambda_p": ("rnn",),
    "gate_w": ("rnn", None),
    "gate_b": ("rnn",),
    "tm_w": ("embed", "mlp"),
    "cm_w": ("embed", "mlp"),
    "cm_w2": ("mlp", "embed"),
    "lora_a": ("embed", "lora"),
    "lora_b": ("lora", "embed"),
}


def _leaf_logical_axes(path, shape):
    keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    last = keys[-1]
    axes = _PARAM_AXES.get(last)
    if axes is None:
        # norm scales / biases / scalars: replicate
        return (None,) * len(shape)
    if len(axes) < len(shape):          # leading layer-stack dims
        return (None,) * (len(shape) - len(axes)) + tuple(axes)
    if len(axes) > len(shape):          # squeezed trailing dims
        return tuple(axes[-len(shape):]) if len(shape) else ()
    return tuple(axes)


def infer_param_specs(params, rules: ShardingRules):
    """Pytree of PartitionSpec matching ``params`` (works on ShapeDtypeStruct
    trees too — used by the AOT dry-run)."""
    def leaf_spec(path, leaf):
        axes = _leaf_logical_axes(path, leaf.shape)
        return rules.spec_for_shape(leaf.shape, axes)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def infer_param_shardings(params, rules: ShardingRules):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(rules.mesh, s), infer_param_specs(params, rules),
        is_leaf=lambda x: isinstance(x, P))
