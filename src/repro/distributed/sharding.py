"""Logical-axis sharding rules (MaxText-style) for every architecture.

Model code annotates activations with *logical* axes (``constrain(h,
"batch", "seq", "embed")``) and parameter leaves carry name-derived logical
specs. A :class:`AxisRules` binding maps logical axes onto mesh axes with
divisibility checks — non-divisible dims silently fall back to replication,
which is what makes one rule-set serve all 10 architectures (36-head
starcoder2 simply replicates heads and keeps the flat-feature TP sharding).

When no rules are active (unit tests, CPU smoke runs) ``constrain`` is the
identity, so the model code is mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

LogicalAxes = Union[None, str, Tuple[str, ...]]

# Default logical -> mesh-axis mapping. "fsdp" shards parameter rows over the
# data axis (ZeRO-3 style); "tp"/"heads"/"vocab"/"ff" shard over model.
DEFAULT_RULES: Dict[str, LogicalAxes] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "ff": "model",
    "tp": "model",
    "experts": "model",
    "fsdp": "data",
    # decode KV-cache time dimension: sharding it over 'model' divides the
    # dominant decode memory by the TP degree regardless of KV-head count
    # (GQA head counts rarely divide 16; the 32k time axis always does).
    # GSPMD turns the cache update into a masked per-shard write and the
    # softmax over time into tiny (B,H)-scale cross-shard reductions.
    "kv": "model",
    # ZeRO-3 output-dim sharding (perf iteration #2g): weight matrices shard
    # their OUTPUT dim over (data, model) jointly, leaving contraction dims
    # whole — GSPMD then all-gathers weight shards (weight-sized traffic)
    # instead of partial-summing activation-sized tensors. Enabled per arch
    # via rules override {"zero3": True}.
    "fsdp_tp": ("data", "model"),
    "zero3": False,
}

# 2-D weight leaves that flip to (None, "fsdp_tp") under zero3.
ZERO3_LEAVES = {
    "wq", "wk", "wv", "wo", "wg", "wu", "wd", "w_in", "w_out",
    "wy", "wx", "wr", "wi",
}


@dataclasses.dataclass
class AxisRules:
    mesh: Mesh
    rules: Dict[str, LogicalAxes] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        merged = dict(DEFAULT_RULES)
        merged.update(self.rules)
        self.rules = merged

    def mesh_axes(self, logical: Optional[str]) -> Tuple[str, ...]:
        if logical is None:
            return ()
        ax = self.rules.get(logical)
        if ax is None:
            return ()
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        return tuple(a for a in axes if a in self.mesh.shape)

    def axis_size(self, axes: Tuple[str, ...]) -> int:
        return int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1

    def spec_for(self, shape: Sequence[int], logical: Sequence[Optional[str]]) -> P:
        """Resolve logical dims to a PartitionSpec with divisibility checks
        and no mesh-axis reuse."""
        used: set = set()
        out = []
        for dim, name in zip(shape, logical):
            axes = self.mesh_axes(name)
            if axes and not (set(axes) & used) and dim % self.axis_size(axes) == 0:
                out.append(axes if len(axes) > 1 else axes[0])
                used.update(axes)
            else:
                out.append(None)
        return P(*out)

    def sharding_for(self, shape: Sequence[int], logical: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(shape, logical))


_ACTIVE: Optional[AxisRules] = None


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = rules
    try:
        yield rules
    finally:
        _ACTIVE = prev


def active_rules() -> Optional[AxisRules]:
    return _ACTIVE


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate activation sharding; identity when no rules are active."""
    r = _ACTIVE
    if r is None:
        return x
    if x.ndim != len(logical):
        return x
    spec = r.spec_for(x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter / cache / batch specs by leaf name
# ---------------------------------------------------------------------------

# leaf-name -> logical axes of its *unstacked* dims
PARAM_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "tok": ("vocab", "fsdp"),
    "w": ("fsdp", "vocab"),          # untied head
    "final_norm": (None,),
    "ln": (None,), "ln1": (None,), "ln2": (None,),
    "wq": ("fsdp", "tp"), "wk": ("fsdp", "tp"), "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",),
    "wg": ("fsdp", "tp"), "wu": ("fsdp", "tp"), "wd": ("tp", "fsdp"),
    "router": ("fsdp", None),
    "ewg": ("experts", "fsdp", None),
    "ewu": ("experts", "fsdp", None),
    "ewd": ("experts", None, "fsdp"),
    "w_in": ("fsdp", "tp"),
    "conv_w": ("tp", None), "conv_b": ("tp",),
    "w_x": ("tp", None), "w_dt": (None, "tp"), "b_dt": ("tp",),
    "a_log": ("tp", None), "d_skip": ("tp",),
    "w_out": ("tp", "fsdp"),
    "wy": ("fsdp", "tp"), "wx": ("fsdp", "tp"),
    "wr": ("fsdp", "tp"), "wi": ("fsdp", "tp"),
    "br": ("tp",), "bi": ("tp",), "lam": ("tp",),
}

CACHE_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "k": ("batch", "kv", "heads", None),   # heads dropped if 'model' taken by kv
    "v": ("batch", "kv", "heads", None),
    "k_scale": ("batch", "kv", "heads", None),   # int8-KV scales (perf #3)
    "v_scale": ("batch", "kv", "heads", None),
    "conv": ("batch", None, "tp"),
    "h": ("batch", "tp", None),      # ssm state (B, Din, N); rec uses 2 dims
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
        if hasattr(entry, "name"):
            return str(entry.name)
    return ""


def param_specs(shapes_tree: Any, rules: AxisRules) -> Any:
    """Tree of NamedSharding for a parameter pytree (stacked segment leaves
    get a leading replicated dim)."""

    zero3 = bool(rules.rules.get("zero3"))

    def spec(path, leaf):
        name = _leaf_name(path)
        logical = PARAM_LOGICAL.get(name)
        shape = tuple(leaf.shape)
        if logical is None:
            return NamedSharding(rules.mesh, P())
        if zero3 and name in ZERO3_LEAVES and len(logical) == 2:
            logical = (None, "fsdp_tp")
        if len(shape) == len(logical) + 1:       # stacked scan dim
            logical = (None, *logical)
        elif len(shape) != len(logical):
            return NamedSharding(rules.mesh, P())
        return rules.sharding_for(shape, logical)

    return jax.tree_util.tree_map_with_path(spec, shapes_tree)


def cache_specs(cache_tree: Any, rules: AxisRules) -> Any:
    def spec(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        if name in ("pos",) or leaf.ndim == 0:
            return NamedSharding(rules.mesh, P())
        if name == "ring":
            return NamedSharding(rules.mesh, P())
        logical = CACHE_LOGICAL.get(name)
        if logical is None:
            return NamedSharding(rules.mesh, P())
        if name == "h" and len(shape) == 3:       # stacked rec state (n,B,Dr)
            logical = ("batch", "tp")
        if len(shape) == len(logical) + 1:
            logical = (None, *logical)
        elif len(shape) != len(logical):
            return NamedSharding(rules.mesh, P())
        return rules.sharding_for(shape, logical)

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def batch_specs(batch_tree: Any, rules: AxisRules) -> Any:
    def spec(path, leaf):
        logical = ("batch",) + (None,) * (leaf.ndim - 1)
        return rules.sharding_for(tuple(leaf.shape), logical)

    return jax.tree_util.tree_map_with_path(spec, batch_tree)


def replicated(tree: Any, rules: AxisRules) -> Any:
    return jax.tree.map(lambda _: NamedSharding(rules.mesh, P()), tree)


def constrain_params(tree: Any) -> Any:
    """Pin a parameter/optimizer pytree to its canonical :func:`param_specs`
    layout under the active rules; identity when no rules are active.

    Applied to train-step *outputs*: without an output pin, XLA is free to
    pick a different layout for an output leaf than ``param_specs`` assigned
    the matching input (e.g. replicating a small norm vector on the way in
    but sharding it over ``model`` on the way out). The next call of a step
    function jitted with explicit ``in_shardings`` then rejects the
    now-mismatched committed argument instead of resharding it — which is
    exactly what broke step 2 of the elastic re-mesh restart path.
    """
    r = _ACTIVE
    if r is None:
        return tree
    return jax.tree.map(
        jax.lax.with_sharding_constraint, tree, param_specs(tree, r)
    )


# ---------------------------------------------------------------------------
# Replica-plane device placement (see repro/serving/replica.py)
# ---------------------------------------------------------------------------


def replica_devices(replicas: int) -> list:
    """Device assignment for an R-replica serving plane.

    With more than one local device, replicas round-robin over the device
    list: under ``ReplicaSet(placement="overlapped")`` each
    :class:`~repro.serving.replica.ReplicaWorker`'s router pins its wave
    dispatches to its assigned device (``jax.device_put`` of the padded
    wave tables + the per-device jit executable), so R wave programs from
    one drive cycle run concurrently. On a single device the assignment
    is ``None`` everywhere — placement is a no-op and the ReplicaSet
    instead *fuses* same-budget replica waves along the batch axis, the
    single-device degenerate of sharding the wave program's (T, B) tables
    over a batch-axis device slice.
    """
    devs = jax.devices()
    if len(devs) <= 1:
        return [None] * int(replicas)
    return [devs[i % len(devs)] for i in range(int(replicas))]


def replica_mesh(replicas: int) -> Optional[Mesh]:
    """1-axis ``("replica",)`` mesh over ``min(replicas, local devices)``
    devices — the binding a ``jax.shard_map`` lowering of the fused wave
    dispatch would shard the batch axis over. None on a single device
    (nothing to shard; the fused batch-axis dispatch covers it)."""
    devs = jax.devices()
    n = min(int(replicas), len(devs))
    if n <= 1:
        return None
    return make_mesh((n,), ("replica",), devices=devs[:n])
