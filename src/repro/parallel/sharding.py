"""Logical-axis sharding rules with divisibility fallback.

The same model code must shard correctly for every assigned architecture on
the mandated production meshes — ``(data=16, model=16)`` single-pod and
``(pod=2, data=16, model=16)`` multi-pod — even when a tensor dimension is
not divisible by a mesh axis (e.g. MQA kv_heads=1, Mixtral's 8 experts vs a
16-way model axis, ``long_500k``'s global_batch=1).

We therefore use MaxText-style *logical axis rules*: every tensor dimension
is annotated with a logical name ("batch", "embed", "heads", ...), and a
rule table maps each name to an ordered list of mesh-axis candidates.  Spec
resolution walks dimensions left to right, picking the first candidate whose
mesh size divides the dimension and whose axes are not already used by this
tensor; otherwise the dimension is replicated.  This gives automatic,
documented fallbacks instead of lowering errors.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AxisCandidates = Sequence[Tuple[str, ...]]
Rules = Dict[str, AxisCandidates]


# ---------------------------------------------------------------------------
# Default rule table for the production meshes.
#
# Axis roles:
#   pod    — cross-pod data parallelism (the paper's two-pod spine hop)
#   data   — intra-pod data parallelism + FSDP weight/optimizer sharding
#   expert — expert parallelism for MoE (routed weights + dispatched
#            tokens; acts as extra data parallelism for dense weights)
#   model  — tensor parallelism (heads / mlp / vocab / experts)
#
# Candidates are tried in order; each entry is a tuple of mesh axes that
# shard the dimension jointly (e.g. batch over pod AND data).
#
# Public access goes through ``repro.parallel.plan`` (``default_rules()``
# or ``ParallelPlan.rules``); the legacy ``DEFAULT_RULES`` name is a
# module-``__getattr__`` deprecation shim over this table.
_DEFAULT_RULES: Rules = {
    # activations
    # the expert axis joins the batch shard on EP meshes (EP-as-DP for
    # activations outside the routed FFN); dropped where absent
    "batch":        (("pod", "data", "expert"), ("pod", "data"),
                     ("data", "expert"), ("data",), ("pod",)),
    "act_seq":      (("model",),),            # sequence parallel regions
    "act_embed":    (),                       # replicated within shard
    "act_heads":    (("model",),),
    "act_mlp":      (("model",),),
    "act_exp":      (("expert",), ("model",)),
    # weights (FSDP over data; TP over model)
    "vocab":        (("model",),),
    "embed":        (("data",), ("model",)),
    "mlp":          (("model",), ("data",)),
    "heads":        (("model",),),
    # kv_heads replicate over `model` when indivisible (Megatron MQA style);
    # sharding head_dim instead forces resharding between q·k and p·v dots
    # (measured: involuntary-remat copies + 29 GB temps on qwen3 train_4k).
    "kv_heads":     (("model",),),
    "head_dim":     (),
    "qkv_embed":    (("data",),),             # embed dim of attention weights
    # experts prefer the dedicated EP axis (Mixtral's 8 experts on a
    # 16-way cell → ep=8 with TP-on-d_ff via `mlp`); the model/data
    # candidates are the dense-folded fallback on EP-less meshes
    "experts":      (("expert",), ("model",), ("data",)),
    "ssm_heads":    (("model",),),
    "ssm_state":    (),
    "conv_width":   (),
    "layers":       (),                       # scan dim, never sharded
    "norm":         (),
    # kv cache
    "cache_batch":  (("pod", "data"), ("data",)),
    "cache_seq":    (("data",), ("pod", "data")),
    "cache_kv":     (("model",),),
    "cache_kv_dim": (),
    # misc
    "frontend":     (),
}


# Rule overlays, applied by perf variants (see EXPERIMENTS.md §Perf).
def with_overrides(base: Rules, **overrides: AxisCandidates) -> Rules:
    out = dict(base)
    out.update(overrides)
    return out


def __getattr__(name: str):
    if name == "DEFAULT_RULES":
        import warnings
        warnings.warn(
            "repro.parallel.sharding.DEFAULT_RULES is deprecated; use "
            "repro.parallel.plan (plan.rules / default_rules()) — the "
            "ParallelPlan API carries the rule table with the mesh",
            DeprecationWarning, stacklevel=2)
        return _DEFAULT_RULES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# Ambient mesh + rules context (threaded through with_logical_constraint).
def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """Build a device mesh — the one place this repository does.

    Every axis is ``Auto``: shardings are laid out by the compiler from
    ``constrain`` hints and the arrays' NamedShardings, never carried
    as types.  Without ``devices`` JAX orders all its devices for the
    physical topology; with them, the first ``prod(shape)`` keep their
    order (a pool's node grouping depends on it).
    """
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)
    arr = np.asarray(list(devices)[:math.prod(shape)]).reshape(shape)
    return Mesh(arr, tuple(axes), axis_types=auto)


class _ShardingCtx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Rules = _DEFAULT_RULES


_CTX = _ShardingCtx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    """Activate a mesh + rule table for ``logical_to_spec``/``constrain``."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = rules if rules is not None else _DEFAULT_RULES
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> Rules:
    return _CTX.rules


# ---------------------------------------------------------------------------
def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_to_spec(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
) -> P:
    """Resolve logical dimension names to a PartitionSpec.

    Greedy left-to-right first-fit with two constraints per tensor:
      (1) divisibility: the joint mesh size must divide the dim size,
      (2) exclusivity: a mesh axis may appear at most once per spec.
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if mesh is None:
        return P()
    assert len(logical) == len(shape), (logical, shape)
    used: set = set()
    out: List[Union[None, str, Tuple[str, ...]]] = []
    for name, dim in zip(logical, shape):
        picked = None
        if name is not None:
            for cand in rules.get(name, ()):  # ordered candidates
                cand = tuple(a for a in cand if a in mesh.shape)
                if not cand or any(a in used for a in cand):
                    continue
                if dim % _axis_size(mesh, cand) != 0:
                    continue
                picked = cand
                break
        if picked is None:
            out.append(None)
        else:
            used.update(picked)
            out.append(picked if len(picked) > 1 else picked[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
) -> Optional[NamedSharding]:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(logical, shape, mesh, rules))


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` via logical names; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = logical_to_spec(logical, x.shape, mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(tree, logical_tree, mesh: Optional[Mesh] = None,
                   rules: Optional[Rules] = None):
    """Map a pytree of ShapeDtypeStructs + a matching pytree of logical-axis
    tuples to NamedShardings (used for jit in_shardings/out_shardings)."""
    mesh = mesh or _CTX.mesh

    def one(x, names):
        return named_sharding(names, x.shape, mesh, rules)

    return jax.tree.map(one, tree, logical_tree,
                        is_leaf=lambda t: isinstance(t, tuple) and all(
                            isinstance(e, (str, type(None))) for e in t))


# ---------------------------------------------------------------------------
# Param metadata: models attach logical axes to every parameter via
# ``ParamSpec`` so the launcher can derive shardings without tracing twice.
class LogicalAxes(tuple):
    """A tuple of logical dim names attached to a param as pytree metadata."""
    __slots__ = ()


def spec_tree_for_params(param_shapes, logical_axes_tree, mesh=None, rules=None):
    def one(sds, names):
        return named_sharding(tuple(names), sds.shape, mesh, rules)
    return jax.tree.map(one, param_shapes, logical_axes_tree,
                        is_leaf=lambda t: isinstance(t, LogicalAxes))
