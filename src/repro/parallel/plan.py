"""Topology-aware parallelism planning — ONE entry point over mesh /
sharding / pipeline / fabric.

The paper's central engineering claim (§4.2, Table 10, C1) is that the
parallelism layout must follow the fabric: the rail-optimized two-pod
800 GbE leaf–spine makes the cross-pod spine hop the one narrow link, and
collectives are engineered around it.  Before this module that knowledge
was scattered over four uncoordinated APIs (``launch.mesh`` hard-coded
shapes, ``parallel.sharding`` owned rule tables, ``parallel.pipeline``
staged by hand, ``core.fabric``/``core.collectives`` modelled costs nobody
consulted at plan time).  ``ParallelPlan`` unifies them:

    plan = plan_parallelism(get_config("qwen3-32b"), chips=512)
    print(plan.scorecard)            # every candidate layout, scored
    mesh = plan.mesh()               # jax Mesh, pod boundary on the
                                     # slowest-varying axis
    shardings = plan.shardings(state, axes)   # logical-rule resolution
    with plan.activate():            # ambient mesh + rules for constrain()
        jax.jit(step)(...)

The auto-planner enumerates candidate ``(pod, data, model[, pipe])``
factorizations of the chip count, scores each with the fabric analytical
model (cross-pod spine bytes, per-rail NIC utilization, DCQCN throughput
collapse under incast — :mod:`repro.core.fabric`) plus the hierarchical
collective schedule of :mod:`repro.core.collectives`, and optionally
refines finalists with while-aware HLO cost analysis
(:mod:`repro.core.hlo_cost`) of the actually-lowered step.

Traffic model (documented invariants, per training step):

* DP gradients.  Grads per (model, pipe) shard are ``P/(model·pipe)``
  bytes (fp32 wire).  A *flat* ring all-reduce over the pod-spanning
  ``pod×data`` axis pushes ~``2·G`` per ring link and every DP ring
  crosses the spine on ``pods`` cut links → ``4·P_bytes`` total spine
  traffic.  The *hierarchical* schedule (reduce-scatter intra-rail →
  cross-pod all-reduce on ``1/data`` of the bytes → all-gather, exactly
  ``collectives.hierarchical_psum``) crosses the spine with pre-reduced
  data only: ``2·(pods-1)/pods · P_bytes`` (× the optional bf16/int8
  compression factor).
* Pipeline across pods.  Placing the ``pipe`` axis on the pod boundary
  replaces the spine's share of the gradient all-reduce with microbatch
  activation point-to-point: ``2 · tokens · d_model · act_bytes`` per
  cut — usually orders of magnitude below the gradient volume, the
  classic "pipeline over the slow domain" layout the planner can now
  discover instead of it being hand-coded.
* TP / EP / FSDP stay on intra-pod rails and are charged against per-NIC
  bandwidth (``FabricSpec.nic_bw``); the spine leg is charged against
  the leaf–spine bisection with the DCQCN throughput factor for the
  synchronized-burst oversubscription the paper measures in Table 10.
* Expert parallelism is a first-class axis.  MoE configs enumerate
  ``(pod, data, expert, model[, pipe])`` factorizations: the routed
  dispatch/combine all-to-all rides intra-pod rails when the ``expert``
  axis stays inside a pod, while an *expert-spanning* layout (expert
  axis on the pod cut) keeps the heavy expert-weight gradients off the
  spine entirely — each expert's DP replicas share a pod — and pays only
  the dense-parameter all-reduce plus the pod-crossing all-to-all share,
  charged with an extra DCQCN aggravation factor (all-to-all is
  synchronized N:1 bursts into each spine port, far worse incast than a
  pipelined ring all-reduce).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.config import CHIP, ModelConfig, SHAPES, ShapeConfig, StepKind
from repro.core.fabric import (FABRIC, FabricSpec, dcqcn_throughput_factor)
from repro.parallel.sharding import (Rules, _DEFAULT_RULES, logical_to_spec,
                                     make_mesh, tree_shardings, use_sharding,
                                     with_overrides)

GRAD_WIRE_BYTES = 4          # fp32 master gradients on the wire
ACT_WIRE_BYTES = 2           # bf16 activations / boundary tensors
RAIL_EFFICIENCY = 0.85       # achievable fraction of NIC line rate
OVERLAP = 0.7                # comm/compute overlap (Table 10: ~72% measured)
A2A_INCAST_FACTOR = 1.5      # all-to-all synchronized-burst load on the
                             # spine vs a pipelined ring (DCQCN sees the
                             # instantaneous N:1 fan-in, not the mean)

_COMPRESS_FACTOR = {"none": 1.0, "bf16": 0.5, "int8": 0.25, "int8_ef": 0.25}


def default_rules() -> Rules:
    """The production logical-axis rule table (copy; safe to mutate)."""
    return dict(_DEFAULT_RULES)


def pod_capacity(fabric: FabricSpec = FABRIC) -> int:
    """GPUs a single pod can host (the zero-spine-traffic ceiling)."""
    return (fabric.nodes // fabric.pods) * fabric.gpus_per_node


# ---------------------------------------------------------------------------
# Plan building blocks
@dataclass(frozen=True)
class PipelineSpec:
    """GPipe staging over a ``pipe`` mesh axis (parallel.pipeline)."""
    stages: int
    vp: int = 1                      # virtual pipeline chunks per device
    microbatches: int = 8
    axis: str = "pipe"
    spans_pods: bool = False         # pipe axis sits on the pod boundary

    @property
    def bubble_fraction(self) -> float:
        m = max(self.microbatches * max(self.vp, 1), 1)
        return (self.stages - 1) / (m + self.stages - 1) if self.stages > 1 \
            else 0.0


@dataclass(frozen=True)
class CollectiveSchedule:
    """How DP gradients reduce (core.collectives.hierarchical_psum)."""
    intra_axis: Optional[str] = "data"    # rail-level reduce-scatter axis
    inter_axis: Optional[str] = None      # spine-crossing all-reduce axis
    hierarchical: bool = True             # False = flat GSPMD all-reduce
    compress: str = "none"                # cross-pod wire compression


@dataclass(frozen=True)
class Layout:
    """One candidate (pod, data, expert, model[, pipe]) factorization.

    ``expert`` is the EP degree for MoE configs (1 for dense).  The
    expert axis acts as data parallelism for every non-routed weight
    (``dp_ranks`` includes it); ``expert_spans_pods`` places it on the
    pod cut so each expert's DP replicas share a pod and expert-weight
    gradients never cross the spine."""
    pod: int = 1
    data: int = 1
    model: int = 1
    pipe: int = 1
    pipe_spans_pods: bool = False
    expert: int = 1
    expert_spans_pods: bool = False

    @property
    def chips(self) -> int:
        return self.pod * self.data * self.expert * self.model * self.pipe

    @property
    def dp_ranks(self) -> int:
        # EP is data parallelism for everything but the routed FFN
        return self.pod * self.data * self.expert

    def mesh_tuple(self) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
        """(shape, axis_names); the pod-spanning axis is slowest-varying so
        contiguous device halves land in contiguous pods."""
        dims: List[Tuple[str, int]] = []
        if self.expert > 1 and self.expert_spans_pods:
            dims.append(("expert", self.expert))
        if self.pipe > 1 and self.pipe_spans_pods:
            dims.append(("pipe", self.pipe))
        if self.pod > 1:
            dims.append(("pod", self.pod))
        if self.pipe > 1 and not self.pipe_spans_pods:
            dims.append(("pipe", self.pipe))
        if self.data > 1:
            dims.append(("data", self.data))
        if self.expert > 1 and not self.expert_spans_pods:
            dims.append(("expert", self.expert))
        if self.model > 1:
            dims.append(("model", self.model))
        if not dims:
            dims = [("data", 1)]
        return (tuple(s for _, s in dims), tuple(n for n, _ in dims))

    def __str__(self) -> str:
        parts = []
        if self.pipe > 1:
            parts.append(f"pipe={self.pipe}"
                         + ("⊗pod" if self.pipe_spans_pods else ""))
        if self.expert > 1:
            parts.append(f"expert={self.expert}"
                         + ("⊗pod" if self.expert_spans_pods else ""))
        if self.pod > 1:
            parts.append(f"pod={self.pod}")
        parts.append(f"data={self.data}")
        parts.append(f"model={self.model}")
        return "(" + ", ".join(parts) + ")"


class _MeshShape:
    """Deviceless mesh stand-in: just ``.shape`` (all logical_to_spec
    needs), so plans resolve shardings without building jax devices."""

    def __init__(self, shape: Dict[str, int]):
        self.shape = dict(shape)


# ---------------------------------------------------------------------------
# Scoring
@dataclass(frozen=True)
class LayoutScore:
    layout: Layout
    cross_pod_bytes: float           # total spine-crossing bytes / step
    rail_bytes_per_gpu: float        # intra-pod NIC bytes / step / GPU
    compute_s: float
    rail_s: float
    spine_s: float
    step_s: float                    # modeled step time (overlap + bubble)
    dcqcn_factor: float              # spine throughput under incast
    rail_utilization: float          # rail_s / step_s (port busy fraction)
    hbm_per_gpu: float
    feasible: bool
    fallbacks: Tuple[str, ...]       # logical dims that replicate (rule
    schedule: CollectiveSchedule = CollectiveSchedule()      # fallback)
    vp: int = 1                      # chosen virtual-pipeline interleaving
    hlo_flops: Optional[float] = None        # per-device, from HLO probe
    hlo_bytes: Optional[float] = None
    hlo_coll_bytes: Optional[float] = None
    notes: str = ""

    def row(self) -> str:
        probe = (f" hloColl={self.hlo_coll_bytes / 1e9:8.2f}GB"
                 if self.hlo_coll_bytes is not None else "")
        return (f"{str(self.layout):34s} xpod={self.cross_pod_bytes / 1e9:9.2f}GB "
                f"rail={self.rail_bytes_per_gpu / 1e9:8.2f}GB/gpu "
                f"step={self.step_s:7.3f}s dcqcn={self.dcqcn_factor:4.2f} "
                f"{'ok ' if self.feasible else 'OOM'}"
                + (f" vp={self.vp}" if self.vp > 1 else "")
                + f"{probe}"
                + (f" fallbacks={','.join(self.fallbacks)}"
                   if self.fallbacks else ""))


def _sharding_fallbacks(cfg: ModelConfig, shape: ShapeConfig, layout: Layout,
                        rules: Rules) -> Tuple[str, ...]:
    """Logical dims whose rule candidates reference live mesh axes but
    still resolve replicated (divisibility/exclusivity fallback) — the MQA
    kv_heads=1 / Mixtral 8-experts-on-16-way / global_batch=1 cases."""
    mesh_shape, axis_names = layout.mesh_tuple()
    mesh = _MeshShape(dict(zip(axis_names, mesh_shape)))
    live = {a for a, s in mesh.shape.items() if s > 1}
    probes: List[Tuple[str, int]] = [("batch", shape.global_batch)]
    if cfg.num_heads:
        probes.append(("heads", cfg.num_heads))
    if cfg.num_kv_heads:
        probes.append(("kv_heads", cfg.num_kv_heads))
    if cfg.num_experts:
        probes.append(("experts", cfg.num_experts))
    if cfg.d_ff:
        probes.append(("mlp", cfg.d_ff))
    probes.append(("vocab", cfg.padded_vocab))
    out = []
    for name, dim in probes:
        cands = rules.get(name, ())
        wants_live = any(set(c) & live for c in cands)
        if not wants_live:
            continue
        spec = logical_to_spec((name,), (dim,), mesh, rules)
        if len(spec) == 0 or spec[0] is None:
            out.append(name)
    return tuple(out)


def score_layout(cfg: ModelConfig, shape: ShapeConfig, layout: Layout,
                 *, fabric: FabricSpec = FABRIC,
                 schedule: Optional[CollectiveSchedule] = None,
                 rules: Optional[Rules] = None,
                 interleave: bool = True) -> LayoutScore:
    """Score one candidate layout with the fabric analytical model.

    All byte formulas are per *training* step (the shape's kind scales
    FLOPs; serving steps have no gradient reduction).

    With ``interleave=True`` (default) pipelined layouts are scored with
    the best interleaved-1F1B virtual-pipelining factor ``vp`` (layer
    chunks per device): the bubble shrinks to ``(p-1)/(vp·m + p-1)`` but
    every microbatch crosses each stage boundary ``vp`` times, so the
    stage-boundary activation traffic scales ×``vp`` — the planner trades
    the two instead of assuming plain GPipe (which over-penalized
    deep-pipe layouts)."""
    rules = rules if rules is not None else _DEFAULT_RULES
    if schedule is None:
        schedule = CollectiveSchedule(
            inter_axis="pod" if layout.pod > 1 else None)
    tokens = shape.tokens_per_step
    train = shape.kind == StepKind.TRAIN
    chips = layout.chips

    param_bytes = cfg.param_count() * GRAD_WIRE_BYTES
    # routed-expert weights (w1/w3/w2 per expert, config.param_count's MoE
    # branch) vs the dense remainder: with a real `expert` axis only the
    # dense share is replicated across it, and an expert-spanning layout
    # keeps the (dominant, for Mixtral-class models) expert gradients off
    # the spine entirely
    expert_param_bytes = 0.0
    if cfg.num_experts:
        expert_param_bytes = (cfg.num_layers * 3 * cfg.d_model * cfg.d_ff
                              * cfg.num_experts * GRAD_WIRE_BYTES)
    dense_param_bytes = param_bytes - expert_param_bytes
    grad_shard = param_bytes / (layout.model * layout.pipe)   # per DP ring
    local_tokens = tokens / max(layout.dp_ranks, 1)
    layers_per_stage = max(cfg.num_layers // layout.pipe, 1)

    flops = (cfg.flops_per_token() if train
             else 2.0 * cfg.param_count(active_only=True)) * tokens
    compute_s = flops / (chips * CHIP.peak_bf16_flops)

    # --- intra-pod rail traffic, per GPU --------------------------------
    rail = 0.0
    if train and layout.dp_ranks > 1:
        if layout.expert > 1:
            # dense grads are replicated over the expert axis too, so
            # their FSDP/ZeRO group widens to data×expert; expert grads
            # reduce over data only (each expert lives on one EP rank)
            de = layout.data * layout.expert
            rail += (2 * (de - 1) / de
                     * dense_param_bytes / (layout.model * layout.pipe))
            rail += (2 * (layout.data - 1) / max(layout.data, 1)
                     * expert_param_bytes
                     / (layout.expert * layout.model * layout.pipe))
        else:
            # FSDP/ZeRO reduce-scatter + all-gather over the data rail group
            rail += 2 * (layout.data - 1) / max(layout.data, 1) * grad_shard
    if layout.model > 1 and cfg.uses_attention:
        # 2 activation all-reduces per layer fwd (+2 bwd when training)
        n_ar = (4 if train else 2) * layers_per_stage
        rail += (n_ar * 2 * (layout.model - 1) / layout.model
                 * local_tokens * cfg.d_model * ACT_WIRE_BYTES)
    a2a_unit = 0.0                       # per-GPU dispatch+combine bytes
    if layout.expert > 1 and cfg.num_experts:
        # EP all-to-all over the expert axis (fwd; ×2 when training):
        # each rank keeps ~1/expert of its routed tokens and exchanges
        # the rest.  Intra-pod EP rides the per-NIC rails.
        a2a_unit = ((4 if train else 2) * local_tokens
                    * cfg.num_experts_per_tok * cfg.d_model * ACT_WIRE_BYTES
                    * (layout.expert - 1) / layout.expert)
        if layout.expert_spans_pods:
            rail += a2a_unit / fabric.pods      # intra-pod share only
        else:
            rail += a2a_unit
    elif layout.model > 1 and cfg.num_experts:
        # dense-folded EP (no expert axis): dispatch+combine all-to-all
        # rides the model axis (fwd; ×2 when training)
        rail += ((4 if train else 2) * local_tokens
                 * cfg.num_experts_per_tok * cfg.d_model * ACT_WIRE_BYTES
                 * (layout.model - 1) / layout.model)
    pipe_rail_unit = 0.0
    if layout.pipe > 1 and not layout.pipe_spans_pods:
        # stage-boundary activations stay on intra-pod rails (×vp under
        # interleaving — every microbatch visits each device vp times)
        pipe_rail_unit = ((2 if train else 1) * local_tokens * cfg.d_model
                          * ACT_WIRE_BYTES)

    # --- cross-pod spine traffic, total --------------------------------
    spans = (layout.pod > 1 or layout.pipe_spans_pods
             or layout.expert_spans_pods)
    cross_base, pipe_cross_unit = 0.0, 0.0
    a2a_incast = 1.0
    if spans and layout.pipe_spans_pods:
        # activation p2p at the one stage boundary on the pod cut (×vp)
        pipe_cross_unit = ((2 if train else 1) * tokens * cfg.d_model
                           * ACT_WIRE_BYTES)
    elif spans and layout.expert_spans_pods:
        # expert axis on the pod cut: expert grads never cross the spine
        # (each expert's DP replicas share a pod) — the cut carries only
        # the dense-remainder all-reduce plus the pod-crossing share of
        # the dispatch/combine all-to-all.  All-to-all is synchronized
        # N:1 bursts into each spine port, which DCQCN punishes far
        # harder than a pipelined ring — charge the aggravated offered
        # load below via ``a2a_incast``.
        if train:
            if schedule.hierarchical:
                cross_base = (2 * (fabric.pods - 1) / fabric.pods
                              * dense_param_bytes
                              * _COMPRESS_FACTOR.get(schedule.compress, 1.0))
            else:
                cross_base = 2 * dense_param_bytes * fabric.pods
        cross_base += (chips * a2a_unit
                       * (fabric.pods - 1) / fabric.pods)
        a2a_incast = A2A_INCAST_FACTOR
    elif spans and train:
        if schedule.hierarchical:
            cross_base = (2 * (layout.pod - 1) / layout.pod * param_bytes
                          * _COMPRESS_FACTOR.get(schedule.compress, 1.0))
        else:
            # flat ring over pod×data: ~2·G per ring link, `pods` cut
            # links per ring, model·pipe rings
            cross_base = (2 * grad_shard * layout.pod * layout.model
                          * layout.pipe)
    bisection = fabric.leaf_per_pod * fabric.spines * fabric.leaf_spine_bw

    # --- memory feasibility ---------------------------------------------
    state_mult = 4.0 if train else 0.5            # p+g+2×adam | bf16 params
    shard = layout.model * layout.pipe * (layout.dp_ranks if train
                                          else layout.expert)
    hbm = param_bytes * state_mult / max(shard, 1)
    hbm += (local_tokens / max(layout.pipe, 1)) * cfg.d_model \
        * ACT_WIRE_BYTES * 8                      # live activation estimate
    feasible = hbm < CHIP.hbm_bytes

    # --- interleaved-1F1B vp search over bubble vs boundary traffic -----
    micro = max(8, 2 * layout.pipe)
    vp_opts = [1]
    if layout.pipe > 1 and interleave:
        vp_opts = [v for v in (1, 2, 3, 4)
                   if cfg.num_layers % (layout.pipe * v) == 0] or [1]
    best = None
    for vp in vp_opts:
        rail_v = rail + pipe_rail_unit * vp
        cross_v = cross_base + pipe_cross_unit * vp
        rail_s = rail_v / (fabric.nic_bw * RAIL_EFFICIENCY)
        dcqcn = 1.0
        if cross_v > 0:
            offered = (chips / fabric.pods) * fabric.nic_bw / bisection
            dcqcn = dcqcn_throughput_factor(offered * a2a_incast, fabric)
        spine_s = cross_v / (bisection * dcqcn) if cross_v else 0.0
        bubble = 0.0
        if layout.pipe > 1:
            bubble = PipelineSpec(stages=layout.pipe, vp=vp,
                                  microbatches=micro).bubble_fraction
        comm_s = rail_s + spine_s
        step_s = ((compute_s + (1.0 - OVERLAP) * comm_s)
                  / max(1.0 - bubble, 1e-9))
        cand = (step_s, vp, rail_v, cross_v, rail_s, spine_s, dcqcn)
        if best is None or cand[0] < best[0]:
            best = cand
    step_s, vp, rail, cross, rail_s, spine_s, dcqcn = best

    return LayoutScore(
        layout=layout, cross_pod_bytes=cross, rail_bytes_per_gpu=rail,
        compute_s=compute_s, rail_s=rail_s, spine_s=spine_s, step_s=step_s,
        dcqcn_factor=dcqcn,
        rail_utilization=min(rail_s / step_s, 1.0) if step_s else 0.0,
        hbm_per_gpu=hbm, feasible=feasible,
        fallbacks=_sharding_fallbacks(cfg, shape, layout, rules),
        schedule=schedule, vp=vp)


def naive_production_layout(chips: int,
                            fabric: FabricSpec = FABRIC) -> Layout:
    """What ``make_production_mesh`` hard-coded for this chip count — the
    planner's baseline (flat collective schedule, no fabric awareness)."""
    if chips > pod_capacity(fabric):
        pods = math.ceil(chips / pod_capacity(fabric))
        rest = chips // pods
        model = 16 if rest % 16 == 0 else 1
        return Layout(pod=pods, data=rest // model, model=model)
    model = 16 if chips % 16 == 0 and chips >= 256 else \
        max(d for d in (1, 2, 4, 8) if chips % d == 0)
    return Layout(pod=1, data=chips // model, model=model)


def enumerate_layouts(cfg: ModelConfig, chips: int,
                      fabric: FabricSpec = FABRIC) -> List[Layout]:
    """Candidate (pod, data, expert, model[, pipe]) factorizations of
    ``chips``; the ``expert`` axis only appears for MoE configs, with EP
    degrees dividing ``num_experts``."""
    cap = pod_capacity(fabric)
    if chips > cap * fabric.pods:
        raise ValueError(f"{chips} chips exceed fabric capacity "
                         f"{cap * fabric.pods}")
    pods = math.ceil(chips / cap)
    model_opts = [m for m in (1, 2, 4, 8, 16, 32) if chips % m == 0]
    pipe_opts = [p for p in (1, 2, 4, 8, 16)
                 if chips % p == 0 and cfg.num_layers % p == 0]
    ep_opts = [1]
    if cfg.num_experts:
        ep_opts += [e for e in (2, 4, 8, 16, 32)
                    if cfg.num_experts % e == 0 and chips % e == 0]
    out: List[Layout] = []
    for m in model_opts:
        for p in pipe_opts:
            for ep in ep_opts:
                # m/p/ep each divide chips, but their PRODUCT may not —
                # every branch must re-check or the truncated `rest`
                # yields a layout using fewer chips than requested
                if chips % (m * p * ep) != 0:
                    continue
                if pods == 1:
                    rest = chips // (m * p * ep)
                    if rest >= 1:
                        out.append(Layout(pod=1, data=rest, model=m,
                                          pipe=p, expert=ep))
                    continue
                # pod-spanning DP with hierarchical collectives
                if chips % (pods * m * p * ep) == 0:
                    rest = chips // (pods * m * p * ep)
                    if rest >= 1:
                        out.append(Layout(pod=pods, data=rest, model=m,
                                          pipe=p, expert=ep))
                # pipeline stages across the pod cut (pipe ≥ pods)
                if p > 1 and p % pods == 0:
                    rest = chips // (m * p * ep)
                    if rest >= 1:
                        out.append(Layout(pod=1, data=rest, model=m,
                                          pipe=p, expert=ep,
                                          pipe_spans_pods=True))
                # expert axis across the pod cut (ep ≥ pods, pod-major)
                if ep > 1 and ep % pods == 0:
                    rest = chips // (m * p * ep)
                    if rest >= 1:
                        out.append(Layout(pod=1, data=rest, model=m,
                                          pipe=p, expert=ep,
                                          expert_spans_pods=True))
    return sorted(set(out), key=lambda l: (l.pipe_spans_pods,
                                           l.expert_spans_pods, l.pipe,
                                           l.pod, l.expert, l.model))


# ---------------------------------------------------------------------------
@dataclass
class PlanScorecard:
    """Every candidate scored, plus the naive baseline — human-readable."""
    arch: str
    chips: int
    objective: str
    scores: List[LayoutScore]
    chosen: LayoutScore
    naive: LayoutScore

    def __str__(self) -> str:
        lines = [f"ParallelPlan scorecard — {self.arch} @ {self.chips} chips"
                 f" (objective={self.objective})",
                 f"  naive  {self.naive.row()}"]
        for s in self.scores:
            mark = "→" if s.layout == self.chosen.layout else " "
            lines.append(f"  {mark}      {s.row()}")
        win = (1.0 - (self.chosen.cross_pod_bytes
                      / self.naive.cross_pod_bytes)) * 100 \
            if self.naive.cross_pod_bytes else 0.0
        lines.append(f"  chosen {self.chosen.layout} — cross-pod "
                     f"{self.chosen.cross_pod_bytes / 1e9:.2f} GB/step vs "
                     f"naive {self.naive.cross_pod_bytes / 1e9:.2f} GB "
                     f"({win:+.1f}% spine relief)")
        return "\n".join(lines)


@dataclass(frozen=True)
class ParallelPlan:
    """A complete parallelism layout: mesh + rules + staging + schedule.

    Replaces hand-threading ``make_production_mesh`` + ``DEFAULT_RULES``
    (both kept as deprecation shims over this class)."""
    mesh_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    rules: Rules = field(default_factory=default_rules)
    pipeline: Optional[PipelineSpec] = None
    collectives: CollectiveSchedule = field(default_factory=CollectiveSchedule)
    fabric: FabricSpec = FABRIC
    name: str = "custom"
    score: Optional[LayoutScore] = field(default=None, compare=False,
                                         repr=False)
    scorecard: Optional[PlanScorecard] = field(default=None, compare=False,
                                               repr=False)

    # -- topology ---------------------------------------------------------
    @property
    def chips(self) -> int:
        return int(math.prod(self.mesh_shape))

    @property
    def is_trivial(self) -> bool:
        return self.chips <= 1

    def axis_size(self, axis: str) -> int:
        try:
            return self.mesh_shape[self.axis_names.index(axis)]
        except ValueError:
            return 1

    def mesh(self, devices=None):
        """Build the jax Mesh (device order: pod-spanning axis slowest)."""
        return make_mesh(self.mesh_shape, self.axis_names, devices)

    def _mesh_shape_obj(self) -> _MeshShape:
        return _MeshShape(dict(zip(self.axis_names, self.mesh_shape)))

    # -- sharding ---------------------------------------------------------
    def spec(self, logical: Sequence[Optional[str]], shape: Sequence[int]):
        """Deviceless PartitionSpec resolution through the plan's rules."""
        return logical_to_spec(logical, shape, self._mesh_shape_obj(),
                               self.rules)

    def shardings(self, tree, axes_tree, mesh=None):
        """NamedShardings for a pytree of arrays/ShapeDtypeStructs."""
        mesh = mesh if mesh is not None else self.mesh()
        return tree_shardings(tree, axes_tree, mesh, self.rules)

    @contextlib.contextmanager
    def activate(self, mesh=None):
        """Ambient mesh + rules (sharding.constrain) and jax mesh context."""
        mesh = mesh if mesh is not None else self.mesh()
        with use_sharding(mesh, self.rules):
            with mesh:
                yield mesh

    # -- derivation -------------------------------------------------------
    def with_overrides(self, **rule_overrides) -> "ParallelPlan":
        """New plan with rule-table entries overridden (perf variants)."""
        return dataclasses.replace(
            self, rules=with_overrides(self.rules, **rule_overrides))

    def replace(self, **kw) -> "ParallelPlan":
        return dataclasses.replace(self, **kw)

    # -- serialization ----------------------------------------------------
    def to_json(self) -> str:
        d = {
            "name": self.name,
            "mesh_shape": list(self.mesh_shape),
            "axis_names": list(self.axis_names),
            "rules": {k: [list(c) for c in v] for k, v in self.rules.items()},
            "collectives": dataclasses.asdict(self.collectives),
        }
        if self.pipeline is not None:
            d["pipeline"] = dataclasses.asdict(self.pipeline)
        return json.dumps(d, indent=1)

    @classmethod
    def from_json(cls, text: str) -> "ParallelPlan":
        d = json.loads(text)
        return cls(
            mesh_shape=tuple(d["mesh_shape"]),
            axis_names=tuple(d["axis_names"]),
            rules={k: tuple(tuple(c) for c in v)
                   for k, v in d.get("rules", {}).items()} or default_rules(),
            pipeline=PipelineSpec(**d["pipeline"]) if "pipeline" in d
            else None,
            collectives=CollectiveSchedule(**d.get("collectives", {})),
            name=d.get("name", "custom"))

    def describe(self) -> str:
        mesh = "×".join(f"{a}={s}" for a, s in zip(self.axis_names,
                                                   self.mesh_shape))
        lines = [f"ParallelPlan[{self.name}] mesh=({mesh}) "
                 f"chips={self.chips}"]
        c = self.collectives
        if c.inter_axis:
            lines.append(f"  collectives: {'hierarchical' if c.hierarchical else 'flat'} "
                         f"intra={c.intra_axis} inter={c.inter_axis} "
                         f"compress={c.compress}")
        if self.pipeline:
            p = self.pipeline
            lines.append(f"  pipeline: {p.stages} stages vp={p.vp} "
                         f"micro={p.microbatches}"
                         + (" (spans pods)" if p.spans_pods else ""))
        if self.score:
            lines.append(f"  modeled: cross-pod "
                         f"{self.score.cross_pod_bytes / 1e9:.2f} GB/step, "
                         f"step {self.score.step_s:.3f}s, rail util "
                         f"{self.score.rail_utilization:.2f}")
        return "\n".join(lines)

    # -- HLO refinement ---------------------------------------------------
    def hlo_cost(self, arch: str, shape, *, rules=None):
        """Lower the (arch × shape) cell on this plan's mesh and return
        while-aware :class:`repro.core.hlo_cost.CostTotals` (per device).
        Needs ``jax.device_count() >= plan.chips`` (fake devices OK)."""
        import jax
        from repro.core.hlo_cost import analyze_hlo
        from repro.launch.cells import build_cell   # lazy: avoids cycle
        mesh = self.mesh()
        with use_sharding(mesh, rules or self.rules):
            cell = build_cell(arch, shape, mesh, rules=rules or self.rules)
            with mesh:
                lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                                  out_shardings=cell.out_shardings
                                  ).lower(*cell.abstract_args)
                hlo = lowered.compile().as_text()
        return analyze_hlo(hlo)


def plan_from_layout(layout: Layout, *, rules: Optional[Rules] = None,
                     fabric: FabricSpec = FABRIC, name: str = "custom",
                     compress: str = "none", vp: int = 1,
                     score: Optional[LayoutScore] = None,
                     scorecard: Optional[PlanScorecard] = None
                     ) -> ParallelPlan:
    shape, axes = layout.mesh_tuple()
    pipeline = None
    if layout.pipe > 1:
        pipeline = PipelineSpec(stages=layout.pipe, vp=vp,
                                microbatches=max(8, 2 * layout.pipe),
                                spans_pods=layout.pipe_spans_pods)
    collectives = CollectiveSchedule(
        intra_axis="data" if "data" in axes else None,
        inter_axis="pod" if "pod" in axes else None,
        hierarchical=True, compress=compress)
    return ParallelPlan(mesh_shape=shape, axis_names=axes,
                        rules=rules if rules is not None else default_rules(),
                        pipeline=pipeline, collectives=collectives,
                        fabric=fabric, name=name, score=score,
                        scorecard=scorecard)


# ---------------------------------------------------------------------------
# HLO probe cache — measured lowerings are expensive (minutes of XLA
# compile on 512 fake devices); key them by everything that changes the
# compiled module and reuse across planner invocations.
def _probe_cache_dir(override=None):
    import pathlib
    if override is not None:
        return pathlib.Path(override)
    return pathlib.Path(os.environ.get("REPRO_HLO_PROBE_CACHE",
                                       "experiments/hlo_probes"))


def _probe_key(probe_arch: str, shape, layout: Layout) -> str:
    """(config, shape, layout, jax version) — a new jax can lower the
    same cell differently, so measured totals are version-scoped.  The
    shape key spells out seq/batch/kind (two shapes sharing a ``name``
    must not alias); ``probe_arch`` is the registry name — re-registering
    a DIFFERENT config under the same name needs ``probe_cache=False``
    or a fresh cache dir."""
    import jax
    shape_id = (f"{shape.name}-s{shape.seq_len}-b{shape.global_batch}"
                f"-{shape.kind.value}" if isinstance(shape, ShapeConfig)
                else str(shape))
    layout_id = (f"pod{layout.pod}-data{layout.data}-model{layout.model}"
                 f"-pipe{layout.pipe}"
                 + ("x" if layout.pipe_spans_pods else ""))
    if layout.expert > 1:       # suffix only when EP is live: pre-EP cache
        layout_id += (f"-ep{layout.expert}"       # keys stay valid
                      + ("x" if layout.expert_spans_pods else ""))
    return f"{probe_arch}_{shape_id}_{layout_id}_jax{jax.__version__}"


def _probe_load(path) -> Optional[Tuple[float, float, float]]:
    try:
        d = json.loads(path.read_text())
        return (float(d["flops"]), float(d["bytes_accessed"]),
                float(d["coll_bytes"]))
    except (OSError, ValueError, KeyError):
        return None


def _probe_store(path, flops: float, bytes_accessed: float,
                 coll_bytes: float):
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(
            {"flops": flops, "bytes_accessed": bytes_accessed,
             "coll_bytes": coll_bytes}, indent=1))
    except OSError as e:                    # read-only checkout: probe
        warnings.warn(f"hlo probe cache write failed: {e}")  # still valid


# ---------------------------------------------------------------------------
# The auto-planner
_OBJECTIVES = ("balanced", "min_cross_pod_bytes", "min_step_time")


def plan_parallelism(model_cfg: ModelConfig, *, chips: int,
                     fabric: FabricSpec = FABRIC,
                     objective: str = "balanced",
                     shape: Optional[ShapeConfig] = None,
                     rules: Optional[Rules] = None,
                     compress: str = "none",
                     exclude_nodes: Sequence[int] = (),
                     hlo_probe: bool = False,
                     probe_arch: Optional[str] = None,
                     probe_shape=None,
                     probe_top_k: int = 2,
                     probe_cache: bool = True,
                     probe_cache_dir=None) -> ParallelPlan:
    """Map (model config × chip count × fabric) → the best ParallelPlan.

    Enumerates candidate layouts, scores each with the fabric/collectives
    analytical model, and returns the winner under ``objective`` with the
    full :class:`PlanScorecard` attached.  With ``hlo_probe=True`` the
    top-``probe_top_k`` finalists are actually lowered (``probe_arch`` ×
    ``probe_shape`` on this process's devices) and re-ranked with
    while-aware HLO cost totals — the compiled step, not just the model.

    Measured probes are cached as JSON under ``probe_cache_dir``
    (default ``$REPRO_HLO_PROBE_CACHE`` or ``experiments/hlo_probes/``),
    keyed by (probe config, probe shape, layout, jax version), and
    reused instead of recompiling finalists on every invocation; pass
    ``probe_cache=False`` to force fresh lowering.

    ``exclude_nodes`` marks failed/drained nodes (paper §8.7): the
    fabric model shrinks by that many nodes (less pod capacity, same
    pod count), and ``chips`` must already be the surviving chip count
    — the elastic runtime passes both after a device loss.
    """
    if objective not in _OBJECTIVES:
        raise ValueError(f"objective {objective!r} not in {_OBJECTIVES}")
    shape = shape if shape is not None else SHAPES["train_4k"]
    rules = rules if rules is not None else default_rules()
    if exclude_nodes:
        lost = len(set(exclude_nodes))
        if lost >= fabric.nodes:
            raise ValueError(f"excluding {lost} of {fabric.nodes} nodes "
                             "leaves no capacity")
        fabric = dataclasses.replace(fabric, nodes=fabric.nodes - lost)

    layouts = enumerate_layouts(model_cfg, chips, fabric)
    scores = [score_layout(model_cfg, shape, l, fabric=fabric, rules=rules,
                           schedule=CollectiveSchedule(
                               inter_axis="pod" if l.pod > 1 else None,
                               compress=compress))
              for l in layouts]

    def key(s: LayoutScore):
        penalty = s.step_s * (1.0 + 0.1 * len(s.fallbacks))
        if objective == "min_cross_pod_bytes":
            primary = (s.cross_pod_bytes, penalty)
        elif objective == "min_step_time":
            primary = (s.step_s, s.cross_pod_bytes)
        else:
            primary = (penalty, s.cross_pod_bytes)
        return (not s.feasible,) + primary + (
            s.layout.pipe, s.layout.model, s.layout.data, s.layout.expert)

    scores.sort(key=key)

    if hlo_probe and probe_arch is None:
        raise ValueError(
            "hlo_probe=True needs probe_arch (a registry name resolvable "
            "by launch.cells.build_cell; register reduced configs via "
            "repro.configs.register_config)")
    if hlo_probe:
        cache_dir = _probe_cache_dir(probe_cache_dir)
        sh = probe_shape if probe_shape is not None else shape
        probed = []
        for s in scores[:probe_top_k]:
            cache_path = cache_dir / f"{_probe_key(probe_arch, sh, s.layout)}.json"
            cached = _probe_load(cache_path) if probe_cache else None
            if cached is not None:
                flops, bytes_accessed, coll = cached
            else:
                import jax
                if jax.device_count() < chips:
                    raise ValueError(
                        f"hlo_probe needs >= {chips} devices (have "
                        f"{jax.device_count()}); run under "
                        f"XLA_FLAGS=--xla_force_host_platform_device_count="
                        f"{chips} (or warm {cache_dir} on a host that has "
                        "them)")
                plan_i = plan_from_layout(s.layout, rules=rules,
                                          fabric=fabric)
                totals = plan_i.hlo_cost(probe_arch, sh)
                flops, bytes_accessed = totals.flops, totals.bytes_accessed
                coll = float(totals.collective_total)
                if probe_cache:
                    _probe_store(cache_path, flops, bytes_accessed, coll)
            probed.append(dataclasses.replace(
                s, hlo_flops=flops, hlo_bytes=bytes_accessed,
                hlo_coll_bytes=coll))
        # re-rank probed finalists by compiled-step roofline bound
        def hlo_key(s: LayoutScore):
            t = max(s.hlo_flops / CHIP.peak_bf16_flops,
                    s.hlo_bytes / CHIP.hbm_bandwidth,
                    s.hlo_coll_bytes / CHIP.ici_link_bandwidth)
            return (t, s.cross_pod_bytes)
        probed.sort(key=hlo_key)
        scores = probed + scores[probe_top_k:]

    chosen = scores[0]
    naive = score_layout(model_cfg, shape, naive_production_layout(chips,
                                                                   fabric),
                         fabric=fabric, rules=rules,
                         schedule=CollectiveSchedule(
                             inter_axis="pod", hierarchical=False))
    card = PlanScorecard(arch=model_cfg.name, chips=chips,
                         objective=objective, scores=scores, chosen=chosen,
                         naive=naive)
    return plan_from_layout(chosen.layout, rules=rules, fabric=fabric,
                            name=f"auto/{objective}", compress=compress,
                            vp=chosen.vp, score=chosen, scorecard=card)


def replan(plan: ParallelPlan, model_cfg: ModelConfig, *,
           exclude_nodes: Sequence[int] = (),
           chips: Optional[int] = None,
           shape: Optional[ShapeConfig] = None,
           objective: str = "balanced",
           fabric: Optional[FabricSpec] = None) -> ParallelPlan:
    """Full re-plan after node loss (§8.7) — the elastic upgrade over
    ``shrink_data_axis``.

    Re-runs the auto-planner over the surviving chip count with the
    failed nodes excluded from the fabric model, carrying the old plan's
    rule table and wire compression.  Unlike the legacy data-axis shrink,
    every axis is back on the table: the planner may trade model/pipe
    parallelism to use *all* surviving chips (a 16-way TP group shrink
    strands ``chips mod 16`` GPUs; a re-plan can drop to 8-way and use
    every one).

    ``fabric`` defaults to the old plan's fabric *before* the loss;
    ``chips`` defaults to ``plan.chips - lost_nodes × gpus_per_node``.
    """
    fabric = fabric if fabric is not None else plan.fabric
    if chips is None:
        chips = plan.chips - len(set(exclude_nodes)) * fabric.gpus_per_node
    if chips < 1:
        raise ValueError(f"no chips survive the loss of nodes "
                         f"{sorted(set(exclude_nodes))}")
    return plan_parallelism(model_cfg, chips=chips, fabric=fabric,
                            objective=objective, shape=shape,
                            rules=plan.rules,
                            compress=plan.collectives.compress,
                            exclude_nodes=exclude_nodes)


# ---------------------------------------------------------------------------
# Named plans + CLI resolution
def single_pod_plan(rules: Optional[Rules] = None) -> ParallelPlan:
    """The mandated (data=16, model=16) single-pod production layout."""
    return ParallelPlan(mesh_shape=(16, 16), axis_names=("data", "model"),
                        rules=rules if rules is not None else default_rules(),
                        collectives=CollectiveSchedule(intra_axis="data"),
                        name="single-pod")


def multi_pod_plan(rules: Optional[Rules] = None) -> ParallelPlan:
    """The mandated (pod=2, data=16, model=16) two-pod layout with the
    hierarchical cross-pod collective schedule (paper C1)."""
    return ParallelPlan(mesh_shape=(2, 16, 16),
                        axis_names=("pod", "data", "model"),
                        rules=rules if rules is not None else default_rules(),
                        collectives=CollectiveSchedule(
                            intra_axis="data", inter_axis="pod"),
                        name="multi-pod")


def _parse_kv_layout(spec: str) -> Tuple[Layout, int]:
    kv: Dict[str, int] = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        k = k.strip()
        if k not in ("pod", "data", "ep", "model", "pipe", "vp"):
            raise ValueError(f"unknown layout key {k!r} in {spec!r} "
                             "(want pod/data/ep/model/pipe/vp)")
        kv[k] = int(v)
    vp = kv.pop("vp", 1)
    if vp > 1 and kv.get("pipe", 1) <= 1:
        raise ValueError(f"vp={vp} needs pipe>1 in {spec!r}")
    return Layout(pod=kv.get("pod", 1), data=kv.get("data", 1),
                  model=kv.get("model", 1), pipe=kv.get("pipe", 1),
                  expert=kv.get("ep", 1)), vp


def resolve_plan(spec: Optional[str] = None,
                 model_cfg: Optional[ModelConfig] = None, *,
                 chips: Optional[int] = None,
                 shape: Optional[ShapeConfig] = None,
                 fabric: FabricSpec = FABRIC,
                 objective: str = "balanced",
                 rules: Optional[Rules] = None) -> ParallelPlan:
    """One ``--plan`` flag for every launcher.

    ``auto`` | ``single-pod`` | ``multi-pod`` | a JSON plan file |
    ``pod=2,data=16,model=16``-style explicit layouts.  ``auto`` needs a
    model config and a chip count (defaults to ``jax.device_count()``).
    """
    spec = (spec or "auto").strip()
    if spec == "single-pod":
        return single_pod_plan(rules)
    if spec == "multi-pod":
        return multi_pod_plan(rules)
    if spec == "auto":
        if chips is None:
            import jax
            chips = jax.device_count()
        if chips <= 1:
            return ParallelPlan(mesh_shape=(1,), axis_names=("data",),
                                rules=rules if rules is not None
                                else default_rules(), name="trivial")
        if model_cfg is None:
            raise ValueError("--plan auto needs a model config "
                             "(pass model_cfg to resolve_plan)")
        return plan_parallelism(model_cfg, chips=chips, fabric=fabric,
                                objective=objective, shape=shape,
                                rules=rules)
    if spec.endswith(".json") or os.path.exists(spec):
        with open(spec) as f:
            return ParallelPlan.from_json(f.read())
    if "=" in spec:
        layout, vp = _parse_kv_layout(spec)
        plan = plan_from_layout(layout, rules=rules, fabric=fabric,
                                name=spec)
        if vp > 1:
            plan = plan.replace(pipeline=dataclasses.replace(
                plan.pipeline, vp=vp))
        return plan
    raise ValueError(
        f"unknown plan spec {spec!r}: want auto | single-pod | multi-pod | "
        "a JSON plan file | pod=2,data=16,model=16")
