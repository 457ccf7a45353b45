"""``pallas_call`` site extraction and kernel-parameter binding.

One :class:`KernelSite` per ``pl.pallas_call`` call expression, with:

  * the grid (const sizes where statically known),
  * ``dimension_semantics`` declarations from
    ``compiler_params=pltpu.CompilerParams(...)``,
  * every in/out/scratch/scalar-prefetch operand as a :class:`RefInfo`
    carrying its block shape (``None`` for non-constant extents), its
    dtype where declared (``out_shape``/``scratch_shapes``), and its
    index-map :class:`~repro.analysis.semantic.indexmap.IndexMapSummary`,
  * the resolved kernel ``FunctionDef`` with each positional parameter
    bound to its RefInfo.

Kernel resolution is interprocedural within the module: the first
``pallas_call`` argument may be the kernel name, a
``functools.partial(kernel, ...)`` wrapping it (positional partial args
shift the binding window; keyword partials drop those parameters), or a
local variable assigned either form.  Grid/spec expressions chase
single-assignment local names exactly as RL004 does.  Anything dynamic
beyond that yields ``kernel=None`` — rules skip, never guess.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.analysis.semantic.domain import dtype_from_expr
from repro.analysis.semantic.indexmap import (IndexMapSummary,
                                              summarize_index_map)
from repro.analysis.visitor import ModuleContext, const_int

PALLAS_CALL = "jax.experimental.pallas.pallas_call"
BLOCK_SPEC = "jax.experimental.pallas.BlockSpec"
GRID_SPECS = {
    "jax.experimental.pallas.tpu.PrefetchScalarGridSpec",
    "jax.experimental.pallas.GridSpec",
}
TPU_COMPILER_PARAMS = "jax.experimental.pallas.tpu.CompilerParams"
SCRATCH_CTORS = {
    "jax.experimental.pallas.tpu.VMEM",
    "jax.experimental.pallas.tpu.SMEM",
}
SHAPE_DTYPE_STRUCT = "jax.ShapeDtypeStruct"


@dataclass
class RefInfo:
    """One kernel operand Ref as the analyzer knows it."""
    name: Optional[str]               # kernel parameter name, once bound
    role: str                         # in | out | scratch | scalar_prefetch
    block_shape: Optional[Tuple[Optional[int], ...]] = None
    dtype: Optional[str] = None       # canonical dtype where declared
    index_map: Optional[IndexMapSummary] = None
    spec_node: Optional[ast.AST] = None    # BlockSpec / VMEM / struct node
    index: int = 0                    # position within its role group


@dataclass
class KernelSite:
    call: ast.Call                    # the pallas_call expression
    scope: ast.AST                    # enclosing function (or module)
    grid_rank: Optional[int]
    grid_sizes: Tuple[Optional[int], ...] = ()
    num_scalar_prefetch: int = 0
    dim_semantics: Optional[Tuple[Optional[str], ...]] = None
    ins: List[RefInfo] = field(default_factory=list)
    outs: List[RefInfo] = field(default_factory=list)
    scratch: List[RefInfo] = field(default_factory=list)
    kernel: Optional[ast.AST] = None  # resolved kernel FunctionDef
    bindings: Dict[str, RefInfo] = field(default_factory=dict)

    @property
    def all_refs(self) -> List[RefInfo]:
        prefetch = [RefInfo(None, "scalar_prefetch", index=i)
                    for i in range(self.num_scalar_prefetch)]
        return prefetch + self.ins + self.outs + self.scratch

    def semantics_of(self, dim: int) -> Optional[str]:
        if self.dim_semantics is None or dim >= len(self.dim_semantics):
            return None
        return self.dim_semantics[dim]


# -- expression helpers ------------------------------------------------------
def _kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _chase(ctx: ModuleContext, expr: Optional[ast.expr],
           scope: ast.AST) -> Optional[ast.expr]:
    seen = 0
    while isinstance(expr, ast.Name) and seen < 4:
        resolved = ctx.resolve_local(expr.id, scope)
        if resolved is None:
            return expr
        expr, seen = resolved, seen + 1
    return expr


def _as_list(expr: Optional[ast.expr]) -> Optional[List[ast.expr]]:
    if expr is None:
        return None
    if isinstance(expr, (ast.List, ast.Tuple)):
        return list(expr.elts)
    return [expr]


def _const_shape(expr: Optional[ast.expr]
                 ) -> Optional[Tuple[Optional[int], ...]]:
    if not isinstance(expr, (ast.Tuple, ast.List)):
        return None
    return tuple(const_int(e) for e in expr.elts)


def _block_spec_info(ctx: ModuleContext, spec: ast.expr, role: str,
                     idx: int, grid_rank: Optional[int],
                     prefetch: int) -> RefInfo:
    info = RefInfo(name=None, role=role, spec_node=spec, index=idx)
    if isinstance(spec, ast.Call) and ctx.dotted(spec.func) == BLOCK_SPEC:
        if spec.args:
            info.block_shape = _const_shape(spec.args[0])
        imap = spec.args[1] if len(spec.args) > 1 \
            else _kwarg(spec, "index_map")
        if imap is not None and grid_rank is not None:
            info.index_map = summarize_index_map(imap, grid_rank, prefetch)
    return info


def _scratch_info(ctx: ModuleContext, expr: ast.expr, idx: int) -> RefInfo:
    info = RefInfo(name=None, role="scratch", spec_node=expr, index=idx)
    if isinstance(expr, ast.Call) and ctx.dotted(expr.func) in SCRATCH_CTORS:
        if expr.args:
            info.block_shape = _const_shape(expr.args[0])
        if len(expr.args) > 1:
            info.dtype = dtype_from_expr(ctx, expr.args[1])
    return info


def _out_dtype(ctx: ModuleContext, struct: Optional[ast.expr]
               ) -> Optional[str]:
    if isinstance(struct, ast.Call) and \
            ctx.dotted(struct.func) == SHAPE_DTYPE_STRUCT:
        dt = struct.args[1] if len(struct.args) > 1 \
            else _kwarg(struct, "dtype")
        if dt is not None:
            return dtype_from_expr(ctx, dt)
    return None


def _dim_semantics(ctx: ModuleContext, call: ast.Call, scope: ast.AST
                   ) -> Optional[Tuple[Optional[str], ...]]:
    """``compiler_params=pltpu.CompilerParams(dimension_semantics=…)``."""
    cp = _chase(ctx, _kwarg(call, "compiler_params"), scope)
    if not (isinstance(cp, ast.Call)
            and ctx.dotted(cp.func) == TPU_COMPILER_PARAMS):
        return None
    ds = _kwarg(cp, "dimension_semantics")
    if not isinstance(ds, (ast.Tuple, ast.List)):
        return None
    return tuple(e.value if isinstance(e, ast.Constant)
                 and isinstance(e.value, str) else None for e in ds.elts)


# -- kernel resolution -------------------------------------------------------
def _resolve_kernel(ctx: ModuleContext, expr: ast.expr, scope: ast.AST
                    ) -> Tuple[Optional[ast.AST], int, set]:
    """(kernel def, positional shift, keyword-bound names) of the first
    pallas_call argument, chasing partials and local aliases."""
    shift, bound_kw = 0, set()
    for _ in range(4):
        expr = _chase(ctx, expr, scope)
        if isinstance(expr, ast.Call) and \
                ctx.dotted(expr.func) == "functools.partial" and expr.args:
            shift += len(expr.args) - 1
            bound_kw |= {kw.arg for kw in expr.keywords if kw.arg}
            expr = expr.args[0]
            continue
        break
    if isinstance(expr, ast.Name):
        fn = None
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = ctx.functions.get(
                f"{ctx.qualname(scope)}.<locals>.{expr.id}")
        fn = fn or ctx.functions.get(expr.id)
        return fn, shift, bound_kw
    if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return expr, shift, bound_kw
    return None, shift, bound_kw


def _bind_params(site: KernelSite, kernel: ast.AST, shift: int,
                 bound_kw: set) -> bool:
    """Map the kernel's positional parameters to the site's refs, in
    Pallas order: scalar-prefetch, ins, outs, scratch."""
    args = getattr(kernel, "args", None)
    if args is None:
        return False
    params = [a.arg for a in (args.posonlyargs + args.args)]
    params = [p for p in params[shift:] if p not in bound_kw]
    refs = site.all_refs
    if len(params) != len(refs):
        return False
    for name, ref in zip(params, refs):
        ref.name = name
        site.bindings[name] = ref
    return True


# -- site extraction ---------------------------------------------------------
def extract_site(ctx: ModuleContext, call: ast.Call) -> KernelSite:
    scope = ctx.func_of(call) or ctx.tree
    site = KernelSite(call=call, scope=scope, grid_rank=None)

    in_specs_expr = _kwarg(call, "in_specs")
    out_specs_expr = _kwarg(call, "out_specs")
    out_shape_expr = _kwarg(call, "out_shape")
    grid_expr = _kwarg(call, "grid")

    grid_spec = _chase(ctx, _kwarg(call, "grid_spec"), scope)
    if isinstance(grid_spec, ast.Call) and \
            ctx.dotted(grid_spec.func) in GRID_SPECS:
        n = _kwarg(grid_spec, "num_scalar_prefetch")
        site.num_scalar_prefetch = (const_int(n) or 0) if n is not None else 0
        in_specs_expr = _kwarg(grid_spec, "in_specs")
        out_specs_expr = _kwarg(grid_spec, "out_specs")
        grid_expr = _kwarg(grid_spec, "grid")

    grid = _chase(ctx, grid_expr, scope)
    if isinstance(grid, (ast.Tuple, ast.List)):
        site.grid_rank = len(grid.elts)
        site.grid_sizes = tuple(const_int(e) for e in grid.elts)
    elif grid is not None and const_int(grid) is not None:
        site.grid_rank = 1
        site.grid_sizes = (const_int(grid),)

    site.dim_semantics = _dim_semantics(ctx, call, scope)

    in_specs = _as_list(_chase(ctx, in_specs_expr, scope))
    out_specs = _as_list(_chase(ctx, out_specs_expr, scope))
    out_shapes = _as_list(_chase(ctx, out_shape_expr, scope))
    scratch = _as_list(_chase(ctx, _kwarg(call, "scratch_shapes"), scope))

    pre = site.num_scalar_prefetch
    for i, spec in enumerate(in_specs or []):
        site.ins.append(
            _block_spec_info(ctx, spec, "in", i, site.grid_rank, pre))
    n_out = len(out_specs) if out_specs is not None else \
        (len(out_shapes) if out_shapes is not None else 0)
    for i in range(n_out):
        spec = out_specs[i] if out_specs is not None and i < len(out_specs) \
            else None
        if spec is not None:
            info = _block_spec_info(ctx, spec, "out", i, site.grid_rank, pre)
        else:
            # no out_specs: the whole array is one block revisited by
            # every grid step (constant index map)
            info = RefInfo(name=None, role="out", spec_node=call, index=i,
                           index_map=IndexMapSummary(
                               [], site.grid_rank or 0))
        if out_shapes is not None and i < len(out_shapes):
            info.dtype = _out_dtype(ctx, out_shapes[i])
            if info.block_shape is None:
                struct = out_shapes[i]
                if isinstance(struct, ast.Call) and struct.args:
                    info.block_shape = _const_shape(struct.args[0])
        site.outs.append(info)
    for i, expr in enumerate(scratch or []):
        site.scratch.append(_scratch_info(ctx, expr, i))

    _infer_operand_dtypes(ctx, call, scope, site)

    if call.args:
        kernel, shift, bound_kw = _resolve_kernel(ctx, call.args[0], scope)
        if kernel is not None and _bind_params(site, kernel, shift, bound_kw):
            site.kernel = kernel
    return site


def _infer_operand_dtypes(ctx: ModuleContext, call: ast.Call, scope: ast.AST,
                          site: KernelSite):
    """Fill unknown in-ref dtypes from the application's operands.

    ``BlockSpec`` declares no dtype, but the call that APPLIES the
    ``pallas_call`` result does pass concrete operands — and when an
    operand (chased through local single assignments) is the
    ``x.astype(<dtype>)`` form, that dtype is the in-ref's.  This is how
    quantized-cache refs (int8/fp8 operands) become recognizable to
    RL009 without per-kernel registration."""
    apply = next((n for n in ast.walk(scope)
                  if isinstance(n, ast.Call) and n.func is call), None)
    if apply is None:
        return
    pre = site.num_scalar_prefetch
    for i, info in enumerate(site.ins):
        if info.dtype is not None:
            continue
        ai = pre + i
        if ai >= len(apply.args):
            continue
        op = _chase(ctx, apply.args[ai], scope)
        if isinstance(op, ast.Call) and isinstance(op.func, ast.Attribute) \
                and op.func.attr == "astype" and op.args:
            info.dtype = dtype_from_expr(ctx, op.args[0])


def kernel_sites(ctx: ModuleContext) -> List[KernelSite]:
    """All pallas_call sites in the module (cached on the context — every
    semantic rule shares one extraction pass)."""
    cached = getattr(ctx, "_pallas_sites", None)
    if cached is not None:
        return cached
    sites = [extract_site(ctx, node) for node in ast.walk(ctx.tree)
             if isinstance(node, ast.Call)
             and ctx.dotted(node.func) == PALLAS_CALL]
    ctx._pallas_sites = sites
    return sites
