"""Pallas kernel contract rules (pack ``pallas``).

Every ``pl.pallas_call`` in this repo encodes the same implicit contract:
the grid must cover every output block exactly, block shapes should sit on
the dtype's native (sublane, lane) tiling, the per-step VMEM working set
must fit the budget, and an output block revisited across a grid axis (the
``R_TILE`` accumulation pattern of ``ipls_aggregate_batched``) must guard
its writes with ``@pl.when`` — an unguarded write either clobbers the
accumulator or reads uninitialized memory on the first visit. These rules
resolve grids/BlockSpecs statically, folding module constants (``BR``,
``LANES``, ...) through :class:`repro.analysis.core.ConstEnv`; dimensions
that do not fold (runtime shapes like ``rows``) are skipped, never guessed,
so a finding is always a real structural fact about the call site.

Native minimum tiles (sublane x lane) per dtype — see
/opt/skills/guides/pallas_guide.md:

    float32 (8, 128) | bfloat16/float16 (16, 128) | int8/uint8/fp8 (32, 128)
"""
from __future__ import annotations

import ast
import dataclasses
import math
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.core import (
    Finding,
    FileContext,
    Options,
    Rule,
    deref,
    keyword_arg,
    local_assignments,
    register,
    tail_name,
    walk_calls,
)

# minimum (sublane) rows per dtype; lanes are always 128
SUBLANE = {
    "float32": 8,
    "int32": 8,
    "uint32": 8,
    "bfloat16": 16,
    "float16": 16,
    "int8": 32,
    "uint8": 32,
    "float8_e4m3fn": 32,
    "float8_e5m2": 32,
}
DTYPE_BYTES = {
    "float32": 4,
    "int32": 4,
    "uint32": 4,
    "bfloat16": 2,
    "float16": 2,
    "int8": 1,
    "uint8": 1,
    "float8_e4m3fn": 1,
    "float8_e5m2": 1,
}
LANES = 128


@dataclasses.dataclass
class SpecInfo:
    """One parsed BlockSpec (or scratch shape)."""

    node: ast.AST
    shape_vals: Optional[List[Optional[float]]] = None  # None = no block shape
    index_params: Optional[List[str]] = None  # None = no index_map
    index_body: Optional[List[ast.AST]] = None  # elements of the returned tuple
    dtype: Optional[str] = None


@dataclasses.dataclass
class PallasCallInfo:
    node: ast.Call
    kernel_name: Optional[str]
    grid_vals: Optional[List[Optional[float]]]  # None = no/unresolvable grid
    in_specs: List[SpecInfo]
    out_specs: List[SpecInfo]
    out_shapes: List[Tuple[Optional[List[Optional[float]]], Optional[str]]]
    scratch: List[SpecInfo]
    # scalar-prefetch operands (``PrefetchScalarGridSpec``): they lead the
    # kernel's refs and trail every index_map's grid indices
    num_prefetch: int = 0


def _dtype_tail(node: Optional[ast.AST]) -> Optional[str]:
    if node is None:
        return None
    name = tail_name(node)
    return name if name in SUBLANE else None


def _parse_blockspec(call: ast.AST, ctx: FileContext, env) -> Optional[SpecInfo]:
    call = deref(call, env)
    if not isinstance(call, ast.Call) or tail_name(call.func) != "BlockSpec":
        return None
    info = SpecInfo(node=call)
    shape_node = call.args[0] if call.args else keyword_arg(call, "block_shape")
    index_node = call.args[1] if len(call.args) > 1 else keyword_arg(call, "index_map")
    shape_node = deref(shape_node, env)
    if isinstance(shape_node, (ast.Tuple, ast.List)):
        info.shape_vals = [ctx.consts.fold(el) for el in shape_node.elts]
    index_node = deref(index_node, env)
    if isinstance(index_node, ast.Lambda):
        info.index_params = [a.arg for a in index_node.args.args]
        body = index_node.body
        info.index_body = list(body.elts) if isinstance(body, ast.Tuple) else [body]
    return info


def _parse_out_shape(node: ast.AST, ctx: FileContext, env):
    node = deref(node, env)
    if isinstance(node, ast.Call) and tail_name(node.func) == "ShapeDtypeStruct":
        shape_node = deref(node.args[0] if node.args else keyword_arg(node, "shape"), env)
        dtype_node = node.args[1] if len(node.args) > 1 else keyword_arg(node, "dtype")
        vals = (
            [ctx.consts.fold(el) for el in shape_node.elts]
            if isinstance(shape_node, (ast.Tuple, ast.List))
            else None
        )
        return vals, _dtype_tail(dtype_node)
    return None, None


def _parse_scratch(node: ast.AST, ctx: FileContext, env) -> Optional[SpecInfo]:
    node = deref(node, env)
    # pltpu.VMEM((shape), dtype); SMEM/semaphores are tiny — ignored
    if isinstance(node, ast.Call) and tail_name(node.func) == "VMEM" and node.args:
        shape_node = deref(node.args[0], env)
        info = SpecInfo(node=node)
        if isinstance(shape_node, (ast.Tuple, ast.List)):
            info.shape_vals = [ctx.consts.fold(el) for el in shape_node.elts]
        info.dtype = _dtype_tail(node.args[1] if len(node.args) > 1 else None)
        return info
    return None


def _kernel_name(node: ast.AST) -> Optional[str]:
    """First positional arg of pallas_call: a Name, or functools.partial(Name, ...)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call) and tail_name(node.func) == "partial" and node.args:
        inner = node.args[0]
        if isinstance(inner, ast.Name):
            return inner.id
    return None


def parse_pallas_calls(ctx: FileContext) -> List[PallasCallInfo]:
    out: List[PallasCallInfo] = []
    for call in walk_calls(ctx.tree):
        if tail_name(call.func) != "pallas_call":
            continue
        fn = ctx.enclosing_function(call)
        env = local_assignments(fn) if fn is not None else {}
        # grid / specs given directly, or bundled in grid_spec=GridSpec(...)
        # / PrefetchScalarGridSpec(num_scalar_prefetch=n, ...)
        src = call
        num_prefetch = 0
        grid_spec = deref(keyword_arg(call, "grid_spec"), env)
        if isinstance(grid_spec, ast.Call):
            src = grid_spec
            n = keyword_arg(grid_spec, "num_scalar_prefetch")
            num_prefetch = int(ctx.consts.fold(n) or 0) if n is not None else 0

        grid_node = deref(keyword_arg(src, "grid"), env)
        if isinstance(grid_node, (ast.Tuple, ast.List)):
            grid_vals = [ctx.consts.fold(el) for el in grid_node.elts]
        elif grid_node is not None:
            v = ctx.consts.fold(grid_node)
            grid_vals = [v] if v is not None else None
        else:
            grid_vals = None

        def spec_list(kw: str) -> List[SpecInfo]:
            node = deref(keyword_arg(src, kw), env)
            if node is None:
                return []
            elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
            specs = []
            for el in elts:
                s = _parse_blockspec(el, ctx, env)
                if s is not None:
                    specs.append(s)
            return specs

        in_specs = spec_list("in_specs")
        out_specs = spec_list("out_specs")
        shapes_node = deref(keyword_arg(call, "out_shape"), env)
        out_shapes = []
        if shapes_node is not None:
            elts = (
                shapes_node.elts
                if isinstance(shapes_node, (ast.Tuple, ast.List))
                else [shapes_node]
            )
            out_shapes = [_parse_out_shape(el, ctx, env) for el in elts]
        for spec, (_, dt) in zip(out_specs, out_shapes):
            spec.dtype = dt

        scratch_node = deref(keyword_arg(src, "scratch_shapes"), env)
        scratch = []
        if isinstance(scratch_node, (ast.Tuple, ast.List)):
            for el in scratch_node.elts:
                s = _parse_scratch(el, ctx, env)
                if s is not None:
                    scratch.append(s)

        out.append(
            PallasCallInfo(
                node=call,
                kernel_name=_kernel_name(call.args[0]) if call.args else None,
                grid_vals=grid_vals,
                in_specs=in_specs,
                out_specs=out_specs,
                out_shapes=out_shapes,
                scratch=scratch,
                num_prefetch=num_prefetch,
            )
        )
    return out


@register
class IndexMapContract(Rule):
    """PL01: index_map arity must equal the grid rank (plus one ref per
    scalar-prefetch operand of a ``PrefetchScalarGridSpec``) and its returned
    tuple must have one component per block-shape dimension. An arity/rank
    drift — the classic symptom of adding a grid axis without updating every
    spec — compiles to wrong indexing or crashes at trace time deep in
    Mosaic."""

    id = "PL01"
    pack = "pallas"
    title = "BlockSpec index_map arity/rank must match grid and block shape"

    def check(self, ctx: FileContext, options: Options) -> Iterator[Finding]:
        for info in parse_pallas_calls(ctx):
            n_grid = len(info.grid_vals) if info.grid_vals is not None else None
            for role, specs in (("in", info.in_specs), ("out", info.out_specs)):
                for i, spec in enumerate(specs):
                    if spec.index_params is None:
                        continue
                    if (
                        n_grid is not None
                        and len(spec.index_params) != n_grid + info.num_prefetch
                    ):
                        extra = (
                            f" + {info.num_prefetch} scalar-prefetch refs"
                            if info.num_prefetch
                            else ""
                        )
                        yield Finding(
                            self.id,
                            ctx.path,
                            spec.node.lineno,
                            f"{role}_specs[{i}] index_map takes "
                            f"{len(spec.index_params)} args but the grid has "
                            f"{n_grid} axes{extra}",
                        )
                    if spec.shape_vals is not None and spec.index_body is not None:
                        if len(spec.index_body) != len(spec.shape_vals):
                            yield Finding(
                                self.id,
                                ctx.path,
                                spec.node.lineno,
                                f"{role}_specs[{i}] index_map returns "
                                f"{len(spec.index_body)} block indices for a "
                                f"rank-{len(spec.shape_vals)} block shape",
                            )


@register
class OutputCoverage(Rule):
    """PL02: every output block must be written by some grid step. Checks the
    resolvable part: a block-index component that is a bare grid parameter
    must sweep exactly ceil(dim / block) blocks; a constant component pins
    that dimension to one block, which is only valid when one block spans the
    whole dimension. Components the folder cannot resolve are skipped."""

    id = "PL02"
    pack = "pallas"
    title = "grid must cover every output block exactly"

    def check(self, ctx: FileContext, options: Options) -> Iterator[Finding]:
        for info in parse_pallas_calls(ctx):
            for i, spec in enumerate(info.out_specs):
                if spec.index_params is None or spec.index_body is None:
                    continue
                shape = spec.shape_vals or []
                out_dims = (
                    info.out_shapes[i][0] if i < len(info.out_shapes) else None
                )
                for d, comp in enumerate(spec.index_body):
                    block_d = shape[d] if d < len(shape) else None
                    out_d = out_dims[d] if out_dims and d < len(out_dims) else None
                    nblocks = (
                        math.ceil(out_d / block_d)
                        if (out_d and block_d)
                        else None
                    )
                    if isinstance(comp, ast.Name) and comp.id in spec.index_params:
                        axis = spec.index_params.index(comp.id)
                        grid_ax = (
                            info.grid_vals[axis]
                            if info.grid_vals is not None
                            and axis < len(info.grid_vals)
                            else None
                        )
                        if grid_ax is not None and nblocks is not None and grid_ax != nblocks:
                            word = "misses" if grid_ax < nblocks else "overruns"
                            yield Finding(
                                self.id,
                                ctx.path,
                                spec.node.lineno,
                                f"out_specs[{i}] dim {d}: grid axis "
                                f"'{comp.id}' sweeps {int(grid_ax)} blocks but the "
                                f"output needs {nblocks} — {word} output blocks",
                            )
                    elif isinstance(comp, ast.Constant) and isinstance(comp.value, int):
                        if comp.value != 0:
                            yield Finding(
                                self.id,
                                ctx.path,
                                spec.node.lineno,
                                f"out_specs[{i}] dim {d} is pinned to block "
                                f"{comp.value}; blocks 0..{comp.value - 1} are "
                                "never written",
                            )
                        elif nblocks is not None and nblocks != 1:
                            yield Finding(
                                self.id,
                                ctx.path,
                                spec.node.lineno,
                                f"out_specs[{i}] dim {d} is pinned to block 0 "
                                f"but the output spans {nblocks} blocks",
                            )


@register
class TileAlignment(Rule):
    """PL03: the last two block dimensions should be multiples of the dtype's
    native (sublane, lane) tile — (8,128) f32, (16,128) bf16, (32,128) int8.
    Misaligned blocks force Mosaic to pad every VMEM tile (silent bandwidth
    loss) and some layouts are rejected outright on real TPUs. Inputs default
    to the f32 tile when their dtype is unknowable; outputs use the
    ``out_shape`` dtype. Rank-0/1 blocks and unresolvable dims are skipped —
    among them a dim written as the operand's own ``x.shape[d]``, which spans
    the array and is the other layout Mosaic accepts; VMEM *scratch* is
    exempt (private, compiler-padded — PL04 budgets it)."""

    id = "PL03"
    pack = "pallas"
    title = "block shapes should align to the dtype's native (sublane, lane) tile"

    def check(self, ctx: FileContext, options: Options) -> Iterator[Finding]:
        for info in parse_pallas_calls(ctx):
            for role, specs in (("in", info.in_specs), ("out", info.out_specs)):
                for i, spec in enumerate(specs):
                    if not spec.shape_vals or len(spec.shape_vals) < 2:
                        continue
                    sub, lane = spec.shape_vals[-2], spec.shape_vals[-1]
                    if sub is None or lane is None:
                        continue
                    dtype = spec.dtype or "float32"
                    need_sub = SUBLANE[dtype]
                    bad_lane = lane % LANES != 0
                    bad_sub = sub % need_sub != 0
                    if bad_lane or bad_sub:
                        yield Finding(
                            self.id,
                            ctx.path,
                            spec.node.lineno,
                            f"{role}_specs[{i}] block tail "
                            f"({int(sub)}, {int(lane)}) is not a multiple of the "
                            f"native {dtype} tile ({need_sub}, {LANES})",
                        )


@register
class VmemBudget(Rule):
    """PL04: estimated per-grid-step VMEM footprint must fit the budget
    (default 16 MiB, ``--vmem-budget-mb``). Model: 2x every in/out block
    (the pipeline double-buffers HBM<->VMEM copies) plus scratch, bytes from
    the resolved dtype (inputs default f32). Specs with unresolvable dims are
    left out, so the estimate is a lower bound — an over-budget finding is
    real, a pass is best-effort."""

    id = "PL04"
    pack = "pallas"
    title = "estimated VMEM working set exceeds the budget"

    def check(self, ctx: FileContext, options: Options) -> Iterator[Finding]:
        for info in parse_pallas_calls(ctx):
            total = 0
            for spec in info.in_specs + info.out_specs:
                if not spec.shape_vals or any(v is None for v in spec.shape_vals):
                    continue
                nbytes = DTYPE_BYTES[spec.dtype or "float32"]
                total += 2 * int(math.prod(spec.shape_vals)) * nbytes
            for spec in info.scratch:
                if not spec.shape_vals or any(v is None for v in spec.shape_vals):
                    continue
                nbytes = DTYPE_BYTES[spec.dtype or "float32"]
                total += int(math.prod(spec.shape_vals)) * nbytes
            if total > options.vmem_budget_bytes:
                yield Finding(
                    self.id,
                    ctx.path,
                    info.node.lineno,
                    f"estimated VMEM working set {total / 2**20:.1f} MiB exceeds "
                    f"the {options.vmem_budget_bytes / 2**20:.0f} MiB budget "
                    "(2x in/out blocks + scratch)",
                )


def _guarded_nodes(kernel: ast.FunctionDef) -> set:
    """All AST nodes inside nested functions decorated with ``@pl.when``."""
    guarded: set = set()
    for node in ast.walk(kernel):
        if isinstance(node, ast.FunctionDef) and node is not kernel:
            if any(
                isinstance(dec, ast.Call) and tail_name(dec.func) == "when"
                for dec in node.decorator_list
            ):
                for sub in ast.walk(node):
                    guarded.add(id(sub))
    return guarded


@register
class RevisitedAccumulation(Rule):
    """PL05: an output whose index_map ignores a grid axis is revisited — the
    same block is live across every step of that axis, so every write to its
    ref must sit under ``@pl.when`` (the init/accumulate/emit pattern of
    ``ipls_aggregate_batched``'s R_TILE walk). An unguarded write either
    clobbers partial accumulation or, on the first visit, reads a block that
    was never initialized."""

    id = "PL05"
    pack = "pallas"
    title = "revisited output block written without @pl.when guard"

    def check(self, ctx: FileContext, options: Options) -> Iterator[Finding]:
        kernels: Dict[str, ast.FunctionDef] = {
            n.name: n for n in ast.walk(ctx.tree) if isinstance(n, ast.FunctionDef)
        }
        for info in parse_pallas_calls(ctx):
            kernel = kernels.get(info.kernel_name or "")
            if kernel is None:
                continue
            pos_params = [a.arg for a in kernel.args.args]  # kw-only are static
            n_in = info.num_prefetch + len(info.in_specs)
            guarded = _guarded_nodes(kernel)
            for i, spec in enumerate(info.out_specs):
                if spec.index_params is None or spec.index_body is None:
                    continue
                used = {
                    n.id
                    for comp in spec.index_body
                    for n in ast.walk(comp)
                    if isinstance(n, ast.Name)
                }
                axes = spec.index_params[: len(spec.index_params) - info.num_prefetch]
                ignored = [p for p in axes if p not in used]
                if not ignored:
                    continue  # every grid axis moves the block: no revisit
                if n_in + i >= len(pos_params):
                    continue
                ref = pos_params[n_in + i]
                for node in ast.walk(kernel):
                    if not isinstance(node, (ast.Assign, ast.AugAssign)):
                        continue
                    targets = (
                        node.targets if isinstance(node, ast.Assign) else [node.target]
                    )
                    for tgt in targets:
                        if (
                            isinstance(tgt, ast.Subscript)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == ref
                            and id(node) not in guarded
                        ):
                            yield Finding(
                                self.id,
                                ctx.path,
                                node.lineno,
                                f"kernel '{kernel.name}' writes revisited output "
                                f"ref '{ref}' (block constant across grid "
                                f"axis '{ignored[0]}') outside any @pl.when "
                                "guard",
                            )
