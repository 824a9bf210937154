"""pallas-budget: kernels fit VMEM and keep their index math divisible
(DESIGN.md §12.7).

A Pallas kernel that oversubscribes VMEM fails at *compile* time on
hardware — which in this repo means in the TPU CI tier or, worse, at
first tune-time on a customer box, not in the CPU-interpret tier-1 run
that merged the PR. This rule bounds the damage statically: for every
``pl.pallas_call`` it prices the per-grid-step footprint from the
``BlockSpec`` block shapes and ``scratch_shapes``, assuming worst-case
4-byte elements and the guide's double-buffered pipeline (×2 on in/out
blocks; scratch is already explicitly multi-buffered via ``n_buf``),
and compares against the per-backend budget below (16 MiB/core on TPU,
per the Pallas guide).

Symbolic dims (``block``, ``d_pad``…) are priced at the documented
upper bounds in ``DIM_BOUNDS``; a symbolic dim with no bound is itself
a finding — an unpriceable kernel is an unreviewable kernel.

Two shape-discipline checks ride along:
  * a constant trailing block dim not divisible by 128 wastes lanes on
    every TPU generation (the VPU/MXU lane width);
  * ``pl.ds(i * name, name)`` strided indexing requires a visible
    ``assert ... % name == 0``-style divisibility guard somewhere in
    the module — otherwise the last partial block reads out of bounds
    (Pallas pads silently in interpret mode and corrupts on hardware).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from repro.analysis.engine import FileContext, Finding, Rule, call_name

#: per-backend VMEM/shared-memory budget in bytes per grid step
BACKEND_BUDGETS = {"tpu": 16 * 1024 * 1024}

#: documented upper bounds for symbolic block-shape dims (DESIGN.md §12.7)
DIM_BOUNDS: Dict[str, int] = {
    "block": 4096,      # feature-block width, lane-aligned
    "d_pad": 65536,     # padded feature dim ceiling
    "n_buf": 8,         # streaming slot depth
    "rows": 32,         # sublanes of one HBM tile (8 fp32 … 32 int8)
    "width": 4096,      # lane-aligned fetch width, max(block, 128)
    "chunk": 2048,      # (query, arm) pairs per pull launch
    "n_out": 16,        # pull output rows; n_out·chunk ≤ 32768 words
}

_WORST_CASE_ITEMSIZE = 4   # f32/i32; bf16 kernels only ever cost less
_PIPELINE_FACTOR = 2       # double-buffered in/out blocks
_LANE = 128


def _dim_value(node: ast.AST) -> Optional[int]:
    """Concrete or bounded value of one block-shape dim, None when
    unpriceable."""
    if isinstance(node, ast.Constant) and node.value is None:
        return 1                    # squeezed dim: one element per block
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        return DIM_BOUNDS.get(node.id)
    return None


def _block_shape(spec: ast.Call) -> Optional[ast.Tuple]:
    """The block-shape tuple of a pl.BlockSpec(...) call, if present."""
    if spec.args and isinstance(spec.args[0], ast.Tuple):
        return spec.args[0]
    for kw in spec.keywords:
        if kw.arg in ("block_shape", None):
            if isinstance(kw.value, ast.Tuple):
                return kw.value
    return None


def _is_any_space(spec: ast.Call) -> bool:
    return any(kw.arg == "memory_space" for kw in spec.keywords)


class PallasBudgetRule(Rule):
    name = "pallas-budget"
    doc = ("every pallas_call's priced VMEM footprint fits the backend "
           "budget; strided pl.ds indexing carries a divisibility guard")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        calls = [n for n in ast.walk(ctx.tree)
                 if isinstance(n, ast.Call)
                 and call_name(n).endswith("pallas_call")]
        if not calls:
            return
        guards = self._divisibility_guards(ctx)
        for call in calls:
            yield from self._check_budget(ctx, call)
        yield from self._check_strides(ctx, guards)

    # -- VMEM pricing --------------------------------------------------------

    def _check_budget(self, ctx: FileContext,
                      call: ast.Call) -> Iterable[Finding]:
        total = 0
        priceable = True
        for kw in call.keywords:
            if kw.arg in ("in_specs", "out_specs", "scratch_shapes"):
                specs = kw.value.elts if isinstance(
                    kw.value, (ast.List, ast.Tuple)) else [kw.value]
                factor = 1 if kw.arg == "scratch_shapes" \
                    else _PIPELINE_FACTOR
                for spec in specs:
                    if not isinstance(spec, ast.Call):
                        continue
                    if _is_any_space(spec):
                        continue  # stays in HBM — not a VMEM block
                    shape = _block_shape(spec)
                    if shape is None:
                        continue
                    cost = _WORST_CASE_ITEMSIZE
                    for dim in shape.elts:
                        v = _dim_value(dim)
                        if v is None:
                            priceable = False
                            yield ctx.finding(
                                self.name, dim,
                                f"unpriceable block-shape dim "
                                f"{ctx.line_text(dim.lineno)!r} — give "
                                f"the symbol an upper bound in "
                                f"analysis.rules_pallas.DIM_BOUNDS so "
                                f"the VMEM footprint stays reviewable")
                        else:
                            cost *= v
                    total += cost * factor
                    # lane-alignment on the trailing dim
                    last = shape.elts[-1] if shape.elts else None
                    lv = _dim_value(last) if last is not None else None
                    if (isinstance(last, ast.Constant) and lv
                            and lv >= _LANE and lv % _LANE):
                        yield ctx.finding(
                            self.name, last,
                            f"trailing block dim {lv} is not a multiple "
                            f"of the {_LANE}-wide lane — pad to the "
                            f"lane width or throughput drops on every "
                            f"TPU generation")
        budget = BACKEND_BUDGETS["tpu"]
        if priceable and total > budget:
            yield ctx.finding(
                self.name, call,
                f"worst-case VMEM footprint {total // 1024} KiB exceeds "
                f"the {budget // (1024 * 1024)} MiB/core TPU budget "
                f"(priced at {_WORST_CASE_ITEMSIZE}-byte elements, "
                f"x{_PIPELINE_FACTOR} pipeline buffers) — shrink the "
                f"block shapes or tighten DIM_BOUNDS")

    # -- strided-index divisibility ------------------------------------------

    def _divisibility_guards(self, ctx: FileContext) -> Set[str]:
        """Names appearing as '% name' inside any assert in the module."""
        out: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assert):
                continue
            for sub in ast.walk(node.test):
                if (isinstance(sub, ast.BinOp)
                        and isinstance(sub.op, ast.Mod)
                        and isinstance(sub.right, ast.Name)):
                    out.add(sub.right.id)
        return out

    def _check_strides(self, ctx: FileContext,
                       guards: Set[str]) -> Iterable[Finding]:
        flagged: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node).endswith("pl.ds")
                    and len(node.args) == 2):
                continue
            start, size = node.args
            if not isinstance(size, ast.Name):
                continue
            strided = (isinstance(start, ast.BinOp)
                       and isinstance(start.op, ast.Mult)
                       and any(isinstance(s, ast.Name)
                               and s.id == size.id
                               for s in (start.left, start.right)))
            if strided and size.id not in guards \
                    and size.id not in flagged:
                flagged.add(size.id)
                yield ctx.finding(
                    self.name, node,
                    f"strided pl.ds(i * {size.id}, {size.id}) with no "
                    f"'% {size.id}' divisibility assert in the module — "
                    f"a ragged last block reads out of bounds on "
                    f"hardware (interpret mode pads silently)")
