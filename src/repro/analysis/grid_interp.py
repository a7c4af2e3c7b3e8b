"""Grid abstract interpreter: prove per-kernel launch invariants statically.

PR 7 proved *resource* feasibility (VMEM budgets) and one kernel's DMA
protocol. This module proves the remaining structural invariants of every
Pallas kernel body in ``repro.kernels`` — the properties that interpret
mode surfaces as exceptions but real hardware turns into silent
corruption:

1. **Bounds safety** — every ``BlockSpec`` index-map access and every
   in-kernel ``pl.dslice`` / ``pl.load`` / subscript stays inside its
   ref's shape for all grid points (``grid-oob-access``).
2. **Accumulator discipline** — scratch state is written under a guard
   that provably covers the first visit before any read (the
   ``_init``/``_acc`` protocol; ``acc-init-gap``) and accumulated values
   reach the output before being clobbered or dropped
   (``acc-flush-gap``).
3. **Output coverage / store discipline** — the grid × out-``BlockSpec``
   index map tiles the output exactly (``output-coverage-gap``) and
   revisited (output-stationary) blocks are stored only on their final
   visit (``store-before-final-visit``).
4. **Race freedom** — no scratch ref carries state across a grid axis
   declared ``"parallel"`` in ``dimension_semantics``
   (``parallel-axis-race``).

Two engines share one AST front end:

* a **concrete grid simulator** that enumerates a small, representative
  geometry per kernel (declared in :data:`GEOMETRIES`) in Pallas
  iteration order (row-major, last axis innermost) and runs boolean-mask
  state machines per ref — exact for the simulated geometry;
* an **interval evaluator** over affine forms of ``pl.program_id(d)``,
  loop variables and static args (sound interval arithmetic incl.
  ``//``/``%`` by positive constants, with guard-based range refinement)
  used by :func:`check_config_bounds` to prove bounds for *arbitrary*
  ``(variant, bm, bn)`` configs in O(1) of the grid size — this is what
  ``kernels.autotune`` and ``sparse.api.plan`` call per candidate.

BSR and any kernel whose index maps read scalar-prefetched arrays are
proved *conditionally on the host prep contract* (``ops.prep_bsr``
guarantees sorted ``row_of`` with a sentinel and at least one block per
block-row); the proof matrix marks these.

Pure Python + numpy (no jax import), like the rest of ``repro.analysis``.
"""
from __future__ import annotations

import ast
import dataclasses
import itertools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .kernel_check import RULE_OOB, Violation

# Rule identifiers (stable: tests, CI output and the registry key on
# these). RULE_OOB lives in kernel_check so LAUNCH_RULES can name it
# without importing this module.
RULE_ACC_INIT = "acc-init-gap"
RULE_ACC_FLUSH = "acc-flush-gap"
RULE_STORE_FINAL = "store-before-final-visit"
RULE_COVERAGE = "output-coverage-gap"
RULE_RACE = "parallel-axis-race"
RULE_UNVERIFIABLE = "grid-unverifiable"

RULES: Dict[str, str] = {
    RULE_OOB: "every BlockSpec index-map / dslice / load access must stay "
              "inside its ref's shape for all grid points",
    RULE_ACC_INIT: "scratch state must be initialized under a guard "
                   "covering the first visit before any read",
    RULE_ACC_FLUSH: "accumulated scratch state must reach the output "
                    "before being overwritten or dropped at grid exit",
    RULE_STORE_FINAL: "revisited (output-stationary) out blocks may be "
                      "stored only on their final visit",
    RULE_COVERAGE: "the grid x out-BlockSpec index maps must tile the "
                   "output exactly",
    RULE_RACE: "no scratch ref may carry state across a grid axis "
               "declared \"parallel\" in dimension_semantics",
    RULE_UNVERIFIABLE: "a guard, slot or index the interpreter cannot "
                       "evaluate statically",
}

GRID_RULES = tuple(RULES)


@dataclasses.dataclass(frozen=True)
class GridFinding:
    kernel: str
    rule: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.rule} [{self.kernel}] (line {self.line}): " \
               f"{self.message}"


# ----------------------------------------------------------------------
# Interval domain.
@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed integer interval [lo, hi] — the abstract value of an affine
    form over grid ids / loop vars with known ranges."""
    lo: int
    hi: int

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @staticmethod
    def of(v) -> "Interval":
        if isinstance(v, Interval):
            return v
        return Interval(int(v), int(v))

    def __add__(self, o):
        o = Interval.of(o)
        return Interval(self.lo + o.lo, self.hi + o.hi)

    __radd__ = __add__

    def __sub__(self, o):
        o = Interval.of(o)
        return Interval(self.lo - o.hi, self.hi - o.lo)

    def __rsub__(self, o):
        return Interval.of(o) - self

    def __mul__(self, o):
        o = Interval.of(o)
        c = [self.lo * o.lo, self.lo * o.hi, self.hi * o.lo, self.hi * o.hi]
        return Interval(min(c), max(c))

    __rmul__ = __mul__

    def __neg__(self):
        return Interval(-self.hi, -self.lo)

    def __floordiv__(self, o):
        # Sound only for a concrete positive divisor (floor is monotonic).
        o = Interval.of(o)
        if o.lo != o.hi or o.lo <= 0:
            raise _OpaqueError("floordiv by non-constant/non-positive")
        return Interval(self.lo // o.lo, self.hi // o.lo)

    def __mod__(self, o):
        o = Interval.of(o)
        if o.lo != o.hi or o.lo <= 0:
            raise _OpaqueError("mod by non-constant/non-positive")
        c = o.lo
        if self.lo // c == self.hi // c and self.lo >= 0:
            return Interval(self.lo % c, self.hi % c)
        return Interval(0, c - 1)      # range spans a period boundary

    def cmp(self, op: str, o) -> Optional[bool]:
        """Tri-state comparison: True / False / None (undecidable)."""
        o = Interval.of(o)
        if op == "<":
            if self.hi < o.lo:
                return True
            if self.lo >= o.hi:
                return False
        elif op == "<=":
            if self.hi <= o.lo:
                return True
            if self.lo > o.hi:
                return False
        elif op == ">":
            return Interval.of(o).cmp("<", self)
        elif op == ">=":
            return Interval.of(o).cmp("<=", self)
        elif op == "==":
            if self.lo == self.hi == o.lo == o.hi:
                return True
            if self.hi < o.lo or self.lo > o.hi:
                return False
        elif op == "!=":
            eq = self.cmp("==", o)
            return None if eq is None else not eq
        return None


MAYBE = object()                       # undecidable guard value


class _OpaqueError(Exception):
    """Raised when an expression is not statically evaluable."""


@dataclasses.dataclass(frozen=True)
class DSlice:
    """Abstract value of ``pl.dslice(start, size)``."""
    start: Any                         # int | Interval
    size: int


class _FullSlice:
    pass


FULL = _FullSlice()


@dataclasses.dataclass
class RefVal:
    """What a kernel ref parameter looks like to the evaluator: a shape
    (for ``idx_ref.shape[1]``-style closures) and an ``.at`` property so
    ``buf.at[...]`` parses; data reads stay opaque (the event layer
    tracks them)."""
    name: str
    shape: Tuple[int, ...]

    @property
    def at(self):
        return self


class _PlShim:
    """``pl.*`` as seen from one grid point (or an interval thereof)."""

    def __init__(self, pids: Sequence[Any], grid: Sequence[int]):
        self._pids = tuple(pids)
        self._grid = tuple(grid)

    def program_id(self, d):
        return self._pids[int(d)]

    def num_programs(self, d):
        return self._grid[int(d)]

    def dslice(self, start, size):
        return DSlice(start, int(size))

    ds = dslice

    def load(self, *a, **k):
        raise _OpaqueError("pl.load value is opaque")

    def when(self, *a, **k):
        raise _OpaqueError("pl.when outside decorator position")


def _imax(a, b):
    if isinstance(a, Interval) or isinstance(b, Interval):
        a, b = Interval.of(a), Interval.of(b)
        return Interval(max(a.lo, b.lo), max(a.hi, b.hi))
    return max(a, b)


def _imin(a, b):
    if isinstance(a, Interval) or isinstance(b, Interval):
        a, b = Interval.of(a), Interval.of(b)
        return Interval(min(a.lo, b.lo), min(a.hi, b.hi))
    return min(a, b)


class _JnpShim:
    maximum = staticmethod(_imax)
    minimum = staticmethod(_imin)

    def __getattr__(self, name):
        raise _OpaqueError(f"jnp.{name} is opaque")


_CMP_OPS = {ast.Lt: "<", ast.LtE: "<=", ast.Gt: ">", ast.GtE: ">=",
            ast.Eq: "==", ast.NotEq: "!="}


def _eval(node: ast.expr, env: Dict[str, Any]):
    """Evaluate an index/guard expression over ints, Intervals, numpy
    arrays (scalar prefetch), DSlices and shims. Raises ``_OpaqueError``
    for anything outside that language; comparisons over intervals may
    return ``MAYBE``."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _OpaqueError(f"unbound name {node.id!r}")
    if isinstance(node, ast.Tuple):
        return tuple(_eval(e, env) for e in node.elts)
    if isinstance(node, ast.Attribute):
        base = _eval(node.value, env)
        try:
            return getattr(base, node.attr)
        except AttributeError:
            raise _OpaqueError(f"no attribute {node.attr!r}")
    if isinstance(node, ast.Subscript):
        base = _eval(node.value, env)
        idx = _eval(node.slice, env)
        if isinstance(base, (tuple, np.ndarray)):
            try:
                v = base[idx]
            except (IndexError, TypeError, ValueError):
                raise _OpaqueError("unevaluable subscript")
            return int(v) if isinstance(v, np.integer) else v
        raise _OpaqueError("subscript of opaque value")
    if isinstance(node, ast.Slice):
        if node.lower is None and node.upper is None and node.step is None:
            return FULL
        raise _OpaqueError("non-trivial python slice")
    if isinstance(node, ast.UnaryOp):
        v = _eval(node.operand, env)
        if isinstance(node.op, ast.USub):
            return -v
        if isinstance(node.op, ast.Not):
            if v is MAYBE:
                return MAYBE
            return not v
        raise _OpaqueError("unary op")
    if isinstance(node, ast.BinOp):
        lhs, rhs = _eval(node.left, env), _eval(node.right, env)
        op = node.op
        if isinstance(op, (ast.BitOr, ast.BitAnd)):
            # boolean combinators in guards: (t == 0) | (...)
            if lhs is MAYBE or rhs is MAYBE:
                if isinstance(op, ast.BitOr) and (lhs is True
                                                  or rhs is True):
                    return True
                if isinstance(op, ast.BitAnd) and (lhs is False
                                                   or rhs is False):
                    return False
                return MAYBE
            return (lhs | rhs) if isinstance(op, ast.BitOr) else (lhs & rhs)
        try:
            if isinstance(op, ast.Add):
                return lhs + rhs
            if isinstance(op, ast.Sub):
                return lhs - rhs
            if isinstance(op, ast.Mult):
                return lhs * rhs
            if isinstance(op, ast.FloorDiv):
                if isinstance(lhs, Interval) or isinstance(rhs, Interval):
                    return Interval.of(lhs) // Interval.of(rhs)
                return lhs // rhs
            if isinstance(op, ast.Mod):
                if isinstance(lhs, Interval) or isinstance(rhs, Interval):
                    return Interval.of(lhs) % Interval.of(rhs)
                return lhs % rhs
            if isinstance(op, ast.Div):
                return lhs / rhs
        except (TypeError, ZeroDivisionError):
            raise _OpaqueError("arithmetic on opaque operands")
        raise _OpaqueError(f"binop {type(op).__name__}")
    if isinstance(node, ast.Compare):
        if len(node.ops) != 1:
            raise _OpaqueError("chained comparison")
        lhs = _eval(node.left, env)
        rhs = _eval(node.comparators[0], env)
        op = node.ops[0]
        if isinstance(op, (ast.Is, ast.IsNot)):
            r = lhs is rhs
            return r if isinstance(op, ast.Is) else not r
        sym = _CMP_OPS.get(type(op))
        if sym is None:
            raise _OpaqueError("comparison op")
        if isinstance(lhs, Interval) or isinstance(rhs, Interval):
            r = Interval.of(lhs).cmp(sym, rhs)
            return MAYBE if r is None else r
        v = {"<": lhs < rhs, "<=": lhs <= rhs, ">": lhs > rhs,
             ">=": lhs >= rhs, "==": lhs == rhs, "!=": lhs != rhs}[sym]
        return bool(v)
    if isinstance(node, ast.BoolOp):
        vals = [_eval(v, env) for v in node.values]
        if isinstance(node.op, ast.And):
            if any(v is False for v in vals):
                return False
            return MAYBE if any(v is MAYBE for v in vals) else True
        if any(v is True for v in vals):
            return True
        return MAYBE if any(v is MAYBE for v in vals) else False
    if isinstance(node, ast.IfExp):
        t = _eval(node.test, env)
        if t is MAYBE:
            raise _OpaqueError("interval-valued IfExp test")
        return _eval(node.body if t else node.orelse, env)
    if isinstance(node, ast.Call):
        fn = _eval(node.func, env)
        if not callable(fn):
            raise _OpaqueError("call of non-callable")
        args = [_eval(a, env) for a in node.args]
        kwargs = {k.arg: _eval(k.value, env) for k in node.keywords
                  if k.arg is not None}
        try:
            return fn(*args, **kwargs)
        except _OpaqueError:
            raise
        except Exception:
            raise _OpaqueError("call failed")
    raise _OpaqueError(f"unsupported node {type(node).__name__}")


def _slice_shim(*args):
    if all(a is None for a in args):
        return FULL
    raise _OpaqueError("non-trivial slice()")


def _fold_assign(stmt: ast.stmt, env: Dict[str, Any]) -> None:
    """Best-effort fold of one assignment into ``env`` (skip on opaque)."""
    try:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            tgt = stmt.targets[0]
            if isinstance(tgt, ast.Name):
                env[tgt.id] = _eval(stmt.value, env)
            elif isinstance(tgt, ast.Tuple):
                if isinstance(stmt.value, ast.Tuple) \
                        and len(tgt.elts) == len(stmt.value.elts):
                    pairs = list(zip(tgt.elts, stmt.value.elts))
                    for t_el, v_el in pairs:
                        if isinstance(t_el, ast.Name):
                            try:
                                env[t_el.id] = _eval(v_el, env)
                            except _OpaqueError:
                                pass
                else:
                    val = _eval(stmt.value, env)
                    if isinstance(val, tuple) \
                            and len(val) == len(tgt.elts):
                        for t_el, v in zip(tgt.elts, val):
                            if isinstance(t_el, ast.Name):
                                env[t_el.id] = v
        elif isinstance(stmt, ast.AugAssign) \
                and isinstance(stmt.target, ast.Name):
            cur = env.get(stmt.target.id)
            if cur is None:
                raise _OpaqueError("augassign of unbound name")
            fake = ast.BinOp(left=ast.Name(id=stmt.target.id,
                                           ctx=ast.Load()),
                             op=stmt.op, right=stmt.value)
            env[stmt.target.id] = _eval(fake, env)
    except _OpaqueError:
        pass


# ----------------------------------------------------------------------
# Kernel model: parsed pallas_call launch geometry + kernel body.
def _dotted_name(node: ast.expr) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _tname(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


@dataclasses.dataclass
class BlockModel:
    """One BlockSpec: a block shape + index-map lambda, or an
    ``memory_space=ANY`` whole-operand ref (no automatic pipeline)."""
    block_shape: Optional[Tuple[int, ...]]
    index_map: Optional[ast.Lambda]

    @property
    def is_any(self) -> bool:
        return self.block_shape is None


@dataclasses.dataclass
class SimRef:
    name: str
    kind: str                          # in | out | scratch | prefetch | sem
    shape: Tuple[int, ...]


@dataclasses.dataclass
class KernelModel:
    entry: str
    kernel_fn: ast.FunctionDef
    kernel_kwargs: Dict[str, Any]
    grid: Tuple[int, ...]
    in_specs: List[BlockModel]
    out_spec: BlockModel
    out_shape: Tuple[int, ...]
    scratch: List[Tuple[str, Tuple[int, ...]]]   # (kind, shape)
    semantics: Tuple[str, ...]
    num_scalar_prefetch: int
    entry_env: Dict[str, Any]


class ModelError(Exception):
    """The launch geometry could not be parsed/evaluated statically."""


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _parse_specs(node: ast.expr, env: Dict[str, Any]) -> List[BlockModel]:
    if not isinstance(node, (ast.List, ast.Tuple)):
        raise ModelError("in_specs is not a literal list")
    return [_parse_spec(el, env) for el in node.elts]


def _parse_spec(el: ast.expr, env: Dict[str, Any]) -> BlockModel:
    if not (isinstance(el, ast.Call) and _tname(el.func) == "BlockSpec"):
        raise ModelError("non-BlockSpec entry in specs")
    if len(el.args) >= 2 and isinstance(el.args[1], ast.Lambda):
        shape = _eval(el.args[0], env)
        if not isinstance(shape, tuple):
            shape = (shape,)
        return BlockModel(tuple(int(d) for d in shape), el.args[1])
    if _kw(el, "memory_space") is not None:
        return BlockModel(None, None)
    raise ModelError("BlockSpec without (shape, index_map) or "
                     "memory_space")


def build_model(tree: ast.Module, entry: str,
                env: Dict[str, Any]) -> KernelModel:
    """Parse one entry point's ``pl.pallas_call`` launch into a
    :class:`KernelModel`, folding the entry body's simple assignments
    (``grid = ...``, ``n_ct = n // bn``) over the geometry ``env``."""
    fn = next((n for n in tree.body if isinstance(n, ast.FunctionDef)
               and n.name == entry), None)
    if fn is None:
        raise ModelError(f"entry point {entry!r} not found")
    env = dict(env)
    partials: Dict[str, Tuple[str, List[ast.keyword]]] = {}
    for stmt in fn.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and isinstance(stmt.value, ast.Call) \
                and _tname(stmt.value.func) == "partial" \
                and stmt.value.args \
                and isinstance(stmt.value.args[0], ast.Name):
            partials[stmt.targets[0].id] = (stmt.value.args[0].id,
                                            stmt.value.keywords)
        _fold_assign(stmt, env)
    call = next((n for n in ast.walk(fn) if isinstance(n, ast.Call)
                 and _tname(n.func) == "pallas_call"), None)
    if call is None or not call.args:
        raise ModelError(f"{entry}: no pallas_call found")

    # Kernel function: a Name, or functools.partial(_kernel, **static).
    karg = call.args[0]
    kw_nodes: List[ast.keyword] = []
    if isinstance(karg, ast.Call) and _tname(karg.func) == "partial" \
            and karg.args and isinstance(karg.args[0], ast.Name):
        kname, kw_nodes = karg.args[0].id, karg.keywords
    elif isinstance(karg, ast.Name) and karg.id in partials:
        kname, kw_nodes = partials[karg.id]
    elif isinstance(karg, ast.Name):
        kname = karg.id
    else:
        raise ModelError(f"{entry}: cannot resolve kernel function")
    kfn = next((n for n in tree.body if isinstance(n, ast.FunctionDef)
                and n.name == kname), None)
    if kfn is None:
        raise ModelError(f"{entry}: kernel body {kname!r} not found")
    kkw: Dict[str, Any] = {}
    for k in kw_nodes:
        if k.arg is None:
            continue
        try:
            kkw[k.arg] = _eval(k.value, env)
        except _OpaqueError:
            pass                       # e.g. scale=1.0/np.sqrt(hd)

    # Launch geometry, either flat kwargs or a PrefetchScalarGridSpec.
    nsp = 0
    grid_e = _kw(call, "grid")
    in_e, out_e, scratch_e = (_kw(call, "in_specs"),
                              _kw(call, "out_specs"),
                              _kw(call, "scratch_shapes"))
    gs = _kw(call, "grid_spec")
    if gs is not None:
        if not (isinstance(gs, ast.Call)
                and _tname(gs.func) == "PrefetchScalarGridSpec"):
            raise ModelError(f"{entry}: unsupported grid_spec")
        nsp_e = _kw(gs, "num_scalar_prefetch")
        nsp = int(_eval(nsp_e, env)) if nsp_e is not None else 0
        grid_e, in_e = _kw(gs, "grid"), _kw(gs, "in_specs")
        out_e = _kw(gs, "out_specs")
        scratch_e = _kw(gs, "scratch_shapes")
    if grid_e is None or in_e is None or out_e is None:
        raise ModelError(f"{entry}: grid/in_specs/out_specs not found")
    grid = _eval(grid_e, env)
    if not isinstance(grid, tuple):
        grid = (grid,)
    grid = tuple(int(g) for g in grid)

    in_specs = _parse_specs(in_e, env)
    out_spec = _parse_spec(out_e, env)

    shape_e = _kw(call, "out_shape")
    if not (isinstance(shape_e, ast.Call)
            and _tname(shape_e.func) == "ShapeDtypeStruct"
            and shape_e.args):
        raise ModelError(f"{entry}: out_shape is not a ShapeDtypeStruct")
    out_shape = tuple(int(d) for d in _eval(shape_e.args[0], env))

    scratch: List[Tuple[str, Tuple[int, ...]]] = []
    if scratch_e is not None:
        if not isinstance(scratch_e, (ast.List, ast.Tuple)):
            raise ModelError(f"{entry}: scratch_shapes not literal")
        for el in scratch_e.elts:
            if not isinstance(el, ast.Call):
                raise ModelError(f"{entry}: non-call scratch entry")
            kind = _dotted_name(el.func)
            kind = "sem" if "SemaphoreType" in kind else "VMEM"
            shp = _eval(el.args[0], env) if el.args else ()
            if not isinstance(shp, tuple):
                shp = (shp,)
            scratch.append((kind, tuple(int(d) for d in shp)))

    semantics: Tuple[str, ...] = tuple("arbitrary" for _ in grid)
    cp = _kw(call, "compiler_params")
    if isinstance(cp, ast.Call):
        ds = _kw(cp, "dimension_semantics")
        if ds is not None:
            semantics = tuple(_eval(ds, env))
    if len(semantics) != len(grid):
        raise ModelError(f"{entry}: dimension_semantics arity "
                         f"{len(semantics)} != grid rank {len(grid)}")

    return KernelModel(entry, kfn, kkw, grid, in_specs, out_spec,
                       out_shape, scratch, semantics, nsp, env)


# ----------------------------------------------------------------------
# Per-kernel concrete geometries: the smallest launch that exercises
# every guard arm (>= 2 tiles per axis, >= 3 reduction steps, at least
# one revisited output row for BSR). The simulator is exact for the
# geometry it runs; these are chosen so every structural invariant is
# load-bearing at this size.
@dataclasses.dataclass
class Geometry:
    module: str                        # file under repro/kernels/
    entry: str
    env: Dict[str, Any]
    operands: Tuple[Tuple[int, ...], ...]   # per in_spec array shapes
    prefetch: Tuple[np.ndarray, ...] = ()
    note: str = ""                     # proof-conditionality note


_INCRS_ENV = dict(m=16, mp=16, bm=8, n=256, bn=128, n_sections=3,
                  smax=4, section=16, k=48)
_INCRS_OPS = ((3, 16, 4), (3, 16, 4), (48, 256))

GEOMETRIES: Dict[str, Geometry] = {
    "incrs_spmm": Geometry(
        "incrs_spmm.py", "incrs_spmm", dict(_INCRS_ENV), _INCRS_OPS),
    "incrs_spmm_reuse": Geometry(
        "incrs_spmm.py", "incrs_spmm_reuse", dict(_INCRS_ENV),
        _INCRS_OPS),
    "incrs_spmm_pipelined": Geometry(
        "incrs_spmm.py", "incrs_spmm_pipelined", dict(_INCRS_ENV),
        _INCRS_OPS),
    "bsr_spmm": Geometry(
        "bsr_spmm.py", "bsr_spmm",
        dict(nnz=4, bm=8, bk=8, k=16, n=256, bn=128, n_block_rows=3),
        ((4, 8, 8), (16, 256)),
        prefetch=(np.array([0, 1, 2, 2, 2], dtype=np.int64),
                  np.array([0, 1, 0, 1], dtype=np.int64)),
        note="conditional on the ops.prep_bsr contract: row_of sorted "
             "with one sentinel repeat, >= 1 block per block-row"),
    "dense_mm": Geometry(
        "dense_mm.py", "dense_mm",
        dict(m=16, k=32, n=256, bm=8, bk=16, bn=128),
        ((16, 32), (32, 256))),
    "index_match_spmm": Geometry(
        "index_match_spmm.py", "index_match_spmm",
        dict(m=16, n=16, bm=8, bn=8, rounds=16, n_rounds=2, rmax_a=3,
             rmax_b=3),
        ((2, 16, 3), (2, 16, 3), (2, 16, 3), (2, 16, 3))),
    "flash_attention": Geometry(
        "flash_attention.py", "flash_attention",
        dict(lanes=4, g=2, sq=16, sk=16, hd=8, bq=8, bk=8, window=None,
             soft_cap=None),
        ((4, 16, 8), (2, 16, 8), (2, 16, 8))),
    "incrs_gather": Geometry(
        "incrs_gather.py", "incrs_gather",
        dict(m=16, bm=8, n_sections=3, smax=4, section=16),
        ((3, 16, 4), (3, 16, 4))),
    "spgemm_condense": Geometry(
        "spgemm/kernels.py", "spgemm_condense",
        dict(m=16, n=16, bm=8, bn=8, rounds=16, n_rounds=2, rmax_a=3,
             rmax_b=3),
        ((2, 16, 3), (2, 16, 3), (2, 16, 3), (2, 16, 3))),
    "spgemm_merge": Geometry(
        "spgemm/kernels.py", "spgemm_merge",
        dict(m=16, n=16, bm=8, bn=8, n_rounds=2),
        ((2, 16, 16),)),
}

KERNELS = tuple(GEOMETRIES)


def kernels_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "kernels")


def package_dir() -> str:
    return os.path.dirname(os.path.dirname(__file__))


def module_path(module: str) -> str:
    """Resolve a ``Geometry.module`` string to a file path. Plain names
    live under ``repro/kernels/``; "/"-qualified names (e.g.
    ``spgemm/kernels.py``) are relative to the repro package root."""
    if "/" in module:
        return os.path.join(package_dir(), *module.split("/"))
    return os.path.join(kernels_dir(), module)


def _load_source(module: str,
                 sources: Optional[Dict[str, str]] = None) -> str:
    if sources is not None and module in sources:
        return sources[module]
    with open(module_path(module)) as f:
        return f.read()


# ----------------------------------------------------------------------
# Event extraction: kernel body -> ordered item tree.
#   ("assign", stmt)                     fold into env at run time
#   ("access", Access)                   ref read/write/touch
#   ("when", guard|None, items)          pl.when / python-if true branch
#   ("if", test, items, else_items)
#   ("loop", var, lo, hi, items)         unrolled fori_loop body
#   ("call", helper, [arg exprs], line)  local helper invocation
#   ("opaque", line, reason)
@dataclasses.dataclass
class Access:
    kind: str                          # read | write | touch
    ref: str
    index: Optional[ast.expr]          # None = whole ref
    line: int
    reads_self: bool = False
    value_reads: Tuple[Tuple[str, Optional[ast.expr]], ...] = ()


class _Extractor:
    def __init__(self, refnames):
        self.refs = set(refnames)
        self.helpers: Dict[str, Tuple[List[str], list]] = {}

    def _sub_target(self, node):
        """(ref, index) if node is a subscript (or .at subscript) rooted
        at a ref name, else None."""
        if not isinstance(node, ast.Subscript):
            return None
        base = node.value
        if isinstance(base, ast.Attribute) and base.attr == "at":
            base = base.value
        if isinstance(base, ast.Name) and base.id in self.refs:
            return base.id, node.slice
        return None

    def scan_expr(self, node, items, reads):
        """Ordered scan of an expression for accesses/calls/loops.
        ``reads`` collects (ref, idx) read pairs for RMW detection."""
        if node is None:
            return
        if isinstance(node, ast.Call):
            name = _tname(node.func)
            if name == "fori_loop" and len(node.args) >= 3 \
                    and isinstance(node.args[2], ast.Name):
                self.scan_expr(node.args[0], items, reads)
                self.scan_expr(node.args[1], items, reads)
                body = node.args[2].id
                if body in self.helpers:
                    params, bitems = self.helpers[body]
                    items.append(("loop", params[0], node.args[0],
                                  node.args[1], bitems))
                else:
                    items.append(("opaque", node.lineno,
                                  f"fori_loop body {body!r} not found"))
                return
            if name == "make_async_copy":
                kinds = ("read", "write", "touch")
                for pos, arg in enumerate(node.args[:3]):
                    tgt = self._sub_target(arg)
                    if tgt is not None:
                        ref, idx = tgt
                        self.scan_expr(idx, items, reads)
                        k = kinds[pos]
                        if k == "read":
                            reads.append((ref, idx))
                        items.append(("access",
                                      Access(k, ref, idx, node.lineno)))
                    else:
                        self.scan_expr(arg, items, reads)
                return
            if name in ("load", "store") and len(node.args) >= 2 \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in self.refs:
                ref = node.args[0].id
                idx = node.args[1]
                self.scan_expr(idx, items, reads)
                kind = "read" if name == "load" else "write"
                if kind == "read":
                    reads.append((ref, idx))
                items.append(("access", Access(kind, ref, idx,
                                               node.lineno)))
                for extra in node.args[2:]:
                    self.scan_expr(extra, items, reads)
                return
            if isinstance(node.func, ast.Name) \
                    and node.func.id in self.helpers:
                for a in node.args:
                    self.scan_expr(a, items, reads)
                items.append(("call", node.func.id, list(node.args),
                              node.lineno))
                return
            self.scan_expr(node.func, items, reads)
            for a in node.args:
                self.scan_expr(a, items, reads)
            for k in node.keywords:
                self.scan_expr(k.value, items, reads)
            return
        tgt = self._sub_target(node)
        if tgt is not None:
            ref, idx = tgt
            self.scan_expr(idx, items, reads)
            is_at = isinstance(node.value, ast.Attribute)
            kind = "touch" if is_at else "read"
            if kind == "read":
                reads.append((ref, idx))
            items.append(("access", Access(kind, ref, idx, node.lineno)))
            return
        for child in ast.iter_child_nodes(node):
            self.scan_expr(child, items, reads)

    def extract(self, stmts) -> list:
        items: list = []
        for stmt in stmts:
            if isinstance(stmt, ast.FunctionDef):
                guard = None
                is_when = False
                for dec in stmt.decorator_list:
                    if isinstance(dec, ast.Call) \
                            and _tname(dec.func) == "when" and dec.args:
                        guard, is_when = dec.args[0], True
                if is_when:
                    items.append(("when", guard, self.extract(stmt.body)))
                else:
                    params = [a.arg for a in stmt.args.args]
                    self.helpers[stmt.name] = (params,
                                               self.extract(stmt.body))
                continue
            if isinstance(stmt, ast.If):
                body = self.extract(stmt.body)
                orelse = self.extract(stmt.orelse)
                items.append(("if", stmt.test, body, orelse))
                continue
            if isinstance(stmt, (ast.For, ast.While)):
                items.append(("opaque", stmt.lineno,
                              "python-level loop in kernel body"))
                continue
            if isinstance(stmt, ast.Assign):
                reads: list = []
                self.scan_expr(stmt.value, items, reads)
                for tgt in stmt.targets:
                    st = self._sub_target(tgt)
                    if st is not None:
                        ref, idx = st
                        self.scan_expr(idx, items, reads)
                        items.append(("access", Access(
                            "write", ref, idx, stmt.lineno,
                            reads_self=any(r == ref for r, _ in reads),
                            value_reads=tuple(reads))))
                items.append(("assign", stmt))
                continue
            if isinstance(stmt, ast.AugAssign):
                st = self._sub_target(stmt.target)
                reads = []
                self.scan_expr(stmt.value, items, reads)
                if st is not None:
                    ref, idx = st
                    self.scan_expr(idx, items, reads)
                    items.append(("access", Access("read", ref, idx,
                                                   stmt.lineno)))
                    items.append(("access", Access(
                        "write", ref, idx, stmt.lineno, reads_self=True,
                        value_reads=tuple(reads) + ((ref, idx),))))
                else:
                    items.append(("assign", stmt))
                continue
            if isinstance(stmt, (ast.Expr, ast.Return)):
                reads = []
                self.scan_expr(stmt.value, items, reads)
                continue
            # Anything else (with/try/...) is outside the kernel DSL.
            items.append(("opaque", stmt.lineno,
                          f"unsupported statement "
                          f"{type(stmt).__name__}"))
        return items


# ----------------------------------------------------------------------
# Grid simulation.
class _RefState:
    def __init__(self, shape):
        self.live = np.zeros(shape, dtype=bool)
        self.flushed = np.ones(shape, dtype=bool)
        self.writer = np.full(shape, -1, dtype=np.int64)

    def reset(self):
        self.live[...] = False
        self.flushed[...] = True
        self.writer[...] = -1


def _region(index: Optional[ast.expr], shape: Tuple[int, ...],
            env: Dict[str, Any]) -> List[Tuple[int, int]]:
    """Evaluate a subscript/index expression to per-dim [lo, hi) element
    bounds (conservative outer box under interval values)."""
    if index is None:
        return [(0, d) for d in shape]
    v = _eval(index, env)
    elems = list(v) if isinstance(v, tuple) else [v]
    out: List[Tuple[int, int]] = []
    it = iter(range(len(elems)))
    for pos, el in enumerate(elems):
        if el is Ellipsis:
            # expand to cover the remaining unmatched dims
            n_rest = len(elems) - pos - 1
            while len(out) < len(shape) - n_rest:
                out.append((0, shape[len(out)]))
            continue
        d = shape[len(out)] if len(out) < len(shape) else 0
        if isinstance(el, _FullSlice):
            out.append((0, d))
        elif isinstance(el, (int, np.integer)) \
                and not isinstance(el, bool):
            out.append((int(el), int(el) + 1))
        elif isinstance(el, Interval):
            out.append((el.lo, el.hi + 1))
        elif isinstance(el, DSlice):
            s = el.start
            if isinstance(s, Interval):
                out.append((s.lo, s.hi + el.size))
            else:
                out.append((int(s), int(s) + el.size))
        else:
            raise _OpaqueError(f"unsupported index element "
                               f"{type(el).__name__}")
    del it
    while len(out) < len(shape):
        out.append((0, shape[len(out)]))
    if len(out) > len(shape):
        raise _OpaqueError("index rank exceeds ref rank")
    return out


def _map_blocks(spec: BlockModel, pids, prefetch, env):
    """Evaluate a BlockSpec index map at one grid point (or interval)."""
    lam = spec.index_map
    child = dict(env)
    params = [a.arg for a in lam.args.args]
    vals = list(pids) + list(prefetch)
    for p, v in zip(params, vals):
        child[p] = v
    r = _eval(lam.body, child)
    if not isinstance(r, tuple):
        r = (r,)
    return r


class _Sim:
    """Shared walker for the concrete grid simulator and the
    interval-bounds pass (``bounds_only=True`` skips all state)."""

    def __init__(self, model: KernelModel, geom: Geometry,
                 extractor: _Extractor, items: list,
                 refs: Dict[str, SimRef], kernel_env: Dict[str, Any],
                 bounds_only: bool = False):
        self.model, self.geom = model, geom
        self.helpers = extractor.helpers
        self.items, self.refs = items, refs
        self.kernel_env = kernel_env
        self.bounds_only = bounds_only
        self.findings: List[GridFinding] = []
        self._seen: set = set()
        self.acc_refs = self._classify_accumulators(items)
        self.state: Dict[str, _RefState] = {}
        self.step = -1
        self.coords: Tuple[int, ...] = ()
        self.steps: List[Tuple[int, ...]] = []
        self.out_name: Optional[str] = None
        self.cur_block: Optional[Tuple[int, ...]] = None
        self.final_visit: Dict[Tuple[int, ...], int] = {}
        self.cov: Optional[np.ndarray] = None

    # -- finding plumbing ------------------------------------------------
    def emit(self, rule: str, line: int, message: str, key=None):
        k = key if key is not None else (rule, line, message)
        if k in self._seen:
            return
        self._seen.add(k)
        self.findings.append(GridFinding(self.model.entry, rule, line,
                                         message))

    def unverifiable(self, line: int, reason: str):
        self.emit(RULE_UNVERIFIABLE, line, reason,
                  key=(RULE_UNVERIFIABLE, line))

    # -- accumulator classification --------------------------------------
    def _classify_accumulators(self, items) -> set:
        """Scratch refs that carry cross-step numeric state: targets of
        read-modify-write, plus any scratch read directly by a store to
        the output ref (the flush)."""
        acc: set = set()

        def walk(its):
            for it in its:
                if it[0] == "access":
                    a: Access = it[1]
                    ref = self.refs.get(a.ref)
                    if ref is None:
                        continue
                    if a.kind == "write" and a.reads_self \
                            and ref.kind == "scratch":
                        acc.add(a.ref)
                    if a.kind == "write" and ref.kind == "out":
                        for r, _ in a.value_reads:
                            if self.refs.get(r) is not None \
                                    and self.refs[r].kind == "scratch":
                                acc.add(r)
                elif it[0] == "when":
                    walk(it[2])
                elif it[0] == "if":
                    walk(it[2])
                    walk(it[3])
                elif it[0] == "loop":
                    walk(it[4])
        walk(items)
        for name, (_, bitems) in self.helpers.items():
            walk(bitems)
        return acc

    # -- guard refinement (interval mode) --------------------------------
    def _refine(self, test: ast.expr, env: Dict[str, Any]):
        """Environment for the true branch of ``test``; None if the
        branch is infeasible; ``env`` unchanged if unrefinable."""
        def affine_name(node):
            # node == name + c  ->  (name, c)
            if isinstance(node, ast.Name):
                return node.id, 0
            if isinstance(node, ast.BinOp) \
                    and isinstance(node.op, (ast.Add, ast.Sub)) \
                    and isinstance(node.left, ast.Name) \
                    and isinstance(node.right, ast.Constant) \
                    and isinstance(node.right.value, int):
                c = node.right.value
                return node.left.id, (c if isinstance(node.op, ast.Add)
                                      else -c)
            return None

        if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
            for sub in test.values:
                env = self._refine(sub, env)
                if env is None:
                    return None
            return env
        if not (isinstance(test, ast.Compare) and len(test.ops) == 1):
            return env
        sides = [(test.left, test.comparators[0], type(test.ops[0]))]
        flip = {ast.Lt: ast.Gt, ast.Gt: ast.Lt, ast.LtE: ast.GtE,
                ast.GtE: ast.LtE, ast.Eq: ast.Eq, ast.NotEq: ast.NotEq}
        sides.append((test.comparators[0], test.left,
                      flip.get(type(test.ops[0]))))
        for left, right, op in sides:
            an = affine_name(left)
            if an is None or op is None:
                continue
            name, c = an
            cur = env.get(name)
            if not isinstance(cur, Interval):
                continue
            try:
                rv = _eval(right, env)
            except _OpaqueError:
                continue
            if isinstance(rv, Interval):
                rv_lo, rv_hi = rv.lo, rv.hi
            elif isinstance(rv, (int, np.integer)):
                rv_lo = rv_hi = int(rv)
            else:
                continue
            lo, hi = cur.lo, cur.hi
            if op is ast.Lt:               # name + c < rv
                hi = min(hi, rv_hi - 1 - c)
            elif op is ast.LtE:
                hi = min(hi, rv_hi - c)
            elif op is ast.Gt:
                lo = max(lo, rv_lo + 1 - c)
            elif op is ast.GtE:
                lo = max(lo, rv_lo - c)
            elif op is ast.Eq and rv_lo == rv_hi:
                lo, hi = max(lo, rv_lo - c), min(hi, rv_lo - c)
            else:
                continue
            if lo > hi:
                return None
            env = dict(env)
            env[name] = Interval(lo, hi)
        return env

    # -- item runner -----------------------------------------------------
    def run_items(self, items, env):
        for it in items:
            tag = it[0]
            if tag == "assign":
                _fold_assign(it[1], env)
            elif tag == "access":
                self.do_access(it[1], env)
            elif tag == "when" or tag == "if":
                test = it[1]
                body = it[2]
                orelse = it[3] if tag == "if" else []
                if test is None:
                    self.run_items(body, dict(env))
                    continue
                try:
                    g = _eval(test, env)
                except _OpaqueError as e:
                    self.unverifiable(getattr(test, "lineno", 0),
                                      f"guard not statically "
                                      f"evaluable: {e}")
                    continue
                if g is MAYBE:
                    if not self.bounds_only:
                        self.unverifiable(getattr(test, "lineno", 0),
                                          "guard undecidable at a "
                                          "concrete grid point")
                        continue
                    renv = self._refine(test, env)
                    if renv is not None:
                        self.run_items(body, dict(renv))
                    if orelse:
                        self.run_items(orelse, dict(env))
                elif g:
                    self.run_items(body, dict(env))
                elif orelse:
                    self.run_items(orelse, dict(env))
            elif tag == "loop":
                var, lo_e, hi_e, body = it[1], it[2], it[3], it[4]
                try:
                    lo = int(_eval(lo_e, env))
                    hi = int(_eval(hi_e, env))
                except (_OpaqueError, TypeError, ValueError):
                    self.unverifiable(getattr(lo_e, "lineno", 0),
                                      "fori_loop bounds not static")
                    continue
                if self.bounds_only:
                    if hi > lo:
                        child = dict(env)
                        child[var] = Interval(lo, hi - 1)
                        self.run_items(body, child)
                else:
                    for t in range(lo, hi):
                        child = dict(env)
                        child[var] = t
                        self.run_items(body, child)
            elif tag == "call":
                name, args, line = it[1], it[2], it[3]
                params, bitems = self.helpers[name]
                child = dict(env)
                for p, a_expr in zip(params, args):
                    try:
                        child[p] = _eval(a_expr, env)
                    except _OpaqueError:
                        child.pop(p, None)
                self.run_items(bitems, child)
            elif tag == "opaque":
                self.unverifiable(it[1], it[2])

    # -- one access ------------------------------------------------------
    def do_access(self, a: Access, env):
        ref = self.refs.get(a.ref)
        if ref is None:
            return
        try:
            region = _region(a.index, ref.shape, env)
        except _OpaqueError as e:
            self.unverifiable(a.line, f"{a.ref}: index not statically "
                                      f"evaluable ({e})")
            return
        for (lo, hi), dim in zip(region, ref.shape):
            if lo < 0 or hi > dim or lo >= hi:
                self.emit(RULE_OOB, a.line,
                          f"{a.ref}: access [{lo}, {hi}) outside "
                          f"dim of size {dim}"
                          + ("" if self.bounds_only else
                             f" at grid point {self.coords}"),
                          key=(RULE_OOB, a.line, a.ref))
                return
        if self.bounds_only or ref.kind in ("in", "prefetch", "sem"):
            return
        st = self.state[a.ref]
        sl = tuple(slice(lo, hi) for lo, hi in region)
        sem = self.model.semantics
        if a.kind == "read":
            if not st.live[sl].all():
                self.emit(RULE_ACC_INIT, a.line,
                          f"{a.ref}: read at grid point {self.coords} "
                          f"covers elements never initialized on this "
                          f"visit sequence (missing/insufficient "
                          f"init guard)",
                          key=(RULE_ACC_INIT, a.line, a.ref))
            for w in np.unique(st.writer[sl]):
                if w < 0 or w == self.step:
                    continue
                for ax, (cw, cn) in enumerate(
                        zip(self.steps[int(w)], self.coords)):
                    if cw != cn and sem[ax] == "parallel":
                        self.emit(
                            RULE_RACE, a.line,
                            f"{a.ref}: read at grid point "
                            f"{self.coords} observes a write from "
                            f"grid point {self.steps[int(w)]} across "
                            f"parallel axis {ax} "
                            f"(dimension_semantics"
                            f"={sem})",
                            key=(RULE_RACE, a.line, a.ref, ax))
        elif a.kind == "write":
            if ref.kind == "out":
                if self.cur_block is None:
                    # The out index map itself failed (OOB/opaque) at
                    # this grid point — already reported by the spec-map
                    # check; no block to attribute the store to.
                    st.live[sl] = True
                    st.writer[sl] = self.step
                    return
                if self.step != self.final_visit.get(self.cur_block,
                                                     self.step):
                    self.emit(RULE_STORE_FINAL, a.line,
                              f"{a.ref}: out block {self.cur_block} "
                              f"stored at grid point {self.coords} "
                              f"but revisited later (store must "
                              f"cover only the final visit)",
                              key=(RULE_STORE_FINAL, a.line))
                off = self._block_offset()
                gsl = tuple(slice(o + lo, o + hi) for o, (lo, hi)
                            in zip(off, region))
                self.cov[gsl] = True
                for r, ridx in a.value_reads:
                    rr = self.refs.get(r)
                    if rr is None or rr.kind != "scratch":
                        continue
                    try:
                        rreg = _region(ridx, rr.shape, env)
                    except _OpaqueError:
                        continue
                    rsl = tuple(slice(lo, hi) for lo, hi in rreg)
                    self.state[r].flushed[rsl] = True
            else:
                if a.ref in self.acc_refs and not a.reads_self:
                    pending = st.live[sl] & ~st.flushed[sl]
                    if pending.any():
                        self.emit(
                            RULE_ACC_FLUSH, a.line,
                            f"{a.ref}: plain write at grid point "
                            f"{self.coords} overwrites accumulated "
                            f"state that never reached the output "
                            f"(flush guard missing or on the wrong "
                            f"axis)",
                            key=(RULE_ACC_FLUSH, a.line, a.ref))
                st.flushed[sl] = False
            st.live[sl] = True
            st.writer[sl] = self.step

    def _block_offset(self):
        bshape = self.model.out_spec.block_shape
        return tuple(int(b) * d for b, d in zip(self.cur_block, bshape))


# ----------------------------------------------------------------------
# Drivers.
_VARIANT_ENTRY = {"expand": "incrs_spmm", "reuse": "incrs_spmm_reuse",
                  "pipelined": "incrs_spmm_pipelined"}


def _analyze(geom: Geometry, source: Optional[str] = None,
             sources: Optional[Dict[str, str]] = None,
             bounds_only: bool = False
             ) -> Tuple[List[GridFinding], Optional[KernelModel]]:
    entry = geom.entry
    try:
        src = source if source is not None \
            else _load_source(geom.module, sources)
        tree = ast.parse(src)
    except (OSError, SyntaxError) as e:
        return [GridFinding(entry, RULE_UNVERIFIABLE, 0,
                            f"cannot parse {geom.module}: {e}")], None
    try:
        model = build_model(tree, entry, geom.env)
    except (ModelError, _OpaqueError) as e:
        return [GridFinding(entry, RULE_UNVERIFIABLE, 0, str(e))], None

    params = [a.arg for a in model.kernel_fn.args.args]
    expect = (model.num_scalar_prefetch + len(model.in_specs) + 1
              + len(model.scratch))
    if len(params) != expect:
        return [GridFinding(
            entry, RULE_UNVERIFIABLE, model.kernel_fn.lineno,
            f"kernel takes {len(params)} positional refs, launch "
            f"supplies {expect}")], model
    if len(geom.operands) != len(model.in_specs):
        return [GridFinding(
            entry, RULE_UNVERIFIABLE, model.kernel_fn.lineno,
            f"geometry declares {len(geom.operands)} operands, launch "
            f"has {len(model.in_specs)} in_specs")], model

    refs: Dict[str, SimRef] = {}
    kenv: Dict[str, Any] = dict(model.kernel_kwargs)
    kenv["jnp"] = _JnpShim()
    kenv["slice"] = _slice_shim
    pos = 0
    for i in range(model.num_scalar_prefetch):
        arr = geom.prefetch[i]
        refs[params[pos]] = SimRef(params[pos], "prefetch", arr.shape)
        kenv[params[pos]] = arr
        pos += 1
    for i, spec in enumerate(model.in_specs):
        shape = tuple(geom.operands[i]) if spec.is_any \
            else spec.block_shape
        refs[params[pos]] = SimRef(params[pos], "in", shape)
        kenv[params[pos]] = RefVal(params[pos], shape)
        pos += 1
    out_name = params[pos]
    refs[out_name] = SimRef(out_name, "out", model.out_spec.block_shape)
    kenv[out_name] = RefVal(out_name, model.out_spec.block_shape)
    pos += 1
    for kind, shp in model.scratch:
        refs[params[pos]] = SimRef(
            params[pos], "scratch" if kind == "VMEM" else "sem", shp)
        kenv[params[pos]] = RefVal(params[pos], shp)
        pos += 1

    ex = _Extractor(refs)
    items = ex.extract(model.kernel_fn.body)
    sim = _Sim(model, geom, ex, items, refs, kenv,
               bounds_only=bounds_only)
    sim.out_name = out_name

    def check_spec_maps(pids):
        specs = list(zip(model.in_specs,
                         [tuple(o) for o in geom.operands])) \
            + [(model.out_spec, model.out_shape)]
        blocks_out = None
        for si, (spec, array) in enumerate(specs):
            if spec.is_any:
                continue
            try:
                bidx = _map_blocks(spec, pids, geom.prefetch,
                                   model.entry_env)
            except _OpaqueError as e:
                sim.unverifiable(spec.index_map.lineno,
                                 f"index map not statically "
                                 f"evaluable: {e}")
                continue
            if len(bidx) != len(spec.block_shape):
                sim.unverifiable(spec.index_map.lineno,
                                 f"index map arity {len(bidx)} != "
                                 f"block rank {len(spec.block_shape)}")
                continue
            ok = True
            for d, (bi, bd, ad) in enumerate(zip(bidx, spec.block_shape,
                                                 array)):
                iv = bi if isinstance(bi, Interval) \
                    else Interval.of(int(bi))
                if iv.lo < 0 or (iv.hi + 1) * bd > ad:
                    sim.emit(RULE_OOB, spec.index_map.lineno,
                             f"index map block [{iv.lo}, {iv.hi}] x "
                             f"block dim {bd} exceeds array dim {ad} "
                             f"(axis {d})",
                             key=(RULE_OOB, spec.index_map.lineno, d))
                    ok = False
            if spec is model.out_spec and ok:
                blocks_out = tuple(int(b) for b in bidx) \
                    if not bounds_only else None
        return blocks_out

    if bounds_only:
        pids = tuple(Interval(0, g - 1) for g in model.grid)
        check_spec_maps(pids)
        env = dict(kenv)
        env["pl"] = _PlShim(pids, model.grid)
        sim.run_items(items, env)
        return sim.findings, model

    steps = list(itertools.product(*[range(g) for g in model.grid]))
    sim.steps = steps
    blocks: List[Optional[Tuple[int, ...]]] = []
    for coords in steps:
        blocks.append(check_spec_maps(coords))
    for si, b in enumerate(blocks):
        if b is not None:
            sim.final_visit[b] = si

    for name, ref in refs.items():
        if ref.kind == "scratch" or name == out_name:
            sim.state[name] = _RefState(ref.shape)
    sim.cov = np.zeros(model.out_shape, dtype=bool)

    prev_block: Optional[Tuple[int, ...]] = None
    for si, coords in enumerate(steps):
        sim.step, sim.coords, sim.cur_block = si, coords, blocks[si]
        if blocks[si] != prev_block:
            sim.state[out_name].reset()
            prev_block = blocks[si]
        env = dict(kenv)
        env["pl"] = _PlShim(coords, model.grid)
        sim.run_items(items, env)

    for name, st in sim.state.items():
        if name in sim.acc_refs and (st.live & ~st.flushed).any():
            sim.emit(RULE_ACC_FLUSH, model.kernel_fn.lineno,
                     f"{name}: accumulated state still unflushed at "
                     f"grid exit (dropped flush)",
                     key=(RULE_ACC_FLUSH, name, "exit"))
    if not sim.cov.all():
        missing = int(sim.cov.size - sim.cov.sum())
        sim.emit(RULE_COVERAGE, model.kernel_fn.lineno,
                 f"{missing}/{sim.cov.size} output elements never "
                 f"written by any grid step (grid x out index map "
                 f"does not tile the output)")
    return sim.findings, model


def check_kernel_grid(entry: str, source: Optional[str] = None,
                      sources: Optional[Dict[str, str]] = None
                      ) -> List[GridFinding]:
    """Run the full grid interpreter (bounds + accumulator + coverage +
    race) for one kernel entry point over its declared geometry.

    ``source`` overrides the kernel module's source text (mutation
    fixtures); ``sources`` maps module filenames to override texts.
    """
    if entry not in GEOMETRIES:
        return [GridFinding(entry, RULE_UNVERIFIABLE, 0,
                            f"no geometry declared for {entry!r}")]
    findings, _ = _analyze(GEOMETRIES[entry], source=source,
                           sources=sources)
    return findings


def check_all_grids(sources: Optional[Dict[str, str]] = None
                    ) -> List[GridFinding]:
    """Grid-interpreter findings for every registered kernel."""
    out: List[GridFinding] = []
    for entry in KERNELS:
        out.extend(check_kernel_grid(entry, sources=sources))
    return out


_BOUNDS_CACHE: Dict[tuple, tuple] = {}


def check_config_bounds(variant: str, *, m: int, n: int, bm: int,
                        bn: int, n_sections: int, smax: int,
                        section: int,
                        source: Optional[str] = None) -> List[Violation]:
    """Interval-prove bounds safety of one fused-SpMM ``(variant, bm,
    bn)`` config in O(1) of the grid size — every dslice/load/index-map
    access checked with ``pl.program_id`` ranging over the whole grid.

    Used by ``kernels.autotune.split_candidates`` and
    ``sparse.api.plan`` alongside the VMEM prefilter. Alignment and
    section-geometry errors are RULE_GRID/RULE_ALIGN territory
    (``check_incrs_config``); this pass assumes a tileable geometry and
    returns [] when it cannot even form a grid.
    """
    from . import vmem
    entry = _VARIANT_ENTRY.get(variant)
    if entry is None:
        return []
    if min(m, n, bm, bn, n_sections, smax, section) <= 0:
        return []
    eff_bm, mp = vmem.resolve_row_tile(m, bm)
    if n % bn or mp % eff_bm:
        return []
    env = dict(m=mp, mp=mp, bm=eff_bm, n=n, bn=bn,
               n_sections=n_sections, smax=smax, section=section,
               k=n_sections * section)
    ops = ((n_sections, mp, smax), (n_sections, mp, smax),
           (n_sections * section, n))
    geom = Geometry("incrs_spmm.py", entry, env, ops)
    # This sits on the auto-dispatch hot path (model_pick_variant runs
    # per spmm call): memoize per resolved config, keyed on the kernel
    # file's mtime so edits invalidate. Explicit `source` bypasses.
    key = None
    if source is None:
        try:
            mtime = os.stat(module_path(geom.module)).st_mtime_ns
        except OSError:
            mtime = 0
        key = (entry, mp, n, eff_bm, bn, n_sections, smax, section,
               mtime)
        hit = _BOUNDS_CACHE.get(key)
        if hit is not None:
            return list(hit)
    findings, _ = _analyze(geom, source=source, bounds_only=True)
    out = [Violation(f.rule, f"{variant}: {f.message} "
                     f"(line {f.line})")
           for f in findings]
    if key is not None:
        if len(_BOUNDS_CACHE) > 256:
            _BOUNDS_CACHE.clear()
        _BOUNDS_CACHE[key] = tuple(out)
    return out


_MATCHED_ENTRY = {
    "index_match": ("index_match_spmm.py", "index_match_spmm"),
    "condense": ("spgemm/kernels.py", "spgemm_condense"),
    "merge": ("spgemm/kernels.py", "spgemm_merge"),
}


def check_matched_bounds(stage: str, *, m: int, n: int, bm: int, bn: int,
                         rounds: int, n_rounds: int, rmax_a: int,
                         rmax_b: int,
                         source: Optional[str] = None) -> List[Violation]:
    """Interval-prove bounds safety of one matched-family stage (fused
    index-match, SpGEMM condense, or SpGEMM merge) at one config —
    the matched-family analogue of ``check_config_bounds``, with the
    same mtime-keyed memo (``check_matched_config`` runs on the SpGEMM
    launch path). Assumes a tileable geometry; returns [] when it cannot
    even form a grid (RULE_GRID/RULE_ALIGN territory)."""
    info = _MATCHED_ENTRY.get(stage)
    if info is None:
        return []
    module, entry = info
    if min(m, n, bm, bn, rounds, n_rounds, rmax_a, rmax_b) <= 0:
        return []
    if m % bm or n % bn:
        return []
    if stage == "merge":
        env = dict(m=m, n=n, bm=bm, bn=bn, n_rounds=n_rounds)
        ops: Tuple[Tuple[int, ...], ...] = ((n_rounds, m, n),)
    else:
        env = dict(m=m, n=n, bm=bm, bn=bn, rounds=rounds,
                   n_rounds=n_rounds, rmax_a=rmax_a, rmax_b=rmax_b)
        ops = ((n_rounds, m, rmax_a), (n_rounds, m, rmax_a),
               (n_rounds, n, rmax_b), (n_rounds, n, rmax_b))
    geom = Geometry(module, entry, env, ops)
    key = None
    if source is None:
        try:
            mtime = os.stat(module_path(module)).st_mtime_ns
        except OSError:
            mtime = 0
        key = (entry, m, n, bm, bn, rounds, n_rounds, rmax_a, rmax_b,
               mtime)
        hit = _BOUNDS_CACHE.get(key)
        if hit is not None:
            return list(hit)
    findings, _ = _analyze(geom, source=source, bounds_only=True)
    out = [Violation(f.rule, f"{stage}: {f.message} (line {f.line})")
           for f in findings]
    if key is not None:
        if len(_BOUNDS_CACHE) > 256:
            _BOUNDS_CACHE.clear()
        _BOUNDS_CACHE[key] = tuple(out)
    return out


# ----------------------------------------------------------------------
# Proof matrix.
PROPERTIES = ("bounds", "accumulator", "coverage", "race", "dma")
_PROP_RULES = {
    "bounds": (RULE_OOB,),
    "accumulator": (RULE_ACC_INIT, RULE_ACC_FLUSH),
    "coverage": (RULE_COVERAGE, RULE_STORE_FINAL),
    "race": (RULE_RACE,),
}


def proof_matrix(sources: Optional[Dict[str, str]] = None
                 ) -> Dict[str, Dict[str, str]]:
    """Per-kernel x per-property proof status: ``proved``, ``proved*``
    (conditional on a stated host-prep contract), ``FAILED``,
    ``unverified``, or ``n/a``."""
    from . import kernel_check
    matrix: Dict[str, Dict[str, str]] = {}
    for entry in KERNELS:
        geom = GEOMETRIES[entry]
        findings, model = _analyze(geom, sources=sources)
        unv = any(f.rule == RULE_UNVERIFIABLE for f in findings)
        ok = "proved*" if geom.note else "proved"
        row: Dict[str, str] = {}
        for prop in ("bounds", "accumulator", "coverage", "race"):
            if any(f.rule in _PROP_RULES[prop] for f in findings):
                row[prop] = "FAILED"
            elif unv:
                row[prop] = "unverified"
            else:
                row[prop] = ok
        if model is not None and not model.scratch:
            row["accumulator"] = "n/a"
            row["race"] = "n/a"
        uses_dma = model is not None and any(
            isinstance(n, ast.Call)
            and _tname(n.func) == "make_async_copy"
            for n in ast.walk(model.kernel_fn))
        if not uses_dma:
            row["dma"] = "n/a"
        else:
            src = _load_source(geom.module, sources)
            dma = kernel_check.check_dma_pairing(
                src, func=model.kernel_fn.name)
            row["dma"] = "FAILED" if dma else "proved"
        matrix[entry] = row
    return matrix


def format_proof_matrix(matrix: Optional[Dict[str, Dict[str, str]]]
                        = None) -> str:
    """Render the proof matrix as an aligned text table."""
    if matrix is None:
        matrix = proof_matrix()
    name_w = max(len(k) for k in matrix) + 2
    col_w = max(max(len(p) for p in PROPERTIES),
                max(len(v) for row in matrix.values()
                    for v in row.values())) + 2
    lines = [" " * name_w
             + "".join(p.ljust(col_w) for p in PROPERTIES)]
    for entry, row in matrix.items():
        lines.append(entry.ljust(name_w)
                     + "".join(row[p].ljust(col_w)
                               for p in PROPERTIES))
    lines.append("")
    lines.append("proved* = conditional on the stated host-prep "
                 "contract (see analysis.grid_interp.GEOMETRIES notes)")
    return "\n".join(lines)


# ----------------------------------------------------------------------
# Property-test surface (tests/test_grid_interp.py hypothesis suite).
def interval_of(expr: str, env: Dict[str, Any]) -> Tuple[int, int]:
    """Sound [lo, hi] of an affine index expression; ``env`` values may
    be ints or (lo, hi) tuples."""
    node = ast.parse(expr, mode="eval").body
    e: Dict[str, Any] = {}
    for k, v in env.items():
        e[k] = Interval(v[0], v[1]) if isinstance(v, tuple) else v
    r = Interval.of(_eval(node, e))
    return r.lo, r.hi


def map_in_bounds(map_src: str, grid: Sequence[int],
                  block_shape: Sequence[int],
                  array_shape: Sequence[int]) -> bool:
    """Interval verdict for one index-map lambda: True only when every
    grid point's block provably fits inside the array."""
    lam = ast.parse(map_src, mode="eval").body
    if not isinstance(lam, ast.Lambda):
        raise ValueError("map_src must be a lambda expression")
    spec = BlockModel(tuple(int(b) for b in block_shape), lam)
    pids = tuple(Interval(0, g - 1) for g in grid)
    try:
        bidx = _map_blocks(spec, pids, (), {})
    except _OpaqueError:
        return False
    if len(bidx) != len(block_shape):
        return False
    for bi, bd, ad in zip(bidx, spec.block_shape, array_shape):
        iv = bi if isinstance(bi, Interval) else Interval.of(int(bi))
        if iv.lo < 0 or (iv.hi + 1) * bd > ad:
            return False
    return True
