"""Kernel codegen: lower parsed kernel ASTs to vectorized JAX functions.

Strategy (the TPU-first answer to the reference's per-work-item OpenCL
execution model, SURVEY.md §7): instead of launching one scalar program per
work item, we *vectorize over work items* — a launch chunk of ``B``
consecutive work items becomes one array program where every scalar local
variable is a ``(B,)`` vector and ``get_global_id(0)`` is
``offset + iota(B)``.  This maps the kernel straight onto the TPU VPU/MXU
and lets XLA fuse the whole body.

Key mechanisms:

- **Affine index tracking** — every integer value carries an optional
  ``(stride, offset)`` annotation meaning ``value == stride*gid + offset``,
  with ``offset`` a literal or a runtime value that is the same in every
  lane, and where the build can tell, the bounds of ``offset``.
  Loads/stores with stride-1 indices lower to ``lax.dynamic_slice`` /
  ``lax.dynamic_update_slice`` wherever the offset points (``a[j*n + i]``
  is a contiguous window; a masked store a select into it); a stride that
  is a build-time integer (a literal, or a value parameter the kernel
  multiplies with inside an index: :func:`pitch_params`) reads a column of
  the buffer seen as ``[rows, stride]``, and the columns a counted loop's
  passes walk (``a[i*n + j]``) one 2-D slice and a transposition; a buffer
  a loop touches at the lane's own element only (``x[i] += ...``) rides
  the loop as a local.  An index that is neither affine nor lane-uniform,
  or a strided one that cannot be proved inside its row, is a per-lane
  gather / scatter (docs/KERNEL_LANGUAGE.md, *Affine accesses*).
- **Masked control flow** — ``if``/``else`` run both branches under
  disjoint masks (stores become masked read-modify-writes, locals merge via
  ``where``); an early ``return`` folds into a cumulative return-mask.
  This is the standard SIMT→SIMD predication transform.
- **Vectorized loops** — ``for``/``while`` lower to ``lax.while_loop`` with
  a per-item active mask (loops run until *all* items are done — exactly the
  mandelbrot iteration pattern); locals keep their declared C dtype so loop
  carries are shape/dtype-stable and nothing recompiles when trip counts
  change at runtime.

- **Run windows and row gathers** — a ``for`` whose variable goes up by
  one a pass and indexes buffers it does not store to as ``T[j]`` (a CSR
  row loop) reads, in every lane, a run of consecutive elements: the runs
  are fetched once for 32 passes as ONE 128-wide row a lane of a
  half-overlapping view of the table (row ``r`` holds elements
  ``[64 r, 64 r + 128)``: a run of 32 lies whole in one row) and moved up
  by the run's offset in six select stages over whole lines
  (``_run_window``), where a pass would gather a chunk-wide element each.
  The view costs twice the table's bytes of device memory.  On a TPU lane
  any other per-lane gather fetches the element's row of the plain view
  and picks its lane (``_take_rows``).  The chip gathers rows several
  times faster than it gathers elements (PERF.md, PR 26, PR 27).
- **Kept views** — such a view, and the ``[rows, s]`` view a strided
  window is cut from, is computed from ONE array and nothing else; of an
  array the kernel never stores to it is the same launch after launch.  A
  build reports the views it asks for and takes them as an argument
  (:class:`ViewSpec`); the launcher builds each once an upload and keeps it
  (kernel/registry.py).  Whatever a launch is not handed it builds itself.

- **Work-group cooperation** — a launch covers WHOLE work-groups; a
  ``__local T a[K];`` array is a value ``[groups of the launch, K]`` beside
  the lane vectors, read and written by a shift along ``K``, a broadcast, or
  a gather / scatter inside the row (:func:`_local_load`), and a
  ``barrier()`` lowers to nothing: every statement has run for all lanes of
  the launch before the next one starts.  The build refuses a barrier that
  the work items of a group do not all reach (:func:`_check_barriers`).

The launch boundary: ``build_kernel_fn`` returns ``fn(offset, *buffers,
value_args) -> updated buffers``, where ``offset`` is a *runtime* scalar —
the load balancer can re-partition the global range every call without
triggering recompilation (the reference's NDRange-offset semantics,
Cores.cs:607-613, preserved under jit).
"""

from __future__ import annotations

import itertools
import math
import re
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..errors import KernelCompileError, KernelLanguageError
from . import lang
from .lang import (
    Assign,
    Barrier,
    BinOp,
    Break,
    Call,
    Cast,
    Continue,
    CrementStmt,
    Decl,
    For,
    DoWhile,
    If,
    Index,
    KernelDef,
    LocalDecl,
    Member,
    Num,
    Return,
    Ternary,
    UnOp,
    Var,
    VecLit,
    VECTOR_TYPES,
    While,
)

__all__ = ["build_kernel_fn", "KernelBuildInfo", "ViewSpec", "ctype_to_dtype",
           "pitch_params"]


# ---------------------------------------------------------------------------
# C type lattice
# ---------------------------------------------------------------------------

_INT_TYPES = {"char", "uchar", "short", "ushort", "int", "uint", "long", "ulong", "bool"}
_WIDE_INTS = {"int", "uint", "long", "ulong"}  # hold an index whole
_FLOAT_TYPES = {"float", "double", "half"}
_RANK = {
    "bool": 0, "char": 1, "uchar": 1, "short": 2, "ushort": 2,
    "int": 3, "uint": 4, "long": 5, "ulong": 6,
    "half": 7, "float": 8, "double": 9,
}


def _x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


def ctype_to_dtype(ctype: str):
    """Map a C type to the jnp dtype actually used on this backend.  long and
    double degrade to 32-bit when x64 is disabled (standard JAX behavior on
    TPU; the CPU test rig enables x64 for full-width parity)."""
    table = {
        "bool": jnp.bool_,
        "char": jnp.int8,
        "uchar": jnp.uint8,
        "short": jnp.int16,
        "ushort": jnp.uint16,
        "int": jnp.int32,
        "uint": jnp.uint32,
        "long": jnp.int64 if _x64_enabled() else jnp.int32,
        "ulong": jnp.uint64 if _x64_enabled() else jnp.uint32,
        "half": jnp.float16,
        "float": jnp.float32,
        "double": jnp.float64 if _x64_enabled() else jnp.float32,
    }
    if ctype not in table:
        raise KernelLanguageError(f"unsupported type {ctype!r}")
    return jnp.dtype(table[ctype])


def _dtype_to_ctype(dtype) -> str:
    name = jnp.dtype(dtype).name
    table = {
        "bool": "bool", "int8": "char", "uint8": "uchar", "int16": "short",
        "uint16": "ushort", "int32": "int", "uint32": "uint", "int64": "long",
        "uint64": "ulong", "float16": "half", "float32": "float",
        "float64": "double", "bfloat16": "half",
    }
    return table.get(name, "float")


def _promote(t1: str, t2: str) -> str:
    """C usual arithmetic conversions (simplified to the rank lattice)."""
    a, b = (t1, t2) if _RANK[t1] >= _RANK[t2] else (t2, t1)
    if a in _FLOAT_TYPES:
        return a
    # integer promotion: everything below int promotes to int
    if _RANK[a] < _RANK["int"]:
        return "int"
    return a


@dataclass
class KVal:
    """A value in the vectorized program.

    ``affine`` — when not None, ``(stride, const)`` with a *Python-int*
    stride such that ``value == stride * gid + const`` elementwise (``gid``
    being the global work-item id vector); ``const`` is a Python int or a
    traced scalar.  Drives the contiguous slice fast path: stride-1 indices
    with an int ``const`` lower to dynamic_slice/dynamic_update_slice over a
    ``const``-padded buffer (padding makes tail chunks exact — a clamped
    slice would silently shift the window).  A value that is the same in
    every lane has stride 0 and is its own ``const``.

    ``span`` — ``(lo, hi)`` in Python ints with ``lo <= const <= hi`` on
    every pass, where the build can tell (literals, a value parameter taken
    as a launcher key, a counted loop's variable, sums and products of
    these); None where it cannot.  With the launch's global range it is
    what proves an affine access in bounds (:func:`_in_bounds`).
    """

    value: Any
    ctype: str
    affine: Optional[tuple[int, Any]] = None
    span: Optional[tuple[int, int]] = None

    @property
    def is_vector(self) -> bool:
        return hasattr(self.value, "ndim") and self.value.ndim > 0


class _Ctx:
    """Interpretation context for one kernel launch chunk.

    ``shape`` is the vector shape every work-item-parallel value carries:
    ``(B,)`` for the XLA lowering, ``(rows, 128)`` for the Pallas tile
    lowering (pallas_backend.py) — the interpreter itself is shape-agnostic.
    """

    pallas = False  # the Pallas tile subclass flips this

    def __init__(self, B: int, offset, global_size, local_size: int, ctx_info: dict,
                 in_range: bool = True):
        self.B = B
        self.shape: tuple[int, ...] = (B,)
        self.offset = offset  # scalar int32 (traced)
        self.env: dict[str, KVal] = {}
        self.bufs: dict[str, Any] = {}
        self.buf_ctypes: dict[str, str] = {}
        self.stored: set[str] = set()
        self.mask: Any = None  # None == all-active; else bool of self.shape
        # the lane-UNIFORM conditions we are under (a 0-d bool, None == true):
        # an ``if`` whose condition is proved the same in every lane, the
        # break / continue flags of a counted loop.  Kept apart from the lane
        # mask because a lane-uniform local is merged under this part alone
        # (_assign): no lane outside the lane mask ever observes it
        self.umask: Any = None
        self.return_mask: Any = None  # items that already returned
        self.global_size = global_size
        # the launch vouches that its work items lie in [0, global_size): a
        # compute with a global offset hands out items beyond it, and then
        # nothing is proved from the range (_in_bounds, _strided_rows)
        self.in_range = in_range
        self.local_size = local_size
        self.info = ctx_info
        idx = jnp.arange(B, dtype=jnp.int32)
        self.gid = KVal(offset + idx, "int", affine=(1, 0), span=(0, 0))
        # padded-view cache for shifted slice loads: name -> {const: padded}
        self._pad_cache: dict[str, dict[int, Any]] = {}
        # remainder stack (statements that can still run after the current
        # one, per enclosing block) — liveness input for free-run
        # elimination; and the active (mask, names) free-run grant
        self._after_stack: list[list] = []
        self._freerun: tuple | None = None
        # private fixed-size arrays (``float acc[4];``): name -> length;
        # the env value is a (length, *shape) vector-per-element stack
        self.private: dict[str, int] = {}
        # VECTOR TYPES (kernel/vectors.py).  The ``__global floatN*``
        # parameters: name -> N (``buf_ctypes`` holds the element type); the
        # vector locals, which are private arrays of N scalars with a type:
        # name -> ``floatN``; every access of a vector parameter the walk
        # lowered, (id of the Index node, is it the store) -> kind; does the
        # kernel name a vector type anywhere (build_kernel_fn sets it)
        self.widths: dict[str, int] = {}
        self.vectors: dict[str, str] = {}
        self.vector_access: dict[tuple[int, bool], str] = {}
        self.has_vectors = False
        # WORK-GROUP COOPERATION.  ``__local`` arrays: name -> length; the env
        # value is ``[groups of the launch, length]``.  What the build proved
        # the same in every lane of a GROUP, and the locals that are
        # ``get_local_id(0)`` (adopt() takes both); how each access site of a
        # local array was lowered: (id of the Index node, is it the store) ->
        # ``shift`` / ``uniform`` / ``row``
        self.local: dict[str, int] = {}
        self.group_uniform: set[str] = set()
        self.tid_vars: frozenset[str] = frozenset()
        # the reads at ``local id + (the same in a group)``: id of the Index
        # node -> pitch (_group_sites; _group_slice serves them)
        self.group_sites: dict[int, int] = {}
        # of them, those whose walk the build followed through their loop,
        # by the loop's id (_settled_walks); inside the passes that loop makes
        # with their windows settled, id of the Index node -> (block, row) of
        # this pass's slice; and the reads so served (_exec_masked)
        self.group_walks: dict[int, list] = {}
        self.settled: dict[int, tuple] = {}
        self.settled_sites: set[int] = set()
        # the masked loops whose common passes may run with no mask, by the
        # loop's id (_common_walks; adopt() takes them), and those that were
        # built so (_exec_masked)
        self.peels: dict[int, "_Peel"] = {}
        self.peeled: set[int] = set()
        self.local_access: dict[tuple[int, bool], str] = {}
        self.cooperative = False  # the kernel has a __local array or a barrier
        # per-innermost-loop masks: lanes that executed `break` (persist
        # for the loop's remaining iterations) / `continue` (reset per
        # iteration) — saved and restored by _exec_loop.  In a counted
        # loop (``counted``) every lane takes an exit together and the two
        # are 0-d flags
        self.break_mask: Any = None
        self.continue_mask: Any = None
        self.counted = False
        # statically-proven lane-uniform locals (adopt() takes them from
        # _uniform_vars) — drives scalarized uniform-index loads, scalar
        # predicates and counted loops
        self.uniform_vars: set[str] = set()
        # the kernel has a ``return``: no proof, every loop is masked
        self.returns = False
        # helper functions (lang.FuncDef by name) inlined at call sites
        self.helpers: dict = {}
        # the innermost run-window loop's tables: buffer name -> (loop
        # variable, this pass's row of the window) — see _exec_loop
        self.runs: dict[str, tuple[str, Any]] = {}
        # a TPU launcher reads per-lane gathers through 128-wide rows
        # (build_kernel_fn sets it; _take_rows)
        self.row_gathers = False
        # row views of buffers: (name, overlapping) -> (buffer, its
        # [rows, 128] view)
        self._rows_cache: dict[tuple[str, bool], tuple[Any, Any]] = {}
        # KEPT VIEWS (see ViewSpec): the array parameters the kernel never
        # stores to; the views of them that the launch was handed as
        # arguments, (name, kind) -> view; and every (name, kind) the build
        # asked for, handed or not (build_kernel_fn reports them)
        self.readonly: frozenset[str] = frozenset()
        self.kept: dict[tuple[str, str], Any] = {}
        self.asked: set[tuple[str, str]] = set()
        # the innermost strided-window loop's reads: id of the Index node
        # -> this pass's row of its window (_exec_counted)
        self.windows: dict[int, Any] = {}
        # buffers riding a loop as a local because the loop touches them at
        # the lane's own element only: name -> (the local's name in ``env``,
        # the index expression) — see _own_element_bufs
        self.own: dict[str, tuple[str, Any]] = {}
        # how each buffer access was lowered: (id of the Index node, is it
        # the store) -> kind; and the (loop, buffer) pairs carried as locals
        self.access: dict[tuple[int, bool], str] = {}
        self.carried: set[tuple[int, str]] = set()
        # the element width in bytes of every store lowered to a scatter
        self.scattered: list[int] = []
        # LANE COMPACTION (_exec_compacted): are we inside a chunk of
        # compacted lanes (the work-item id is data there); the reads such a
        # chunk made once, before its passes: id of the Index node -> value;
        # how the chunks' trace lowered the access sites (``access`` keeps
        # what the dense trace of the same site says); the loops made
        # compactable
        self.compacting = False
        self.hoisted: dict[int, Any] = {}
        self.compact_access: dict[tuple[int, bool], str] = {}
        self.compact_loops = self.compact_ordered = 0

    def adopt(self, kernel: KernelDef, uniform_vars: set[str],
              coop: "_Coop | None" = None, peels: dict | None = None) -> None:
        """Take what a build knows of ``kernel`` before its body runs."""
        self.uniform_vars = uniform_vars
        self.peels = peels or {}
        if coop is not None:
            self.group_uniform, self.tid_vars = coop.group_uniform, coop.tid_vars
            self.group_sites = coop.group_sites
            self.group_walks = coop.group_walks
            self.cooperative = True
        self.returns = _contains_return(kernel.body)
        self.helpers = getattr(kernel, "helpers", {}) or {}

    def lane_arrays(self) -> frozenset:
        """The arrays whose elements are never the same over a launch
        whatever the index: a lane's private ones, a group's ``__local``."""
        return frozenset(self.private) | frozenset(self.local)

    def broadcast_scalar(self, val, dtype):
        """Materialize a scalar as a full work-item vector of this ctx's
        shape (subclasses may force a computed layout)."""
        return jnp.full(self.shape, val, dtype=dtype)

    def any_lane(self, mask):
        """0-d bool: is any lane of ``mask`` set (the Pallas subclass
        reduces a float tile: Mosaic reduces no bools)."""
        return jnp.any(mask)

    def counted_loop(self, node, lane_vars: list, carried_bufs: list) -> None:
        """Hook for the Pallas subclass, called as a counted loop is entered
        (:func:`_exec_counted`): its probe counts the tiles the loop keeps
        alive across its passes.  Nothing for the XLA lowering."""

    def force_computed(self, vec):
        """Hook for the Pallas subclass: rewrite a (possibly constant)
        vector so Mosaic assigns it a non-replicated layout, making it a
        legal while-loop carry.  Identity for the XLA lowering."""
        return vec

    def padded_view(self, name: str, c: int):
        """Buffer padded so the shifted window [offset+c, offset+c+B) is
        always in bounds; returns (padded, left_pad).  Edge padding so an
        out-of-range element reads the nearest valid one — the SAME clamp
        semantics as the gather path (a zero pad would give the two load
        paths different out-of-bounds values for the same kernel)."""
        cache = self._pad_cache.setdefault(name, {})
        if c in cache:
            return cache[c]
        buf = self.bufs[name]
        lo, hi = max(0, -c), max(0, c)
        padded = jnp.pad(buf, (lo, hi), mode="edge")
        cache[c] = (padded, lo)
        return padded, lo

    def invalidate_padded(self, name: str) -> None:
        self._pad_cache.pop(name, None)

    def kept_view(self, name: str, kind: str):
        """The view ``kind`` of buffer ``name`` that the launch was handed,
        or None: the build then makes it itself.  Only a parameter the
        kernel never stores to can have one (its buffer is the launch's
        argument wherever the body reads it); asking is what reports the
        view to the launcher (:class:`ViewSpec`)."""
        if name not in self.readonly:
            return None
        self.asked.add((name, kind))
        return self.kept.get((name, kind))

    def rows_view(self, name: str, overlapping: bool = False):
        """The buffer as ``[rows, 128]`` for the row gathers: the plain
        view (:func:`_rows_of`, for :func:`_take_rows`) or the OVERLAPPING
        one (:func:`_runs_of`, for :func:`_run_window`).  A kept view where
        the launch was handed one; else built here, once for as long as the
        buffer is the same (``_exec_loop`` asks for a run table's before it
        enters)."""
        kept = self.kept_view(name, "runs" if overlapping else "rows")
        if kept is not None:
            return kept
        buf = self.bufs[name]
        hit = self._rows_cache.get((name, overlapping))
        if hit is not None and hit[0] is buf:
            return hit[1]
        rows = (_runs_of if overlapping else _rows_of)(buf)
        self._rows_cache[(name, overlapping)] = (buf, rows)
        return rows

    def masks(self) -> tuple:
        """``(lane, uniform)``: the current mask in its two parts, either
        None when all-true.  ``lane`` is the branch mask minus returned /
        broken / continued items; ``uniform`` the 0-d conditions (``umask``
        and a counted loop's break / continue flags)."""
        lane, uni = self.mask, self.umask
        for excl in (self.return_mask, self.break_mask, self.continue_mask):
            if excl is not None:
                inv = jnp.logical_not(excl)
                if inv.ndim == 0:
                    uni = inv if uni is None else jnp.logical_and(uni, inv)
                else:
                    lane = inv if lane is None else jnp.logical_and(lane, inv)
        return lane, uni

    def active_mask(self):
        """Combined current mask (both parts of :meth:`masks`)."""
        lane, uni = self.masks()
        if uni is None:
            return lane
        return uni if lane is None else jnp.logical_and(lane, uni)


# ---------------------------------------------------------------------------
# expression evaluation
# ---------------------------------------------------------------------------


def _as_dtype(v: KVal, ctype: str) -> KVal:
    if v.ctype == ctype:
        return v
    if v.ctype in VECTOR_TYPES or ctype in VECTOR_TYPES:
        # (a scalar becomes a vector only where the language says so, and
        # there vectors.splat makes it)
        raise vectors.refused(
            "vector-conversion", f"a {v.ctype} where a {ctype} is needed: "
            "conversions between vectors and scalars, and between vector "
            "types (convert_T, as_T), are not supported; name a component")
    dt = ctype_to_dtype(ctype)
    val = v.value
    if hasattr(val, "astype"):
        val = val.astype(dt)
    else:
        val = jnp.asarray(val, dtype=dt) if not isinstance(val, (int, float, bool)) else dt.type(val)
    if v.ctype in _INT_TYPES and ctype in _INT_TYPES:
        return KVal(val, ctype, v.affine, v.span)
    return KVal(val, ctype)


def _int_const(value: int, ctype: str = "int") -> KVal:
    """A compile-time integer: stride 0, its own bounds."""
    return KVal(value, ctype, (0, value), (value, value))


def _const_int(v: KVal) -> Optional[int]:
    """Python-int view of a compile-time constant, else None."""
    if v.affine is not None and v.affine[0] == 0 and isinstance(v.affine[1], int):
        return v.affine[1]
    if isinstance(v.value, int):
        return v.value
    return None


def _eval(ctx: _Ctx, node) -> KVal:
    if isinstance(node, Num):
        if node.ctype in _INT_TYPES:
            return KVal(node.value, node.ctype, (0, node.value), (node.value,) * 2)
        return KVal(node.value, node.ctype)
    if isinstance(node, Var):
        if node.name in ctx.private and node.name in ctx.vectors:
            return vectors.read_local(ctx, node.name)
        if node.name in ctx.private or node.name in ctx.local:
            raise KernelLanguageError(
                f"{'private' if node.name in ctx.private else '__local'} "
                f"array {node.name!r} used without an index",
                line=node.line,
            )
        if node.name in ctx.env:
            return ctx.env[node.name]
        raise KernelCompileError(f"undefined variable {node.name!r}", line=node.line)
    if isinstance(node, Index):
        return _load(ctx, node)
    if isinstance(node, BinOp):
        return _binop(ctx, node)
    if isinstance(node, VecLit):
        return vectors.literal(ctx, node)
    if isinstance(node, Member):
        return vectors.member(ctx, node)
    if isinstance(node, UnOp):
        v = _eval(ctx, node.operand)
        if node.op == "+":
            return v
        if v.ctype in VECTOR_TYPES and node.op == "-":
            return vectors.negate(v)
        if v.ctype in VECTOR_TYPES and node.op == "~":
            raise vectors.refused("vector-operator", "~ on a vector is not "
                                  "supported", node.line)
        if node.op == "-":
            aff = span = None
            if v.affine is not None:
                s, o = v.affine
                aff = (-s, -o)
                span = v.span and (-v.span[1], -v.span[0])
            return KVal(-_num(v), v.ctype if v.ctype in _FLOAT_TYPES else _promote(v.ctype, "int"), aff, span)
        if node.op == "!":
            return KVal(jnp.logical_not(_truthy(v)), "bool")
        if node.op == "~":
            return KVal(~_num(_as_dtype(v, _promote(v.ctype, "int"))), _promote(v.ctype, "int"))
        raise KernelCompileError(f"unknown unary op {node.op}", line=node.line)
    if isinstance(node, Ternary):
        c = _truthy(_eval(ctx, node.cond))
        a = _eval(ctx, node.then)
        b = _eval(ctx, node.other)
        if a.ctype in VECTOR_TYPES or b.ctype in VECTOR_TYPES:
            raise vectors.refused(
                "vector-select", "?: with a vector operand is not supported; "
                "assign under an if", node.line)
        t = _promote(a.ctype, b.ctype)
        av, bv = _num(_as_dtype(a, t)), _num(_as_dtype(b, t))
        return KVal(jnp.where(c, av, bv), t)
    if isinstance(node, Cast):
        return _as_dtype(_eval(ctx, node.operand), node.ctype)
    if isinstance(node, Call):
        return _call(ctx, node)
    raise KernelCompileError(f"cannot evaluate node {type(node).__name__}", line=getattr(node, "line", 0))


def _num(v: KVal):
    """Raw numeric payload with the KVal's dtype materialized."""
    val = v.value
    if isinstance(val, (int, float, bool)):
        return ctype_to_dtype(v.ctype).type(val)
    return val


def _truthy(v: KVal):
    if v.ctype in VECTOR_TYPES:
        raise vectors.refused(
            "vector-comparison", f"a {v.ctype} as a condition: a vector is "
            "neither true nor false; test a component")
    if v.ctype == "bool":
        return v.value if hasattr(v.value, "dtype") else jnp.asarray(v.value, jnp.bool_)
    return _num(v) != 0


def _binop(ctx: _Ctx, node: BinOp) -> KVal:
    op = node.op
    if op in ("&&", "||"):
        l = _truthy(_eval(ctx, node.left))
        r = _truthy(_eval(ctx, node.right))
        fn = jnp.logical_and if op == "&&" else jnp.logical_or
        return KVal(fn(l, r), "bool")

    a = _eval(ctx, node.left)
    b = _eval(ctx, node.right)
    if a.ctype in VECTOR_TYPES or b.ctype in VECTOR_TYPES:
        return vectors.binop(ctx, op, a, b, node.line)

    if op in ("==", "!=", "<", ">", "<=", ">="):
        t = _promote(a.ctype, b.ctype)
        av, bv = _num(_as_dtype(a, t)), _num(_as_dtype(b, t))
        fns = {
            "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
            ">": jnp.greater, "<=": jnp.less_equal, ">=": jnp.greater_equal,
        }
        return KVal(fns[op](av, bv), "bool")

    t = _promote(a.ctype, b.ctype)
    ac, bc = _as_dtype(a, t), _as_dtype(b, t)
    av, bv = _num(ac), _num(bc)

    affine = span = None
    if t in _INT_TYPES and op in ("+", "-", "*"):
        ka, kb = ac.affine, bc.affine
        ca, cb = _const_int(ac), _const_int(bc)
        sa, sb = ac.span, bc.span
        both = sa is not None and sb is not None
        if op == "+" and ka is not None and kb is not None:
            affine = (ka[0] + kb[0], (ka[1], kb[1]))
            span = both and (sa[0] + sb[0], sa[1] + sb[1])
        elif op == "-" and ka is not None and kb is not None:
            affine = (ka[0] - kb[0], (ka[1], kb[1]))
            span = both and (sa[0] - sb[1], sa[1] - sb[0])
        elif op == "*" and ka is not None and cb is not None:
            affine = (ka[0] * cb, (ka[1], cb))
            span = sa and tuple(sorted((sa[0] * cb, sa[1] * cb)))
        elif op == "*" and kb is not None and ca is not None:
            affine = (kb[0] * ca, (kb[1], ca))
            span = sb and tuple(sorted((sb[0] * ca, sb[1] * ca)))
        elif op == "*" and ka is not None and kb is not None and ka[0] == kb[0] == 0:
            # two values that are the same in every lane: so is the product
            affine = (0, (ka[1], kb[1]))
            if both:
                ends = [x * y for x in sa for y in sb]
                span = (min(ends), max(ends))
        span = span or None

    if op in ("+", "-", "*"):
        out = av + bv if op == "+" else av - bv if op == "-" else av * bv
        if affine is not None:
            # the ``const`` part by the same rule; of a value that is the
            # same in every lane it is the value itself, computed once
            parts = affine[1]
            if affine[0] == 0 and not all(type(x) is int for x in parts):
                affine = (0, out)
            else:
                affine = (affine[0], _OFF[op](*parts))
        return KVal(out, t, affine, span)
    if op == "/":
        if t in _FLOAT_TYPES:
            return KVal(av / bv, t)
        return KVal(lax.div(jnp.asarray(av), jnp.asarray(bv)), t)  # C truncating division
    if op == "%":
        if t in _FLOAT_TYPES:
            return KVal(jnp.fmod(av, bv), t)
        return KVal(lax.rem(jnp.asarray(av), jnp.asarray(bv)), t)  # C remainder semantics
    if op in ("&", "|", "^"):
        it = t if t in _INT_TYPES else "int"
        av, bv = _num(_as_dtype(ac, it)), _num(_as_dtype(bc, it))
        fns = {"&": jnp.bitwise_and, "|": jnp.bitwise_or, "^": jnp.bitwise_xor}
        return KVal(fns[op](av, bv), it)
    if op in ("<<", ">>"):
        it = t if t in _INT_TYPES else "int"
        av = _num(_as_dtype(ac, it))
        bv = _num(_as_dtype(bc, it))
        fn = jnp.left_shift if op == "<<" else jnp.right_shift
        return KVal(fn(av, bv), it)
    raise KernelCompileError(f"unknown operator {op}", line=node.line)


# arithmetic on the ``const`` part of an affine value: a Python int or a
# traced scalar; the trivial cases add no operation to the trace

def _add_off(a, b):
    if type(a) is int and a == 0:
        return b
    return a if type(b) is int and b == 0 else a + b


def _sub_off(a, b):
    return a if type(b) is int and b == 0 else a - b


def _mul_off(a, c):
    if type(c) is int and c == 1:
        return a
    return c if type(a) is int and a == 1 else a * c


_OFF = {"+": _add_off, "-": _sub_off, "*": _mul_off}


# ---------------------------------------------------------------------------
# builtins
# ---------------------------------------------------------------------------

_UNARY_FLOAT = {
    "sqrt": jnp.sqrt, "rsqrt": lax.rsqrt, "cbrt": jnp.cbrt, "exp": jnp.exp,
    "exp2": jnp.exp2, "exp10": lambda x: jnp.power(10.0, x), "log": jnp.log,
    "log2": jnp.log2, "log10": jnp.log10, "sin": jnp.sin, "cos": jnp.cos,
    "tan": jnp.tan, "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh, "asinh": jnp.arcsinh,
    "acosh": jnp.arccosh, "atanh": jnp.arctanh, "fabs": jnp.abs,
    "floor": jnp.floor, "ceil": jnp.ceil, "round": jnp.round, "rint": jnp.round,
    "trunc": jnp.trunc, "erf": lax.erf, "erfc": lax.erfc,
    "degrees": jnp.degrees, "radians": jnp.radians, "sign": jnp.sign,
}

_BINARY_FLOAT = {
    "pow": jnp.power, "powr": jnp.power, "atan2": jnp.arctan2,
    "fmod": jnp.fmod, "remainder": jnp.remainder, "hypot": jnp.hypot,
    "copysign": jnp.copysign, "fdim": lambda a, b: jnp.maximum(a - b, 0.0),
    "nextafter": jnp.nextafter,
}

_UNSUPPORTED_CALLS = {
    "mem_fence": "memory fences order one work item's accesses and meet no "
                 "other work item; use barrier(), which the group reaches together",
    "read_mem_fence": "memory fences (use barrier())",
    "write_mem_fence": "memory fences (use barrier())",
    "barrier": "a barrier is a statement of its own, `barrier(CLK_LOCAL_MEM_FENCE);`",
    "work_group_barrier": "a barrier is a statement of its own",
}


def _inline_helper(ctx: _Ctx, fdef, arg_nodes, call_line: int) -> KVal:
    """Inline a helper call: evaluate args in the caller's scope, execute
    the body in a FRESH scope — helpers see ONLY their params and locals
    (no captured kernel variables, no buffers, no caller private arrays,
    and no inherited uniformity facts, whose names are kernel-scoped) —
    and return the final ``return expr;`` value coerced to the declared
    return type."""
    if len(arg_nodes) != len(fdef.params):
        raise KernelCompileError(
            f"helper {fdef.name!r} takes {len(fdef.params)} argument(s), "
            f"got {len(arg_nodes)}", line=call_line,
        )
    stack = ctx.info.setdefault("inline_stack", [])
    if fdef.name in stack:
        raise KernelLanguageError(
            f"recursive helper call {fdef.name!r} is not supported",
            line=call_line,
        )
    vals = [_eval(ctx, a) for a in arg_nodes]
    saved_env, saved_priv, saved_local = ctx.env, ctx.private, ctx.local
    saved_bufs, saved_bct = ctx.bufs, ctx.buf_ctypes
    saved_uniform, saved_vectors = ctx.uniform_vars, ctx.vectors
    ctx.env, ctx.private, ctx.vectors = {}, {}, {}
    for p, v in zip(fdef.params, vals):
        if p.ctype in VECTOR_TYPES:
            vectors.bind(ctx, p.name, p.ctype,
                         vectors.splat(ctx, v, p.ctype, call_line).value)
        else:
            ctx.env[p.name] = _as_dtype(v, p.ctype)
    ctx.local = {}
    ctx.bufs = {}
    ctx.buf_ctypes = {}
    ctx.uniform_vars = set()
    stack.append(fdef.name)
    # the final `return expr;` still READS helper locals after any loops in
    # the body — it must be visible to free-run liveness (else a loop
    # would treat the returned accumulator as dead-after and skip its
    # per-lane freeze)
    ctx._after_stack.append([fdef.body[-1]])
    try:
        _exec_block(ctx, fdef.body[:-1])
        ret = _eval(ctx, fdef.body[-1].value)
        if fdef.ret_ctype in VECTOR_TYPES:
            return vectors.splat(ctx, ret, fdef.ret_ctype, call_line)
        return _as_dtype(ret, fdef.ret_ctype)
    finally:
        ctx._after_stack.pop()
        stack.pop()
        ctx.env = saved_env
        ctx.private, ctx.local = saved_priv, saved_local
        ctx.bufs = saved_bufs
        ctx.buf_ctypes = saved_bct
        ctx.uniform_vars, ctx.vectors = saved_uniform, saved_vectors


def _call(ctx: _Ctx, node: Call) -> KVal:
    name = node.name
    if name in ctx.helpers:
        return _inline_helper(ctx, ctx.helpers[name], node.args, node.line)
    if name.startswith("native_") or name.startswith("half_"):
        name = name.split("_", 1)[1]
    if name.startswith("atomic_") or name.startswith("atom_"):
        raise KernelLanguageError(
            f"{node.name}: atomics are not supported (the order in which the "
            "lanes of a launch run against each other is unspecified); reduce "
            "through a __local array and barrier(), one partial a work-group",
            line=node.line,
        )
    if name in _UNSUPPORTED_CALLS:
        raise KernelLanguageError(f"{name}: {_UNSUPPORTED_CALLS[name]}", line=node.line)

    vectors.refuse_builtin(name, node.line)

    args = [_eval(ctx, a) for a in node.args]
    if any(a.ctype in VECTOR_TYPES for a in args):
        raise vectors.refused(
            "vector-builtin", f"{node.name} of a vector is not supported; "
            "call it a component", node.line)

    if name in ("get_global_id", "get_local_id", "get_group_id", "get_global_size",
                "get_local_size", "get_num_groups", "get_global_offset", "get_work_dim"):
        dim = _const_int(args[0]) if args else 0
        if name != "get_work_dim" and dim not in (0, None):
            raise KernelLanguageError(
                f"{name}({dim}): only dimension 0 is supported (the reference's "
                "NDRange is 1-D, ClNdRange.cs:29-71)", line=node.line)
        if name == "get_global_id":
            return ctx.gid
        if name == "get_global_size":
            if isinstance(ctx.global_size, int):
                return _int_const(ctx.global_size)
            return KVal(ctx.global_size, "int")
        if name == "get_local_size":
            return _int_const(ctx.local_size)
        if name == "get_local_id":
            g = _num(ctx.gid)
            return KVal(lax.rem(g, jnp.int32(ctx.local_size)), "int")
        if name == "get_group_id":
            g = _num(ctx.gid)
            return KVal(lax.div(g, jnp.int32(ctx.local_size)), "int")
        if name == "get_num_groups":
            gs = ctx.global_size
            return KVal(gs // ctx.local_size if isinstance(gs, int) else lax.div(gs, ctx.local_size), "int")
        if name == "get_global_offset":
            return _int_const(0)
        return _int_const(1)  # get_work_dim

    if name in _UNARY_FLOAT:
        a = args[0]
        t = a.ctype if a.ctype in _FLOAT_TYPES else "float"
        if name in ("fabs", "sign") and a.ctype in _INT_TYPES:
            t = a.ctype
            return KVal(jnp.abs(_num(a)) if name == "fabs" else jnp.sign(_num(a)), t)
        return KVal(_UNARY_FLOAT[name](_num(_as_dtype(a, t))), t)

    if name in _BINARY_FLOAT:
        t = _promote(args[0].ctype, args[1].ctype)
        if t not in _FLOAT_TYPES:
            t = "float"
        av, bv = _num(_as_dtype(args[0], t)), _num(_as_dtype(args[1], t))
        return KVal(_BINARY_FLOAT[name](av, bv), t)

    if name == "abs":
        return KVal(jnp.abs(_num(args[0])), args[0].ctype)
    if name in ("min", "fmin"):
        t = _promote(args[0].ctype, args[1].ctype)
        return KVal(jnp.minimum(_num(_as_dtype(args[0], t)), _num(_as_dtype(args[1], t))), t)
    if name in ("max", "fmax"):
        t = _promote(args[0].ctype, args[1].ctype)
        return KVal(jnp.maximum(_num(_as_dtype(args[0], t)), _num(_as_dtype(args[1], t))), t)
    if name == "clamp":
        t = _promote(_promote(args[0].ctype, args[1].ctype), args[2].ctype)
        x, lo, hi = (_num(_as_dtype(a, t)) for a in args)
        return KVal(jnp.clip(x, lo, hi), t)
    if name in ("mad", "fma"):
        t = "float"
        for a in args:
            t = _promote(t, a.ctype) if a.ctype in _FLOAT_TYPES else t
        a, b, c = (_num(_as_dtype(x, t)) for x in args)
        return KVal(a * b + c, t)
    if name == "mix":
        t = "float"
        a, b, w = (_num(_as_dtype(x, t)) for x in args)
        return KVal(a + (b - a) * w, t)
    if name == "step":
        t = "float"
        edge, x = (_num(_as_dtype(a, t)) for a in args)
        return KVal(jnp.where(x < edge, 0.0, 1.0).astype(ctype_to_dtype(t)), t)
    if name == "smoothstep":
        t = "float"
        e0, e1, x = (_num(_as_dtype(a, t)) for a in args)
        u = jnp.clip((x - e0) / (e1 - e0), 0.0, 1.0)
        return KVal(u * u * (3.0 - 2.0 * u), t)
    if name == "select":
        # OpenCL select(a, b, c) == c ? b : a
        c = _truthy(args[2])
        t = _promote(args[0].ctype, args[1].ctype)
        return KVal(jnp.where(c, _num(_as_dtype(args[1], t)), _num(_as_dtype(args[0], t))), t)
    if name == "isnan":
        return KVal(jnp.isnan(_num(args[0])), "bool")
    if name == "isinf":
        return KVal(jnp.isinf(_num(args[0])), "bool")
    if name == "isfinite":
        return KVal(jnp.isfinite(_num(args[0])), "bool")

    raise KernelLanguageError(f"unknown function {node.name!r}", line=node.line)


# ---------------------------------------------------------------------------
# loads / stores
# ---------------------------------------------------------------------------


def _private_index(ctx: _Ctx, node: Index, k: int):
    """Evaluate a private-array index: (const | per-lane vector, clamped)."""
    idx = _eval(ctx, node.index)
    if idx.ctype not in _INT_TYPES:
        raise KernelLanguageError("array index must be an integer", line=node.line)
    c = _const_int(idx)
    if c is not None:
        if not 0 <= c < k:
            raise KernelCompileError(
                f"private array index {c} out of bounds [0, {k})", line=node.line
            )
        return c
    iv = _num(_as_dtype(idx, "int"))
    if not hasattr(iv, "ndim") or iv.ndim == 0:
        iv = jnp.full(ctx.shape, iv, dtype=jnp.int32)
    return jnp.clip(iv, 0, k - 1)


def _private_load(ctx: _Ctx, node: Index) -> KVal:
    k = ctx.private[node.base]
    val = ctx.env[node.base]
    ix = _private_index(ctx, node, k)
    if isinstance(ix, int):
        return KVal(val.value[ix], val.ctype)
    return KVal(jnp.take_along_axis(val.value, ix[None], axis=0)[0], val.ctype)


def _private_store(ctx: _Ctx, node: Index, v: KVal) -> None:
    k = ctx.private[node.base]
    cur = ctx.env[node.base]
    payload = _num(_as_dtype(v, cur.ctype))
    if not hasattr(payload, "ndim") or payload.ndim == 0:
        payload = ctx.broadcast_scalar(payload, ctype_to_dtype(cur.ctype))
    m = ctx.active_mask()
    ix = _private_index(ctx, node, k)
    if isinstance(ix, int):
        row = cur.value[ix]
        new_row = payload if m is None else jnp.where(m, payload, row)
        ctx.env[node.base] = KVal(cur.value.at[ix].set(new_row), cur.ctype)
        return
    # per-lane dynamic element: each lane updates its own (index, lane) cell
    gathered = jnp.take_along_axis(cur.value, ix[None], axis=0)[0]
    new_vals = payload if m is None else jnp.where(m, payload, gathered)
    ctx.env[node.base] = KVal(_scatter_lanes(cur.value, ix, new_vals), cur.ctype)


def _scatter_lanes(stack, ix, vals):
    """stack[(ix[lane], lane)] = vals[lane] for every lane position."""
    lanes = jnp.indices(stack.shape[1:])
    return stack.at[(ix,) + tuple(lanes)].set(vals)


# ---------------------------------------------------------------------------
# work-group cooperation: ``__local`` arrays.  A launch covers ``G`` whole
# work-groups of ``L`` work items; lane ``b`` of it is work item ``t = b % L``
# of group ``g = b // L`` (the launch's offset is a multiple of ``L``, so
# ``get_local_id(0)`` is ``t``).  ``__local T a[K];`` is a value ``A[G, K]``:
# a load ``a[e]`` gives lane ``(g, t)`` the element ``A[g, e(g, t)]``, a store
# under the lanes' mask ``m`` sets ``A[g, e(g, t)] = v(g, t)`` where
# ``m(g, t)``; a statement's loads see the tile as it was before its stores.
# Three lowerings, chosen from the syntax alone (:func:`_local_kind`):
# ``shift`` (``e = tid + u`` with ``u`` the same in every lane of a group: the
# lanes' elements are a window of the row), ``uniform`` (``e = u``: one
# element a group), ``row`` (anything else: a gather / scatter inside the
# row, always right).  An index outside ``[0, K)`` is undefined in OpenCL:
# here a load reads the nearest element and a store is dropped.
# ---------------------------------------------------------------------------


def _under_int_casts(node):
    while isinstance(node, Cast) and node.ctype in _INT_TYPES:
        node = node.operand
    return node


def _is_id_call(node, name: str) -> bool:
    """Is ``node`` the call ``name(0)`` (``get_local_id``, ``get_group_id``),
    under integer casts?"""
    node = _under_int_casts(node)
    return (isinstance(node, Call) and node.name == name
            and all(isinstance(a, Num) and a.value == 0 for a in node.args))


def _is_local_id(node) -> bool:
    """Is ``node`` ``get_local_id(0)``, under integer casts?"""
    return _is_id_call(node, "get_local_id")


def _is_tid(ctx: _Ctx, node) -> bool:
    """Is ``node`` the work item's local id: ``get_local_id(0)``, or a local
    that is nothing else (:func:`_tid_vars`), under integer casts?"""
    inner = _under_int_casts(node)
    if isinstance(inner, Var):
        return inner.name in ctx.tid_vars
    return _is_local_id(inner)


def _local_kind(ctx: _Ctx, index) -> tuple:
    """``(kind, rest)`` of a ``__local`` access at ``index``: ``rest`` are the
    ``(sign, term)`` leaves of ``u`` where the kind is ``shift``."""
    private = frozenset(ctx.private)
    if _expr_uniform(index, ctx.group_uniform, private, group=True):
        return "uniform", ()
    terms = _terms(index, 1, [])
    rest = [(sg, t) for sg, t in terms if not _is_tid(ctx, t)]
    if ([sg for sg, t in terms if _is_tid(ctx, t)] == [1]
            and all(_expr_uniform(t, ctx.group_uniform, private, group=True)
                    for _, t in rest)):
        return "shift", tuple(rest)
    return "row", ()


def _group_value(ctx: _Ctx, v: KVal, launch_uniform: bool):
    """An integer proved the same in every lane of a group as a Python int,
    a 0-d ``int32`` (the same over the launch) or ``int32[G]``."""
    c = _const_int(v)
    if c is not None:
        return c
    iv = jnp.asarray(_num(_as_dtype(v, "int")), jnp.int32)
    if iv.ndim == 0 or launch_uniform:
        return _lane0(iv)
    return iv.reshape(-1, ctx.local_size)[:, 0]


def _local_index(ctx: _Ctx, node: Index):
    """``(kind, u)`` of the access site ``node``, ``u`` the group's part of
    the index (:func:`_group_value`; the whole index as ``int32[G, L]`` where
    the kind is ``row``)."""
    kind, rest = _local_kind(ctx, node.index)
    if kind == "shift":
        u, parts = _int_const(0), [t for _, t in rest]
        for sign, term in rest:
            u = _binop(ctx, BinOp(op="+" if sign > 0 else "-", left=_Lit(u),
                                  right=_Lit(_eval(ctx, term)), line=node.line))
    else:
        u, parts = _eval(ctx, node.index), [node.index]
    if u.ctype not in _INT_TYPES:
        raise KernelLanguageError("array index must be an integer", line=node.line)
    if kind == "row":
        iv = jnp.asarray(_num(_as_dtype(u, "int")), jnp.int32)
        return kind, jnp.broadcast_to(iv, ctx.shape).reshape(-1, ctx.local_size)
    arrays = ctx.lane_arrays()
    return kind, _group_value(ctx, u, all(
        _expr_uniform(t, ctx.uniform_vars, arrays) for t in parts))


def _shift_rows(x, d, width: int, lo, hi):
    """``out[g, k] = x[g, k + d_g]`` for ``k`` in ``[0, width)`` where that
    exists, ``lo`` before the row and ``hi`` behind it; ``d`` a Python int, a
    0-d value (one slice) or ``int32[G]`` (a slice a row)."""
    g, w = x.shape
    if type(d) is int and 0 <= d and d + width <= w:
        return x if (d, width) == (0, w) else lax.slice_in_dim(x, d, d + width, axis=1)
    fill = (g, width)
    ext = jnp.concatenate([jnp.broadcast_to(jnp.asarray(lo, x.dtype), fill), x,
                           jnp.broadcast_to(jnp.asarray(hi, x.dtype), fill)], axis=1)
    start = width + jnp.clip(jnp.asarray(d, jnp.int32), -width, w)
    if start.ndim == 0:
        return lax.dynamic_slice_in_dim(ext, start, width, axis=1)
    return jax.vmap(lambda row, s0: lax.dynamic_slice(row, (s0,), (width,)))(ext, start)


def _group_mask(ctx: _Ctx):
    """The active mask as ``bool[G, L]``, None when every lane is active."""
    m = ctx.active_mask()
    if m is None:
        return None
    return jnp.broadcast_to(m, ctx.shape).reshape(-1, ctx.local_size)


def _local_load(ctx: _Ctx, node: Index) -> KVal:
    """``a[e]`` of a ``__local`` array (see the section's comment)."""
    tile, k, L = ctx.env[node.base], ctx.local[node.base], ctx.local_size
    A = tile.value
    kind, u = _local_index(ctx, node)
    ctx.local_access[id(node), False] = kind
    if kind == "shift":
        out = _shift_rows(A, u, L, A[:, :1], A[:, -1:])
    elif kind == "uniform":
        if type(u) is int or u.ndim == 0:
            at = min(max(u, 0), k - 1) if type(u) is int else jnp.clip(u, 0, k - 1)
            one = lax.dynamic_slice_in_dim(A, at, 1, axis=1)
        else:
            one = jnp.take_along_axis(A, jnp.clip(u, 0, k - 1)[:, None], axis=1)
        out = jnp.broadcast_to(one, (A.shape[0], L))
    else:
        out = jnp.take_along_axis(A, jnp.clip(u, 0, k - 1), axis=1)
    return KVal(out.reshape(ctx.shape), tile.ctype)


def _local_store(ctx: _Ctx, node: Index, val: KVal) -> None:
    """``a[e] = v`` of a ``__local`` array under the active mask."""
    tile, k, L = ctx.env[node.base], ctx.local[node.base], ctx.local_size
    A = tile.value
    G = A.shape[0]
    v = jnp.broadcast_to(jnp.asarray(_num(_as_dtype(val, tile.ctype)), A.dtype),
                         ctx.shape).reshape(G, L)
    kind, u = _local_index(ctx, node)
    ctx.local_access[id(node), True] = kind
    m = _group_mask(ctx)
    if kind == "shift":
        # element k of a row is written by work item k - u, where that is one
        # and it is active
        back = -u if type(u) is int else jnp.negative(u)
        written = _shift_rows(jnp.ones((G, L), jnp.bool_) if m is None else m,
                              back, k, False, False)
        new = jnp.where(written, _shift_rows(v, back, k, 0, 0), A)
    elif kind == "uniform":
        # one element a group: the value of its first active work item (two
        # that store different values there race in OpenCL too)
        if m is None:
            some, first = True, v[:, :1]
        else:
            some = jnp.any(m, axis=1, keepdims=True)
            first = jnp.take_along_axis(
                v, jnp.argmax(m, axis=1)[:, None].astype(jnp.int32), axis=1)
        col = lax.broadcasted_iota(jnp.int32, (G, k), 1)
        at = jnp.asarray(u, jnp.int32)
        hit = jnp.logical_and(col == (at if at.ndim == 0 else at[:, None]), some)
        new = jnp.where(hit, first, A)
    else:
        ix = u if m is None else jnp.where(m, u, k)
        ix = jnp.where(ix < 0, k, ix)  # outside the row: dropped
        rows = lax.broadcasted_iota(jnp.int32, (G, L), 0)
        new = A.at[rows, ix].set(v, mode="drop")
    ctx.env[node.base] = KVal(new, tile.ctype)


def _loaded(value, ctype: str) -> KVal:
    """Wrap a loaded buffer value as its DECLARED ctype: when the caller's
    array dtype differs (e.g. f16 storage behind a float-declared param),
    the load converts so every in-kernel computation runs in the declared
    type — the store's cast back to storage dtype is the symmetric
    inverse.  Without this, loop carries seeded from a load keep the
    storage dtype while arithmetic promotes, and lax.while raises a carry
    dtype mismatch at trace time."""
    dt = ctype_to_dtype(ctype)
    if hasattr(value, "dtype") and value.dtype != dt:
        value = value.astype(dt)
    return KVal(value, ctype)


# ---------------------------------------------------------------------------
# per-lane reads through 128-wide rows.  On the chip a gather of single
# elements costs 9-20 ns an element whatever the pattern, and a gather of
# whole rows of 128 costs 3-12 ns a ROW (PERF.md, PR 26): so an element is
# read by fetching its row and picking its lane, and a loop that walks a
# per-lane run ``T[j], T[j + 1], ...`` fetches the run's row once for many
# passes (_exec_loop).  What a row costs is the fetch, not its bytes (11.8
# ns a row, 22.3 for two neighbours in one gather: PERF.md, PR 27), so a
# run is laid out to need one.
# ---------------------------------------------------------------------------

_ROW = 128             # elements of a row of ``_Ctx.rows_view``
_RUN_WINDOW = 32       # passes one refill of a loop's run windows serves
_LANE_CHUNK = 1 << 18  # work items whose rows are materialized at once


def _words_of(buf):
    """A table of 1-byte elements (``char`` masks) as ``[rows, 128]`` 32-bit
    WORDS for the row gathers, which fetch 4-byte elements: row ``r`` holds
    elements ``[512 r, 512 r + 512)``, element ``512 r + 128 k + l`` in bits
    ``[8 k, 8 k + 8)`` of lane ``l`` (put there by shifts: no byte order is
    assumed), the last element repeated to the end of the last row.  The
    four bytes of a word lie 128 elements apart, which is how the chip keeps
    a byte array in memory: packing ADJACENT bytes cost four strided reads
    of the table a launch (1.9 ms each for a million bytes) or 17-35 s of the
    chip's compiler a launcher (PERF.md, PR 40)."""
    n = buf.shape[0]
    u = lax.bitcast_convert_type(jnp.pad(buf, (0, -n % (4 * _ROW)), mode="edge"),
                                 jnp.uint8).astype(jnp.uint32).reshape(-1, 4, _ROW)
    w = u[:, 0] | (u[:, 1] << 8) | (u[:, 2] << 16) | (u[:, 3] << 24)
    return lax.bitcast_convert_type(w, jnp.int32)


def _rows_of(buf):
    """``buf`` as ``[rows, 128]``.  Element ``i`` sits at ``i + 128`` of a
    padded copy: one row of the first element before it, the last element
    repeated after it, so that a read reaching over either end reads what a
    gather's clamp reads.  The plain view is that copy cut into rows.  A
    table of 1-byte elements is its words (:func:`_words_of`), four
    elements a lane, with no row before the first."""
    if buf.dtype.itemsize == 1:
        return _words_of(buf)
    n = buf.shape[0]
    return jnp.pad(buf, (_ROW, -n % _ROW + _ROW), mode="edge").reshape(-1, _ROW)


def _runs_of(buf):
    """The OVERLAPPING ``[rows, 128]`` view, a row every 64 elements: row
    ``k`` holds ``[128 k, 128 k + 128)`` of :func:`_rows_of`'s padded copy
    and row ``K + k`` holds ``[128 k + 64, 128 k + 192)``, ``K`` rows a
    half, so that a run of ``_RUN_WINDOW`` elements lies whole in one row
    wherever it starts.  It is twice the buffer's bytes (one concatenate:
    the halves are the copy, and the copy 64 elements on)."""
    n = buf.shape[0]
    half, tail = _ROW // 2, -n % _ROW
    lo, hi = buf[:1], buf[-1:]
    return jnp.concatenate([
        jnp.broadcast_to(lo, (_ROW,)), buf,
        jnp.broadcast_to(hi, (tail,)),
        jnp.broadcast_to(lo, (half,)), buf,
        jnp.broadcast_to(hi, (tail + half,))]).reshape(-1, _ROW)


class ViewSpec(NamedTuple):
    """A launch-invariant VIEW a build asks for: a value computed from ONE
    array parameter and nothing else (no offset, no value argument but a
    launcher key, no other array), of a parameter the kernel never stores
    to.  What launch k + 1 would build is bit for bit what launch k built
    for as long as the array is the same object, so a launcher may build it
    once, when it first meets the array, and hand it to every launch as an
    argument (kernel/registry.py keeps them).  ``build_kernel_fn`` reports
    the specs a trace asked for in ``KernelBuildInfo.views`` and its ``fn``
    takes the built views as ``views={(param, kind): view}``; whatever it is
    not handed it builds in the launch.

    ``kind``: ``rows`` (:func:`_rows_of`), ``runs`` (:func:`_runs_of`),
    ``pitch:<s>`` (the buffer as ``[rows, s]``: a strided window's view)."""

    param: int  # position among the kernel's array parameters
    kind: str

    @property
    def pitch(self) -> int | None:
        """``s`` of a ``pitch:<s>`` view, None for the row views."""
        return int(self.kind[6:]) if self.kind.startswith("pitch:") else None

    @property
    def build(self) -> Callable:
        """``array -> view``: what the launch itself would compute, under a
        name of its own (a jitted builder reads ``jit_view_<kind>``)."""
        pitch = self.pitch
        of = ((lambda buf: buf.reshape(-1, pitch)) if pitch else
              {"rows": _rows_of, "runs": _runs_of}[self.kind])

        def view(buf):
            return of(buf)

        view.__name__ = "view_" + hlo_name(self.kind)
        return view

    def nbytes(self, shape: tuple, itemsize: int) -> int:
        """What the view of a buffer of ``shape`` holds on a device, where
        a 2-D array lies in tiles of ``(8, 128)`` elements."""
        n, pitch = shape[0], self.pitch
        if pitch:
            rows, cols = n // pitch, pitch
        else:
            blocks = -(-n // _ROW)
            rows, cols = (2 * blocks + 2 if self.kind == "runs"
                          else blocks + 2), _ROW
        return (-(-rows // 8) * 8) * (-(-cols // _ROW) * _ROW) * itemsize


def _by_lane_chunks(fn, ix, lead: tuple, dtype):
    """``fn(int32[C]) -> dtype[*lead, C]`` over ``ix`` in chunks of
    ``_LANE_CHUNK`` lanes, joined along the last axis: a row gather holds
    128 elements a lane, which for a whole rung would be gigabytes."""
    B = ix.shape[0]
    C = _LANE_CHUNK
    if B <= C:
        return fn(ix)
    n = -(-B // C)
    if n * C != B:
        ix = jnp.pad(ix, (0, n * C - B))
    at = (0,) * len(lead)

    def body(c, out):
        part = fn(lax.dynamic_slice(ix, (c * C,), (C,)))
        return lax.dynamic_update_slice(out, part, at + (c * C,))

    out = lax.fori_loop(0, n, body, jnp.zeros(lead + (n * C,), dtype))
    return out if n * C == B else out[..., :B]


def _take_rows(ctx: _Ctx, name: str, iv):
    """``buf[clip(iv)]`` for every lane: the element's row fetched whole,
    its lane picked by a compare and a sum (on the value's bits, so that
    every float comes back as it was stored).  Of a table of 1-byte
    elements the row of the element's WORD is fetched and the byte shifted
    out of it (on the chip a gather of single bytes costs 10.8 ns an
    element, the row of its word a third: PERF.md, PR 40)."""
    buf = ctx.bufs[name]
    rows, n, bytewise = ctx.rows_view(name), buf.shape[0], buf.dtype.itemsize == 1
    bits = lax.bitcast_convert_type(rows, jnp.int32)

    def pick(ic):
        ic = jnp.clip(ic, 0, n - 1) + _ROW
        g = bits.at[ic >> 7].get(mode="promise_in_bounds")
        lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
        hit = lane == (ic & (_ROW - 1))[:, None]
        return jnp.sum(jnp.where(hit, g, 0), axis=1, dtype=jnp.int32)

    def pick_byte(ic):
        # a byte table's row holds 512 elements, byte k of lane l being
        # element 128 k + l of them (_words_of)
        ic = jnp.clip(ic, 0, n - 1)
        g = bits.at[ic >> 9].get(mode="promise_in_bounds")
        lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
        hit = lane == (ic & (_ROW - 1))[:, None]
        got = jnp.sum(jnp.where(hit, g, 0), axis=1, dtype=jnp.int32)
        return (got >> (((ic >> 7) & 3) << 3)) & 0xFF

    out = _by_lane_chunks(pick_byte if bytewise else pick,
                          iv.astype(jnp.int32), (), jnp.int32)
    if bytewise:
        return lax.bitcast_convert_type(out.astype(jnp.uint8), buf.dtype)
    return lax.bitcast_convert_type(out, rows.dtype)


def _run_window(ctx: _Ctx, name: str, j0):
    """``out[r, lane] = buf[clip(j0[lane] + r)]`` for ``r`` in
    ``[0, _RUN_WINDOW)``.  A lane's run lies whole in ONE row of the
    overlapping row view, at an offset under 64: the rows are fetched,
    transposed for real (lanes last, in tiles of 128: line ``e * tiles +
    k`` is element ``e`` of tile ``k``'s rows) and moved up by the offset
    one bit at a time, a select between two slices of whole lines a bit:
    six stages over 63, 47, 39, 35, 33, 32 rows."""
    rows, n = ctx.rows_view(name, overlapping=True), ctx.bufs[name].shape[0]
    W, half = _RUN_WINDOW, _ROW // 2
    width = -(-(W + half - 1) // 8) * 8  # of a fetched row, what a run can reach

    def window(jc):
        c = jc.shape[0]
        tiles = -(-c // _ROW)
        if c % _ROW:
            jc = jnp.pad(jc, (0, -c % _ROW))
        start = jnp.clip(jc, -_ROW, n - 1) + _ROW
        r0 = (start >> 7) + ((start >> 6) & 1) * (rows.shape[0] // 2)
        off = (start & (half - 1)).reshape(tiles, _ROW)
        g = rows.at[r0].get(mode="promise_in_bounds")
        # Flattened to 2-D the transposition has to be made (as a 3-D
        # ``[width, tiles, 128]`` XLA keeps the gather's layout, elements
        # minor, and every stage slices the minor dimension); behind the
        # barrier the first stage's slices stay slices of it
        t = g[:, :width].reshape(tiles, _ROW, width).transpose(2, 0, 1)
        t = lax.optimization_barrier(t.reshape(width * tiles, _ROW))
        bit = half // 2
        while bit:
            keep = W + bit - 1
            up = jnp.broadcast_to(((off & bit) != 0)[None], (keep, tiles, _ROW))
            t = jnp.where(up.reshape(keep * tiles, _ROW),
                          t[bit * tiles:(bit + keep) * tiles], t[:keep * tiles])
            bit //= 2
        return t.reshape(W, tiles * _ROW)[:, :c]

    return _by_lane_chunks(window, j0.astype(jnp.int32), (W,), rows.dtype)


# ---------------------------------------------------------------------------
# affine accesses: an index ``s * gid + u`` with ``u`` the same in every
# lane.  Stride 1 is a contiguous window of the buffer wherever ``u`` points
# (a slice, never a gather); a stride that is a build-time integer makes the
# lanes' elements a COLUMN of the buffer seen as ``[rows, s]``, and the
# columns that a counted loop's passes walk one after the other a 2-D slice
# of that view (_exec_counted).  A slice does not clamp element by element
# as a gather does, so each path needs the access proved in bounds from the
# launch's global range and the bounds of ``u`` (``KVal.span``), or puts the
# clamped elements in itself (_shift_fill).
# ---------------------------------------------------------------------------

_INT32_MAX = (1 << 31) - 1
# passes one strided window serves, at most: one block of the blocked view
# (on the chip 128 passes a window ran the row walk of a 1 GiB matrix in
# 21.8 ms a launch, 1024 passes in 25.4: PERF.md, PR 30)
_STRIDE_WINDOW = _ROW
_WINDOW_ELEMS = 1 << 24     # elements of one, at most (lanes x passes)


def _in_bounds(ctx: _Ctx, idx: KVal, n: int) -> bool:
    """Is ``idx`` (affine, stride >= 0) provably inside ``[0, n)`` in every
    lane of every launch of this build?  Only a build whose launches keep
    their work items in ``[0, global_size)`` (``in_range``) can tell: a
    compute with a global offset runs items ``offset + global_size`` and
    beyond through the same geometry."""
    g = ctx.global_size
    if (idx.affine is None or idx.span is None or not isinstance(g, int)
            or not ctx.in_range):
        return False
    stride, (lo, hi) = idx.affine[0], idx.span
    top = hi + stride * (g - 1)
    return stride >= 0 and lo >= 0 and top < n and top <= _INT32_MAX


def _shift_fill(w, d, lo, hi):
    """``out[..., k] = w[..., k + d]`` where that exists, ``lo`` before it
    and ``hi`` behind (``d`` a traced scalar): what a window that had to be
    moved back inside its buffer holds once it is moved out again."""
    b = w.shape[-1]
    fill = w.shape[:-1] + (b,)
    ext = jnp.concatenate([jnp.broadcast_to(lo, fill), w,
                           jnp.broadcast_to(hi, fill)], axis=-1)
    return lax.dynamic_slice_in_dim(ext, b + jnp.clip(d, -b, b), b, axis=-1)


def _slice_clamped(buf, start, b: int):
    """``buf[clip(start + k)]`` for ``k`` in ``[0, b)`` without a gather:
    the window at the nearest start that lies inside the buffer, moved to
    where it was asked for, the first or last element beyond the ends."""
    n = buf.shape[0]
    first, last = buf[:1], buf[-1:]
    if n < b:
        buf = jnp.pad(buf, (0, b - n), mode="edge")
    s0 = jnp.clip(start, 0, buf.shape[0] - b)
    w = lax.dynamic_slice(buf, (s0,), (b,))
    return _shift_fill(w, start - s0, first, last)


def _strided_rows(ctx: _Ctx, name: str, stride: int, blocked: bool,
                  window: bool = False):
    """``(view, row0, moved)``: buffer ``name`` as rows of ``stride``
    elements and where the chunk's block of rows starts in it; ``moved`` is
    None when the global range proves every lane's row inside the view, else
    how far the block had to be moved back (:func:`_shift_fill` moves its
    lanes out again).  The view is ``[rows, stride]``, or ``blocked``
    ``[rows, stride / 128, 128]``: on the chip a 1-D buffer lies in memory
    as its ``[n / 128, 128]`` view does, so the blocked view of a stride of
    whole tiles costs nothing where the 2-D one is a copy of the buffer (the
    compiler's choice, not this code's: PERF.md, PR 30).  A ``window`` reads
    whole ``(8, 128)`` tiles of the 2-D view and 512-byte pieces of the
    blocked one (3.3 against 11.5 ms over 1 GiB: PERF.md, PR 30), so it asks
    for the 2-D view as a KEPT one (:class:`ViewSpec`), whose copy is made
    once an upload, and takes ``blocked`` only where the launch was not
    handed it.  None when the buffer is no whole number of rows, or has
    fewer rows than the chunk has lanes."""
    buf = ctx.bufs[name]
    n = buf.shape[0]
    rows = n // stride
    if n % stride or rows < ctx.B:
        return None
    view = ctx.kept_view(name, f"pitch:{stride}") if window else None
    if view is None:
        view = buf.reshape((rows, stride // _ROW, _ROW) if blocked
                           else (rows, stride))
    g = ctx.global_size
    if ctx.in_range and isinstance(g, int) and g <= rows:
        return view, ctx.offset, None
    row0 = jnp.clip(ctx.offset, 0, rows - ctx.B)
    return view, row0, ctx.offset - row0


def _strided_load(ctx: _Ctx, name: str, idx: KVal):
    """``buf[s * gid + u]`` of buffer ``name`` for a build-time ``s >= 2``
    and ``u`` proved in ``[0, s)``: column ``u`` of the lanes' rows.  None
    where that is not proved."""
    stride, u = idx.affine
    if (not isinstance(stride, int) or stride < 2 or idx.span is None
            or idx.span[0] < 0 or idx.span[1] >= stride):
        return None
    at = _strided_rows(ctx, name, stride, stride % _ROW == 0)
    if at is None:
        return None
    view, row0, moved = at
    buf, u = ctx.bufs[name], jnp.asarray(u, jnp.int32)
    if view.ndim == 3:
        col = lax.dynamic_slice(view, (row0, u >> 7, u & (_ROW - 1)),
                                (ctx.B, 1, 1))[:, 0, 0]
    else:
        col = lax.dynamic_slice(view, (row0, u), (ctx.B, 1))[:, 0]
    # a lane whose row lies behind the buffer reads the last element
    return col if moved is None else _shift_fill(col, moved, buf[:1], buf[-1:])


def _strided_window(ctx: _Ctx, site, c, width: int):
    """``(win, d)``: the columns that ``width`` passes from column ``c`` on
    read, one row of ``win`` a column (``[columns, lanes]``: a 2-D slice of
    the view, transposed), pass ``r`` reading row ``r + d``.  A window that
    would reach over the end of the rows starts ``d`` columns early: the
    passes that would leave the row never run (``u + j < s`` is what made
    the read a site).  The blocked view is cut at whole blocks of 128: the
    one the walk starts in, and the next unless it is known to start on
    one."""
    view, row0, moved = site.at
    if view.ndim == 3:
        blocks = 1 + (not site.aligned)
        k0 = jnp.clip(c >> 7, 0, view.shape[1] - blocks)
        win = lax.dynamic_slice(view, (row0, k0, jnp.int32(0)),
                                (ctx.B, blocks, _ROW))
        win, d = win.reshape(ctx.B, blocks * _ROW).T, c - (k0 << 7)
    else:
        c0 = jnp.clip(c, 0, site.stride - width)
        win, d = lax.dynamic_slice(view, (row0, c0), (ctx.B, width)).T, c - c0
    if moved is not None:
        ends = ctx.bufs[site.node.base]
        win = _shift_fill(win, moved, ends[:1], ends[-1:])
    return win, d


def _group_index(ctx: _Ctx, idx) -> Any:
    """An index's value as ``int32[G, L]``."""
    return jnp.broadcast_to(jnp.asarray(_num(_as_dtype(idx, "int")), jnp.int32),
                            ctx.shape).reshape(-1, ctx.local_size)


def _group_starts(iv, m):
    """``(u, some)`` of an index ``local id + u`` (``iv``:
    :func:`_group_index`) under the group mask ``m`` (:func:`_group_mask`):
    ``u`` a group, 0 where it has no active lane (``some``).  ``u`` is the
    same in every active lane: their largest (a reduction; picking one lane's
    would be a gather)."""
    if m is None:
        return iv[:, 0], jnp.ones(iv.shape[0], jnp.bool_)
    tid = lax.broadcasted_iota(jnp.int32, iv.shape, 1)
    some, lowest = jnp.any(m, axis=1), jnp.iinfo(jnp.int32).min
    return jnp.where(some, jnp.max(jnp.where(m, iv - tid, lowest), axis=1), 0), some


def _group_pitched(ctx: _Ctx, name: str, pitch: int) -> bool:
    """Can the windows of a launch's groups be ONE slice of buffer ``name``
    seen as ``[n / pitch, pitch / 128, 128]``: a pitch of whole rows that
    holds a window, a buffer of whole pitches, one a group at least."""
    n, L = ctx.bufs[name].shape[0], ctx.local_size
    return not (pitch <= 0 or pitch % _ROW or L % _ROW or L > pitch
                or n % pitch or n // pitch < ctx.B // L)


def _group_first(u, some, pitch: int):
    """``(u0, base)``: group 0's start as each group has it (its own start
    less its place times ``pitch``), and as the active groups have it; their
    starts lie ``pitch`` apart where ``base == u0`` in all of them."""
    base = u - pitch * jnp.arange(u.shape[0], dtype=jnp.int32)
    lowest = jnp.iinfo(jnp.int32).min
    return jnp.where(jnp.any(some), jnp.max(jnp.where(some, base, lowest)), 0), base


def _group_block(ctx: _Ctx, name: str, pitch: int, start: Callable):
    """The windows of a launch's groups as one slice: ``L`` elements from row
    ``row`` of each of the blocks ``block ..`` of the buffer seen as blocks of
    ``pitch`` (which on the chip is how it lies in memory:
    :func:`_strided_rows`); ``start()`` gives ``(block, row)``, computed
    behind the view as PR 46's launcher did (its text is pinned:
    tests/test_local_memory.py)."""
    buf, L = ctx.bufs[name], ctx.local_size
    view = buf.reshape(buf.shape[0] // pitch, pitch // _ROW, _ROW)
    return lax.dynamic_slice(view, (*start(), jnp.int32(0)),
                             (ctx.B // L, L // _ROW, _ROW)).reshape(ctx.shape)


def _group_slice(ctx: _Ctx, name: str, idx: KVal, pitch: int = 0):
    """``buf[clip(idx)]`` of buffer ``name`` where ``idx`` is ``local id + u``
    with ``u`` the same in every ACTIVE work item of a group
    (:func:`_group_sites`): the ``L`` work items of a group read ``L``
    neighbouring elements, so a group fetches ONE window ``buf[u : u + L]``
    where a gather fetched a row of 128 a lane.  ``u`` is the index less the
    local id in the group's ACTIVE lanes (a lane that has left a masked loop
    may hold anything: its value is masked away, as a gather's is, and so is
    all that a group with no active lane reads).

    Two fetches, picked at run time from the ``u`` of the pass (both are
    compiled into the launcher).  Where the windows of the active groups lie
    ``pitch`` apart (the build's hint, whole rows of 128), start on a row and
    stay inside the buffer and inside their ``pitch`` elements, they are ONE
    slice ``[G, L / 128, 128]`` of the buffer seen as ``[n / pitch, pitch /
    128, 128]`` (:func:`_group_block`).  Anywhere else a window a group,
    element for element what the gather's clamp reads: taken at the nearest
    start inside the buffer and, where that is not where it was asked for,
    moved out again with the first or last element beyond the ends
    (:func:`_shift_rows`).  The chip's compiler makes a loop over the groups
    of the windows' fetch, which is why the one slice is worth its check
    (PERF.md s.6, PR 46, has both timed); a loop that moves the windows by a
    step the build knows makes the check once, not a pass
    (:func:`_settle`)."""
    buf, L = ctx.bufs[name], ctx.local_size
    n, G = buf.shape[0], ctx.B // L
    u, some = _group_starts(_group_index(ctx, idx), _group_mask(ctx))

    def windows():
        first, last = buf[:1], buf[-1:]
        wide = buf if n >= L else jnp.pad(buf, (0, L - n), mode="edge")
        start = jnp.clip(u, 0, wide.shape[0] - L)
        rows = lax.gather(
            wide, start[:, None], lax.GatherDimensionNumbers(
                offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)),
            slice_sizes=(L,), mode="promise_in_bounds")
        moved = u - start
        return lax.cond(jnp.any(moved != 0),
                        lambda: _shift_rows(rows, moved, L, first, last),
                        lambda: rows).reshape(ctx.shape)

    if not _group_pitched(ctx, name, pitch):
        return windows()
    u0, base = _group_first(u, some, pitch)
    at = lax.rem(u0, jnp.int32(pitch))
    fits = (jnp.all((base == u0) | ~some) & (u0 >= 0)
            & (u0 <= n - pitch * (G - 1) - L)
            & ((u0 & (_ROW - 1)) == 0) & (at <= pitch - L))
    return lax.cond(fits, lambda: _group_block(ctx, name, pitch, lambda: (
        lax.div(u0, jnp.int32(pitch)), lax.div(at, jnp.int32(_ROW)))), windows)


class _Settled(NamedTuple):
    """One group read of a loop that settles its windows once
    (:func:`_settle`): the slice its passes take."""

    site: int    # id of the read's Index node
    block: Any   # 0-d int32: the block of ``pitch`` group 0 reads in the first pass
    row: Any     # 0-d int32: the row inside their blocks the windows start on
    step: int    # blocks more a pass
    last: int    # the last block the slice of all groups may start in


def _settle(ctx: _Ctx, walks: list, entering) -> tuple:
    """``(ok, [_Settled])`` for the group reads ``walks`` of a masked loop
    (:func:`_settled_walks`) whose buffers take the one slice
    (:func:`_group_pitched`), in the order of the walks.

    What :func:`_group_slice` tests of a pass's starts, taken ONCE, from the
    starts the FIRST pass will have (the index where the loop is entered and
    what the pass adds ahead of the read) in the lanes ``entering`` that
    pass: ``ok``, do the lanes of every group agree, the groups' starts lie
    ``pitch`` apart, on a row, at ``row`` inside their ``pitch`` elements.
    Every lane that stays moves its index by the same multiple of the pitch
    a pass and lanes only leave, so all of this holds in every later pass
    for the lanes still there; what moves is the block group 0 reads,
    ``step`` more a pass, and the slice lies inside the buffer while that is
    in ``[0, last]``: a scalar the loop carries and its condition compares."""
    m = jnp.broadcast_to(entering, ctx.shape).reshape(-1, ctx.local_size)
    ok, sites = jnp.bool_(True), []
    for w in walks:
        name, pitch = w.node.base, ctx.group_sites[id(w.node)]
        if not _group_pitched(ctx, name, pitch):
            continue
        first = _group_index(ctx, _eval(ctx, w.node.index)) + jnp.int32(w.before)
        u, some = _group_starts(first, m)
        agree = (first - lax.broadcasted_iota(jnp.int32, m.shape, 1)
                 == u[:, None]) | ~m
        u0, base = _group_first(u, some, pitch)
        block = jnp.floor_divide(u0, jnp.int32(pitch))
        at = u0 - block * pitch
        ok = (ok & jnp.all(agree) & jnp.all((base == u0) | ~some)
              & ((at & (_ROW - 1)) == 0) & (at <= pitch - ctx.local_size))
        sites.append(_Settled(
            id(w.node), block, lax.div(at, jnp.int32(_ROW)), w.step // pitch,
            ctx.bufs[name].shape[0] // pitch - ctx.B // ctx.local_size))
    return ok, sites


def _note(ctx: _Ctx, node: Index, store: bool, kind: str) -> None:
    """Record how an access site was lowered.  A chunk of compacted lanes
    traces the sites of its loop a second time: that goes to a record of its
    own, and ``access`` keeps the kinds the dense path gives them."""
    (ctx.compact_access if ctx.compacting else ctx.access)[id(node), store] = kind
    if kind == "scatter" and not ctx.compacting:
        ctx.scattered.append(ctx.bufs[node.base].dtype.itemsize)


def _load(ctx: _Ctx, node: Index) -> KVal:
    if node.base in ctx.private:
        return _private_load(ctx, node)
    if node.base in ctx.local:
        return _local_load(ctx, node)
    if node.base not in ctx.bufs:
        raise KernelCompileError(f"{node.base!r} is not an array parameter", line=node.line)
    if node.base in ctx.widths:
        return vectors.load(ctx, node)
    buf = ctx.bufs[node.base]
    ctype = ctx.buf_ctypes[node.base]
    idx = _eval(ctx, node.index)
    if idx.ctype not in _INT_TYPES:
        raise KernelLanguageError("array index must be an integer", line=node.line)
    if ctx.pallas:
        kv = ctx.pallas_load(node, buf, ctype, idx)  # type: ignore[attr-defined]
        return _loaded(kv.value, ctype)
    if id(node) in ctx.hoisted:
        return _loaded(ctx.hoisted[id(node)], ctype)  # read once a chunk
    run = ctx.runs.get(node.base)
    if run is not None and isinstance(node.index, Var) and node.index.name == run[0]:
        _note(ctx, node, False, "gather")  # a row gather a refill
        return _loaded(run[1], ctype)  # this pass's row of the run window
    own = ctx.own.get(node.base)
    if own is not None and _same_expr(node.index, own[1]):
        return _loaded(ctx.env[own[0]].value, ctype)  # rides the loop
    if id(node) in ctx.windows:
        _note(ctx, node, False, "strided")
        return _loaded(ctx.windows[id(node)], ctype)  # this pass's column
    if idx.affine is not None and idx.affine[0] == 1:
        _note(ctx, node, False, "slice")
        c = idx.affine[1]
        if not isinstance(c, int):
            # a runtime offset, the same in every lane: still contiguous
            start = jnp.asarray(ctx.offset + c, jnp.int32)
            if _in_bounds(ctx, idx, buf.shape[0]):
                return _loaded(lax.dynamic_slice(buf, (start,), (ctx.B,)), ctype)
            return _loaded(_slice_clamped(buf, start, ctx.B), ctype)
        if c == 0:
            start = jnp.asarray(ctx.offset, jnp.int32)
            return _loaded(lax.dynamic_slice(buf, (start,), (ctx.B,)), ctype)
        padded, lo = ctx.padded_view(node.base, c)
        start = jnp.asarray(ctx.offset + c + lo, jnp.int32)
        return _loaded(lax.dynamic_slice(padded, (start,), (ctx.B,)), ctype)
    if idx.affine is not None and idx.affine[0] != 0:
        col = _strided_load(ctx, node.base, idx)
        if col is not None:
            _note(ctx, node, False, "strided")
            return _loaded(col, ctype)
    if ctx.uniform_vars and _expr_uniform(
        node.index, ctx.uniform_vars, ctx.lane_arrays()
    ):
        _note(ctx, node, False, "uniform")
        # lane-uniform index (the n-body ``x[j]`` pattern): ONE element
        # load broadcast to the chunk instead of a (B,)-wide gather per
        # loop iteration — the dominant cost of gather-loop kernels
        iv = _num(_as_dtype(idx, "int"))
        sidx = iv if (not hasattr(iv, "ndim") or iv.ndim == 0) else iv.reshape(-1)[0]
        sidx = jnp.clip(jnp.asarray(sidx, jnp.int32), 0, buf.shape[0] - 1)
        return _loaded(lax.dynamic_slice(buf, (sidx,), (1,))[0], ctype)
    if id(node) in ctx.group_sites:
        _note(ctx, node, False, "group")
        pitch = ctx.group_sites[id(node)]
        if id(node) in ctx.settled:
            return _loaded(_group_block(ctx, node.base, pitch,
                                        lambda: ctx.settled[id(node)]), ctype)
        return _loaded(_group_slice(ctx, node.base, idx, pitch), ctype)
    _note(ctx, node, False, "gather")
    iv = _num(_as_dtype(idx, "int"))
    if not hasattr(iv, "ndim") or iv.ndim == 0:
        iv = jnp.full((ctx.B,), iv, dtype=jnp.int32)
    if ctx.row_gathers and (buf.dtype.itemsize == 4
                            or buf.dtype in (jnp.int8, jnp.uint8)):
        return _loaded(_take_rows(ctx, node.base, iv), ctype)
    return _loaded(jnp.take(buf, iv, mode="clip"), ctype)


def _store(ctx: _Ctx, node: Index, val: KVal) -> None:
    if node.base in ctx.private:
        _private_store(ctx, node, val)
        return
    if node.base in ctx.local:
        _local_store(ctx, node, val)
        return
    if node.base not in ctx.bufs:
        raise KernelCompileError(f"{node.base!r} is not an array parameter", line=node.line)
    if node.base in ctx.widths:
        vectors.store(ctx, node, val)
        return
    buf = ctx.bufs[node.base]
    ctype = ctx.buf_ctypes[node.base]
    v = _num(_as_dtype(val, ctype))
    one_value = not hasattr(v, "ndim") or v.ndim == 0  # the same in every lane
    if one_value:
        v = ctx.broadcast_scalar(v, ctype_to_dtype(ctype))
    if hasattr(buf, "dtype") and v.dtype != buf.dtype:
        # a store converts to the buffer's STORAGE dtype (a caller may
        # pass e.g. f16 arrays to a float-declared kernel — compute runs
        # in the declared ctype, storage keeps the array's dtype); the
        # gather path's .at[].set already casts, the slice paths below
        # would crash on the mismatch instead
        v = v.astype(buf.dtype)
    idx = _eval(ctx, node.index)
    if ctx.pallas:
        ctx.pallas_store(node, buf, ctype, idx, v)  # type: ignore[attr-defined]
        return
    own = ctx.own.get(node.base)
    if own is not None and _same_expr(node.index, own[1]):
        # rides the loop as a local: merged as an assignment is (_assign)
        m, fr = ctx.active_mask(), ctx._freerun
        if m is not None and not (fr is not None and m is fr[0] and own[0] in fr[1]):
            v = jnp.where(m, v, ctx.env[own[0]].value)
        ctx.env[own[0]] = KVal(v, ctx.env[own[0]].ctype)
        return
    m = ctx.active_mask()
    if (idx.affine is not None and idx.affine[0] == 1
            and (m is not None or not isinstance(idx.affine[1], int))
            and _in_bounds(ctx, idx, buf.shape[0])):
        # every lane owns its element and none lies outside the buffer: a
        # masked store is a select into the window, at a runtime offset too
        _note(ctx, node, True, "slice")
        start = jnp.asarray(ctx.offset + idx.affine[1], jnp.int32)
        if m is not None:
            v = jnp.where(m, v, lax.dynamic_slice(buf, (start,), (ctx.B,)))
        ctx.bufs[node.base] = lax.dynamic_update_slice(buf, v, (start,))
        ctx.invalidate_padded(node.base)
    elif (idx.affine is not None and idx.affine[0] == 1
            and isinstance(idx.affine[1], int) and m is None):
        _note(ctx, node, True, "slice")
        c = idx.affine[1]
        if c == 0:
            start = jnp.asarray(ctx.offset, jnp.int32)
            ctx.bufs[node.base] = lax.dynamic_update_slice(buf, v, (start,))
        else:
            n = buf.shape[0]
            lo, hi = max(0, -c), max(0, c)
            padded = jnp.pad(buf, (lo, hi))
            start = jnp.asarray(ctx.offset + c + lo, jnp.int32)
            updated = lax.dynamic_update_slice(padded, v, (start,))
            ctx.bufs[node.base] = lax.slice(updated, (lo,), (lo + n,))
        ctx.invalidate_padded(node.base)
    elif one_value and _expr_uniform(node.index, ctx.uniform_vars,
                                     ctx.lane_arrays()):
        # every lane names the same element and stores the same value (a
        # flag the kernel raises for the host, ``over[0] = true``): ONE
        # element is written if any lane is active, where a scatter would
        # send a chunk of equal indices.  An index outside the buffer
        # stores nothing, as the scatter's ``drop`` does
        _note(ctx, node, True, "uniform")
        at = _lane0(_num(_as_dtype(idx, "int"))).astype(jnp.int32)
        n = buf.shape[0]
        hit = jnp.logical_and(at >= 0, at < n)
        if m is not None:
            hit = jnp.logical_and(hit, m if m.ndim == 0 else ctx.any_lane(m))
        at = jnp.clip(at, 0, n - 1)
        one = jnp.where(hit, _lane0(v), lax.dynamic_slice(buf, (at,), (1,)))
        ctx.bufs[node.base] = lax.dynamic_update_slice(buf, one, (at,))
        ctx.invalidate_padded(node.base)
    else:
        _note(ctx, node, True, "scatter")
        iv = _num(_as_dtype(idx, "int"))
        if not hasattr(iv, "ndim") or iv.ndim == 0:
            iv = jnp.full((ctx.B,), iv, dtype=jnp.int32)
        if m is not None:
            # redirect masked-off lanes out of bounds and drop them — a
            # read-modify-write would race with active lanes hitting the
            # same index (duplicate-index scatter order is unspecified)
            iv = jnp.where(m, iv, jnp.int32(buf.shape[0]))
        ctx.bufs[node.base] = buf.at[iv].set(v, mode="drop")
        ctx.invalidate_padded(node.base)
    ctx.stored.add(node.base)


# ---------------------------------------------------------------------------
# statements
# ---------------------------------------------------------------------------


def _exec_block(ctx: _Ctx, stmts: list) -> None:
    # remainder stack: lets a loop see every statement that can still run
    # after it returns (this block's tail + all enclosing blocks' tails) —
    # the liveness input for free-run predication elimination (_exec_loop)
    stack = ctx._after_stack
    for i, s in enumerate(stmts):
        stack.append(stmts[i + 1 :])
        try:
            _exec(ctx, s)
        finally:
            stack.pop()


def _exec(ctx: _Ctx, node) -> None:
    if isinstance(node, Barrier):
        # every statement before it has run for all lanes of the launch, and
        # a launch holds whole groups: nothing to do (_check_barriers has
        # proved that the group reaches it together)
        if ctx.info.get("inline_stack"):
            raise KernelLanguageError(
                "a barrier inside a helper function is not supported; "
                "call barrier() from the kernel", line=node.line)
        return
    if isinstance(node, LocalDecl):
        # at kernel scope (the parser's rule), in a launch of whole groups
        # (build_kernel_fn's): zero at its start (OpenCL leaves it undefined)
        ctx.local[node.name] = node.size
        ctx.env[node.name] = KVal(
            jnp.zeros((ctx.B // ctx.local_size, node.size),
                      ctype_to_dtype(node.ctype)), node.ctype)
        return
    if isinstance(node, Decl) and node.ctype in VECTOR_TYPES:
        vectors.declare(ctx, node)
        return
    if isinstance(node, Decl):
        for name, init in node.names:
            ctx.vectors.pop(name, None)  # whatever it was in another scope
            if name in node.arrays:
                if ctx.pallas:
                    from .pallas_backend import PallasUnsupported

                    raise PallasUnsupported(
                        f"private array {name!r} (Pallas tile path has no "
                        "per-item scratch stacking; XLA lowering handles it)"
                    )
                k = node.arrays[name]
                ctx.private[name] = k
                ctx.env[name] = KVal(
                    jnp.zeros((k,) + ctx.shape, ctype_to_dtype(node.ctype)),
                    node.ctype,
                )
                continue
            if init is not None:
                v = _as_dtype(_eval(ctx, init), node.ctype)
            else:
                zero = ctype_to_dtype(node.ctype).type(0)
                v = (KVal(zero, node.ctype, (0, 0), (0, 0))
                     if node.ctype in _INT_TYPES else KVal(zero, node.ctype))
            ctx.env[name] = v
        return
    if isinstance(node, Assign):
        if node.target is None:  # bare call statement
            _eval(ctx, node.value)
            return
        _assign(ctx, node.target, node.op, node.value)
        return
    if isinstance(node, CrementStmt):
        one = Num(value=1, ctype="int", line=node.line)
        _assign(ctx, node.target, "+=" if node.op == "++" else "-=", one)
        return
    if isinstance(node, If):
        _exec_if(ctx, node)
        return
    if isinstance(node, (For, While)):
        _exec_loop(ctx, node)
        return
    if isinstance(node, DoWhile):
        # body once unconditionally (under the active mask), then the loop.
        # The first pass counts as "inside a loop" for nested loops: the
        # body re-runs via the While, so an inner loop's free-run liveness
        # cannot be derived from the remainder stack alone.  break/continue
        # in the first pass bind to THIS do-while: continue skips the rest
        # of the pass, break also excludes the lane from the While (all
        # lanes, by a 0-d flag, where the loop is a counted one).
        saved = ctx.break_mask, ctx.continue_mask, ctx.counted
        ctx.break_mask = None
        ctx.continue_mask = None
        ctx.counted = _loop_counted(ctx, node)
        ctx.info["in_loop"] = ctx.info.get("in_loop", 0) + 1
        try:
            _exec_block(ctx, node.body)
        finally:
            ctx.info["in_loop"] -= 1
            first_broke = ctx.break_mask
            ctx.break_mask, ctx.continue_mask, ctx.counted = saved
        loop = While(cond=node.cond, body=node.body, line=node.line)
        if first_broke is not None:
            part = "umask" if first_broke.ndim == 0 else "mask"
            outer = getattr(ctx, part)
            nb = jnp.logical_not(first_broke)
            setattr(ctx, part, nb if outer is None else jnp.logical_and(outer, nb))
            try:
                _exec_loop(ctx, loop)
            finally:
                setattr(ctx, part, outer)
        else:
            _exec_loop(ctx, loop)
        return
    if isinstance(node, (Break, Continue)):
        if not ctx.info.get("in_loop", 0):
            raise KernelLanguageError(
                f"'{'break' if isinstance(node, Break) else 'continue'}' "
                "outside a loop", line=node.line,
            )
        if ctx.counted:
            # proved to be taken by every lane together: a 0-d flag
            m = ctx.masks()[1]
            if m is None:
                m = jnp.asarray(True)
        else:
            m = ctx.active_mask()
            if m is None:
                m = jnp.ones(ctx.shape, jnp.bool_)
            elif m.ndim == 0:
                m = jnp.broadcast_to(m, ctx.shape)
        if isinstance(node, Break):
            ctx.break_mask = (
                m if ctx.break_mask is None else jnp.logical_or(ctx.break_mask, m)
            )
        else:
            ctx.continue_mask = (
                m if ctx.continue_mask is None
                else jnp.logical_or(ctx.continue_mask, m)
            )
        return
    if isinstance(node, Return):
        m = ctx.active_mask()
        if m is None:
            m = jnp.ones(ctx.shape, jnp.bool_)
        ctx.return_mask = m if ctx.return_mask is None else jnp.logical_or(ctx.return_mask, m)
        return
    raise KernelCompileError(f"cannot execute node {type(node).__name__}", line=getattr(node, "line", 0))


def _assign(ctx: _Ctx, target, op: str, value_expr) -> None:
    rhs = _eval(ctx, value_expr)
    if op != "=":
        base_op = op[:-1]
        cur = _eval(ctx, target)
        rhs = _binop(ctx, BinOp(op=base_op, left=_Lit(cur), right=_Lit(rhs), line=getattr(target, "line", 0)))
    if isinstance(target, Var):
        name = target.name
        if name in ctx.private and name in ctx.vectors:
            vectors.assign_local(ctx, name, rhs, getattr(target, "line", 0))
            return
        if name in ctx.private or name in ctx.local:
            raise KernelLanguageError(
                f"cannot assign to array {name!r} as a whole; "
                "assign elements", line=getattr(target, "line", 0),
            )
        if name in ctx.env:
            old = ctx.env[name]
            new = _as_dtype(rhs, old.ctype)  # assignment keeps the declared C type
            fr = ctx._freerun
            if name in ctx.uniform_vars:
                # proved the same in every lane that can observe it, and
                # assigned only where it was declared (_uniform_vars): the
                # lanes that the lane mask holds back never read it, and it
                # stays a 0-d value merged under the uniform conditions
                m = ctx.masks()[1]
            else:
                m = ctx.active_mask()
            if (
                m is not None
                and fr is not None
                and m is fr[0]
                and name in fr[1]
            ):
                m = None  # free-run: dead lanes' values are never observed
            if m is not None:
                ov, nv = _num(old), _num(new)
                merged = jnp.where(m, nv, ov)
                new = KVal(merged, old.ctype, None)
            ctx.env[name] = new
        else:
            raise KernelCompileError(f"assignment to undeclared variable {name!r}",
                                     line=getattr(target, "line", 0))
        return
    if isinstance(target, Index):
        _store(ctx, target, rhs)
        return
    raise KernelCompileError("invalid assignment target", line=getattr(target, "line", 0))


class _Lit:
    """Wrap an already-evaluated KVal so it can re-enter _eval."""

    def __init__(self, v: KVal):
        self.v = v
        self.line = 0


_orig_eval = _eval


def _eval(ctx: _Ctx, node) -> KVal:  # noqa: F811 - deliberate wrapper
    if isinstance(node, _Lit):
        return node.v
    return _orig_eval(ctx, node)


def _exec_if(ctx: _Ctx, node: If) -> None:
    cond = _truthy(_eval(ctx, node.cond))
    is_const_true = isinstance(node.cond, Num) and node.cond.value == 1
    if is_const_true and not node.other:
        _exec_block(ctx, node.then)  # bare { } block
        return

    # a condition proved the same in every lane joins the uniform part of
    # the mask as a 0-d value, any other the lane mask
    part = "mask"
    if _expr_uniform(node.cond, ctx.uniform_vars, ctx.lane_arrays()):
        part, cvec = "umask", _lane0(cond)
    elif not hasattr(cond, "ndim") or cond.ndim == 0:
        cvec = jnp.broadcast_to(cond, ctx.shape)
    else:
        cvec = cond
    outer_mask = getattr(ctx, part)

    # early-return pattern: if (cond) return;
    then_mask = cvec if outer_mask is None else jnp.logical_and(outer_mask, cvec)
    else_mask = jnp.logical_not(cvec) if outer_mask is None else jnp.logical_and(outer_mask, jnp.logical_not(cvec))

    setattr(ctx, part, then_mask)
    # the else branch runs AFTER the then branch in trace order: for a loop
    # inside `then`, reads in `other` are still pending — they must count
    # as "read after the loop" for free-run liveness
    ctx._after_stack.append(node.other)
    try:
        _exec_block(ctx, node.then)
    finally:
        ctx._after_stack.pop()
    if node.other:
        setattr(ctx, part, else_mask)
        _exec_block(ctx, node.other)
    setattr(ctx, part, outer_mask)


def _lane0(v):
    """A value proved the same in every lane as a 0-d array (lane 0 of one
    that still rides as a vector)."""
    v = jnp.asarray(v)
    return v[(0,) * v.ndim] if v.ndim else v


def _index_reads(node, var: str, out: set[str]) -> set[str]:
    """Bases of every ``base[var]`` under ``node`` (index exactly the
    variable)."""
    if isinstance(node, Index):
        if isinstance(node.index, Var) and node.index.name == var:
            out.add(node.base)
        _index_reads(node.index, var, out)
    elif isinstance(node, (list, tuple)):
        for x in node:
            _index_reads(x, var, out)
    elif hasattr(node, "__dict__") and not isinstance(node, _Lit):
        for v in vars(node).values():
            if isinstance(v, (list, tuple)) or hasattr(v, "__dict__"):
                _index_reads(v, var, out)
    return out


def _run_reads(ctx: _Ctx, node, cond_expr, carried_bufs) -> tuple:
    """``(j, tables)`` when ``node`` is a ``for`` whose variable ``j`` goes
    up by exactly one a pass (``j++`` / ``j += 1`` as the step, no other
    assignment in the body), differs from lane to lane, and indexes
    buffers the loop does not store to as ``T[j]``: each lane then reads a
    run of consecutive elements of every such ``T``.  ``(None, [])``
    otherwise."""
    none = (None, [])
    if not isinstance(node, For) or node.step is None:
        return none
    step = node.step
    if isinstance(step, CrementStmt):
        up = step.op == "++"
    else:
        up = (isinstance(step, Assign) and step.op == "+="
              and isinstance(step.value, Num) and step.value.value == 1)
    if not up or not isinstance(step.target, Var):
        return none
    j = step.target.name
    if (j not in ctx.env or ctx.env[j].ctype not in _INT_TYPES
            or j in ctx.uniform_vars or j in _assigned_vars(node.body)):
        return none
    tables = _index_reads([node.body, cond_expr], j, set())
    return j, sorted(t for t in tables
                     if t in ctx.bufs and t not in ctx.private
                     and t not in carried_bufs and t not in ctx.widths)


def _walk(node):
    """Every syntax-tree node under ``node`` (statements, expressions,
    lists of either), ``node`` first."""
    if isinstance(node, (list, tuple)):
        for x in node:
            yield from _walk(x)
    elif hasattr(node, "__dict__") and not isinstance(node, _Lit):
        yield node
        for v in vars(node).values():
            if isinstance(v, (list, tuple)) or hasattr(v, "__dict__"):
                yield from _walk(v)


def _index_nodes(node) -> list:
    """Every ``Index`` node under ``node`` (a store's target too)."""
    return [x for x in _walk(node) if isinstance(x, Index)]


def _same_expr(a, b) -> bool:
    """Are two expressions the same tree (whatever lines they stand on)?"""
    if a is b:
        return True
    if type(a) is not type(b):
        return False
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_same_expr, a, b))
    if not hasattr(a, "__dict__"):
        return a == b
    return all(_same_expr(v, vars(b)[k]) for k, v in vars(a).items()
               if k != "line")


def _affine_expr(node) -> bool:
    """Is ``node`` made of what the affine tracker follows and nothing that
    reads memory: literals, variables, ``+ - *``, casts, the ids?"""
    if isinstance(node, (Num, Var)):
        return True
    if isinstance(node, BinOp):
        return (node.op in ("+", "-", "*") and _affine_expr(node.left)
                and _affine_expr(node.right))
    if isinstance(node, UnOp):
        return node.op in ("+", "-") and _affine_expr(node.operand)
    if isinstance(node, Cast):
        return node.ctype in _INT_TYPES and _affine_expr(node.operand)
    if isinstance(node, Call):
        return (node.name == "get_global_id" or node.name in _UNIFORM_CALLS
                ) and all(isinstance(a, Num) for a in node.args)
    return False


def _terms(node, sign: int, out: list) -> list:
    """``node`` as a sum: its ``(sign, term)`` leaves over ``+`` and ``-``."""
    if isinstance(node, BinOp) and node.op in ("+", "-"):
        _terms(node.left, sign, out)
        _terms(node.right, sign if node.op == "+" else -sign, out)
    elif isinstance(node, UnOp) and node.op in ("+", "-"):
        _terms(node.operand, sign if node.op == "+" else -sign, out)
    else:
        out.append((sign, node))
    return out


class _Site(NamedTuple):
    """One read ``T[s * gid + rest + j]`` of a strided-window loop."""

    node: Index
    stride: int
    rest: Any      # what the index is with the loop's variable at 0
    aligned: bool  # is every window known to start at a multiple of 128
    at: tuple = ()  # _strided_rows: the view, the chunk's first row, moved


def _window_sites(ctx: _Ctx, node, counted: _Trips) -> tuple:
    """``(sites, width)``: the reads of a counted loop that walk a strided
    window, and the passes one window serves.  A site: the loop's
    variable ``j`` goes up by one a pass, and the index is ``j`` plus terms
    that nothing in the loop changes, affine in the work-item id with a
    build-time stride ``s >= 2``, with ``rest + j`` proved inside ``[0, s)``
    on every pass; the buffer is one the loop does not store to and a whole
    number of rows of ``s``."""
    if ctx.pallas or counted.step != 1 or counted.span is None:
        return [], 0
    j, body = counted.var, node.body + [node.step]
    changed = _assigned_vars(body) | _stored_bufs(body)
    sites = []
    for ix in _index_nodes(node.body):
        if (ix.base not in ctx.bufs or ix.base in ctx.private
                or ix.base in changed or not _affine_expr(ix.index)
                or ix.base in ctx.widths):
            continue
        terms = _terms(ix.index, 1, [])
        bare = [sg for sg, t in terms if isinstance(t, Var) and t.name == j]
        if bare != [1] or any(
                _vars_read(t) & changed for _, t in terms
                if not (isinstance(t, Var) and t.name == j)):
            continue
        saved = ctx.env[j]
        ctx.env[j] = _int_const(0)
        try:
            rest = _eval(ctx, ix.index)
        finally:
            ctx.env[j] = saved
        if (rest.affine is None or rest.span is None
                or not isinstance(rest.affine[0], int) or rest.affine[0] < 2
                or rest.span[0] + counted.span[0] < 0
                or rest.span[1] + counted.span[1] >= rest.affine[0]):
            continue
        start = ctx.env[j].span  # where the loop's variable sets out
        aligned = (type(rest.affine[1]) is int and start[0] == start[1]
                   and (rest.affine[1] + start[0]) % _ROW == 0)
        sites.append(_Site(ix, rest.affine[0], rest.affine[1], aligned))
    if not sites:
        return [], 0
    width = min([_STRIDE_WINDOW, max(_UNROLL, _WINDOW_ELEMS // ctx.B)]
                + [t.stride for t in sites])
    placed = []
    for t in sites:
        # the kept 2-D view; without one, whole blocks of the blocked view
        # where the rows hold them
        blocked = (width == _ROW and t.stride % _ROW == 0
                   and t.stride >= _ROW * (2 - t.aligned))
        at = _strided_rows(ctx, t.node.base, t.stride, blocked, window=True)
        if at is not None:
            placed.append(t._replace(at=at))
    return placed, width


def _own_element_bufs(ctx: _Ctx, body: list, cond_expr, stored: list) -> dict:
    """``{buffer: (index expression, where its window starts)}`` for the buffers a loop
    stores to that it touches ONLY at the lane's own element: every access
    in the body and the condition has the same index, made of values the
    loop does not change, with stride 1 and proved in bounds.  No other
    lane's pass can observe such an element, so the loop may hold it in a
    local: loaded before, stored after (:func:`_exec_loop`)."""
    out: dict = {}
    if ctx.pallas:
        return out
    changed = _assigned_vars(body)
    sites = _index_nodes([body, cond_expr])
    for k in stored:
        mine = [ix.index for ix in sites if ix.base == k]
        buf = ctx.bufs[k]
        if (k in ctx.private or k in ctx.own or k in ctx.widths or not mine
                or not all(_same_expr(e, mine[0]) for e in mine)
                or not _affine_expr(mine[0]) or _vars_read(mine[0]) & changed
                or buf.dtype != ctype_to_dtype(ctx.buf_ctypes[k])):
            continue
        idx = _eval(ctx, mine[0])
        if (idx.affine is not None and idx.affine[0] == 1
                and _in_bounds(ctx, idx, buf.shape[0])):
            out[k] = (mine[0], jnp.asarray(ctx.offset + idx.affine[1], jnp.int32))
    return out


def _exec_pass(ctx: _Ctx, node, body_core: list, step_stmt) -> None:
    """One pass of a loop's body and step under the masks in place."""
    _exec_block(ctx, body_core)
    # C semantics: `continue` jumps to the for-step (which still runs for
    # continued lanes); `break` skips it too
    ctx.continue_mask = None
    if step_stmt is not None:
        _exec(ctx, step_stmt)
    if ctx.return_mask is not None:
        raise KernelLanguageError(
            "'return' inside a loop is not supported; use the loop condition",
            line=getattr(node, "line", 0),
        )


# passes of a counted loop's body in one pass of its main loop (chosen on
# the chip at the n-body cells' two sizes: PERF.md, PR 29)
_UNROLL = 8


def _loop_counted(ctx: _Ctx, node) -> bool:
    """Does this loop lower to a counted loop on scalars
    (:func:`_exec_counted`)?  The same test that :func:`_uniform_vars`
    made of it, on the set it ended with."""
    return not ctx.returns and not _loop_diverges(
        node, ctx.uniform_vars, ctx.lane_arrays())


# a comparison with its sides exchanged
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}


class _Trips(NamedTuple):
    """What :func:`_trip_count` reads off a loop's syntax."""

    passes: Any                 # 0-d int32
    var: str                    # the loop's variable
    step: int                   # what a pass adds to it
    span: Optional[tuple]       # its bounds inside a pass, where known


def _trip_count(ctx: _Ctx, node) -> Optional[_Trips]:
    """The passes a counted loop will make, as a 0-d int32, where its
    syntax gives them: ``for (...; j < B; j += c)`` (also ``<=``, and
    ``>`` / ``>=`` with ``-=``; ``B`` on either side) with ``j`` an ``int``
    that only the step assigns, ``c`` a literal, ``B`` an ``int`` nothing
    in the loop changes, and no ``break``.  None otherwise."""
    if not isinstance(node, For) or node.step is None or node.cond is None:
        return None
    step, cond = node.step, node.cond
    if isinstance(step, CrementStmt):
        c = 1 if step.op == "++" else -1
    elif (isinstance(step, Assign) and step.op in ("+=", "-=")
          and isinstance(step.value, Num) and step.value.ctype == "int"
          and step.value.value > 0):
        c = step.value.value if step.op == "+=" else -step.value.value
    else:
        return None
    if not isinstance(step.target, Var) or not isinstance(cond, BinOp):
        return None
    j, body = step.target.name, node.body + [step]
    if isinstance(cond.left, Var) and cond.left.name == j:
        op, bound = cond.op, cond.right
    elif isinstance(cond.right, Var) and cond.right.name == j:
        op, bound = _FLIP.get(cond.op), cond.left
    else:
        return None
    if op not in (("<", "<=") if c > 0 else (">", ">=")):
        return None
    if (j not in ctx.uniform_vars or j in _assigned_vars(node.body)
            or _has_break(node.body)
            or _vars_read(bound) & (_assigned_vars(body) | _stored_bufs(body))):
        return None
    jv, bv = ctx.env[j], _eval(ctx, bound)
    if jv.ctype != "int" or bv.ctype not in _INT_TYPES or _promote("int", bv.ctype) != "int":
        return None
    span = None
    if jv.span is not None and bv.span is not None:
        # inside a pass the variable lies between where it started and the
        # last value the condition lets through
        (jlo, jhi), (blo, bhi), strict = jv.span, bv.span, op in ("<", ">")
        span = ((jlo, max(jhi, bhi - strict)) if c > 0
                else (min(jlo, blo + strict), jhi))
    jv, bv = (jnp.asarray(_num(_as_dtype(v, "int"))) for v in (jv, bv))
    d = (bv - jv) if c > 0 else (jv - bv)  # the distance left to go
    by = jnp.int32(abs(c))
    if op in ("<", ">"):
        passes = jnp.where(d > 0, lax.div(d - 1, by) + 1, 0)
    else:
        passes = jnp.where(d >= 0, lax.div(d, by) + 1, 0)
    return _Trips(passes, j, c, span)


def _exec_counted(ctx: _Ctx, node, cond_expr, body_core: list, step_stmt,
                  carried_vars: list, carried_bufs: list) -> None:
    """A loop that every lane leaves on the same pass (:func:`_loop_diverges`
    says when) as a counted loop on scalars: no active mask in the carry, no
    reduction of one a pass.  The carried locals of ``ctx.uniform_vars`` ride
    as 0-d values, so the condition is one and a load at such an index reads
    its element with it.  The body runs under the mask the loop was ENTERED
    with: the other carried locals are assigned without a ``where`` a pass,
    and the lanes outside that mask get their values back by one after the
    loop (stores keep their mask).  With no lane inside it, or under a false
    uniform condition, the loop makes no pass at all.

    Where the syntax gives the passes (:func:`_trip_count`) they run
    ``_UNROLL`` to a pass of the main loop, the rest one by one: the order of
    every lane's operations is the masked form's, the results are its
    results to the last bit.  Otherwise the condition is evaluated, on
    scalars, after every pass.  Run windows (:func:`_run_reads`) are for
    runs that differ from lane to lane and never meet this path."""
    lane0, enter = ctx.masks()
    if lane0 is not None:
        some = ctx.any_lane(lane0)
        enter = some if enter is None else jnp.logical_and(enter, some)
    lane_vars = [k for k in carried_vars if k not in ctx.uniform_vars]
    var_ctypes = {k: ctx.env[k].ctype for k in carried_vars}
    ctx.counted_loop(node, lane_vars, carried_bufs)

    def carried(k):
        """A carried local in the form the carry holds it: 0-d if proved
        uniform, else of the work-item shape (broadcast_scalar: see the
        masked form)."""
        val = _num(ctx.env[k])
        if k not in lane_vars:
            return _lane0(val)
        if not hasattr(val, "ndim") or val.ndim == 0:
            return ctx.broadcast_scalar(val, ctype_to_dtype(var_ctypes[k]))
        return val

    def in_loop(k, val, span=None) -> KVal:
        """A carried local in the carry's form: an integer proved the same
        in every lane is affine with stride 0, within ``span``."""
        if k in lane_vars or var_ctypes[k] not in _INT_TYPES:
            return KVal(val, var_ctypes[k])
        return KVal(val, var_ctypes[k], (0, val), span)

    init_env = {k: carried(k) for k in carried_vars}
    for k in carried_vars:  # as the loop is entered: with the bounds so far
        ctx.env[k] = in_loop(k, init_env[k], ctx.env[k].span)
    init_bufs = {k: ctx.bufs[k] for k in carried_bufs}
    counted = _trip_count(ctx, node)
    trips = counted and counted.passes
    sites, width = _window_sites(ctx, node, counted) if counted else ([], 0)

    def in_loop_state(env_vals, buf_vals, fn, rows=None):
        """``fn()`` with the carried state in place of the context's, and
        this pass's columns of the loop's strided windows."""
        saved = (ctx.env, ctx.bufs, ctx.mask, ctx.umask, ctx.return_mask,
                 ctx.break_mask, ctx.continue_mask, ctx.counted, ctx._freerun,
                 ctx._rows_cache, ctx.windows)
        ctx.env, ctx.bufs = dict(ctx.env), dict(ctx.bufs)
        ctx._rows_cache = dict(ctx._rows_cache)
        if rows:
            ctx.windows = {**ctx.windows, **rows}
        saved_stored = set(ctx.stored)
        ctx.info["in_loop"] = ctx.info.get("in_loop", 0) + 1
        try:
            for k in carried_vars:  # the loop's variable within its bounds
                ctx.env[k] = in_loop(k, env_vals[k], counted.span if (
                    counted and k == counted.var) else None)
            ctx.bufs.update(buf_vals)
            ctx._pad_cache.clear()  # buffers swapped to loop tracers
            # the entered mask's two parts hold for the whole loop: the
            # lane part is the body's mask, the uniform part (and whether
            # any lane is inside) is decided once, before the first pass
            ctx.mask, ctx.umask, ctx.return_mask = lane0, None, None
            ctx.break_mask = ctx.continue_mask = None  # bind to THIS loop
            ctx.counted = True
            ctx._freerun = (lane0, set(lane_vars)) if lane0 is not None else None
            return fn()
        finally:
            ctx.info["in_loop"] -= 1
            ctx.stored = saved_stored | ctx.stored
            (ctx.env, ctx.bufs, ctx.mask, ctx.umask, ctx.return_mask,
             ctx.break_mask, ctx.continue_mask, ctx.counted, ctx._freerun,
             ctx._rows_cache, ctx.windows) = saved

    def cond_of(env_vals, buf_vals):
        return in_loop_state(
            env_vals, buf_vals, lambda: _lane0(_truthy(_eval(ctx, cond_expr))))

    def one_pass(env_vals, buf_vals, rows=None):
        """``(env, bufs, broke)``: one pass, and its 0-d break flag (None
        where nothing broke)."""
        def run():
            env_keys_before = set(ctx.env.keys())
            _exec_pass(ctx, node, body_core, step_stmt)
            out = ({k: carried(k) for k in carried_vars},
                   {k: ctx.bufs[k] for k in carried_bufs}, ctx.break_mask)
            for k in set(ctx.env.keys()) - env_keys_before:
                ctx.private.pop(k, None)  # loop-local declarations scope out
            return out
        return in_loop_state(env_vals, buf_vals, run, rows)

    state = (init_env, init_bufs)
    if trips is not None and enter is not None:
        trips = jnp.where(enter, trips, 0)
    if sites:
        # STRIDED WINDOWS: the loop's variable goes up by one a pass, so the
        # elements ``T[s * gid + u + j]`` that the lanes read in ``width``
        # passes are ``width`` neighbouring columns of their rows of ``T``
        # seen as rows of ``s``: ONE 2-D slice, transposed so that a pass
        # reads a row of it (_strided_window).
        def fetch(st):
            jv = st[0][counted.var]
            return {id(t.node): _strided_window(
                ctx, t, jnp.asarray(t.rest + jv, jnp.int32), width)
                for t in sites}

        def window_pass(st, wins, r):
            rows = {k: lax.dynamic_index_in_dim(w, r + d, 0, keepdims=False)
                    for k, (w, d) in wins.items()}
            return one_pass(*st, rows)[:2]

        def groups(st, wins, n_groups, n_singles):
            """``n_groups`` times ``_UNROLL`` passes, then ``n_singles``."""
            def group(g, s2):
                for t in range(_UNROLL):
                    s2 = window_pass(s2, wins, g * _UNROLL + t)
                return s2

            st = lax.fori_loop(0, n_groups, group, st)
            return lax.fori_loop(
                0, n_singles,
                lambda i, s2: window_pass(s2, wins, n_groups * _UNROLL + i), st)

        state = lax.fori_loop(
            0, lax.div(trips, jnp.int32(width)),
            lambda _, st: groups(st, fetch(st), width // _UNROLL,
                                 width % _UNROLL), state)
        rest = lax.rem(trips, jnp.int32(width))
        state = groups(state, fetch(state), lax.div(rest, jnp.int32(_UNROLL)),
                       lax.rem(rest, jnp.int32(_UNROLL)))
    elif trips is not None:
        def group(_, st):
            for _ in range(_UNROLL):
                st = one_pass(*st)[:2]
            return st

        state = lax.fori_loop(0, lax.div(trips, jnp.int32(_UNROLL)), group, state)
        state = lax.fori_loop(0, lax.rem(trips, jnp.int32(_UNROLL)),
                              lambda _, st: one_pass(*st)[:2], state)
    else:
        # the flag rides as an int32: Mosaic carries no bool
        def flag(env_vals, buf_vals, broke=None):
            go = cond_of(env_vals, buf_vals)
            if broke is not None:
                go = jnp.logical_and(go, jnp.logical_not(broke))
            return go

        def step(st):
            env_vals, buf_vals, broke = one_pass(*st[1:])
            return (flag(env_vals, buf_vals, broke).astype(jnp.int32),
                    env_vals, buf_vals)

        go0 = flag(*state)
        if enter is not None:
            go0 = jnp.logical_and(go0, enter)
        state = lax.while_loop(lambda st: st[0] != 0, step,
                               (go0.astype(jnp.int32), *state))[1:]
    env_f, bufs_f = state
    ctx._pad_cache.clear()
    for k in carried_vars:
        val = env_f[k]
        if lane0 is not None and k in lane_vars and k not in ctx.local:
            # (a __local array's stores kept their own mask: _local_store)
            val = jnp.where(lane0, val, init_env[k])
        ctx.env[k] = KVal(val, var_ctypes[k], None)
    for k in carried_bufs:
        ctx.bufs[k] = bufs_f[k]
        ctx.stored.add(k)


def _exec_loop(ctx: _Ctx, node) -> None:
    """Lower for/while to a counted loop on scalars where every lane leaves
    it together (:func:`_exec_counted`), and otherwise to a vectorized
    lax.while_loop with a per-item active mask (see module docstring)."""
    if isinstance(node, For):
        if node.init is not None:
            _exec(ctx, node.init)
        cond_expr = node.cond if node.cond is not None else Num(value=1, ctype="int", line=node.line)
        body = list(node.body) + ([node.step] if node.step is not None else [])
        body_core, step_stmt = list(node.body), node.step
    else:
        cond_expr = node.cond
        body = list(node.body)
        body_core, step_stmt = body, None

    carried_vars = sorted(_assigned_vars(body) & set(ctx.env.keys()))
    carried_bufs = sorted(_stored_bufs(body) & set(ctx.bufs.keys()))
    # a buffer the loop touches at the lane's own element only rides it as a
    # local, loaded here and stored behind the loop; one that an enclosing
    # loop already holds so stays that loop's local
    own = _own_element_bufs(ctx, body, cond_expr, carried_bufs)
    for k, (expr, start) in own.items():
        ctx.env[k + "[]"] = KVal(
            lax.dynamic_slice(ctx.bufs[k], (start,), (ctx.B,)), ctx.buf_ctypes[k])
        ctx.own[k] = (k + "[]", expr)
        ctx.carried.add((id(node), k))
    held = [k for k in carried_bufs if k in ctx.own]
    carried_vars = sorted(carried_vars + [ctx.own[k][0] for k in held])
    carried_bufs = [k for k in carried_bufs if k not in held]
    if _loop_counted(ctx, node):
        _exec_counted(ctx, node, cond_expr, body_core, step_stmt,
                      carried_vars, carried_bufs)
    elif _compactable(ctx, body, cond_expr):
        _exec_compacted(ctx, node, cond_expr, body_core, step_stmt,
                        carried_vars, carried_bufs)
    else:
        _exec_masked(ctx, node, cond_expr, body_core, step_stmt,
                     carried_vars, carried_bufs)
    for k, (_expr, start) in own.items():
        local = ctx.env.pop(ctx.own.pop(k)[0])
        ctx.bufs[k] = lax.dynamic_update_slice(ctx.bufs[k], local.value, (start,))
        ctx.invalidate_padded(k)
        ctx.stored.add(k)


def _live_after(ctx: _Ctx, carried_vars: list) -> set[str]:
    """The carried locals of a loop inside no other that something may read
    AFTER it: those the statements still to run name (the remainder stack),
    and the buffers riding the loop as locals, which are stored behind it."""
    read_later: set[str] = set()
    for rest in ctx._after_stack:
        _vars_read(rest, read_later)
    read_later |= {local for local, _ in ctx.own.values()}
    return read_later & set(carried_vars)


def _carried_at_shape(ctx: _Ctx, carried_vars: list) -> None:
    """Broadcast a loop's carried locals to the work-item shape so that
    loop-carry shapes are stable (broadcast_scalar: the Pallas subclass
    forces a computed Mosaic layout — a jnp.full constant gets a replicated
    layout the body's computed carries cannot be relaid out to)."""
    for name in carried_vars:
        v = ctx.env[name]
        val = _num(v)
        if not hasattr(val, "ndim") or val.ndim == 0:
            val = ctx.broadcast_scalar(val, ctype_to_dtype(v.ctype))
        ctx.env[name] = KVal(val, v.ctype, None)


def _loop_views(ctx: _Ctx, node, cond_expr, body_core: list,
                carried_bufs: list) -> tuple:
    """``(j, tables)`` of a masked loop's run windows (:func:`_run_reads`),
    with the views the loop's passes gather from made HERE, where the buffers
    are defined, and not anew at every refill or pass."""
    if ctx.pallas:
        return None, []
    run_var, run_tables = _run_reads(ctx, node, cond_expr, carried_bufs)
    for t in run_tables:
        ctx.rows_view(t, overlapping=True)
    # and so is the word view of a byte table the loop only reads
    # (``visited[id]``): a pass gathers from it, none packs it
    if ctx.row_gathers:
        for ix in _index_nodes([body_core, cond_expr]):
            if (ix.base in ctx.bufs and ix.base not in ctx.private
                    and ix.base not in carried_bufs
                    and ctx.bufs[ix.base].dtype in (jnp.int8, jnp.uint8)):
                ctx.rows_view(ix.base)
    return run_var, run_tables


def _common_passes(ctx: _Ctx, peel: "_Peel", each: bool = False):
    """The passes EVERY lane of a masked loop makes (:func:`_common_walks`
    names the loop), as a 0-d unsigned integer, from the walker's values where
    the loop is entered: as many as the lane that starts LAST, every other
    lane at least as many, and no lane's walker passes the bound inside them,
    so none wraps there.  ONE reduction over the lanes a launch.  The
    difference is taken modulo the width and read without a sign, which is
    exact for any two values the first of which is the larger.  None where
    the condition does not compare integers of the walker's width.  With
    ``each``, the passes of EACH lane, its own walker against its own bound
    (the key of :func:`_chunk_lanes`: a lane that leaves by a ``break`` or
    wraps makes other passes than these, and no result rests on them)."""
    w, e = ctx.env[peel.walker], _eval(ctx, peel.bound)
    if w.ctype not in _WIDE_INTS or e.ctype not in _INT_TYPES:
        return None
    t = _promote(w.ctype, e.ctype)
    dt = ctype_to_dtype(t)
    if dt.itemsize != ctype_to_dtype(w.ctype).itemsize:
        return None
    top, end = jnp.asarray(_num(_as_dtype(w, t))), _num(_as_dtype(e, t))
    if not each:
        top, end = jnp.max(top), _lane0(end)
    u = jnp.dtype(f"uint{8 * dt.itemsize}").type
    gap = lax.bitcast_convert_type(end - top, u)
    enters = top < end if peel.strict else top <= end
    return jnp.where(
        enters, lax.div(gap - u(peel.strict), u(peel.step)) + u(1), u(0))


def _exec_masked(ctx: _Ctx, node, cond_expr, body_core: list, step_stmt,
                 carried_vars: list, carried_bufs: list) -> None:
    """A loop that lanes may leave on different passes: a vectorized
    lax.while_loop with a per-item active mask (see module docstring)."""
    run_var, run_tables = _loop_views(ctx, node, cond_expr, body_core,
                                      carried_bufs)
    outer_mask = ctx.active_mask()

    # Free-run predication elimination: a carried variable that is never
    # read AFTER the loop needs no per-lane where-freeze — once a lane's
    # active bit clears it can never re-set (each pass computes
    # ``active = prev AND cond``, monotone in ``prev``), so a dead lane's
    # free-running value only feeds the cond (ANDed away) and masked
    # stores.  This is the optimization the hand-written mandelbrot
    # kernel applies manually (ops/mandelbrot.py: escaped orbits free-run
    # to inf) and removes the dominant per-iteration where chain.  Only at
    # top level (in_loop == 0): inside an enclosing loop the body re-runs,
    # so "after" cannot be derived from the remainder stack alone.
    freerun: set[str] = set()
    if not ctx.info.get("in_loop", 0):
        freerun = set(carried_vars) - _live_after(ctx, carried_vars)
    _carried_at_shape(ctx, carried_vars)

    var_ctypes = {k: ctx.env[k].ctype for k in carried_vars}

    def eval_cond(env, bufs):
        saved_env, saved_bufs, saved_mask = ctx.env, ctx.bufs, ctx.mask
        ctx.env = dict(saved_env)
        ctx.env.update({k: KVal(v, var_ctypes[k], None) for k, v in env.items()})
        ctx.bufs = dict(saved_bufs)
        ctx.bufs.update(bufs)
        c = _truthy(_eval(ctx, cond_expr))
        ctx.env, ctx.bufs, ctx.mask = saved_env, saved_bufs, saved_mask
        if not hasattr(c, "ndim") or c.ndim == 0:
            c = jnp.broadcast_to(c, ctx.shape)
        return c

    init_env = {k: ctx.env[k].value for k in carried_vars}
    init_bufs = {k: ctx.bufs[k] for k in carried_bufs}

    # Pallas/Mosaic: no bool array in a while-loop carry (relayout
    # limitation — the same constraint the hand-written mandelbrot kernel
    # works around, ops/mandelbrot.py); carry the mask as f32 0/1 and
    # re-derive the bool inside the body
    mask_in_carry_f32 = ctx.pallas

    def to_carry_mask(m):
        return ctx.force_computed(m.astype(jnp.float32)) if mask_in_carry_f32 else m

    def from_carry_mask(m):
        return (m > 0.0) if mask_in_carry_f32 else m

    # ROTATED loop: the carry holds the mask of lanes that executed the
    # PREVIOUS pass; each body pass evaluates the condition FIRST (on the
    # carried state), ANDs it in, and executes under that mask.  Putting
    # cond and body in the same trace lets XLA CSE their shared
    # subexpressions (the end-of-body placement recomputed e.g. zx*zx both
    # in the cond and in the next pass's body — ~15% of mandelbrot's
    # per-iteration work).  Price: one trailing fully-masked pass before
    # cond_fun sees an all-false mask (and one masked pass for loops never
    # entered) — masked execution has no observable effects.
    prev0 = (jnp.ones(ctx.shape, jnp.bool_) if outer_mask is None
             else jnp.broadcast_to(outer_mask, ctx.shape))

    def cond_fun(carry):
        prev, _, _ = carry
        if mask_in_carry_f32:
            return jnp.sum(prev) > 0.0
        return jnp.any(prev)

    def body_fun(carry, rows=None, slices=None):
        prev, env_vals, buf_vals = carry
        prev = from_carry_mask(prev)
        saved_env, saved_bufs, saved_mask = dict(ctx.env), dict(ctx.bufs), ctx.mask
        saved_umask, saved_counted = ctx.umask, ctx.counted
        saved_runs, saved_views = ctx.runs, dict(ctx._rows_cache)
        saved_settled = ctx.settled
        if rows:  # this pass's rows of the loop's run windows
            ctx.runs = {**ctx.runs, **rows}
        if slices:  # this pass's slice of each settled group read
            ctx.settled = {**ctx.settled, **slices}
        saved_stored = set(ctx.stored)
        saved_rm = ctx.return_mask
        saved_fr = ctx._freerun
        saved_bk, saved_cn = ctx.break_mask, ctx.continue_mask
        ctx.info["in_loop"] = ctx.info.get("in_loop", 0) + 1
        try:
            for k in carried_vars:
                ctx.env[k] = KVal(env_vals[k], var_ctypes[k], None)
            for k in carried_bufs:
                ctx.bufs[k] = buf_vals[k]
            ctx._pad_cache.clear()  # buffers swapped to loop tracers
            # (a common pass carries no mask: every lane is sure to make it)
            active = None if prev is None else jnp.logical_and(
                prev, eval_cond(env_vals, buf_vals))
            ctx.mask, ctx.umask = active, None  # ``active`` holds both parts
            ctx.counted = False
            ctx.return_mask = None
            ctx.break_mask = None      # break binds to THIS loop
            ctx.continue_mask = None
            # assignments whose mask is EXACTLY this loop's active mask may
            # skip the where-merge for free-run variables (see above)
            ctx._freerun = (active, freerun) if freerun else None
            env_keys_before = set(ctx.env.keys())
            _exec_pass(ctx, node, body_core, step_stmt)
            new_env = {k: _num(ctx.env[k]) for k in carried_vars}
            new_bufs = {k: ctx.bufs[k] for k in carried_bufs}
            # drop loop-local declarations so carry structure stays stable
            # (private-array registrations scope out with their env entry,
            # else a loop-local array would shadow a same-named buffer
            # param after the loop)
            for k in set(ctx.env.keys()) - env_keys_before:
                del ctx.env[k]
                ctx.private.pop(k, None)
            # lanes that broke leave the loop for good
            out_active = (
                active
                if ctx.break_mask is None
                else jnp.logical_and(active, jnp.logical_not(ctx.break_mask))
            )
            return (to_carry_mask(out_active), new_env, new_bufs)
        finally:
            ctx.info["in_loop"] -= 1
            ctx.env, ctx.bufs, ctx.mask = saved_env, saved_bufs, saved_mask
            ctx.umask, ctx.counted = saved_umask, saved_counted
            ctx.stored = saved_stored | ctx.stored
            ctx.return_mask = saved_rm
            ctx._freerun = saved_fr
            ctx.break_mask, ctx.continue_mask = saved_bk, saved_cn
            # row views made inside the body belong to its trace
            ctx.runs, ctx._rows_cache = saved_runs, saved_views
            ctx.settled = saved_settled

    carry0 = (to_carry_mask(prev0), init_env, init_bufs)
    walks = ctx.group_walks.get(id(node)) if run_var is None else None
    ok, sites = _settle(ctx, walks, jnp.logical_and(
        prev0, eval_cond(init_env, init_bufs))) if walks else (True, [])
    blocks = tuple(s.block for s in sites)

    def inside(go, blocks):
        """``go``, with every settled slice inside its buffer."""
        go = jnp.logical_and(ok, go)
        for s, block in zip(sites, blocks):
            go = go & (block >= 0) & (block <= s.last)
        return go

    def settled_pass(carry, blocks):
        slices = {s.site: (block, s.row) for s, block in zip(sites, blocks)}
        return (body_fun(carry, None, slices),
                tuple(block + jnp.int32(s.step) for s, block in zip(sites, blocks)))

    peel = ctx.peels.get(id(node)) if run_var is None and outer_mask is None else None
    common = _common_passes(ctx, peel) if peel and peel.common else None
    if common is not None:
        # THE COMMON PASSES: a loop entered by every lane, whose condition
        # compares a walker the build has followed with a bound they share,
        # makes ``common`` passes in EVERY lane, one scalar known here.  Those
        # run on a counter with no mask: no condition in the lanes, no ``any``,
        # no merge of an assignment or a store (with the group windows settled
        # they keep that loop's conditions, which are scalars too).  What is
        # left runs the loops below from the carry they leave: a pass that
        # some lanes make, the rotated loop's last.  The walker does not ride
        # them: it is where it started plus the passes made times the step
        ctx.peeled.add(id(node))
        noted = len(ctx.scattered)  # (the masked trace notes its stores again)
        w0 = init_env[peel.walker]

        def walked(env, k):
            """``env`` with the walker where ``k`` passes leave it."""
            by = lax.bitcast_convert_type(k * common.dtype.type(peel.step), w0.dtype)
            return {**env, peel.walker: w0 + by}

        def common_pass(c):
            k, env, bufs, blocks = c
            (_, env, bufs), blocks = settled_pass(
                (None, walked(env, k), bufs), blocks)
            del env[peel.walker]
            return k + 1, env, bufs, blocks

        k, env_c, bufs_c, blocks = lax.while_loop(
            lambda c: inside(c[0] < common, c[3]), common_pass,
            (jnp.zeros((), common.dtype),
             {v: init_env[v] for v in carried_vars if v != peel.walker},
             init_bufs, blocks))
        del ctx.scattered[noted:]
        carry0 = (carry0[0], walked(env_c, k), bufs_c)
    if sites:
        # GROUP WINDOWS SETTLED ONCE: the passes whose slices lie inside the
        # buffers run with no check in them; what is left (a tail over the
        # end, a first pass that does not fit) runs the loop below
        ctx.settled_sites.update(s.site for s in sites)
        carry0 = lax.while_loop(lambda c: inside(cond_fun(c[0]), c[1]),
                                lambda c: settled_pass(*c), (carry0, blocks))[0]
    if run_var is None:
        active_f, env_f, bufs_f = lax.while_loop(cond_fun, body_fun, carry0)
    else:
        # RUN WINDOWS: the loop variable goes up by one a pass, so the
        # reads ``T[j]`` of a lane are a run ``T[j0], T[j0 + 1], ...``.
        # The runs of all lanes are fetched once for _RUN_WINDOW passes
        # (_run_window: one row gather a lane) and a pass reads row r of
        # the window where it gathered a chunk-wide element each.
        def refill_and_run(carry):
            wins = {t: _run_window(ctx, t, carry[1][run_var]) for t in run_tables}

            def more(c):
                return jnp.logical_and(c[0] < _RUN_WINDOW, cond_fun(c[1]))

            def one_pass(c):
                r, inner = c
                rows = {t: (run_var, lax.dynamic_index_in_dim(w, r, 0, keepdims=False))
                        for t, w in wins.items()}
                return r + 1, body_fun(inner, rows)

            return lax.while_loop(more, one_pass, (jnp.int32(0), carry))[1]

        active_f, env_f, bufs_f = lax.while_loop(cond_fun, refill_and_run, carry0)
    ctx._pad_cache.clear()
    for k in carried_vars:
        ctx.env[k] = KVal(env_f[k], var_ctypes[k], None)
    for k in carried_bufs:
        ctx.bufs[k] = bufs_f[k]
        ctx.stored.add(k)


# ---------------------------------------------------------------------------
# lane compaction.  A masked loop runs every pass over all the lanes of its
# launch, whatever the mask it was entered under: a BFS level whose frontier
# is a thousandth of the range pays for the range, pass after pass, in every
# gather and scatter of the body.  Where few lanes enter, the loop runs over
# THOSE lanes: their numbers are put in order once (active first) and the
# loop walks them in chunks of ``_COMPACT_WIDTH``, each chunk the masked loop
# at that width with the work-item id as data.  Where most lanes enter, the
# loop runs as it always did; the count of entering lanes decides, at run
# time, between the two (both are compiled into the launcher).
# ---------------------------------------------------------------------------

# lanes of a chunk, and the share of a launch's lanes from which on the loop
# runs over all of them: read off sweeps on the chip (PERF.md, PR 41)
_COMPACT_WIDTH = 8192
_COMPACT_DENSE_SHARE = 0.75


def _compactable(ctx: _Ctx, body: list, cond_expr) -> bool:
    """May this masked loop run over its entering lanes alone
    (:func:`_exec_compacted`)?  Decided from the build alone: the XLA
    lowering, a loop inside no other, entered under a mask that differs from
    lane to lane, over more lanes than one chunk holds, whose body or
    condition reads or writes a buffer at an index that is neither the same
    in every lane nor affine in the work-item id (a gather or a scatter a
    pass: a loop of arithmetic and own-element accesses runs at the vector
    unit's speed over all lanes and gains nothing)."""
    if (ctx.pallas or ctx.info.get("in_loop", 0) or ctx.masks()[0] is None
            or ctx.B <= _COMPACT_WIDTH or ctx.cooperative or ctx.has_vectors):
        # (chunks of entering lanes would tear groups apart; a chunk moves
        # lane vectors to its lanes, not a vector's planes)
        return False
    changed, private = _assigned_vars(body), ctx.lane_arrays()
    for ix in _index_nodes([body, cond_expr]):
        if ix.base not in ctx.bufs or ix.base in private:
            continue
        names = _vars_read(ix.index)
        if not (_expr_uniform(ix.index, ctx.uniform_vars, private)
                or (_affine_expr(ix.index) and not names & changed
                    and all(ctx.env[v].affine is not None
                            for v in names if v in ctx.env))):
            return True
    return False


def _prefix_counts(x):
    """Inclusive prefix sums of ``int32[n]`` (``n`` a multiple of 128): a
    row of 128 at a time by a product with a triangle of ones, the rows'
    totals the same way one level up.  Exact in float32 up to 2**24; the
    chip's compiler takes half a second over it where ``jnp.cumsum`` of
    524 288 elements took it 8.6 s (PERF.md, PR 41)."""
    rows = x.reshape(-1, _ROW).astype(jnp.float32)
    upto = (lax.broadcasted_iota(jnp.int32, (_ROW, _ROW), 0)
            <= lax.broadcasted_iota(jnp.int32, (_ROW, _ROW), 1))
    inc = jnp.dot(rows, upto.astype(jnp.float32),
                  precision=lax.Precision.HIGHEST).astype(jnp.int32)
    if inc.shape[0] == 1:
        return inc.reshape(-1)
    total = inc[:, -1]
    m = total.shape[0]
    before = _prefix_counts(jnp.pad(total, (0, -m % _ROW)))[:m] - total
    return (inc + before[:, None]).reshape(-1)


# the classes of :func:`_chunk_lanes`' key: a count of passes under
# ``_SHORT`` is a class of its own, a longer one shares its class with the
# counts of as many bits; every 32-bit count has one of ``_CLASSES``
_SHORT = 32
_CLASSES = _SHORT + 32 - _SHORT.bit_length() + 1


def _keyed_ranks(entered, trips, width: int):
    """The place (from 1) of every lane set in ``entered`` when those lanes
    stand by the CLASS of ``trips``, their passes to come, from long to
    short, and inside a class in rising order; with no more than ``width`` of
    them there is one class, and the places are the prefix counts.  A
    counting order of the pieces the chip's compiler takes cheaply (PERF.md,
    PR 41): the lanes of a row of 128 are counted by class and placed among
    themselves by comparisons, and ONE prefix count over the rows' counts,
    class after class, says how many stand before a row's lanes of a class."""
    b = entered.shape[0]
    t = jnp.minimum(trips, trips.dtype.type(0xFFFFFFFF)).astype(jnp.uint32)
    cls = jnp.where(t < _SHORT, t, (_CLASSES - 1) - lax.clz(t)).astype(jnp.int32)
    keyed = jnp.sum(entered, dtype=jnp.int32) > width
    cls = jnp.where(entered, jnp.where(keyed, _CLASSES - 1 - cls, 0), _CLASSES)
    rows = jnp.pad(cls, (0, -b % _ROW), constant_values=_CLASSES).reshape(-1, _ROW)
    mine = rows[:, :, None] == jnp.arange(_CLASSES, dtype=jnp.int32)
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)          # [rows, classes]
    m = count.size
    upto = _prefix_counts(jnp.pad(count.T.reshape(-1), (0, -m % _ROW)))[:m]
    before = upto.reshape(count.shape[::-1]).T - count
    ahead = (lax.broadcasted_iota(jnp.int32, (_ROW, _ROW), 1)
             <= lax.broadcasted_iota(jnp.int32, (_ROW, _ROW), 0))
    among = jnp.sum((rows[:, :, None] == rows[:, None, :]) & ahead, axis=2,
                    dtype=jnp.int32)
    return (jnp.sum(jnp.where(mine, before[:, None, :], 0), axis=2)
            + among).reshape(-1)[:b]


def _chunk_lanes(entered, width: int, trips=None) -> Callable:
    """``c -> int32[width]``: the numbers of the lanes of chunk ``c`` of the
    lanes set in ``entered``; beyond the last of them, lane 0 (the caller
    masks those slots off).  The order is built once: every entering lane's
    rank among them, and its number goes to that place of the list.  In
    rising order, the rank a prefix count; with ``trips`` (the passes each
    lane is going to make: :func:`_common_passes`) and more than one chunk,
    by :func:`_keyed_ranks`, so that a chunk makes its own lanes' passes and
    not those of the longest walk among ``width`` strangers."""
    b = entered.shape[0]
    rank = (_prefix_counts(jnp.pad(entered, (0, -b % _ROW)).astype(jnp.int32))[:b]
            if trips is None else _keyed_ranks(entered, trips, width))
    order = jnp.zeros(b + -b % width, jnp.int32).at[
        jnp.where(entered, rank - 1, -1)].set(
            jnp.arange(b, dtype=jnp.int32), mode="drop", unique_indices=True)
    return lambda c: lax.dynamic_slice(order, (c * width,), (width,))


def _at_lanes(ctx: _Ctx, v: KVal, lanes) -> KVal:
    """A local of the launch's lanes at the lanes of a chunk (``ctx.gid`` is
    the chunk's already): a value affine in the work-item id is computed
    from it, any other vector picked out lane by lane (a private array along
    its last axis); what is the same in every lane stays as it is."""
    if not v.is_vector:
        return v
    if (v.affine is not None and type(v.affine[0]) is int
            and v.value.dtype == jnp.int32):
        return KVal(_num(ctx.gid) * jnp.int32(v.affine[0]) + v.affine[1], v.ctype)
    return KVal(v.value.at[..., lanes].get(mode="promise_in_bounds"), v.ctype)


def _exec_compacted(ctx: _Ctx, node, cond_expr, body_core: list, step_stmt,
                    carried_vars: list, carried_bufs: list) -> None:
    """A masked loop over the lanes that ENTER it (:func:`_compactable` says
    which loops).  With ``k`` of the launch's ``B`` lanes entering: none,
    and the loop makes no pass; more than ``_COMPACT_DENSE_SHARE`` of them,
    and it runs over all lanes (:func:`_exec_masked`, as every other loop
    does); else over ``ceil(k / W)`` chunks of ``W`` entering lanes, each
    chunk :func:`_exec_masked` at shape ``(W,)``:

    - the work-item id is data (no affine form): a read ``x[tid]`` is a
      gather, a store there a scatter of distinct indices; such a read of a
      buffer the loop does not store to is made ONCE a chunk, before its
      passes (``ctx.hoisted``);
    - the locals the loop reads are picked out at the chunk's lanes on the
      way in; of those it assigns, the ones something reads after the loop
      (and the buffers riding it as locals: ``ctx.own``) are put back into
      their ``B``-shaped values on the way out, the others never are;
    - run windows, kept views, the word view of a byte table and the
      scatter's ``drop`` work at ``W`` as at ``B``; the slots of the last
      chunk beyond ``k`` are masked off like any lane that did not enter;
    - the buffers the loop stores to ride the chunks as they ride the passes.

    The order in which the lanes of a launch run against each other is
    unspecified (docs/KERNEL_LANGUAGE.md); chunks add nothing a kernel may
    rely on, and which lanes share one is the build's to choose: those with
    as many passes to make, where the syntax gives the count
    (:func:`_chunk_lanes`)."""
    B, W = ctx.B, _COMPACT_WIDTH
    entered = jnp.broadcast_to(ctx.active_mask(), ctx.shape)
    k = jnp.sum(entered, dtype=jnp.int32)
    loop = [body_core, cond_expr, step_stmt]
    _loop_views(ctx, node, cond_expr, body_core, carried_bufs)
    ctx.compact_loops += 1

    live = sorted(_live_after(ctx, carried_vars))
    _carried_at_shape(ctx, carried_vars)
    # the passes each lane is going to make, where the syntax gives them
    # (_common_walks): the key by which the entering lanes go to their chunks
    peel = ctx.peels.get(id(node))
    trips = _common_passes(ctx, peel, each=True) if peel else None
    ctx.compact_ordered += trips is not None
    ctypes = {v: ctx.env[v].ctype for v in live}
    needed = sorted((_vars_read(loop) | set(carried_vars)) & set(ctx.env))
    changed = _assigned_vars(body_core + [step_stmt])
    hoist = [ix for ix in _index_nodes(loop)
             if ix.base in ctx.bufs and ix.base not in ctx.private
             and ix.base not in carried_bufs and ix.base not in ctx.own
             and _affine_expr(ix.index) and not _vars_read(ix.index) & changed]
    if ctx.row_gathers:  # their row views too are made once, out here
        for ix in hoist:
            if ctx.bufs[ix.base].dtype.itemsize == 4:
                ctx.rows_view(ix.base)

    def run(state, fn, **at):
        """``fn()`` with the carried ``state`` (the live locals, the stored
        buffers) in the context's place and ``at`` replacing fields of the
        context.  Nothing a branch traced stays behind but what it
        recorded."""
        fields = ("B", "shape", "gid", "env", "bufs", "mask", "umask",
                  "return_mask", "compacting", "hoisted", "_rows_cache")
        saved = {f: getattr(ctx, f) for f in fields}
        ctx.env, ctx.bufs = dict(ctx.env), {**ctx.bufs, **state[1]}
        ctx._rows_cache = dict(ctx._rows_cache)
        for v in live:
            ctx.env[v] = KVal(state[0][v], ctypes[v], None)
        for f, val in at.items():
            setattr(ctx, f, val)
        ctx._pad_cache.clear()
        try:
            return fn()
        finally:
            for f, val in saved.items():
                setattr(ctx, f, val)
            ctx._pad_cache.clear()

    def masked():
        _exec_masked(ctx, node, cond_expr, body_core, step_stmt,
                     carried_vars, carried_bufs)
        return ({v: ctx.env[v].value for v in live},
                {b: ctx.bufs[b] for b in carried_bufs})

    def chunk(lanes, valid, before):
        ctx.env = {name: _at_lanes(ctx, ctx.env[name], lanes) for name in needed}
        ctx.hoisted = {id(ix): _load(ctx, ix).value for ix in hoist}
        into = jnp.where(valid, lanes, B)  # a slot beyond the last: dropped
        out, bufs = masked()
        return ({v: before[v].at[..., into].set(out[v], mode="drop")
                 for v in live}, bufs)

    def compacted(state):
        lanes_of = _chunk_lanes(entered, W, trips)
        slot = jnp.arange(W, dtype=jnp.int32)

        def one(c, st):
            lanes, valid = lanes_of(c), c * W + slot < k
            return run(st, lambda: chunk(lanes, valid, st[0]), B=W, shape=(W,),
                       gid=KVal(ctx.offset + lanes, "int"), mask=valid,
                       umask=None, return_mask=None, compacting=True)

        return lax.fori_loop(0, lax.div(k + (W - 1), jnp.int32(W)), one, state)

    state = ({v: ctx.env[v].value for v in live},
             {b: ctx.bufs[b] for b in carried_bufs})
    which = jnp.where(k == 0, 0, jnp.where(k > _COMPACT_DENSE_SHARE * B, 1, 2))
    env_f, bufs_f = lax.switch(
        which, [lambda st: st, lambda st: run(st, masked), compacted], state)
    for v in live:
        ctx.env[v] = KVal(env_f[v], ctypes[v], None)
    for b in carried_bufs:
        ctx.bufs[b] = bufs_f[b]
        ctx.stored.add(b)


# ---------------------------------------------------------------------------
# uniformity analysis — which locals provably hold the SAME value in every
# lane (work item) of a launch chunk.  A load indexed by a uniform
# expression (the n-body pattern ``x[j]`` with a uniform loop counter) can
# then be scalarized: one dynamic_slice element broadcast to the chunk,
# instead of a (B,)-wide gather per loop iteration.
# ---------------------------------------------------------------------------

_UNIFORM_CALLS = {
    "get_global_size", "get_local_size", "get_num_groups",
    "get_global_offset", "get_work_dim",
}
_LANE_CALLS = {"get_global_id", "get_local_id", "get_group_id"}
_PURE_BUILTINS = (
    set(_UNARY_FLOAT) | set(_BINARY_FLOAT)
    | {"abs", "min", "max", "fmin", "fmax", "clamp", "mad", "fma", "mix",
       "step", "smoothstep", "select", "isnan", "isinf", "isfinite"}
)


def _expr_uniform(node, uset: set[str], private: set[str] = frozenset(),
                  group: bool = False) -> bool:
    """True iff ``node`` provably evaluates identically in every lane of the
    launch; with ``group``, in every lane of a work-GROUP (``uset`` is then
    :func:`_uniform_vars`'s group set, ``private`` the private arrays alone:
    a ``__local`` array at such an index is the group's one element)."""
    if isinstance(node, Num):
        return True
    if isinstance(node, Var):
        return node.name in uset
    if isinstance(node, Index):
        # a BUFFER load at a uniform index yields the same element in every
        # lane; a PRIVATE array's rows are per-lane, so its loads never are
        # (nor, over a launch, those of a group's __local array: the callers
        # name both in ``private``)
        if node.base in private:
            return False
        return _expr_uniform(node.index, uset, private, group)
    if isinstance(node, BinOp):
        return (_expr_uniform(node.left, uset, private, group)
                and _expr_uniform(node.right, uset, private, group))
    if isinstance(node, UnOp):
        return _expr_uniform(node.operand, uset, private, group)
    if isinstance(node, Cast):
        return _expr_uniform(node.operand, uset, private, group)
    if isinstance(node, Ternary):
        return (
            _expr_uniform(node.cond, uset, private, group)
            and _expr_uniform(node.then, uset, private, group)
            and _expr_uniform(node.other, uset, private, group)
        )
    if isinstance(node, Call):
        name = node.name
        if name.startswith(("native_", "half_")):
            name = name.split("_", 1)[1]
        if group and name == "get_group_id":
            return True
        if name in _LANE_CALLS:
            return False
        if name in _UNIFORM_CALLS:
            return True
        if name not in _PURE_BUILTINS:
            # user helpers (and anything unrecognized) may read lane state
            return False
        return all(_expr_uniform(a, uset, private, group) for a in node.args)
    return False  # unknown node kind: be conservative


def _has_divergent_exit(stmts: list, divergent: bool, uset, private,
                        group: bool = False) -> bool:
    """True if a break/continue can execute under a lane-divergent
    condition anywhere in THIS loop's body (nested loops scope their own
    break/continue and are checked when their own walk runs)."""
    for s in stmts:
        if isinstance(s, (Break, Continue)) and divergent:
            return True
        if isinstance(s, If):
            d = divergent or not _expr_uniform(s.cond, uset, private, group)
            if _has_divergent_exit(s.then, d, uset, private, group):
                return True
            if _has_divergent_exit(s.other, d, uset, private, group):
                return True
    return False


def _has_exit(stmts: list, kind) -> bool:
    """True if an exit of ``kind`` (``Break``, ``Continue``) of THIS loop is
    anywhere in its body."""
    return any(isinstance(s, kind)
               or (isinstance(s, If) and (_has_exit(s.then, kind)
                                          or _has_exit(s.other, kind)))
               for s in stmts)


def _has_break(stmts: list) -> bool:
    """True if a ``break`` of THIS loop is anywhere in its body."""
    return _has_exit(stmts, Break)


def _loop_diverges(node, uset, private, group: bool = False) -> bool:
    """False iff every lane that enters this loop (For, While, DoWhile)
    provably leaves it on the same pass: its condition is lane-uniform and
    reads no buffer the loop stores to (a masked store is per lane), and no
    ``break`` / ``continue`` sits under a divergent condition.  Such a loop
    lowers to a counted loop on scalars (:func:`_exec_counted`); its body
    adds no divergence to what surrounds it."""
    body = node.body + ([node.step] if getattr(node, "step", None) is not None else [])
    if node.cond is not None and (
            not _expr_uniform(node.cond, uset, private, group)
            or _vars_read(node.cond) & _stored_bufs(body)):
        return True
    return _has_divergent_exit(node.body, False, uset, private, group)


def _contains_return(stmts: list) -> bool:
    for s in stmts:
        if isinstance(s, Return):
            return True
        if isinstance(s, If) and (_contains_return(s.then) or _contains_return(s.other)):
            return True
        if isinstance(s, For):
            inner = ([s.init] if s.init is not None else []) + s.body + (
                [s.step] if s.step is not None else []
            )
            if _contains_return(inner):
                return True
        if isinstance(s, (While, DoWhile)) and _contains_return(s.body):
            return True
    return False


def _private_array_names(stmts: list, out: set[str] | None = None) -> set[str]:
    """The private arrays declared under ``stmts`` (a vector local is one),
    and the ``__local`` ones (kernel scope only): see :func:`_local_arrays`
    for those alone."""
    if out is None:
        out = set()
    for s in stmts:
        if isinstance(s, LocalDecl):
            out.add(s.name)
        elif isinstance(s, Decl):
            out.update(s.arrays)
            if s.ctype in VECTOR_TYPES:  # N scalars a work item, as an array
                out.update(name for name, _init in s.names)
        elif isinstance(s, If):
            _private_array_names(s.then, out)
            _private_array_names(s.other, out)
        elif isinstance(s, For):
            if s.init is not None:
                _private_array_names([s.init], out)
            _private_array_names(s.body, out)
        elif isinstance(s, (While, DoWhile)):
            _private_array_names(s.body, out)
    return out


def _regions(body: list, uset: set[str], private, group: bool = False):
    """Yield ``(stmt, path, steady)`` for every statement under ``body``, an
    ``if`` or a loop ahead of what stands inside it (its condition is
    evaluated where the statement stands; a ``for``'s init too, its step with
    the body).

    ``path`` names the REGION the statement stands in, outermost first: a
    region is a stretch of the kernel that the same lanes execute.  The top
    level is ``()``; one more opens for each branch of an ``if`` under a
    condition that is not uniform by ``uset`` (:func:`_expr_uniform`; with
    ``group``, over a work-group) and for the body of a loop that lanes leave
    on different passes (:func:`_loop_diverges`), and in such a loop one more
    for the part of a pass that a ``continue`` can skip (the body; the step
    runs for every lane still in the loop).  ``uset`` is read as it is when a
    statement is reached, so a caller may shrink it while it walks.

    ``steady``: do the lanes that enter the statement's region enter it ONCE?
    False in a region opened inside any loop, and in the skippable part of a
    pass: the lanes there are chosen anew pass by pass."""
    new_region = itertools.count(1).__next__

    def walk(stmts, path: tuple, steady: bool, loops: int):
        def opened(diverges: bool) -> tuple:
            if not diverges:
                return path, steady
            return path + (new_region(),), steady and loops == 0

        for s in stmts:
            yield s, path, steady
            if isinstance(s, If):
                diverges = not _expr_uniform(s.cond, uset, private, group)
                for branch in (s.then, s.other):
                    yield from walk(branch, *opened(diverges), loops)
            elif isinstance(s, (For, While, DoWhile)):
                if isinstance(s, For) and s.init is not None:
                    yield from walk([s.init], path, steady, loops)
                diverges = _loop_diverges(s, uset, private, group)
                mine, still = opened(diverges)
                if diverges and _has_exit(s.body, Continue):
                    yield from walk(s.body, mine + (new_region(),), False,
                                    loops + 1)
                else:
                    yield from walk(s.body, mine, still, loops + 1)
                if getattr(s, "step", None) is not None:
                    yield from walk([s.step], mine, still, loops + 1)

    return walk(body, (), True, 0)


def _uniform_vars(body: list, value_params: set[str],
                  group: bool = False) -> set[str]:
    """The locals that provably hold the SAME value in every lane that can
    observe them (with ``group``: in every such lane of a work-group, where
    ``get_group_id(0)`` and a ``__local`` element at such an index are the
    same too).  Monotone-poisoning fixed point: start assuming every
    local is; poison any variable assigned a non-uniform value, or assigned
    in another REGION than the one it was declared in; repeat until stable.

    A region is a stretch of the kernel that the same lanes execute
    (:func:`_regions` walks them): the top level, and one more for each
    branch of an ``if`` under a divergent condition and for the body of a
    loop that lanes leave on different passes.  A local declared in a region
    lives and dies in it (a loop's pass drops its declarations), so only
    that region's lanes ever read it: assigned there alone, from uniform
    values, it is the same in all of them, whatever the lanes outside would
    have made of it.  The lowering relies on exactly this: such a local stays a
    0-d value that no lane mask is merged into (:func:`_assign`)."""
    # an early `return` folds into a persistent per-lane return-mask that
    # divergently suppresses EVERY later assignment — modeling which
    # suffixes that poisons is subtle, and kernels with early returns are
    # rare, so any Return disables the analysis outright (sound by
    # construction; a divergent return once miscompiled a scalarized load
    # here)
    if _contains_return(body):
        return set()
    arrays = _private_array_names(body)
    uset: set[str] = (set(value_params) | set(_assigned_vars(body))) - arrays
    private = arrays - set(_local_arrays(body)) if group else arrays
    # declared-but-unassigned names also start uniform (zero-init)

    changed = True
    while changed:
        changed = False
        home = dict.fromkeys(value_params, ())  # name -> region declared in

        def poison(name: str) -> None:
            nonlocal changed
            if name in uset:
                uset.discard(name)
                changed = True

        # lanes that leave a loop on different passes make every assignment
        # to an outer local in it diverge: its body is a region of its own
        for s, region, _steady in _regions(body, uset, private, group):
            if isinstance(s, Decl):
                for name, init in s.names:
                    if home.setdefault(name, region) != region:
                        poison(name)  # one name, two regions' lanes
                    if name in s.arrays:
                        poison(name)  # per-lane stores make stacks diverge
                    elif init is not None and not _expr_uniform(
                            init, uset, private, group):
                        poison(name)
            elif isinstance(s, (Assign, CrementStmt)) and isinstance(s.target, Var):
                if home.get(s.target.name) != region or not (
                        isinstance(s, CrementStmt)
                        or _expr_uniform(s.value, uset, private, group)):
                    poison(s.target.name)
    return uset


def _local_arrays(body: list) -> dict:
    """``{name: LocalDecl}`` of a kernel's ``__local`` arrays (kernel scope is
    the only place the parser lets them stand)."""
    return {s.name: s for s in body if isinstance(s, LocalDecl)}


def _assignments(body: list) -> dict:
    """``{local: how many declarations and assignments name it}``."""
    assigned: dict[str, int] = {}
    for node in _walk(body):
        if isinstance(node, Decl):
            for name, _init in node.names:
                assigned[name] = assigned.get(name, 0) + 1
        elif isinstance(node, (Assign, CrementStmt)) and isinstance(
                node.target, Var):
            assigned[node.target.name] = assigned.get(node.target.name, 0) + 1
    return assigned


def _tid_vars(body: list) -> frozenset:
    """The locals that ARE the work item's local id: declared at kernel scope
    as ``get_local_id(0)`` (under integer casts) and assigned nowhere else."""
    assigned = _assignments(body)
    out = set()
    for s in body:
        if not isinstance(s, Decl) or s.ctype not in _INT_TYPES:
            continue
        for name, init in s.names:
            if (_is_local_id(init) and assigned.get(name) == 1
                    and name not in s.arrays):
                out.add(name)
    return frozenset(out)


def _build_int(node, sizes: dict) -> Optional[int]:
    """``node`` as an integer known when a launcher is built: literals and
    the calls ``sizes`` names (``get_local_size`` ..) under ``+ - *`` and
    integer casts, and the locals it names (:func:`_build_locals`); None for
    anything else."""
    node = _under_int_casts(node)
    if isinstance(node, Num):
        return int(node.value) if float(node.value).is_integer() else None
    if isinstance(node, (Call, Var)):
        return sizes.get(node.name)
    if isinstance(node, BinOp) and node.op in ("+", "-", "*"):
        a, b = _build_int(node.left, sizes), _build_int(node.right, sizes)
        if a is None or b is None:
            return None
        return {"+": a + b, "-": a - b, "*": a * b}[node.op]
    return None


def _int_typed(node, ints: set[str]) -> bool:
    """Is ``node`` an integer that no float took part in below its casts:
    its ``+`` and ``-`` are then exact, modulo 2^32 as an index's own cast
    is.  ``ints``: the parameters, buffers and locals of integer type.
    Anything not known (a helper's call, ``min``) is not."""
    if isinstance(node, Num):
        return node.ctype in _INT_TYPES
    if isinstance(node, Var):
        return node.name in ints
    if isinstance(node, Index):
        return node.base in ints
    if isinstance(node, Cast):
        return node.ctype in _INT_TYPES  # a whole number, whatever it was
    if isinstance(node, BinOp):
        return _int_typed(node.left, ints) and _int_typed(node.right, ints)
    if isinstance(node, UnOp):
        return _int_typed(node.operand, ints)
    if isinstance(node, Ternary):
        return _int_typed(node.then, ints) and _int_typed(node.other, ints)
    if isinstance(node, Call):
        return node.name in _UNIFORM_CALLS or node.name in _LANE_CALLS
    return False


def _group_sites(body: list, params: list, gset: set[str],
                 tid_vars: frozenset, every: frozenset,
                 sizes: dict | None = None) -> dict:
    """``{id: pitch}`` of the ``Index`` nodes whose index is ``local id + u``
    with ``u`` the same in every work item of a group THAT IS ACTIVE THERE:
    the reads :func:`_group_slice` serves.  An index qualifies when its terms
    (:func:`_terms`) are ONE of ``get_local_id(0)``, a :func:`_tid_vars` local
    or a WALKER, with sign +1, and a group-uniform rest (``i + blockSize``).

    A walker is a 32- or 64-bit integer local DECLARED in the body (a value
    parameter of ``params`` starts from what the caller gave it, which no
    assignment here shows: never one), every assignment of which keeps
    "local id plus a group-uniform value": a declaration or ``=`` of that
    very form (``get_group_id(0) * (get_local_size(0) * 2) + tid``, ``i +
    gridSize``) and updates by a group-uniform amount (``i += gridSize``,
    ``i -= ..``, ``i++``), all of it in integers (:func:`_int_typed`: a float
    on the way rounds lane by lane, ``tid - 3.5f`` is 0 in work items 3 AND
    4).  The form alone does not make the lanes of a group agree: one that
    skipped an update holds another ``u``.  So every assignment of the
    walker must have run for ALL the lanes that read it, or for none of
    them.  Regions say so (:func:`_regions`, the very walk that
    :func:`_uniform_vars` makes, by group).  The lanes of a region only get
    fewer as it nests, and a loop's lanes only get fewer pass by pass, so a
    read sees a walker whole when each of its assignments stands in the
    read's region or in one around it, and in none that the lanes enter anew
    pass by pass (``steady``: a divergent ``if`` inside a loop, the part of
    a body that a ``continue`` can skip).  The condition of a divergent loop
    is evaluated for the lanes that have left it too (:func:`_exec_masked`):
    it belongs to the region around the loop.  ``2 * tid + u``, ``u - tid``,
    ``tid + x[gid]``, a walker also assigned anything else, a walker read
    behind the loop that moved it: not this form, they keep the gather.

    ``pitch`` is a HINT, proving nothing: the build-time factor of
    ``get_group_id(0)`` in the index and in the declarations of the walkers
    it names (``sizes``: what the launcher knows of the ranges), 0 where
    there is none.  With it the groups' windows may lie ``pitch`` apart, one
    2-D slice of the buffer; the launch checks that they do."""
    if _contains_return(body):
        return {}
    private = every - set(_local_arrays(body))  # a tile's element is a group's
    assigns: dict[str, list] = {}   # local -> [(path, steady, op, value)]
    declared: dict[str, object] = {}  # local -> its first declaration's value
    reads: list = []                # (Index node, path)
    values = {p.name for p in params if not p.is_pointer}
    ints = {p.name for p in params if p.ctype in _INT_TYPES}
    wide = {p.name for p in params if p.ctype in VECTOR_TYPES}  # vectors.load's
    floats: set[str] = set()        # names declared as anything else

    for s, path, steady in _regions(body, gset, private, group=True):
        if isinstance(s, LocalDecl):
            (ints if s.ctype in _INT_TYPES else floats).add(s.name)
        if isinstance(s, Decl):
            (ints if s.ctype in _INT_TYPES else floats).update(
                name for name, _init in s.names)
            for name, init in s.names:
                ok = s.ctype in _WIDE_INTS and name not in s.arrays
                if name not in assigns:
                    declared[name] = init
                assigns.setdefault(name, []).append(
                    (path, steady and ok, "=", init))
            exprs = [init for _name, init in s.names]
        elif isinstance(s, (Assign, CrementStmt)):
            op, value = ((s.op, s.value) if isinstance(s, Assign)
                         else ("+=", Num(value=1, ctype="int", line=s.line)))
            if isinstance(s.target, Var):
                assigns.setdefault(s.target.name, []).append(
                    (path, steady, op, value))
            exprs = [s.target, value]
        elif isinstance(s, (If, For, While, DoWhile)):
            exprs = [s.cond]  # what stands inside comes by itself
        else:
            continue
        reads.extend((ix, path) for ix in _index_nodes(exprs)
                     if ix.base not in wide)

    ints -= floats

    def uniform(node) -> bool:
        """The same in every lane of a group, and a whole number all along."""
        return (_expr_uniform(node, gset, private, group=True)
                and _int_typed(node, ints))

    def whole(name: str, path: tuple) -> bool:
        """Has every assignment of ``name`` run for all the lanes at
        ``path``, or for none?"""
        return all(steady and path[:len(at)] == at
                   for at, steady, _op, _value in assigns[name])

    def kept(node, path: tuple) -> bool:
        """Is ``node`` ``local id + (group-uniform)`` for the lanes at
        ``path``, with the walkers that still stand?"""
        if node is None:
            return False
        ids, rest = [], []
        for sign, term in _terms(node, 1, []):
            leaf = term
            while isinstance(leaf, Cast) and leaf.ctype in _WIDE_INTS:
                leaf = leaf.operand  # (a narrower cast wraps the local id)
            if (isinstance(leaf, Call) and _is_local_id(leaf)
                    or isinstance(leaf, Var)
                    and (leaf.name in tid_vars or leaf.name in walkers)
                    and whole(leaf.name, path)):
                ids.append(sign)
            else:
                rest.append(term)
        return ids == [1] and all(uniform(t) for t in rest)

    # a value parameter's first value is the caller's, as is that of any
    # name first met in an assignment: nothing here has seen it
    walkers = set(declared) - set(values) - set(gset) - set(tid_vars)
    while True:
        lost = {name for name in walkers
                if not all(kept(value, at) if op == "=" else
                           op in ("+=", "-=") and uniform(value)
                           for at, _steady, op, value in assigns[name])}
        if not lost:
            break
        walkers -= lost

    def pitch(node, seen: tuple = ()) -> int:
        total = 0
        for sign, term in _terms(node, 1, []):
            leaf = _under_int_casts(term)
            if isinstance(leaf, Var) and leaf.name in walkers:
                if leaf.name not in seen:  # as it was declared
                    total += sign * pitch(declared[leaf.name],
                                          seen + (leaf.name,))
            elif _is_id_call(leaf, "get_group_id"):
                total += sign
            elif isinstance(leaf, BinOp) and leaf.op == "*":
                for grp, factor in ((leaf.left, leaf.right),
                                    (leaf.right, leaf.left)):
                    if _is_id_call(grp, "get_group_id"):
                        total += sign * (_build_int(factor, sizes or {}) or 0)
        return total

    return {id(ix): pitch(ix.index) for ix, path in reads
            if ix.base not in every and kept(ix.index, path)}


def _build_locals(body: list, sizes: dict) -> dict:
    """``sizes`` and, by name, the integer locals that are a build-time
    integer wherever they are read (:func:`_build_int`): declared once, 32
    bits or wider, with such a value and assigned nowhere else (SHOC's
    ``gridSize``)."""
    known, assigned = dict(sizes), _assignments(body)
    decls = [(s.ctype, name, init) for s in _walk(body) if isinstance(s, Decl)
             for name, init in s.names if name not in s.arrays]
    while True:
        found = {name: v for ctype, name, init in decls
                 if ctype in _WIDE_INTS and init is not None
                 and name not in known and assigned[name] == 1
                 and (v := _build_int(init, known)) is not None}
        if not found:
            return known
        known.update(found)


def _moves(s, name: str, known: dict) -> Optional[int]:
    """What statement ``s`` of a loop's body adds to the local ``name``: 0
    where it assigns it nowhere, None where not by a build-time integer
    (``known``: :func:`_build_locals`)."""
    mine = (isinstance(s, (Assign, CrementStmt))
            and isinstance(s.target, Var) and s.target.name == name)
    if mine and isinstance(s, CrementStmt):
        return 1 if s.op == "++" else -1
    if mine and s.op in ("+=", "-="):
        by = _build_int(s.value, known)
        return None if by is None else by if s.op == "+=" else -by
    return None if name in _assigned_vars([s]) else 0


class _Walk(NamedTuple):
    """A group read whose walker the build has followed through its loop
    (:func:`_settled_walks`)."""

    node: Index
    step: int    # what a pass of the loop adds to the index
    before: int  # of it, what the pass has added where the read stands


def _settled_walks(body: list, sites: dict, sizes: dict) -> dict:
    """``{id of a loop: [_Walk]}``: the group reads (``sites``,
    :func:`_group_sites`) whose index moves, pass by pass of the loop they
    stand in, by an amount the BUILD knows that is a whole multiple of the
    site's pitch.  What :func:`_group_slice` tests of such a read's starts
    can then change from pass to pass in one scalar only, and
    :func:`_exec_masked` settles the rest once, before the loop.

    The index is ``local id + (group-uniform)`` already.  Here: the loop is
    the innermost ``for`` / ``while`` around the read, which stands in its
    body (not in its condition, nor in a loop inside it) and which no
    ``continue`` shortens; of the index's terms ONE may name a local the loop
    assigns, bare and with sign +1 (the walker), and no other reads such a
    local or memory; every assignment the loop makes to the walker is a
    statement of the body itself or the ``for``'s step (so every lane that
    stays makes it, once a pass), ``+=`` / ``-=`` / ``++`` / ``--`` by a
    build-time integer (:func:`_build_locals`).  A step read from
    ``x[get_group_id(0)]``, one that is no multiple of the pitch, a walker
    moved under an ``if``: not here, their reads are checked pass by pass."""
    known = _build_locals(body, sizes)
    out: dict = {}

    def shallow(stmts) -> list:
        """The ``Index`` nodes a pass of THIS loop evaluates."""
        found = []
        for s in stmts:
            if isinstance(s, If):
                found += _index_nodes(s.cond) + shallow(s.then) + shallow(s.other)
            elif not isinstance(s, (For, While, DoWhile)):
                found += _index_nodes(s)
        return found

    for loop in _walk(body):
        if (not isinstance(loop, (For, While))
                or _has_exit(loop.body, Continue)):
            continue
        pass_stmts = loop.body + ([loop.step] if getattr(loop, "step", None) else [])
        moved = _assigned_vars(pass_stmts)
        for k, stmt in enumerate(loop.body):
            for ix in shallow([stmt]):
                pitch = sites.get(id(ix), 0)
                if pitch <= 0 or len(_index_nodes(ix)) > 1:
                    continue
                moving = [(sign, _under_int_casts(t))
                          for sign, t in _terms(ix.index, 1, [])
                          if _vars_read(t) & moved]
                by = [0] * len(pass_stmts)
                if moving:
                    (sign, leaf), *more = moving
                    if more or sign != 1 or not isinstance(leaf, Var):
                        continue
                    by = [_moves(s, leaf.name, known) for s in pass_stmts]
                    if None in by:
                        continue
                step = sum(by)
                if step % pitch == 0 and abs(step) < 1 << 31:
                    out.setdefault(id(loop), []).append(
                        _Walk(ix, step, sum(by[:k])))
    return out


class _Peel(NamedTuple):
    """A masked loop whose common passes can be counted before the first
    (:func:`_common_walks`)."""

    walker: str   # the local its condition compares
    bound: Any    # the expression it is compared with
    strict: bool  # ``walker < bound``; else ``<=``
    step: int     # what a pass adds to the walker: positive
    common: bool  # every lane compares with the same bound and leaves by it


def _common_walks(body: list, uset: set[str], private, sizes: dict) -> dict:
    """``{id of a loop: _Peel}``: the ``for`` / ``while`` loops whose lanes,
    where they leave on different passes, still make a number of passes
    TOGETHER that one scalar gives before the first (:func:`_common_passes`;
    :func:`_exec_masked` runs those with no mask).  From the syntax alone:

    - the condition is ONE comparison ``w < e`` or ``w <= e`` (or the same
      written ``e > w``, ``e >= w``) of a local ``w`` with an expression;
    - ``w`` is moved as :func:`_settled_walks` demands of a walker: every
      assignment a pass makes to it is a statement of the body itself or the
      ``for``'s step, ``+=`` / ``-=`` / ``++`` / ``--`` by build-time integers
      (:func:`_moves`; no float literal among them: ``w += 2.0f`` rounds)
      whose sum is positive;
    - the loop cannot change ``e``: it names no local the loop assigns and no
      buffer the loop stores to;
    - ``e`` is the same in every lane (:func:`_expr_uniform` by ``uset``) and
      the body holds no ``break`` and no ``continue`` (a ``return`` in a
      loop is refused where the loop is built).

    A loop that meets all but the last is named too, ``common`` false: each
    lane's own passes (with a ``break``, no more than those) are still known
    where the loop is entered, which is all the ORDER of a compacted loop's
    lanes asks (:func:`_chunk_lanes`; any order leaves the same memory).  A
    bound the body assigns, a walker moved under an ``if`` or by a run-time
    step, a walk downward: not here, such a loop is masked from its first
    pass and its lanes keep their order."""
    known = _build_locals(body, sizes)
    out: dict = {}
    for loop in _walk(body):
        if not isinstance(loop, (For, While)) or not isinstance(loop.cond, BinOp):
            continue
        pass_stmts = loop.body + ([loop.step] if getattr(loop, "step", None) else [])
        changed = _assigned_vars(pass_stmts) | _stored_bufs(pass_stmts)
        c = loop.cond
        for w, e, op in ((c.left, c.right, c.op),
                         (c.right, c.left, _FLIP.get(c.op))):
            if op not in ("<", "<=") or not isinstance(w, Var):
                continue
            by = [_moves(s, w.name, known) for s in pass_stmts]
            moving = [s for s, d in zip(pass_stmts, by) if d]
            if (None in by or not 0 < sum(by) < 1 << 31
                    or any(isinstance(x, Num) and x.ctype not in _INT_TYPES
                           for x in _walk(moving))
                    or _vars_read(e) & changed):
                continue
            out[id(loop)] = _Peel(
                w.name, e, op == "<", sum(by), _expr_uniform(e, uset, private)
                and not _has_exit(loop.body, (Break, Continue)))
            break
    return out


class _Coop(NamedTuple):
    """What a build knows of a kernel whose work items cooperate
    (:func:`_cooperation`)."""

    arrays: dict            # name -> LocalDecl
    barriers: int           # barrier statements in the kernel's body
    group_uniform: set      # locals the same in every lane of a group
    tid_vars: frozenset     # locals that are get_local_id(0)
    group_sites: dict       # the reads at ``local id + u``: id -> pitch (_group_sites)
    group_walks: dict       # of them, by loop, those it settles once (_settled_walks)

    @property
    def nbytes(self) -> int:
        """The bytes of local memory ONE work-group holds."""
        return sum(d.size * ctype_to_dtype(d.ctype).itemsize
                   for d in self.arrays.values())


def cooperates(kernel: KernelDef) -> bool:
    """Do the kernel's work items cooperate inside their group: has it a
    ``__local`` array or a barrier?"""
    return any(isinstance(n, (LocalDecl, Barrier)) for n in _walk(kernel.body))


def _cooperation(kernel: KernelDef, sizes: dict | None = None) -> Optional[_Coop]:
    """None for a kernel with neither a ``__local`` array nor a barrier (the
    build then does nothing it did not do before); else what the lowering
    needs, with every barrier PROVED to be reached by all work items of a
    group together (:func:`_check_barriers` raises where one is not).
    ``sizes``: the ranges a launcher is built for, by the call that gives
    them (``get_local_size`` ..)."""
    if not cooperates(kernel):
        return None
    arrays = _local_arrays(kernel.body)
    barriers = sum(isinstance(n, Barrier) for n in _walk(kernel.body))
    values = {p.name for p in kernel.params if not p.is_pointer}
    gset = _uniform_vars(kernel.body, values, group=True)
    if barriers:
        _check_barriers(kernel, gset)
    tids = _tid_vars(kernel.body)
    # only a kernel with a tile is promised launches of whole groups
    # (build_kernel_fn refuses a chunk that cuts one)
    every = frozenset(_private_array_names(kernel.body))
    sites = (_group_sites(kernel.body, kernel.params, gset, tids, every, sizes)
             if arrays else {})
    walks = _settled_walks(kernel.body, sites, sizes or {}) if sites else {}
    return _Coop(arrays, barriers, gset, tids, sites, walks)


def _check_barriers(kernel: KernelDef, gset: set[str]) -> None:
    """A barrier is legal only where every work item of a group reaches it:
    at kernel scope, or inside ``if`` / ``for`` / ``while`` whose conditions
    are the same in every lane of a GROUP (``gset``: :func:`_uniform_vars`
    with ``group``), in a loop no ``break`` / ``continue`` leaves under any
    other condition, in a kernel with no early ``return``.  Anywhere else:
    ``KernelLanguageError`` naming ``barrier-divergent`` and the line."""
    private = frozenset(_private_array_names(kernel.body)) - set(
        _local_arrays(kernel.body))

    def refuse(node, why: str):
        raise KernelLanguageError(
            f"barrier-divergent: this barrier is not reached by every work "
            f"item of a group together: {why}.  A barrier may stand at kernel "
            "scope or under conditions built from literals, value parameters, "
            "get_local_size / get_num_groups / get_global_size / get_group_id "
            "and locals assigned only from such", line=node.line)

    def walk(stmts, why) -> None:
        for s in stmts:
            if isinstance(s, Barrier):
                if why:
                    refuse(s, why)
            elif isinstance(s, If):
                inner = why or (None if _expr_uniform(
                    s.cond, gset, private, group=True) else
                    f"the condition of the `if` on line {s.line} differs "
                    "between the work items of a group")
                walk(s.then, inner)
                walk(s.other, inner)
            elif isinstance(s, (For, While, DoWhile)):
                body = s.body + ([s.step] if getattr(s, "step", None) else [])
                inner = why or (None if not _loop_diverges(
                    s, gset, private, group=True) else
                    f"the work items of a group leave the loop on line "
                    f"{s.line} on different passes (its condition, or a break "
                    "/ continue under a condition that differs between them)")
                walk(body, inner)

    if _contains_return(kernel.body):
        first = next(n for n in _walk(kernel.body) if isinstance(n, Barrier))
        refuse(first, "the kernel has an early `return`, which takes work "
                      "items out of every barrier behind it")
    walk(kernel.body, None)


def _loop_counts(kernel: KernelDef, uset: set[str]) -> tuple[int, int]:
    """``(counted, masked)``: how many loops of the kernel, and of the
    helper functions it can reach, lower each way (a helper's body is
    inlined with no uniformity facts of its own)."""
    counted = masked = 0
    helpers = getattr(kernel, "helpers", {}) or {}
    returns = _contains_return(kernel.body)
    seen: set[str] = set()
    todo = [(kernel.body, uset, frozenset(_private_array_names(kernel.body)))]
    while todo:
        node, facts, private = todo.pop()
        if isinstance(node, (list, tuple)):
            todo.extend((x, facts, private) for x in node)
            continue
        if isinstance(node, (For, While, DoWhile)):
            if returns or _loop_diverges(node, facts, private):
                masked += 1
            else:
                counted += 1
        if isinstance(node, Call) and node.name in helpers and node.name not in seen:
            seen.add(node.name)
            hb = helpers[node.name].body
            todo.append((hb, set(), frozenset(_private_array_names(hb))))
        if hasattr(node, "__dict__"):
            todo.extend((v, facts, private) for v in vars(node).values()
                        if isinstance(v, (list, tuple)) or hasattr(v, "__dict__"))
    return counted, masked


def _vars_read(node, out: set[str] | None = None) -> set[str]:
    """Every variable NAME referenced anywhere under ``node`` (statements,
    expressions, conditions, indices).  Conservative liveness input for
    free-run elimination: a name in here might be read."""
    if out is None:
        out = set()
    if isinstance(node, Var):
        out.add(node.name)
        return out
    if isinstance(node, Index):
        # base is a plain string (buffer or private array) — count it
        out.add(node.base)
        _vars_read(node.index, out)
        return out
    if isinstance(node, _Lit):
        return out
    if isinstance(node, (list, tuple)):
        for x in node:
            _vars_read(x, out)
        return out
    if hasattr(node, "__dict__"):
        for v in vars(node).values():
            if isinstance(v, (list, tuple)) or hasattr(v, "__dict__"):
                _vars_read(v, out)
    return out


def _assigned_vars(stmts: list) -> set[str]:
    out: set[str] = set()

    def walk(s):
        if isinstance(s, Decl):
            out.update(n for n, _ in s.names)
        elif isinstance(s, Assign) and isinstance(s.target, Var):
            out.add(s.target.name)
        elif isinstance(s, Assign) and isinstance(s.target, Index):
            # element store: carries the whole private array through loops
            # (buffer bases are filtered out by the env intersection)
            out.add(s.target.base)
        elif isinstance(s, CrementStmt) and isinstance(s.target, Var):
            out.add(s.target.name)
        elif isinstance(s, CrementStmt) and isinstance(s.target, Index):
            out.add(s.target.base)
        elif isinstance(s, If):
            for x in s.then:
                walk(x)
            for x in s.other:
                walk(x)
        elif isinstance(s, For):
            if s.init is not None:
                walk(s.init)
            if s.step is not None:
                walk(s.step)
            for x in s.body:
                walk(x)
        elif isinstance(s, (While, DoWhile)):
            for x in s.body:
                walk(x)

    for s in stmts:
        walk(s)
    return out


def _stored_bufs(stmts: list) -> set[str]:
    out: set[str] = set()

    def walk(s):
        if isinstance(s, (Assign, CrementStmt)) and isinstance(getattr(s, "target", None), Index):
            out.add(s.target.base)
        if isinstance(s, If):
            for x in s.then + s.other:
                walk(x)
        elif isinstance(s, For):
            if s.init is not None:
                walk(s.init)
            if s.step is not None:
                walk(s.step)
            for x in s.body:
                walk(x)
        elif isinstance(s, (While, DoWhile)):
            for x in s.body:
                walk(x)

    for s in stmts:
        walk(s)
    return out


# ---------------------------------------------------------------------------
# kernel function construction
# ---------------------------------------------------------------------------


ACCESS_KINDS = ("slice", "strided", "uniform", "gather", "scatter", "carried")


@dataclass
class KernelBuildInfo:
    """Static description of one compiled kernel function."""

    name: str
    array_params: list[str]
    value_params: list[str]
    array_ctypes: dict[str, str]
    stored_params: list[str]  # params the kernel writes (discovered at trace)
    # which lowering this launcher was built with: "pallas" (Mosaic tile
    # path), "xla" (vectorized lowering) or "python" (a PythonKernel) —
    # and, when a TPU launch was routed AWAY from Pallas, why
    # (kernel/registry.py records both so a run can assert its routing)
    lowering: str = "xla"
    veto: str | None = None
    # a ladder executable (``lowering="ladder"``: the fused window's, repeat
    # mode's) names the build infos of the rung launchers it runs
    rungs: tuple = ()
    # how the kernel's loops were lowered: as counted loops on scalars
    # (every lane proved to leave together: _exec_counted) or under a
    # per-lane active mask
    loops_counted: int = 0
    loops_masked: int = 0
    # of the masked ones, filled at trace: those whose common passes run on a
    # scalar counter with no mask, ahead of the masked loop (_common_walks)
    loops_peeled: int = 0
    # how the kernel's buffer accesses were lowered, counted over its
    # access sites by the walk that lowers them (filled at trace): loads
    # and stores by ``slice`` (contiguous), loads by ``strided`` window or
    # column, by ``uniform`` scalar, by per-lane ``gather``, stores by
    # ``scatter``, and ``carried``: buffers riding a loop as a local; in a
    # kernel whose work items cooperate also ``group``: loads of one window a
    # work-group (:func:`_group_slice`)
    access: dict = field(default_factory=dict)
    # the element widths in bytes of the stores lowered to a scatter, one a
    # store in the order the walk met them (``(4, 1)`` for Rodinia's BFS_1:
    # ``cost[id]`` and ``updating[id]``); filled at trace
    scattered: tuple = ()
    # the value parameters taken as keys of the launcher cache because the
    # kernel multiplies with them inside an index (:func:`pitch_params`),
    # with the values of the newest build
    keyed: dict = field(default_factory=dict)
    # the launch-invariant views the newest trace asked for
    # (:class:`ViewSpec`), and what the launcher made of them on its newest
    # call: views handed to the launch as arguments, and how many of those
    # had to be built on that call (kernel/registry.py; 0 and 0 for a build
    # that asks for none, and in a warm window ``views_built`` is 0)
    views: tuple = ()
    views_kept: int = 0
    views_built: int = 0
    # what the launcher's newest dispatch handed over as run-time scalars
    # (kernel/registry.py): ``(words, loose)``, the 32-bit words that crossed
    # in one vector and the Python or numpy scalars that crossed one by one;
    # None for a launcher that has dispatched nothing (a ladder's rung)
    scalars: tuple | None = None
    # what the newest launch kept current beyond the lane's own range, by
    # array (``u1:16384``: core/cores.py's exchange); "" where nothing
    reach: str = ""
    # a Pallas build's tile: the rows of ``(tile_rows, 128)`` work items a
    # grid step runs, the grid steps a launch makes, and the most tiles a
    # counted loop of the kernel keeps alive across its passes, which is what
    # the rows were fitted to (pallas_backend._fit_rows); 0 on other builds
    tile_rows: int = 0
    tile_grid: int = 0
    loop_live: int = 0
    # lane compaction (:func:`_exec_compacted`), filled at trace: ``(loops,
    # width, gathered, scattered, ordered)``, the masked loops made
    # compactable, the lanes of a chunk, the reads and stores at the lane's
    # own element (a slice on the dense path) that a chunk lowers as a gather
    # and as a scatter, and the loops whose entering lanes go to their chunks
    # by the passes they are going to make (:func:`_chunk_lanes`); ``()``
    # where no loop of the build was made compactable
    compact: tuple = ()
    # work-group cooperation: ``(arrays, bytes, barriers)``, the kernel's
    # ``__local`` arrays, the bytes of them one work-group holds and its
    # barrier statements, from the syntax; and, filled at trace, the access
    # sites of those arrays by lowering, ``{"shift": x, "uniform": y, "row":
    # z}`` (``row`` is the fallback, a gather / scatter inside the group's
    # row).  ``()`` / ``{}`` for a kernel with neither array nor barrier
    local: tuple = ()
    local_sites: dict = field(default_factory=dict)
    # vector types (kernel/vectors.py): ``(params, widths, loads, gathers,
    # stores)``, the ``__global floatN*`` parameters, their ``N`` (sorted,
    # each once) and, filled at trace, the accesses of them that were BUILT,
    # one an access whatever its width: loads by slice, strided window or
    # uniform element, loads by per-lane gather, stores; ``()`` for a kernel
    # with no vector parameter
    vector: tuple = ()


LOCAL_KINDS = ("shift", "uniform", "row")


def hlo_name(*kernel_names: str) -> str:
    """The kernel's name(s) made safe for an HLO identifier
    (``[A-Za-z0-9_.]``; a sequence joined with ``.``): what the jitted
    launchers and the Mosaic call are named after, so that a profile's
    device operations and modules read ``nBody.6/custom-call`` /
    ``jit_nBody`` and not the name of a closure."""
    return ".".join(re.sub(r"[^A-Za-z0-9_.]", "_", n) for n in kernel_names)


def build_kernel_fn(
    kernel: KernelDef,
    chunk: int,
    local_size: int,
    global_size: int,
    platform: str | None = None,
    in_range: bool = True,
) -> tuple[Callable, KernelBuildInfo]:
    """Build the vectorized launch function for one kernel.

    Returns ``(fn, info)`` where ``fn(offset, arrays_tuple, values_tuple)``
    processes work items ``[offset, offset+chunk)`` and returns the tuple of
    updated arrays (all array params, in declaration order).  ``offset`` is a
    runtime scalar — re-balancing never recompiles.  ``chunk`` is static.
    After them ``fn`` takes the launcher's ``keys`` (:func:`pitch_params`)
    and ``views``, ``{(param, kind): view}``: the kept views it is handed
    (:class:`ViewSpec`; every trace leaves what it asked for in
    ``info.views``, so a trace without them is how a launcher learns them).
    ``platform`` is the lane's: on ``"tpu"`` a per-lane gather reads whole
    rows (:func:`_take_rows`).  ``in_range``: every launch of this build
    keeps ``[offset, offset+chunk)`` inside ``[0, global_size)``, which is
    what proves an affine access in bounds; a launch that cannot promise it
    (a compute with a global offset) takes the build without the proof,
    whose windows clamp and whose masked stores scatter.
    """
    array_params = [p for p in kernel.params if p.is_pointer]
    value_params = [p for p in kernel.params if not p.is_pointer]
    info = KernelBuildInfo(
        name=kernel.name,
        array_params=[p.name for p in array_params],
        value_params=[p.name for p in value_params],
        array_ctypes={p.name: p.ctype for p in array_params},
        stored_params=[],
    )

    widths = {p.name: VECTOR_TYPES[p.ctype][1] for p in array_params
              if p.ctype in VECTOR_TYPES}
    has_vectors = lang.uses_vectors(kernel)
    uniform = _uniform_vars(kernel.body, {p.name for p in value_params})
    info.loops_counted, info.loops_masked = _loop_counts(kernel, uniform)
    pitches = pitch_params(kernel)
    readonly = frozenset(info.array_params) - _stored_bufs(kernel.body)
    sizes = {"get_local_size": local_size}
    if isinstance(global_size, int):
        sizes.update(get_global_size=global_size,
                     get_num_groups=global_size // local_size)
    # (raises on a barrier a group does not reach)
    coop = _cooperation(kernel, sizes)
    peels = _common_walks(kernel.body, uniform,
                          frozenset(_private_array_names(kernel.body)), sizes)
    if coop is not None:
        info.local = (len(coop.arrays), coop.nbytes, coop.barriers)
        if coop.arrays and chunk % local_size:
            raise KernelLanguageError(
                f"kernel {kernel.name!r} has a __local array: a launch covers "
                f"whole work-groups, and {chunk} work items are no multiple of "
                f"the local range {local_size}", line=kernel.line)

    def fn(offset, arrays: tuple, values: tuple = (), keys: tuple | None = None,
           views: dict | None = None):
        ctx = _Ctx(chunk, jnp.asarray(offset, jnp.int32), global_size, local_size, {},
                   in_range)
        ctx.adopt(kernel, uniform, coop, peels)
        ctx.row_gathers = platform == "tpu"
        ctx.readonly = readonly
        ctx.kept = {(info.array_params[p], kind): v
                    for (p, kind), v in (views or {}).items()}
        ctx.has_vectors, ctx.widths = has_vectors, widths
        for p, arr in zip(array_params, arrays):
            ctx.bufs[p.name] = arr
            ctx.buf_ctypes[p.name] = p.ctype
            if p.name in widths:
                ctx.buf_ctypes[p.name] = VECTOR_TYPES[p.ctype][0]
                if arr.shape[0] % widths[p.name]:
                    raise KernelCompileError(
                        f"{p.ctype}* {p.name}: an array of {arr.shape[0]} "
                        f"elements is no whole number of {p.ctype}",
                        line=p.line)
        for p, v in zip(value_params, values):
            v = jnp.asarray(v, ctype_to_dtype(p.ctype))
            # an integer argument is the same in every lane: stride 0
            uniform_int = p.ctype in _INT_TYPES and v.ndim == 0
            ctx.env[p.name] = KVal(v, p.ctype, (0, v) if uniform_int else None)
        # the launcher's keys: build-time integers in the arguments' place
        info.keyed = dict(zip((value_params[i].name for i in pitches), keys or ()))
        for name, v in info.keyed.items():
            ctx.env[name] = _int_const(v, ctx.env[name].ctype)
        _exec_block(ctx, kernel.body)
        info.stored_params = [n for n in info.array_params if n in ctx.stored]
        info.access = dict.fromkeys(
            ACCESS_KINDS + (("group",) if coop is not None else ()), 0)
        for kind in ctx.access.values():
            info.access[kind] += 1
        info.access["carried"] = len(ctx.carried)
        info.loops_peeled = len(ctx.peeled)
        if coop is not None:
            info.access["settled"] = len(ctx.settled_sites)
            kinds = list(ctx.local_access.values())
            info.local_sites = {k: kinds.count(k) for k in LOCAL_KINDS}
        info.scattered = tuple(ctx.scattered)
        if widths:
            built = [(store, kind) for (_site, store), kind
                     in ctx.vector_access.items()]
            info.vector = (
                len(widths), tuple(sorted(set(widths.values()))),
                sum(not st and kind != "gather" for st, kind in built),
                sum(not st and kind == "gather" for st, kind in built),
                sum(st for st, _kind in built))
        own = [kind for site, kind in ctx.compact_access.items()
               if ctx.access.get(site) != kind]
        info.compact = (ctx.compact_loops, _COMPACT_WIDTH, own.count("gather"),
                        own.count("scatter"), ctx.compact_ordered
                        ) if ctx.compact_loops else ()
        info.views = tuple(sorted(
            ViewSpec(info.array_params.index(name), kind)
            for name, kind in ctx.asked))
        return tuple(ctx.bufs[p.name] for p in array_params)

    return fn, info


def pitch_params(kernel: KernelDef) -> tuple:
    """Positions, among the kernel's value parameters, of those it
    multiplies with inside an array index: ``a[i * n + j]``, ``a[j * n +
    i]``, or through a local that an index reads (``int row = i * n``).  Such
    an argument is a shape in disguise, the pitch of a 2-D array, and a
    launcher is built for each value of it as one is for each shape
    (kernel/registry.py): only with the pitch known is the row walk a
    window of a 2-D view and the access provably in bounds.  Decided from
    the syntax tree: an ``int`` parameter nothing assigns to, a bare factor
    of a ``*`` in an expression that feeds an index."""
    names = [p.name for p in kernel.params if not p.is_pointer]
    cands = {p.name for p in kernel.params if not p.is_pointer
             and p.ctype in _INT_TYPES} - _assigned_vars(kernel.body)
    if not cands:
        return ()
    sources: dict[str, list] = {}   # local -> the expressions assigned to it
    for node in _walk(kernel.body):
        if isinstance(node, Decl):
            for name, init in node.names:
                if init is not None:
                    sources.setdefault(name, []).append(init)
        elif isinstance(node, Assign) and isinstance(node.target, Var):
            sources.setdefault(node.target.name, []).append(node.value)
    # every expression that feeds an index: the indices, and what is assigned
    # to the locals they read
    feeding = [ix.index for ix in _index_nodes(kernel.body)]
    seen: set[str] = set()
    found: set[str] = set()
    while feeding:
        for node in _walk(feeding.pop()):
            if isinstance(node, BinOp) and node.op == "*":
                found.update(x.name for x in (node.left, node.right)
                             if isinstance(x, Var) and x.name in cands)
            if isinstance(node, Var) and node.name not in seen:
                seen.add(node.name)
                feeding.extend(sources.get(node.name, ()))
    return tuple(i for i, name in enumerate(names) if name in found)


# the vector forms live in a module of their own, which reads this one: the
# import stands behind everything it reads (either module may be imported
# first)
from . import vectors  # noqa: E402
