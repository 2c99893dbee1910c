"""Vector types on the vectorized-XLA lowering (docs/KERNEL_LANGUAGE.md,
*Vector types*).

A vector VALUE of ``N`` components over a launch of ``B`` work items is ONE
array ``[N, B]``: a plane a component, the work items last, which is how a
private array ``float acc[N];`` already rides beside the lane vectors
(``_Ctx.private``).  A vector LOCAL is exactly that private array, plus its
type in ``_Ctx.vectors``: ``v.x`` is the parser's ``v[0]`` and takes the
private array's load and store, masks, loop carries and scoping as they are;
what this module adds is the value as a whole (``v``, ``v = w``, ``v + w``,
literals) and the accesses to memory.

``p[e]`` of a ``__global floatN* p`` is elements ``[N e, N e + N)`` of the
caller's flat array.  It is classified by the SAME forms as a scalar access,
on the index ``e``, and costs ONE access whatever ``N``:

- ``slice``: ``e = gid + u``: ``N B`` contiguous elements, cut into planes;
- ``strided``: ``e = s gid + u``, ``0 <= u < s`` at build time: ``N``
  neighbouring columns of the buffer seen as rows of ``N s``;
- ``uniform``: ``e`` the same in every lane: ``N`` elements, broadcast;
- ``gather``: anything else: on a TPU lane the ROW of 128 that holds the
  vector (it never straddles one: ``128 % N == 0``), fetched once, the ``N``
  components picked out of it; elsewhere ``N`` elements a lane;
- a store: ``slice`` (a select into the window under a mask), else ONE
  ``scatter`` of whole vectors.

Every form lands in ``_Ctx.access`` once, under the scalar form's name, and
in ``_Ctx.vector_access`` (the spans' ``vector`` field).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..errors import KernelLanguageError
from . import codegen as cg
from .lang import VECTOR_TYPES, Member, VecLit, refused

__all__ = ["refused", "is_vector", "refuse_builtin", "splat", "literal",
           "member", "binop", "negate", "declare", "bind", "read_local",
           "assign_local", "load", "store"]


def is_vector(v) -> bool:
    return v.ctype in VECTOR_TYPES


_BUILTINS = {"dot", "length", "fast_length", "cross", "distance",
             "fast_distance", "normalize", "fast_normalize", "shuffle",
             "shuffle2", "any", "all"}


def refuse_builtin(name: str, line: int = 0) -> None:
    """Raise for a builtin that exists for vectors only, by the refusal's
    name and with what to write instead."""
    if name.startswith(("vload", "vstore")):
        raise refused("vload-vstore", f"{name} is not supported; index a "
                      "__global floatN* parameter, p[i]", line)
    if name.startswith(("convert_", "as_")):
        raise refused("vector-conversion", f"{name} is not supported; "
                      "convert component by component", line)
    if name in _BUILTINS:
        raise refused("vector-builtin", f"{name} is not supported; write it "
                      "out in components (v.x * w.x + ..)", line)


def _plane(ctx, v, elem: str):
    """A scalar value (a Python number, 0-d, or of the work-item shape) as
    one plane of the element type."""
    val = jnp.asarray(cg._num(cg._as_dtype(v, elem)), cg.ctype_to_dtype(elem))
    return jnp.broadcast_to(val, ctx.shape)


def splat(ctx, v, ctype: str, line: int = 0):
    """``v`` as a value of the vector type ``ctype``: itself, or a scalar
    converted to the element type and given to every component (OpenCL's one
    implicit conversion to a vector)."""
    elem, n = VECTOR_TYPES[ctype]
    if v.ctype == ctype:
        return v
    if is_vector(v):
        raise refused(
            "vector-conversion", f"a {v.ctype} is no {ctype}; conversions "
            "between vector types (convert_T, as_T) are not supported", line)
    return cg.KVal(jnp.broadcast_to(_plane(ctx, v, elem), (n,) + ctx.shape), ctype)


def literal(ctx, node: VecLit):
    elem, n = VECTOR_TYPES[node.ctype]
    args = [cg._eval(ctx, a) for a in node.args]
    if any(is_vector(a) for a in args):
        raise refused(
            "vector-literal", f"a {node.ctype} is written from scalars; a "
            "vector among them is not supported", node.line)
    if len(args) == 1:
        return splat(ctx, args[0], node.ctype, node.line)
    return cg.KVal(jnp.stack([_plane(ctx, a, elem) for a in args]), node.ctype)


def member(ctx, node: Member):
    """``e.x`` of an expression that is no plain local."""
    v = cg._eval(ctx, node.operand)
    if not is_vector(v):
        raise refused("vector-member", f"a {v.ctype} has no components",
                      node.line)
    elem, n = VECTOR_TYPES[v.ctype]
    if node.comp >= n:
        raise refused("vector-member", f"component {node.comp} of a "
                      f"{v.ctype}", node.line)
    return cg.KVal(v.value[node.comp], elem)


_ARITH = {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply}


def binop(ctx, op: str, a, b, line: int = 0):
    """``+ - * /`` componentwise; a scalar operand is converted to the
    element type and broadcast."""
    if op in ("==", "!=", "<", ">", "<=", ">="):
        raise refused(
            "vector-comparison", f"{op} on vectors gives a vector of masks, "
            "which is not supported; compare components", line)
    if op not in ("+", "-", "*", "/"):
        raise refused(
            "vector-operator", f"{op} on vectors is not supported (+ - * / "
            "and unary minus are)", line)
    vtype = a.ctype if is_vector(a) else b.ctype
    elem, _n = VECTOR_TYPES[vtype]
    av, bv = (splat(ctx, x, vtype, line).value for x in (a, b))
    if op != "/":
        return cg.KVal(_ARITH[op](av, bv), vtype)
    if elem in cg._FLOAT_TYPES:
        return cg.KVal(av / bv, vtype)
    return cg.KVal(lax.div(av, bv), vtype)  # C's truncating division


def negate(v):
    return cg.KVal(-v.value, v.ctype)


# -- locals --------------------------------------------------------------


def bind(ctx, name: str, ctype: str, value) -> None:
    """Make ``name`` a vector local holding ``value`` (``[N, B]``)."""
    elem, n = VECTOR_TYPES[ctype]
    ctx.private[name] = n
    ctx.vectors[name] = ctype
    ctx.env[name] = cg.KVal(value, elem)


def declare(ctx, node) -> None:
    """``float4 v;`` / ``float4 v = e;`` (a ``Decl`` of a vector type)."""
    elem, n = VECTOR_TYPES[node.ctype]
    for name, init in node.names:
        if init is None:
            value = jnp.zeros((n,) + ctx.shape, cg.ctype_to_dtype(elem))
        else:
            value = splat(ctx, cg._eval(ctx, init), node.ctype, node.line).value
        bind(ctx, name, node.ctype, value)


def read_local(ctx, name: str):
    return cg.KVal(ctx.env[name].value, ctx.vectors[name])


def assign_local(ctx, name: str, rhs, line: int = 0) -> None:
    """``v = e`` under the masks in place, as a scalar local is merged
    (``codegen._assign``): the lanes' mask broadcasts over the planes."""
    vtype = ctx.vectors[name]
    new = splat(ctx, rhs, vtype, line).value
    m, fr = ctx.active_mask(), ctx._freerun
    if m is not None and not (fr is not None and m is fr[0] and name in fr[1]):
        new = jnp.where(m, new, ctx.env[name].value)
    ctx.env[name] = cg.KVal(new, VECTOR_TYPES[vtype][0])


# -- memory --------------------------------------------------------------


def _note(ctx, node, store: bool, kind: str, nbytes: int = 0) -> None:
    """Record how the access site was lowered: once, however often a loop's
    passes trace it."""
    if kind == "scatter" and (id(node), store) not in ctx.access:
        ctx.scattered.append(nbytes)
    ctx.access[id(node), store] = ctx.vector_access[id(node), store] = kind


def _index(ctx, node):
    idx = cg._eval(ctx, node.index)
    if idx.ctype not in cg._INT_TYPES:
        raise KernelLanguageError("array index must be an integer", line=node.line)
    return idx


def _per_lane(ctx, idx):
    iv = cg._num(cg._as_dtype(idx, "int"))
    if not hasattr(iv, "ndim") or iv.ndim == 0:
        iv = jnp.full((ctx.B,), iv, dtype=jnp.int32)
    return iv.astype(jnp.int32)


def _row_gather(ctx, name: str, iv, n: int):
    """``[N, B]``: vector ``clip(iv)`` of every lane through the 128-wide
    rows of the flat buffer (``codegen._take_rows``'s view, kept where the
    kernel never stores to it): ONE row a lane, ``N`` picks out of it."""
    buf = ctx.bufs[name]
    rows, count = ctx.rows_view(name), buf.shape[0] // n
    bits = lax.bitcast_convert_type(rows, jnp.int32)

    def pick(ic):
        first = n * jnp.clip(ic, 0, count - 1) + cg._ROW
        g = bits.at[first >> 7].get(mode="promise_in_bounds")
        d = lax.broadcasted_iota(jnp.int32, g.shape, 1) \
            - (first & (cg._ROW - 1))[:, None]
        return jnp.stack([jnp.sum(jnp.where(d == c, g, 0), axis=1,
                                  dtype=jnp.int32) for c in range(n)])

    out = cg._by_lane_chunks(pick, iv, (n,), jnp.int32)
    return lax.bitcast_convert_type(out, rows.dtype)


def load(ctx, node):
    """``p[e]`` of a vector parameter (the module's comment has the forms)."""
    name = node.base
    buf, elem, n = ctx.bufs[name], ctx.buf_ctypes[name], ctx.widths[name]
    vtype, B = f"{elem}{n}", ctx.B
    count = buf.shape[0] // n
    idx = _index(ctx, node)

    def loaded(planes):
        return cg.KVal(cg._loaded(planes, elem).value, vtype)

    if idx.affine is not None and idx.affine[0] == 1 and count >= B:
        _note(ctx, node, False, "slice")
        c = idx.affine[1]
        start = jnp.asarray(ctx.offset + c, jnp.int32)
        if (isinstance(c, int) and c == 0) or cg._in_bounds(ctx, idx, count):
            return loaded(lax.dynamic_slice(buf, (n * start,), (n * B,)).reshape(B, n).T)
        # not proved inside the buffer: the window at the nearest start that
        # is, moved to where it was asked for with the first or last VECTOR
        # beyond the ends (what a gather's clamp reads: codegen._slice_clamped)
        inside = jnp.clip(start, 0, count - B)
        planes = lax.dynamic_slice(buf, (n * inside,), (n * B,)).reshape(B, n).T
        return loaded(cg._shift_fill(planes, start - inside, buf[:n][:, None],
                                     buf[-n:][:, None]))
    if idx.affine is not None and idx.affine[0] not in (0, 1):
        stride, u = idx.affine
        if (isinstance(stride, int) and stride >= 2 and idx.span is not None
                and 0 <= idx.span[0] and idx.span[1] < stride):
            at = cg._strided_rows(ctx, name, n * stride, False)
            if at is not None and at[2] is None:
                _note(ctx, node, False, "strided")
                view, row0, _moved = at
                col = n * jnp.asarray(u, jnp.int32)
                return loaded(lax.dynamic_slice(view, (row0, col), (B, n)).T)
    if ctx.uniform_vars and cg._expr_uniform(
            node.index, ctx.uniform_vars, ctx.lane_arrays()):
        _note(ctx, node, False, "uniform")
        at = jnp.clip(cg._lane0(cg._num(cg._as_dtype(idx, "int"))).astype(jnp.int32),
                      0, count - 1)
        one = lax.dynamic_slice(buf, (n * at,), (n,))
        return loaded(jnp.broadcast_to(one[:, None], (n, B)))
    _note(ctx, node, False, "gather")
    iv = _per_lane(ctx, idx)
    if ctx.row_gathers and buf.dtype.itemsize == 4:
        return loaded(_row_gather(ctx, name, iv, n))
    return loaded(jnp.take(buf.reshape(count, n), iv, axis=0, mode="clip").T)


def store(ctx, node, val) -> None:
    """``p[e] = v`` of a vector parameter under the masks in place."""
    name = node.base
    buf, elem, n = ctx.bufs[name], ctx.buf_ctypes[name], ctx.widths[name]
    B, count = ctx.B, buf.shape[0] // n
    v = splat(ctx, val, f"{elem}{n}", node.line).value.astype(buf.dtype)
    idx = _index(ctx, node)
    m = ctx.active_mask()
    if idx.affine is not None and idx.affine[0] == 1 and (
            (m is None and isinstance(idx.affine[1], int) and idx.affine[1] == 0)
            or cg._in_bounds(ctx, idx, count)):
        # every lane owns its vector and none lies outside the buffer: one
        # contiguous window, a select into it under a mask
        _note(ctx, node, True, "slice")
        start = jnp.asarray(n * (ctx.offset + idx.affine[1]), jnp.int32)
        flat = v.T.reshape(n * B)
        if m is not None:
            keep = jnp.broadcast_to(jnp.broadcast_to(m, (B,))[:, None], (B, n))
            flat = jnp.where(keep.reshape(n * B), flat,
                             lax.dynamic_slice(buf, (start,), (n * B,)))
        ctx.bufs[name] = lax.dynamic_update_slice(buf, flat, (start,))
    else:
        _note(ctx, node, True, "scatter", n * buf.dtype.itemsize)
        iv = _per_lane(ctx, idx)
        if m is not None:
            iv = jnp.where(m, iv, jnp.int32(count))  # dropped
        iv = jnp.where(iv < 0, jnp.int32(count), iv)
        ctx.bufs[name] = buf.reshape(count, n).at[iv].set(
            v.T, mode="drop").reshape(count * n)
    ctx.invalidate_padded(name)
    ctx.stored.add(name)
