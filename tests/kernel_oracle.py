"""Scalar oracle interpreter for the kernel language.

Executes a parsed kernel ONE WORK ITEM AT A TIME with real Python control
flow — no vectorization, no masks, no lowering tricks. This is the
semantic reference the compiled lowerings (vectorized XLA and Pallas
tiles) are differentially fuzzed against: any divergence is a compiler
bug, because per-item sequential execution IS the language's definition
(each kernel invocation describes one work item; cross-item hazards are
excluded by the test generators, as OpenCL leaves them undefined anyway).

Vector types (docs/KERNEL_LANGUAGE.md, *Vector types*): a vector local is a
numpy array of its N components in the work item's private arrays (``v.x`` is
the parser's ``v[0]``), a whole value an array of N, and ``p[e]`` of a
``__global floatN*`` parameter elements ``[N e, N e + N)`` of the flat array.

Matches the lowerings' documented edge choices: C truncating integer
division/remainder, clamped out-of-bounds loads, clamped private-array
indices, f32 arithmetic for float locals.
"""

from __future__ import annotations

import math

import numpy as np

from cekirdekler_tpu.kernel.lang import (
    Assign,
    Barrier,
    BinOp,
    Break,
    Call,
    Cast,
    Continue,
    CrementStmt,
    Decl,
    DoWhile,
    For,
    If,
    Index,
    KernelDef,
    LocalDecl,
    Member,
    Num,
    Return,
    ReturnValue,
    Ternary,
    UnOp,
    Var,
    VecLit,
    While,
    vector_of,
)

_NPT = {
    "bool": np.bool_, "char": np.int8, "uchar": np.uint8,
    "short": np.int16, "ushort": np.uint16, "int": np.int32,
    "uint": np.uint32, "long": np.int64, "ulong": np.uint64,
    "half": np.float16, "float": np.float32, "double": np.float64,
}
_INT = {"bool", "char", "uchar", "short", "ushort", "int", "uint", "long", "ulong"}


class _Return(Exception):
    pass


class _Break(Exception):
    pass


class _Continue(Exception):
    pass


_UNARY = {
    "sqrt": math.sqrt, "rsqrt": lambda x: 1.0 / math.sqrt(x),
    "cbrt": lambda x: math.copysign(abs(x) ** (1.0 / 3.0), x),
    "exp": math.exp, "exp2": lambda x: 2.0 ** x, "exp10": lambda x: 10.0 ** x,
    "log": math.log, "log2": math.log2, "log10": math.log10,
    "sin": math.sin, "cos": math.cos, "tan": math.tan,
    "asin": math.asin, "acos": math.acos, "atan": math.atan,
    "sinh": math.sinh, "cosh": math.cosh, "tanh": math.tanh,
    "asinh": math.asinh, "acosh": math.acosh, "atanh": math.atanh,
    "fabs": abs, "floor": math.floor, "ceil": math.ceil,
    "round": lambda x: float(np.round(np.float64(x))), "rint": lambda x: float(np.round(np.float64(x))),
    "trunc": math.trunc, "erf": math.erf, "erfc": math.erfc,
    "degrees": math.degrees, "radians": math.radians,
    "sign": lambda x: float(np.sign(x)),
}
_BINARY = {
    "pow": math.pow, "powr": math.pow, "atan2": math.atan2,
    "fmod": math.fmod, "remainder": math.remainder, "hypot": math.hypot,
    "copysign": math.copysign,
    "fdim": lambda a, b: max(a - b, 0.0),
    "nextafter": math.nextafter,
}


class Oracle:
    """Per-item executor: ``run(arrays, values, global_size)`` mutates the
    numpy arrays in place, looping items sequentially."""

    def __init__(self, kernel: KernelDef, local_size: int = 64):
        self.kernel = kernel
        self.local_size = local_size
        # ``__global floatN*`` parameters: name -> N
        self.widths = {p.name: vector_of(p.ctype)[1] for p in kernel.params
                       if p.is_pointer and vector_of(p.ctype)}

    def run(self, arrays: dict[str, np.ndarray], values: dict[str, float],
            global_size: int, offset: int = 0) -> None:
        body = self.kernel.body
        if not any(isinstance(s, (LocalDecl, Barrier)) for s in body):
            for i in range(offset, offset + global_size):
                self._run_item(i, arrays, values, global_size)
            return
        # WORK-GROUP COOPERATION: a ``__local`` array is one array a group,
        # zero at its start (as the lowering has it), and a barrier AT KERNEL
        # SCOPE cuts the body into phases: every work item of the group runs
        # a phase to its end before any runs the next.  A barrier inside
        # control flow is not modelled (the statement walk refuses it).
        phases: list[list] = [[]]
        for s in body:
            if isinstance(s, Barrier):
                phases.append([])
            elif not isinstance(s, LocalDecl):
                phases[-1].append(s)
        L = self.local_size
        assert offset % L == 0 and global_size % L == 0, "whole groups a launch"
        for g0 in range(offset, offset + global_size, L):
            tiles = {s.name: np.zeros(s.size, _NPT[s.ctype])
                     for s in body if isinstance(s, LocalDecl)}
            items = [self._item(i, arrays, values, global_size, tiles)
                     for i in range(g0, g0 + L)]
            for phase in phases:
                items = [st for st in items if self._run_phase(phase, st)]

    # -- one work item -------------------------------------------------------
    def _run_item(self, gid, arrays, values, gsize) -> None:
        self._run_phase(self.kernel.body, self._item(gid, arrays, values, gsize))

    def _item(self, gid, arrays, values, gsize, tiles=None) -> tuple:
        env: dict = {}
        priv: dict[str, np.ndarray] = dict(tiles or {})  # shared by the group
        ctypes: dict[str, str] = {}
        for p in self.kernel.params:
            if not p.is_pointer:
                env[p.name] = _NPT[p.ctype](values[p.name])
                ctypes[p.name] = p.ctype
        return (env, priv, ctypes, arrays, gid, gsize)

    def _run_phase(self, stmts, state) -> bool:
        """False once the work item has returned."""
        try:
            self._block(stmts, state)
        except _Return:
            return False
        return True

    def _block(self, stmts, state) -> None:
        for s in stmts:
            self._stmt(s, state)

    def _stmt(self, s, state) -> None:
        env, priv, ctypes, arrays, gid, gsize = state
        if isinstance(s, Decl) and vector_of(s.ctype):
            elem, n = vector_of(s.ctype)
            for name, init in s.names:
                v = self._expr(init, state) if init is not None else 0
                priv[name] = self._vector(v, elem, n)
                ctypes[name] = elem
        elif isinstance(s, Decl):
            for name, init in s.names:
                if name in s.arrays:
                    priv[name] = np.zeros(s.arrays[name], _NPT[s.ctype])
                    ctypes[name] = s.ctype
                else:
                    v = self._expr(init, state) if init is not None else 0
                    env[name] = _NPT[s.ctype](v)
                    ctypes[name] = s.ctype
        elif isinstance(s, Assign):
            if s.target is None:
                self._expr(s.value, state)
                return
            rhs = self._expr(s.value, state)
            if s.op != "=":
                cur = self._expr(s.target, state)
                rhs = self._binval(s.op[:-1], cur, rhs)
            self._store(s.target, rhs, state)
        elif isinstance(s, CrementStmt):
            cur = self._expr(s.target, state)
            self._store(s.target, cur + (1 if s.op == "++" else -1), state)
        elif isinstance(s, If):
            if isinstance(s.cond, Num) and s.cond.value == 1 and not s.other:
                self._block(s.then, state)
            elif self._truthy(self._expr(s.cond, state)):
                self._block(s.then, state)
            else:
                self._block(s.other, state)
        elif isinstance(s, For):
            if s.init is not None:
                self._stmt(s.init, state)
            while s.cond is None or self._truthy(self._expr(s.cond, state)):
                try:
                    self._block(s.body, state)
                except _Break:
                    break
                except _Continue:
                    pass  # C: continue still runs the step
                if s.step is not None:
                    self._stmt(s.step, state)
        elif isinstance(s, While):
            while self._truthy(self._expr(s.cond, state)):
                try:
                    self._block(s.body, state)
                except _Break:
                    break
                except _Continue:
                    continue
        elif isinstance(s, DoWhile):
            while True:
                try:
                    self._block(s.body, state)
                except _Break:
                    break
                except _Continue:
                    pass
                if not self._truthy(self._expr(s.cond, state)):
                    break
        elif isinstance(s, Break):
            raise _Break()
        elif isinstance(s, Continue):
            raise _Continue()
        elif isinstance(s, Return):
            raise _Return()
        else:
            raise AssertionError(f"oracle: unhandled stmt {type(s).__name__}")

    def _store(self, target, val, state) -> None:
        env, priv, ctypes, arrays, gid, gsize = state
        if isinstance(target, Var) and target.name in priv:  # a vector local
            cur = priv[target.name]
            priv[target.name] = self._vector(val, ctypes[target.name], cur.shape[0])
            return
        if isinstance(target, Var):
            env[target.name] = _NPT[ctypes[target.name]](val)
            return
        assert isinstance(target, Index)
        idx = int(self._expr(target.index, state))
        n = self.widths.get(target.base) if target.base not in priv else None
        if n:
            arr = arrays[target.base]
            if 0 <= idx < arr.shape[0] // n:
                arr[n * idx:n * idx + n] = self._vector(val, None, n)
            return
        assert not isinstance(val, np.ndarray) or val.ndim == 0, "vector to scalar"
        if target.base in priv:
            arr = priv[target.base]
            arr[np.clip(idx, 0, arr.shape[0] - 1)] = val
        else:
            arr = arrays[target.base]
            # matches the lowering: masked scatter drops OOB; in-range writes land
            if 0 <= idx < arr.shape[0]:
                arr[idx] = val

    def _expr(self, node, state):
        env, priv, ctypes, arrays, gid, gsize = state
        if isinstance(node, Num):
            return _NPT[node.ctype](node.value)
        if isinstance(node, Var):
            if node.name in priv and node.name not in env:
                return priv[node.name].copy()  # a vector local, whole
            return env[node.name]
        if isinstance(node, VecLit):
            elem, n = vector_of(node.ctype)
            args = [self._expr(a, state) for a in node.args]
            return self._vector(args[0] if len(args) == 1 else args, elem, n)
        if isinstance(node, Member):
            return self._expr(node.operand, state)[node.comp]
        if isinstance(node, Index):
            idx = int(self._expr(node.index, state))
            n = self.widths.get(node.base) if node.base not in priv else None
            if n:
                arr = arrays[node.base]
                at = n * int(np.clip(idx, 0, arr.shape[0] // n - 1))
                return arr[at:at + n].copy()
            if node.base in priv:
                arr = priv[node.base]
            else:
                arr = arrays[node.base]
            return arr[np.clip(idx, 0, arr.shape[0] - 1)]  # clamped loads
        if isinstance(node, UnOp):
            v = self._expr(node.operand, state)
            if node.op == "+":
                return v
            if node.op == "-":
                return -v
            if node.op == "!":
                return np.bool_(not self._truthy(v))
            if node.op == "~":
                return ~np.int32(v) if not isinstance(v, np.integer) else ~v
        if isinstance(node, Ternary):
            c = self._truthy(self._expr(node.cond, state))
            return self._expr(node.then if c else node.other, state)
        if isinstance(node, Cast):
            return _NPT[node.ctype](self._expr(node.operand, state))
        if isinstance(node, BinOp):
            if node.op == "&&":
                return np.bool_(
                    self._truthy(self._expr(node.left, state))
                    and self._truthy(self._expr(node.right, state))
                )
            if node.op == "||":
                return np.bool_(
                    self._truthy(self._expr(node.left, state))
                    or self._truthy(self._expr(node.right, state))
                )
            a = self._expr(node.left, state)
            b = self._expr(node.right, state)
            return self._binval(node.op, a, b)
        if isinstance(node, Call):
            return self._call(node, state)
        raise AssertionError(f"oracle: unhandled expr {type(node).__name__}")

    @staticmethod
    def _vector(v, elem, n):
        """``v`` (a scalar for all components, ``n`` scalars, or a vector) as
        an array of ``n`` in the element type (``elem`` None: as it is)."""
        out = np.asarray(v) if elem is None else np.asarray(v, _NPT[elem])
        return np.broadcast_to(out, (n,)).copy()

    def _binval(self, op, a, b):
        if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            # componentwise; a scalar operand takes the vector's element type
            vec = a if isinstance(a, np.ndarray) else b
            av, bv = (np.broadcast_to(np.asarray(x, vec.dtype), vec.shape)
                      for x in (a, b))
            return np.array([self._binval(op, x, y) for x, y in zip(av, bv)],
                            vec.dtype)
        # promote like the lowering: float wins; ints promote to >= int32
        if isinstance(a, np.floating) or isinstance(b, np.floating):
            fa = np.float32(a) if not isinstance(a, np.float64) and not isinstance(b, np.float64) else np.float64(a)
            fb = type(fa)(b)
            if op == "+":
                return fa + fb
            if op == "-":
                return fa - fb
            if op == "*":
                return fa * fb
            if op == "/":
                return fa / fb
            if op == "%":
                return type(fa)(math.fmod(float(fa), float(fb)))
            return self._cmp(op, fa, fb)
        ia, ib = np.int64(a), np.int64(b)
        if op == "+":
            return np.int32(ia + ib)
        if op == "-":
            return np.int32(ia - ib)
        if op == "*":
            return np.int32(ia * ib)
        if op == "/":
            q = abs(ia) // abs(ib)
            return np.int32(q if (ia >= 0) == (ib >= 0) else -q)  # C trunc
        if op == "%":
            return np.int32(ia - np.int64(self._binval("/", a, b)) * ib)
        if op == "&":
            return np.int32(ia & ib)
        if op == "|":
            return np.int32(ia | ib)
        if op == "^":
            return np.int32(ia ^ ib)
        if op == "<<":
            return np.int32(ia << ib)
        if op == ">>":
            return np.int32(ia >> ib)
        return self._cmp(op, ia, ib)

    @staticmethod
    def _cmp(op, a, b):
        return np.bool_(
            {"==": a == b, "!=": a != b, "<": a < b, ">": a > b,
             "<=": a <= b, ">=": a >= b}[op]
        )

    @staticmethod
    def _truthy(v) -> bool:
        return bool(v)

    def _call(self, node: Call, state):
        env, priv, ctypes, arrays, gid, gsize = state
        name = node.name
        helpers = getattr(self.kernel, "helpers", {}) or {}
        if name in helpers:
            fdef = helpers[name]
            vals = [self._expr(a, state) for a in node.args]
            henv, hpriv, hctypes = {}, {}, {}
            for p, v in zip(fdef.params, vals):
                wide = vector_of(p.ctype)
                if wide:
                    hpriv[p.name], hctypes[p.name] = self._vector(v, *wide), wide[0]
                else:
                    henv[p.name], hctypes[p.name] = _NPT[p.ctype](v), p.ctype
            hstate = (henv, hpriv, hctypes, {}, gid, gsize)  # no buffer access
            self._block(fdef.body[:-1], hstate)
            assert isinstance(fdef.body[-1], ReturnValue)
            ret = self._expr(fdef.body[-1].value, hstate)
            wide = vector_of(fdef.ret_ctype)
            return self._vector(ret, *wide) if wide else _NPT[fdef.ret_ctype](ret)
        if name.startswith(("native_", "half_")):
            name = name.split("_", 1)[1]
        args = [self._expr(a, state) for a in node.args]
        if name == "get_global_id":
            return np.int32(gid)
        if name == "get_global_size":
            return np.int32(gsize)
        if name == "get_local_size":
            return np.int32(self.local_size)
        if name == "get_local_id":
            return np.int32(gid % self.local_size)
        if name == "get_group_id":
            return np.int32(gid // self.local_size)
        if name == "get_num_groups":
            return np.int32(gsize // self.local_size)
        if name == "get_global_offset":
            return np.int32(0)
        if name == "get_work_dim":
            return np.int32(1)
        if name in _UNARY:
            if name in ("fabs", "sign") and isinstance(args[0], np.integer):
                return abs(args[0]) if name == "fabs" else np.int32(np.sign(args[0]))
            return np.float32(_UNARY[name](float(np.float32(args[0]))))
        if name in _BINARY:
            return np.float32(_BINARY[name](float(np.float32(args[0])),
                                            float(np.float32(args[1]))))
        if name == "abs":
            return abs(args[0])
        if name in ("min", "fmin"):
            return min(args[0], args[1])
        if name in ("max", "fmax"):
            return max(args[0], args[1])
        if name == "clamp":
            return min(max(args[0], args[1]), args[2])
        if name in ("mad", "fma"):
            return np.float32(np.float32(args[0]) * np.float32(args[1]) + np.float32(args[2]))
        if name == "mix":
            a, b, w = (np.float32(x) for x in args)
            return np.float32(a + (b - a) * w)
        if name == "step":
            return np.float32(0.0 if float(args[1]) < float(args[0]) else 1.0)
        if name == "smoothstep":
            e0, e1, x = (float(x) for x in args)
            u = min(max((x - e0) / (e1 - e0), 0.0), 1.0)
            return np.float32(u * u * (3.0 - 2.0 * u))
        if name == "select":
            return args[1] if self._truthy(args[2]) else args[0]
        if name == "isnan":
            return np.bool_(math.isnan(float(args[0])))
        if name == "isinf":
            return np.bool_(math.isinf(float(args[0])))
        if name == "isfinite":
            return np.bool_(math.isfinite(float(args[0])))
        raise AssertionError(f"oracle: unknown function {node.name}")
