"""Kernel language front end: lexer + parser for the OpenCL-C-like subset.

The reference accepts raw OpenCL-C kernel strings and hands them to the GPU
driver compiler (ClProgram.cs:62-73; kernel names are regex-extracted at
ClNumberCruncher.cs:219-228).  TPUs cannot execute C, so we define the
*supported kernel contract* (SURVEY.md §7 "kernel-language surface"): a
C-like subset — ``__kernel void name(__global float* a, ...)`` functions with
scalar locals, arithmetic, comparisons, ``if``/``for``/``while`` with
``break``/``continue``, and the common math builtins — which the codegen (codegen.py) vectorizes over work
items and lowers to JAX/XLA.  A work-group's items cooperate through
``__local T name[K];`` arrays declared at kernel scope and ``barrier()``
statements (docs/KERNEL_LANGUAGE.md, *Work-group cooperation*).  The vector
types ``float2 float4 int2 int4 uint2 uint4`` are types of the language
(*Vector types* there): ``__global float4*`` parameters, locals, literals,
``v.x``; what is left of OpenCL's vectors is refused by name.  Unsupported
constructs (``__local`` parameters, atomics, pointers beyond
parameters) raise :class:`KernelLanguageError` with the offending line.

This module is the front end only: source → list of :class:`KernelDef` ASTs.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Optional

from ..errors import KernelCompileError, KernelLanguageError

__all__ = ["tokenize", "parse_kernels", "KernelDef", "Param", "extract_kernel_names",
           "VECTOR_TYPES", "vector_of", "uses_vectors", "any_node", "refused"]

# ---------------------------------------------------------------------------
# lexer
# ---------------------------------------------------------------------------

KEYWORDS = {
    "if", "else", "for", "while", "do", "return", "break", "continue",
    "int", "uint", "long", "ulong", "float", "double", "half", "bool",
    "char", "uchar", "short", "ushort", "void", "const", "unsigned",
    "__kernel", "kernel", "__global", "global", "__local", "local",
    "__constant", "constant", "__private", "private", "restrict", "volatile",
    "size_t", "true", "false",
}

_TOKEN_RE = re.compile(
    r"""
    (?P<ws>\s+)
  | (?P<comment>//[^\n]*|/\*.*?\*/)
  | (?P<num>
        0[xX][0-9a-fA-F]+[uUlL]*
      | (?:\d+\.\d*|\.\d+|\d+)(?:[eE][+-]?\d+)?[fFuUlL]*
    )
  | (?P<id>[A-Za-z_][A-Za-z0-9_]*)
  | (?P<op><<=|>>=|<<|>>|<=|>=|==|!=|&&|\|\||\+\+|--|\+=|-=|\*=|/=|%=|&=|\|=|\^=|->|[-+*/%<>=!&|^~?:.,;(){}\[\]])
    """,
    re.VERBOSE | re.DOTALL,
)


@dataclass(frozen=True)
class Token:
    kind: str  # 'num' | 'id' | 'kw' | 'op' | 'eof'
    text: str
    line: int


def _strip_preprocessor(source: str) -> tuple[str, dict[str, str]]:
    """Handle the tiny preprocessor surface kernels actually use:
    parameterless ``#define NAME value`` substitution; other directives are
    dropped with a warning-free ignore (``#pragma``) or rejected."""
    defines: dict[str, str] = {}
    out_lines: list[str] = []
    for lineno, line in enumerate(source.splitlines(), 1):
        stripped = line.strip()
        if stripped.startswith("#"):
            m = re.match(r"#\s*define\s+(\w+)(?:\s+(.*))?$", stripped)
            if m:
                if "(" in m.group(1):
                    raise KernelLanguageError(
                        "function-like macros are not supported", line=lineno
                    )
                defines[m.group(1)] = (m.group(2) or "").strip()
                out_lines.append("")  # keep line numbers stable
                continue
            if re.match(r"#\s*(pragma|include|ifdef|ifndef|endif|if|else|undef)", stripped):
                out_lines.append("")
                continue
            raise KernelLanguageError(f"unsupported preprocessor directive: {stripped}", line=lineno)
        out_lines.append(line)
    text = "\n".join(out_lines)
    # iterative substitution (defines may reference earlier defines)
    for _ in range(8):
        changed = False
        for name, val in defines.items():
            new = re.sub(rf"\b{re.escape(name)}\b", val, text)
            if new != text:
                text, changed = new, True
        if not changed:
            break
    return text, defines


def tokenize(source: str) -> list[Token]:
    text, _ = _strip_preprocessor(source)
    tokens: list[Token] = []
    pos = 0
    line = 1
    n = len(text)
    while pos < n:
        m = _TOKEN_RE.match(text, pos)
        if m is None:
            raise KernelCompileError(
                f"unexpected character {text[pos]!r}", source=source, line=line
            )
        kind = m.lastgroup
        tok_text = m.group()
        if kind in ("ws", "comment"):
            line += tok_text.count("\n")
        elif kind == "id" and tok_text in KEYWORDS:
            tokens.append(Token("kw", tok_text, line))
        else:
            tokens.append(Token(kind, tok_text, line))  # type: ignore[arg-type]
        pos = m.end()
    tokens.append(Token("eof", "", line))
    return tokens


# ---------------------------------------------------------------------------
# AST
# ---------------------------------------------------------------------------


@dataclass
class Node:
    line: int = field(default=0, kw_only=True)


# expressions
@dataclass
class Num(Node):
    value: float | int
    ctype: str  # 'int' | 'uint' | 'long' | 'float' | 'double'


@dataclass
class Var(Node):
    name: str


@dataclass
class BinOp(Node):
    op: str
    left: Any
    right: Any


@dataclass
class UnOp(Node):
    op: str  # '-', '!', '~', '+'
    operand: Any


@dataclass
class Ternary(Node):
    cond: Any
    then: Any
    other: Any


@dataclass
class Call(Node):
    name: str
    args: list


@dataclass
class Index(Node):
    base: str
    index: Any


@dataclass
class Cast(Node):
    ctype: str
    operand: Any


@dataclass
class VecLit(Node):
    """``(float4)(a, b, c, d)``, ``(float4)(s)`` and, in a declaration, the
    brace form ``{a, b, c, d}``: one scalar a component, or one for all."""

    ctype: str
    args: list


@dataclass
class Member(Node):
    """``e.x`` of a vector-valued expression that is no plain local (an
    element ``p[i].x``, a helper's result): one component, read only.  A
    local's ``v.x`` is no node of its own: the parser writes it
    ``Index(base="v", index=Num(0))``, the local being ``N`` scalars a work
    item as a private array is (docs/KERNEL_LANGUAGE.md, *Vector types*)."""

    operand: Any
    comp: int


# statements
@dataclass
class Decl(Node):
    ctype: str
    names: list[tuple[str, Any | None]]  # (name, init-expr or None)
    # private fixed-size arrays declared in this statement: name -> length
    # (``float acc[4];`` — OpenCL __private memory, ClArray.cs kernels use
    # these for per-work-item scratch)
    arrays: dict = field(default_factory=dict)


@dataclass
class LocalDecl(Node):
    """``__local T name[K];`` at kernel scope: one array of ``K`` elements a
    work-group, shared by its work items (OpenCL __local memory)."""

    ctype: str
    name: str
    size: int


@dataclass
class Barrier(Node):
    """``barrier(flags);`` / ``work_group_barrier(flags);`` — a statement;
    ``flags`` are the fence names it was given (``CLK_LOCAL_MEM_FENCE``)."""

    flags: tuple = ()


@dataclass
class Assign(Node):
    target: Any  # Var or Index
    op: str  # '=', '+=', '-=', '*=', '/=', '%=', '&=', '|=', '^=', '<<=', '>>='
    value: Any


@dataclass
class CrementStmt(Node):
    target: Any  # Var or Index
    op: str  # '++' or '--'


@dataclass
class If(Node):
    cond: Any
    then: list
    other: list


@dataclass
class For(Node):
    init: Any | None  # Decl or Assign
    cond: Any | None
    step: Any | None  # Assign or CrementStmt
    body: list


@dataclass
class While(Node):
    cond: Any
    body: list


@dataclass
class DoWhile(Node):
    """``do { body } while (cond);`` — body runs once unconditionally,
    then loops while cond holds (lowered as body + While)."""

    cond: Any
    body: list


@dataclass
class Return(Node):
    pass


@dataclass
class ReturnValue(Node):
    """``return expr;`` — only valid as the LAST statement of a helper."""

    value: Any = None


@dataclass
class Break(Node):
    pass


@dataclass
class Continue(Node):
    pass


@dataclass
class Param(Node):
    ctype: str        # element type for pointers, value type otherwise
    name: str
    is_pointer: bool = True
    address_space: str = "global"  # 'global' | 'constant' | 'value'
    is_const: bool = False


@dataclass
class FuncDef(Node):
    """A non-kernel helper function (scalar params, scalar return);
    inlined at call sites by the codegen."""

    name: str
    ret_ctype: str = "float"
    params: list[Param] = field(default_factory=list)
    body: list = field(default_factory=list)


@dataclass
class KernelDef(Node):
    name: str
    params: list[Param] = field(default_factory=list)
    body: list = field(default_factory=list)
    source: str = ""
    # helper functions defined in the same source, by name (inlined at
    # call sites — the concept behind the reference's unimplemented
    # ClBuiltInAuxilliaryFunctions, ClBuiltInAuxilliaryFunctions.cs:27-46)
    helpers: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# parser (recursive descent)
# ---------------------------------------------------------------------------

_TYPE_KWS = {
    "int", "uint", "long", "ulong", "float", "double", "half", "bool",
    "char", "uchar", "short", "ushort", "size_t", "void", "unsigned",
}

_ASSIGN_OPS = {"=", "+=", "-=", "*=", "/=", "%=", "&=", "|=", "^=", "<<=", ">>="}

# ---------------------------------------------------------------------------
# vector types (docs/KERNEL_LANGUAGE.md, *Vector types*).  The core: six
# types, as ``__global T*`` parameters, locals and helper parameters; one
# component read or written at a time (``v.x``, ``v.s2``); everything else
# of OpenCL's vectors is refused by NAME (the first word of the message).
# ---------------------------------------------------------------------------

#: vector type -> (element type, components)
VECTOR_TYPES = {f"{t}{n}": (t, n) for t in ("float", "int", "uint") for n in (2, 4)}
_VECTOR_NAME = re.compile(
    r"^(char|uchar|short|ushort|int|uint|long|ulong|float|double|half)(2|3|4|8|16)$")
_COMPONENTS = {"x": 0, "y": 1, "z": 2, "w": 3}


def vector_of(ctype: str):
    """``(element type, components)`` of a vector type, None for a scalar."""
    return VECTOR_TYPES.get(ctype)


def refused(name: str, what: str, line: int = 0) -> KernelLanguageError:
    """A vector construct outside the core, refused by NAME: the message's
    first word."""
    return KernelLanguageError(f"{name}: {what}", line=line)


def component_of(member: str, width: int, line: int) -> int:
    """Which component ``.member`` names in a vector of ``width``."""
    if member in _COMPONENTS:
        k = _COMPONENTS[member]
    elif re.fullmatch(r"[sS][0-9a-fA-F]", member):
        k = int(member[1], 16)
    elif (member in ("lo", "hi", "even", "odd")
          or re.fullmatch(r"[xyzw]{2,4}|[sS][0-9a-fA-F]{2,}", member)):
        raise refused(
            "vector-swizzle", f".{member} names several components; read and "
            "write one at a time (.x .y .z .w, .s0 .. .s3)", line)
    else:
        raise refused("vector-member", f"a vector has no member .{member} "
                       "(.x .y .z .w, .s0 .. .s3)", line)
    if k >= width:
        raise refused("vector-member", f".{member} is component {k} and the "
                       f"vector has {width}", line)
    return k


def any_node(kernel, test) -> bool:
    """Does ``test(node)`` hold for any syntax-tree node of ``kernel``: its
    parameters, its body, the helpers it can call?"""
    seen: set[int] = set()

    def walk(node) -> bool:
        if node is None or isinstance(node, (str, int, float, bool)) \
                or id(node) in seen:
            return False
        seen.add(id(node))
        if isinstance(node, (list, tuple)):
            return any(walk(x) for x in node)
        if isinstance(node, dict):
            return any(walk(x) for x in node.values())
        return hasattr(node, "__dict__") and (test(node) or any(
            walk(v) for k, v in vars(node).items() if k != "source"))

    return walk(kernel.params) or walk(kernel.body) or walk(
        getattr(kernel, "helpers", None))


def uses_vectors(kernel) -> bool:
    """Does ``kernel`` name a vector type anywhere?"""
    return any_node(kernel, lambda node: isinstance(node, (VecLit, Member)) or any(
        getattr(node, k, None) in VECTOR_TYPES for k in ("ctype", "ret_ctype")))


class _Parser:
    def __init__(self, tokens: list[Token], source: str):
        self.toks = tokens
        self.i = 0
        self.source = source
        self._loop_depth = 0  # break/continue outside a loop = parse error
        self._in_helper = False  # `return expr;` only valid in helpers
        # the function being parsed: its vector-typed locals and value
        # parameters, name -> type (``v.x`` needs the width where it stands)
        self._vectors: dict[str, str] = {}

    # -- token helpers ------------------------------------------------------
    @property
    def cur(self) -> Token:
        return self.toks[self.i]

    def peek(self, k: int = 1) -> Token:
        j = min(self.i + k, len(self.toks) - 1)
        return self.toks[j]

    def advance(self) -> Token:
        t = self.cur
        if t.kind != "eof":
            self.i += 1
        return t

    def expect(self, text: str) -> Token:
        t = self.cur
        if t.text != text:
            raise KernelCompileError(
                f"expected {text!r}, found {t.text!r}", source=self.source, line=t.line
            )
        return self.advance()

    def accept(self, text: str) -> bool:
        if self.cur.text == text:
            self.advance()
            return True
        return False

    def err(self, msg: str, line: int | None = None) -> KernelCompileError:
        return KernelCompileError(msg, source=self.source, line=line or self.cur.line)

    # -- types --------------------------------------------------------------
    def at_type(self, tok: Token | None = None) -> bool:
        t = tok or self.cur
        return (t.kind == "kw" and t.text in _TYPE_KWS) or (
            t.kind == "id" and _VECTOR_NAME.match(t.text) is not None)

    def at_decl(self) -> bool:
        """Does a declaration start here: ``const``, a scalar type's keyword,
        or a vector type's name ahead of the variable's?"""
        return self.cur.text == "const" or (self.at_type() and (
            self.cur.kind == "kw" or self.peek().kind == "id"))

    def parse_type(self) -> str:
        parts = []
        while self.cur.kind == "kw" and self.cur.text in (_TYPE_KWS | {"const"}):
            if self.cur.text != "const":
                parts.append(self.cur.text)
            self.advance()
        if not parts and self.cur.kind == "id" and _VECTOR_NAME.match(self.cur.text):
            t = self.advance()
            while self.accept("const"):
                pass
            if t.text not in VECTOR_TYPES:
                raise refused(
                    "vector-width", f"{t.text} is not supported; the vector "
                    f"types are {' '.join(sorted(VECTOR_TYPES))}", t.line)
            return t.text
        if not parts:
            raise self.err("expected a type")
        t = " ".join(parts)
        norm = {
            "unsigned int": "uint", "unsigned long": "ulong", "unsigned char": "uchar",
            "unsigned short": "ushort", "unsigned": "uint", "size_t": "long",
        }
        return norm.get(t, t)

    def parse_helper(self, start: Token) -> FuncDef:
        """A non-kernel function: scalar params, scalar return, inlined at
        call sites.  Exactly one ``return expr;`` — the last statement."""
        self._vectors = {}
        ret = self.parse_type()
        if ret == "void":
            raise KernelLanguageError(
                "helper functions must return a value (kernels are the "
                "only void functions)", line=start.line,
            )
        name_tok = self.advance()
        if name_tok.kind != "id":
            raise self.err(f"expected function name, found {name_tok.text!r}", name_tok.line)
        params = self.parse_params()
        for p in params:
            if p.is_pointer:
                raise KernelLanguageError(
                    f"helper {name_tok.text!r}: pointer parameters are not "
                    "supported — pass array elements by value", line=start.line,
                )
        self.expect("{")
        saved_h, saved_d = self._in_helper, self._loop_depth
        self._in_helper, self._loop_depth = True, 0
        try:
            body = self.parse_block_items()
        finally:
            self._in_helper, self._loop_depth = saved_h, saved_d
        self.expect("}")

        def count_returns(stmts) -> int:
            n = 0
            for st in stmts:
                if isinstance(st, ReturnValue):
                    n += 1
                elif isinstance(st, If):
                    n += count_returns(st.then) + count_returns(st.other)
                elif isinstance(st, For):
                    n += count_returns(st.body)
                elif isinstance(st, (While, DoWhile)):
                    n += count_returns(st.body)
            return n

        if count_returns(body) != 1 or not body or not isinstance(body[-1], ReturnValue):
            raise KernelLanguageError(
                f"helper {name_tok.text!r} must have exactly one 'return "
                "expr;' as its final statement (early returns: use a local "
                "and an if-guard)", line=start.line,
            )
        return FuncDef(name=name_tok.text, ret_ctype=ret, params=params,
                       body=body, line=start.line)

    # -- top level ----------------------------------------------------------
    def parse_program(self) -> list[KernelDef]:
        """Helpers are PROGRAM-scoped by design: every KernelDef shares the
        one helpers dict, so a kernel may call a helper defined textually
        after it (and helpers may call each other regardless of order).
        This diverges from C's declaration-before-use rule — deliberately:
        helper bodies are inlined at call sites during lowering, so textual
        order carries no semantic weight here, and requiring forward
        declarations would add C ceremony with no behavioral payoff.
        Documented in docs/KERNEL_LANGUAGE.md (helper functions)."""
        kernels: list[KernelDef] = []
        helpers: dict = {}
        while self.cur.kind != "eof":
            start = self.cur
            is_kernel = False
            while self.cur.kind == "kw" and self.cur.text in ("__kernel", "kernel"):
                is_kernel = True
                self.advance()
            if not is_kernel:
                helpers_def = self.parse_helper(start)
                if helpers_def.name in helpers:
                    raise KernelLanguageError(
                        f"helper {helpers_def.name!r} redefined",
                        line=helpers_def.line,
                    )
                helpers[helpers_def.name] = helpers_def
                continue
            ret = self.parse_type()
            if ret != "void":
                raise KernelLanguageError(
                    f"kernels must return void, not {ret}", line=start.line
                )
            name_tok = self.advance()
            if name_tok.kind != "id":
                raise self.err(f"expected kernel name, found {name_tok.text!r}", name_tok.line)
            self._vectors = {}
            params = self.parse_params()
            for p in params:
                if not p.is_pointer and p.ctype in VECTOR_TYPES:
                    raise refused(
                        "vector-value-parameter", f"{p.ctype} {p.name}: a "
                        "kernel takes a vector through a __global pointer, "
                        "or its components as scalars", p.line)
            self.expect("{")
            body = self.parse_block_items()
            self.expect("}")
            _local_decls_at_kernel_scope(body)
            kernels.append(
                KernelDef(name=name_tok.text, params=params, body=body,
                          source=self.source, helpers=helpers, line=start.line)
            )
        if not kernels:
            raise self.err("no __kernel functions found in source")
        return kernels

    def parse_params(self) -> list[Param]:
        self.expect("(")
        params: list[Param] = []
        if self.accept(")"):
            return params
        while True:
            line = self.cur.line
            space = "value"
            is_const = False
            while self.cur.kind == "kw" and self.cur.text in (
                "__global", "global", "__constant", "constant", "__local", "local",
                "__private", "private", "const", "restrict", "volatile",
            ):
                t = self.advance().text
                if t in ("__global", "global"):
                    space = "global"
                elif t in ("__constant", "constant"):
                    space = "constant"
                elif t in ("__local", "local"):
                    raise KernelLanguageError(
                        "__local parameters are not supported (every pointer "
                        "parameter binds to an array of the caller's): declare "
                        "it inside the kernel, `__local float tile[256];`",
                        line=line,
                    )
                elif t == "const":
                    is_const = True
            ctype = self.parse_type()
            is_pointer = self.accept("*")
            while self.cur.kind == "kw" and self.cur.text in ("const", "restrict", "volatile"):
                self.advance()
            name_tok = self.advance()
            if name_tok.kind != "id":
                raise self.err(f"expected parameter name, found {name_tok.text!r}", name_tok.line)
            if is_pointer and space == "value":
                space = "global"
            if ctype in VECTOR_TYPES and not is_pointer:
                self._vectors[name_tok.text] = ctype
            else:
                self._vectors.pop(name_tok.text, None)
            params.append(
                Param(ctype=ctype, name=name_tok.text, is_pointer=is_pointer,
                      address_space=space if is_pointer else "value",
                      is_const=is_const, line=line)
            )
            if self.accept(")"):
                return params
            self.expect(",")

    # -- statements ---------------------------------------------------------
    def parse_block_items(self) -> list:
        items = []
        while self.cur.text != "}" and self.cur.kind != "eof":
            items.append(self.parse_statement())
        return items

    def parse_statement(self):
        t = self.cur
        if t.text == "{":
            self.advance()
            body = self.parse_block_items()
            self.expect("}")
            return If(cond=Num(value=1, ctype="int", line=t.line), then=body, other=[], line=t.line)
        if t.kind == "kw":
            if t.text == "if":
                return self.parse_if()
            if t.text == "for":
                return self.parse_for()
            if t.text == "while":
                return self.parse_while()
            if t.text == "do":
                return self.parse_do()
            if t.text == "return":
                self.advance()
                if self._in_helper:
                    expr = self.parse_expr()
                    self.expect(";")
                    return ReturnValue(value=expr, line=t.line)
                if not self.accept(";"):
                    raise KernelLanguageError("kernels are void; 'return value;' unsupported", line=t.line)
                return Return(line=t.line)
            if t.text == "break" or t.text == "continue":
                if self._loop_depth == 0:
                    raise KernelLanguageError(
                        f"'{t.text}' outside a loop", line=t.line
                    )
                self.advance()
                self.expect(";")
                return (Break if t.text == "break" else Continue)(line=t.line)
            if t.text in ("__local", "local"):
                return self.parse_local_decl()
        if self.at_decl():
            return self.parse_decl()
        stmt = self.parse_expr_statement()
        self.expect(";")
        return stmt

    def parse_decl(self) -> Decl:
        line = self.cur.line
        while self.accept("const"):
            pass
        ctype = self.parse_type()
        if self.cur.text == "*":
            raise KernelLanguageError("local pointer variables are not supported", line=line)
        names: list[tuple[str, Any | None]] = []
        arrays: dict = {}
        while True:
            name_tok = self.advance()
            if name_tok.kind != "id":
                raise self.err(f"expected variable name, found {name_tok.text!r}", name_tok.line)
            init = None
            self._vectors.pop(name_tok.text, None)  # a scalar of that name now
            if ctype in VECTOR_TYPES:
                if self.cur.text == "[":
                    raise refused(
                        "vector-array", f"an array of {ctype} is not "
                        "supported; declare the vectors one by one",
                        name_tok.line)
                self._vectors[name_tok.text] = ctype
                if self.accept("="):
                    init = (self.parse_braces(ctype) if self.cur.text == "{"
                            else self.parse_expr())
            elif self.accept("["):
                size_tok = self.advance()
                if size_tok.kind != "num" or not size_tok.text.isdigit():
                    raise KernelLanguageError(
                        "private array size must be an integer literal",
                        line=size_tok.line,
                    )
                self.expect("]")
                size = int(size_tok.text)
                if size <= 0:
                    raise KernelLanguageError(
                        "private array size must be positive", line=size_tok.line
                    )
                if self.cur.text == "=":
                    raise KernelLanguageError(
                        "private array initializers are not supported; assign "
                        "elements explicitly", line=size_tok.line,
                    )
                arrays[name_tok.text] = size
            elif self.accept("="):
                init = self.parse_expr()
            names.append((name_tok.text, init))
            if self.accept(";"):
                break
            self.expect(",")
        return Decl(ctype=ctype, names=names, arrays=arrays, line=line)

    def parse_braces(self, ctype: str) -> VecLit:
        """``{a, b, c, d}`` behind ``float4 v =``."""
        line = self.expect("{").line
        return self._vec_lit(ctype, self._scalars_until("}"), line)

    def _scalars_until(self, close: str) -> list:
        args = [self.parse_expr()]
        while self.accept(","):
            args.append(self.parse_expr())
        self.expect(close)
        return args

    def _vec_lit(self, ctype: str, args: list, line: int) -> VecLit:
        n = VECTOR_TYPES[ctype][1]
        if len(args) not in (1, n):
            raise refused(
                "vector-literal", f"a {ctype} is written from {n} scalars or "
                f"from one for all, not {len(args)} (a vector among them is "
                "not supported)", line)
        return VecLit(ctype=ctype, args=args, line=line)

    def parse_local_decl(self) -> LocalDecl:
        """``__local T name[K];`` (``K`` a literal, or a ``#define``, which
        is one by now): kernel scope is checked once the body is whole."""
        line = self.advance().line
        if self._in_helper:
            raise KernelLanguageError(
                "__local arrays belong to a kernel, not a helper function",
                line=line)
        while self.accept("const") or self.accept("volatile"):
            pass
        ctype = self.parse_type()
        if ctype in VECTOR_TYPES:
            raise refused(
                "vector-local-memory", f"a __local array of {ctype} is not "
                "supported; keep a tile a component", line)
        if self.cur.text == "*":
            raise KernelLanguageError(
                "a __local array indexed through a pointer is not supported; "
                "index the array by its name", line=line)
        name_tok = self.advance()
        if name_tok.kind != "id":
            raise self.err(f"expected array name, found {name_tok.text!r}", name_tok.line)
        if not self.accept("["):
            raise KernelLanguageError(
                f"__local {name_tok.text!r} must be an array, "
                "`__local float tile[256];`", line=line)
        size_tok = self.advance()
        if size_tok.kind != "num" or not size_tok.text.isdigit() \
                or int(size_tok.text) <= 0:
            raise KernelLanguageError(
                "__local array size must be a positive integer literal "
                "(or a #define of one)", line=size_tok.line)
        self.expect("]")
        if self.cur.text == "[":
            raise KernelLanguageError(
                "__local arrays have one dimension", line=line)
        self.expect(";")
        return LocalDecl(ctype=ctype, name=name_tok.text,
                         size=int(size_tok.text), line=line)

    def parse_expr_statement(self):
        """assignment / compound assignment / ++ / -- / bare call"""
        line = self.cur.line
        lhs = self.parse_unary_postfixless()
        t = self.cur.text
        if t in _ASSIGN_OPS or t in ("++", "--"):
            if isinstance(lhs, Member):
                raise refused(
                    "vector-member-store", "one component of an element in "
                    "memory cannot be written alone; read the vector, set "
                    "the component, store the vector (v = p[i]; v.x = ..; "
                    "p[i] = v;)", line)
        if t in _ASSIGN_OPS:
            self.advance()
            value = self.parse_expr()
            if not isinstance(lhs, (Var, Index)):
                raise self.err("invalid assignment target", line)
            return Assign(target=lhs, op=t, value=value, line=line)
        if t in ("++", "--"):
            self.advance()
            if not isinstance(lhs, (Var, Index)):
                raise self.err("invalid ++/-- target", line)
            return CrementStmt(target=lhs, op=t, line=line)
        # bare expression statement (e.g. a call) — only calls are meaningful
        if isinstance(lhs, Call):
            if lhs.name in BARRIER_CALLS:
                return Barrier(flags=_fence_flags(lhs), line=line)
            return Assign(target=None, op="expr", value=lhs, line=line)
        raise self.err(f"expression statement has no effect (near {t!r})", line)

    def parse_if(self) -> If:
        line = self.expect("if").line
        self.expect("(")
        cond = self.parse_expr()
        self.expect(")")
        then = self._stmt_as_block()
        other: list = []
        if self.accept("else"):
            other = self._stmt_as_block()
        return If(cond=cond, then=then, other=other, line=line)

    def _stmt_as_block(self) -> list:
        if self.accept("{"):
            body = self.parse_block_items()
            self.expect("}")
            return body
        return [self.parse_statement()]

    def parse_for(self) -> For:
        line = self.expect("for").line
        self.expect("(")
        init = None
        if not self.accept(";"):
            if self.at_decl():
                init = self.parse_decl()  # consumes ';'
            else:
                init = self.parse_expr_statement()
                self.expect(";")
        cond = None
        if not self.accept(";"):
            cond = self.parse_expr()
            self.expect(";")
        step = None
        if self.cur.text != ")":
            step = self.parse_expr_statement()
        self.expect(")")
        self._loop_depth += 1
        try:
            body = self._stmt_as_block()
        finally:
            self._loop_depth -= 1
        return For(init=init, cond=cond, step=step, body=body, line=line)

    def parse_while(self) -> While:
        line = self.expect("while").line
        self.expect("(")
        cond = self.parse_expr()
        self.expect(")")
        self._loop_depth += 1
        try:
            body = self._stmt_as_block()
        finally:
            self._loop_depth -= 1
        return While(cond=cond, body=body, line=line)

    def parse_do(self) -> DoWhile:
        line = self.expect("do").line
        self._loop_depth += 1
        try:
            body = self._stmt_as_block()
        finally:
            self._loop_depth -= 1
        self.expect("while")
        self.expect("(")
        cond = self.parse_expr()
        self.expect(")")
        self.expect(";")
        return DoWhile(cond=cond, body=body, line=line)

    # -- expressions (precedence climbing) ----------------------------------
    def parse_expr(self):
        return self.parse_ternary()

    def parse_ternary(self):
        cond = self.parse_binary(0)
        if self.accept("?"):
            then = self.parse_expr()
            self.expect(":")
            other = self.parse_ternary()
            return Ternary(cond=cond, then=then, other=other, line=cond.line)
        return cond

    _PREC = [
        ("||",),
        ("&&",),
        ("|",),
        ("^",),
        ("&",),
        ("==", "!="),
        ("<", ">", "<=", ">="),
        ("<<", ">>"),
        ("+", "-"),
        ("*", "/", "%"),
    ]

    def parse_binary(self, level: int):
        if level >= len(self._PREC):
            return self.parse_unary()
        lhs = self.parse_binary(level + 1)
        while self.cur.text in self._PREC[level] and self.cur.kind == "op":
            op = self.advance().text
            rhs = self.parse_binary(level + 1)
            lhs = BinOp(op=op, left=lhs, right=rhs, line=lhs.line)
        return lhs

    def parse_unary(self):
        t = self.cur
        if t.text in ("-", "!", "~", "+") and t.kind == "op":
            self.advance()
            return UnOp(op=t.text, operand=self.parse_unary(), line=t.line)
        if t.text in ("++", "--"):
            raise KernelLanguageError(
                "prefix ++/-- in expressions is not supported; use a statement", line=t.line
            )
        if t.text == "(" and self.at_type(self.peek()) and (
                self.peek().kind == "kw" or self.peek(2).text == ")"):
            # cast
            self.advance()
            ctype = self.parse_type()
            self.expect(")")
            if ctype in VECTOR_TYPES:
                # ``(float4)(a, b, c, d)`` / ``(float4)(s)``: a literal
                if self.cur.text != "(":
                    raise refused(
                        "vector-conversion", f"({ctype}) of a value is not "
                        f"supported; write the literal ({ctype})(a, b, ..)",
                        t.line)
                self.advance()
                return self._postfix_of(
                    self._vec_lit(ctype, self._scalars_until(")"), t.line))
            return Cast(ctype=ctype, operand=self.parse_unary(), line=t.line)
        return self.parse_postfix()

    def parse_unary_postfixless(self):
        """like parse_unary but used at statement heads (no cast ambiguity)"""
        return self.parse_unary()

    def parse_postfix(self):
        return self._postfix_of(self.parse_primary())

    def _postfix_of(self, expr):
        while True:
            t = self.cur
            if t.text == "[":
                self.advance()
                idx = self.parse_expr()
                self.expect("]")
                if not isinstance(expr, Var):
                    raise KernelLanguageError(
                        "only direct parameter arrays can be indexed", line=t.line
                    )
                expr = Index(base=expr.name, index=idx, line=t.line)
            elif t.text in ("++", "--"):
                # postfix on expression position — only valid as a statement;
                # leave for parse_expr_statement by stopping here
                break
            elif t.text == ".":
                self.advance()
                name = self.advance()
                if name.kind != "id":
                    raise self.err(f"expected a member name, found {name.text!r}",
                                   name.line)
                if isinstance(expr, Var):
                    # a local's component is element k of its N scalars
                    if expr.name not in self._vectors:
                        raise refused(
                            "vector-member", f"{expr.name!r} is no vector "
                            "local (structs are not supported)", t.line)
                    width = VECTOR_TYPES[self._vectors[expr.name]][1]
                    k = component_of(name.text, width, name.line)
                    expr = Index(base=expr.name,
                                 index=Num(value=k, ctype="int", line=t.line),
                                 line=t.line)
                else:
                    # the width is the expression's: known where it is lowered
                    expr = Member(operand=expr,
                                  comp=component_of(name.text, 16, name.line),
                                  line=t.line)
            else:
                break
        return expr

    def parse_primary(self):
        t = self.cur
        if t.kind == "num":
            self.advance()
            return _parse_num(t)
        if t.kind == "kw" and t.text in ("true", "false"):
            self.advance()
            return Num(value=1 if t.text == "true" else 0, ctype="int", line=t.line)
        if t.kind == "id":
            name = self.advance().text
            if self.cur.text == "(":
                self.advance()
                args = []
                if not self.accept(")"):
                    while True:
                        args.append(self.parse_expr())
                        if self.accept(")"):
                            break
                        self.expect(",")
                return Call(name=name, args=args, line=t.line)
            return Var(name=name, line=t.line)
        if t.text == "(":
            self.advance()
            e = self.parse_expr()
            self.expect(")")
            return e
        raise self.err(f"unexpected token {t.text!r}")


BARRIER_CALLS = ("barrier", "work_group_barrier")
_FENCE_FLAGS = ("CLK_LOCAL_MEM_FENCE", "CLK_GLOBAL_MEM_FENCE")


def _fence_flags(call: Call) -> tuple:
    """The fence names of a ``barrier(...)`` call: ``CLK_LOCAL_MEM_FENCE``,
    ``CLK_GLOBAL_MEM_FENCE``, both joined by ``|``, or a literal (``0``)."""
    if len(call.args) != 1:
        raise KernelLanguageError(
            f"{call.name} takes one argument, the fence flags", line=call.line)
    flags: list = []

    def walk(e) -> None:
        if isinstance(e, BinOp) and e.op == "|":
            walk(e.left)
            walk(e.right)
        elif isinstance(e, Var) and e.name in _FENCE_FLAGS:
            flags.append(e.name)
        elif not isinstance(e, Num):
            raise KernelLanguageError(
                f"{call.name}: the flags are CLK_LOCAL_MEM_FENCE and / or "
                "CLK_GLOBAL_MEM_FENCE", line=call.line)

    walk(call.args[0])
    return tuple(flags)


def _local_decls_at_kernel_scope(body: list) -> None:
    """A ``__local`` array is the work-group's for the whole kernel: declared
    in a branch or a loop it would be another array a pass."""
    def nested(stmts) -> None:
        for s in stmts:
            if isinstance(s, LocalDecl):
                raise KernelLanguageError(
                    f"__local array {s.name!r} must be declared at kernel "
                    "scope, not inside a branch or a loop", line=s.line)
            inner(s)

    def inner(s) -> None:
        if isinstance(s, If):
            nested(s.then)
            nested(s.other)
        elif isinstance(s, (For, While, DoWhile)):
            nested(s.body)

    for s in body:
        inner(s)


def _parse_num(t: Token) -> Num:
    s = t.text
    suffix = ""
    while s and s[-1] in "fFuUlL":
        suffix += s[-1].lower()
        s = s[:-1]
    if s.startswith(("0x", "0X")):
        val: float | int = int(s, 16)
        ctype = "long" if "l" in suffix else ("uint" if "u" in suffix else "int")
    elif "." in s or "e" in s or "E" in s:
        val = float(s)
        ctype = "float" if "f" in suffix else "double"
    else:
        val = int(s)
        if "f" in suffix:
            val = float(val)
            ctype = "float"
        else:
            ctype = "long" if "l" in suffix else ("uint" if "u" in suffix else "int")
    return Num(value=val, ctype=ctype, line=t.line)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def parse_kernels(source: str) -> list[KernelDef]:
    """Parse a kernel source string into kernel ASTs."""
    return _Parser(tokenize(source), source).parse_program()


_KERNEL_NAME_RE = re.compile(r"(?:__kernel|kernel)\s+void\s+([A-Za-z_][A-Za-z0-9_]*)")


def extract_kernel_names(source: str) -> list[str]:
    """Fast regex name extraction (reference: ClNumberCruncher.cs:219-228)."""
    return _KERNEL_NAME_RE.findall(source)
