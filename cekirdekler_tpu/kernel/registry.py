"""Kernel program registry: parse once, JIT per launch geometry, cache.

Mirrors the reference's compile pipeline — ``ClProgram`` builds the source
per device and ``ClKernel``/``kernelWithId`` clone kernel objects per
(name, computeId) so the same kernel can run concurrently with different
arguments (Worker.cs:263-316).  Here, parsing happens once per source
string; the vectorized launch function is built and jitted once per
(kernel name, chunk size, local size, global size) and XLA's own cache
handles distinct buffer shapes/dtypes.  The balancer changing per-chip
ranges only changes the runtime ``offset`` argument — no recompilation
(chunk sizes are bucketed by the scheduler, core/cores.py).

Also provides the ``@kernel`` decorator path: a user Python function
``f(gid, *arrays, **values)`` written directly in JAX — the escape hatch for
kernels outside the C-subset contract (and the idiomatic TPU path; raw
Pallas kernels plug in the same way via ops/).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import KernelCompileError
from ..trace.spans import TRACER
from . import codegen, lang

__all__ = ["KernelProgram", "kernel", "PythonKernel", "lowering_meta"]


class _Launcher:
    """A jitted launch function as the launcher cache holds it: calls go
    straight through once it has run, and until then sit under a
    ``compile`` span tagged ``<kernel> <shape key>`` — ``jax.jit`` traces
    and compiles at the first call, not when the launcher is built, and
    a rung built while a fused ladder was traced is first compiled on
    its own whenever a per-call launch asks for it.  A call from inside
    another function's trace does not count as having run.  ``info`` is
    the build's :class:`~.codegen.KernelBuildInfo`; the span carries the
    lowering it names (:func:`lowering_meta`), read when the span closes
    because a build may still change its mind while it is traced.
    Everything else (``.lower``, ``.trace``) is the jitted function's
    own."""

    __slots__ = ("_fn", "_tag", "_warm", "info")

    def __init__(self, fn, tag: str, info):
        self._fn, self._tag, self._warm, self.info = fn, tag, False, info

    def __call__(self, *args):
        if self._warm:
            return self._fn(*args)
        _tt = TRACER.t0("compile")
        try:
            out = self._fn(*args)
        finally:
            TRACER.record("compile", _tt, tag=self._tag,
                          **(lowering_meta((self.info,)) if _tt else {}))
        self._warm = not any(
            isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(out))
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _KernelLauncher(_Launcher):
    """The launcher of ONE kernel over one chunk: ``fn(offset, arrays,
    values) -> arrays``.  The executable returns only the arrays the kernel
    may REPLACE (``kept``: the positions of the array parameters it stores
    to, known when the launcher is built); the others are handed back as
    they came.  An executable that returns an argument copies it, so a
    kernel that writes one small array beside gigabytes it only reads
    would copy those on every launch and hold them twice.  The trace
    checks that nothing outside ``kept`` was replaced.  ``.lower`` and
    ``.trace`` are the executable's own: their outputs are the ``kept``
    arrays alone.

    ``pitches`` are the positions of the value arguments that the kernel
    multiplies with inside an index (``codegen.pitch_params``: the pitch of
    a 2-D array, a shape in disguise).  Where a call gives them as plain
    integers they are KEYS of the executable, as a shape is: one build a
    value, in which the lowering sees the integer.  A traced or array-valued
    pitch stays a runtime argument (the build without keys), and so does
    every value after the first ``KEYED_BUILDS``: a factor that changes from
    call to call (a reduction's or an FFT's stride) is no shape, and must
    not compile a launcher a value."""

    __slots__ = ("_kept", "_pitches", "_keyed")
    KEYED_BUILDS = 4

    def __init__(self, raw_fn, tag: str, info, static: bool, kept: tuple,
                 pitches: tuple = ()):
        self._kept, self._pitches = kept, pitches
        self._keyed: set = set()  # the key tuples that have a build

        def replaced(offset, arrays: tuple, values: tuple = (), keys=None):
            out = (raw_fn(offset, arrays, values, keys) if pitches
                   else raw_fn(offset, arrays, values))
            stray = [i for i, (a, o) in enumerate(zip(arrays, out))
                     if o is not a and i not in kept]
            assert not stray, (
                f"{tag}: array parameter(s) {stray} replaced by a kernel "
                f"whose stores name only {kept}")
            return tuple(out[i] for i in kept)

        replaced.__name__ = raw_fn.__name__
        super().__init__(
            jax.jit(replaced, static_argnums=(2, 3) if static else (3,)),
            tag, info)

    def __call__(self, offset, arrays, values=()):
        keys = None
        if self._pitches and all(
                isinstance(values[i], (int, np.integer)) for i in self._pitches):
            keys = tuple(int(values[i]) for i in self._pitches)
            if keys not in self._keyed:
                if len(self._keyed) >= self.KEYED_BUILDS:
                    keys = None
                else:
                    self._keyed.add(keys)
        new = super().__call__(offset, tuple(arrays), values, keys)
        out = list(arrays)
        for i, buf in zip(self._kept, new):
            out[i] = buf
        return tuple(out)


def _beyond(in_range: bool) -> tuple:
    """The tail of a launcher-cache key: nothing for a build whose launches
    stay inside the global range (every key's shape before PR 30), one
    marker for the build of launches that reach beyond it."""
    return () if in_range else ("beyond-range",)


def lowering_meta(infos) -> dict:
    """Span metadata naming what was built for the launchers a span ran:
    ``lowering`` (``pallas``, ``xla``, ``python``; several joined by ``+``
    where a ladder's rungs differ), ``loops`` (``counted:N;masked:M``: how
    many of the kernel's loops run on a scalar counter and how many under a
    per-lane mask; joined the same way; no comma, which would end the
    value in a profiler annotation) and, where a TPU build was routed
    away from Pallas, ``veto`` with the reason.  A ladder executable stands
    for its rungs."""
    leaves = [r for i in infos for r in (i.rungs or (i,))]
    meta = {"lowering": "+".join(sorted({i.lowering for i in leaves})),
            "loops": "+".join(sorted(
                {f"counted:{i.loops_counted};masked:{i.loops_masked}"
                 for i in leaves}))}
    vetoes = sorted({i.veto for i in leaves if i.veto})
    if vetoes:
        meta["veto"] = "; ".join(vetoes)
    # the access sites by how they were lowered, summed over the KERNELS
    # (the rungs of one kernel are builds of the same sites: the most of
    # each kind), and the value arguments that were launcher keys
    per_kernel: dict = {}
    for i in leaves:
        mine = per_kernel.setdefault(i.name, {})
        for kind, n in i.access.items():
            mine[kind] = max(mine.get(kind, 0), n)
    if any(per_kernel.values()):
        meta["access"] = ";".join(
            f"{kind}:{sum(k.get(kind, 0) for k in per_kernel.values())}"
            for kind in codegen.ACCESS_KINDS)
    keyed = sorted({f"{k}={v}" for i in leaves for k, v in i.keyed.items()})
    if keyed:
        meta["keys"] = ";".join(keyed)
    return meta


@dataclass
class PythonKernel:
    """A kernel authored as a Python/JAX function.

    The function receives ``gid`` (an int32 vector of global work-item ids
    for the launch chunk) and the full array arguments, and returns the
    updated arrays (tuple, same order).  Value arguments arrive as keyword
    scalars.
    """

    fn: Callable
    name: str
    array_params: list[str]
    value_params: list[str] = field(default_factory=list)
    # treat the values tuple as a static jit argument (hashable python
    # scalars): lets the kernel body use them as compile-time constants
    # (e.g. loop bounds inside a Pallas kernel)
    static_values: bool = False


def kernel(fn: Callable | None = None, *, name: str | None = None, static_values: bool = False):
    """Decorator: register a Python/JAX function as a kernel.

    >>> @kernel
    ... def scale(gid, a, factor=2.0):
    ...     return a.at[gid].mul(factor)
    """

    def deco(f: Callable) -> PythonKernel:
        import inspect

        sig = inspect.signature(f)
        params = list(sig.parameters.values())
        if not params or params[0].name != "gid":
            raise KernelCompileError(
                f"@kernel function {f.__name__!r} must take 'gid' as its first parameter"
            )
        arrays = [p.name for p in params[1:] if p.default is inspect.Parameter.empty]
        values = [p.name for p in params[1:] if p.default is not inspect.Parameter.empty]
        return PythonKernel(
            fn=f, name=name or f.__name__, array_params=arrays,
            value_params=values, static_values=static_values,
        )

    return deco(fn) if fn is not None else deco


class KernelProgram:
    """A compiled kernel source: name → AST, plus the launch-function cache.

    Accepts a C-subset source string, a :class:`PythonKernel`, or a mixed
    sequence of both (reference: one kernel string holds many ``__kernel``
    functions; names regex-extracted at ClNumberCruncher.cs:219-228).
    """

    def __init__(self, source: str | PythonKernel | Sequence):
        self.source = source if isinstance(source, str) else ""
        self._c_kernels: dict[str, lang.KernelDef] = {}
        self._py_kernels: dict[str, PythonKernel] = {}
        self._cache: dict[tuple, tuple[Callable, Any]] = {}
        self._lock = threading.Lock()
        # partition-safety/flag-soundness verification (analysis/):
        # access summaries build once per kernel on first verify();
        # launch verdicts cache per (names, flag rows, window).  Both
        # dicts are written lock-free by design — concurrent misses
        # recompute the same immutable value, and the serve submit hot
        # path must not grow a lock for a cache read.
        self._analysis_summaries: dict[str, Any] | None = None
        self._verdict_cache: dict[tuple, Any] = {}

        items: list = []
        if isinstance(source, (str, PythonKernel)):
            items = [source]
        else:
            items = list(source)
        for item in items:
            if isinstance(item, str):
                for kdef in lang.parse_kernels(item):
                    self._c_kernels[kdef.name] = kdef
            elif isinstance(item, PythonKernel):
                self._py_kernels[item.name] = item
            else:
                raise KernelCompileError(f"unsupported kernel source: {type(item).__name__}")
        if not self._c_kernels and not self._py_kernels:
            raise KernelCompileError("no kernels found in source")

    @property
    def kernel_names(self) -> list[str]:
        return list(self._c_kernels.keys()) + list(self._py_kernels.keys())

    @property
    def compiled_count(self) -> int:
        """Number of distinct jitted launch geometries in the cache — the
        binary-ladder promise is that this stays O(log(range/step)) no
        matter how many distinct splits the balancer produces."""
        with self._lock:
            return len(self._cache)

    @property
    def fused_compiled_count(self) -> int:
        """Number of distinct FUSED iteration-ladder executables in the
        cache (:meth:`fused_launcher`).  The fused cache key carries no
        range-table row and no iteration count — balancer re-partitioning
        and window-size changes are runtime arguments, so this count moves
        only on a genuine shape change (program sequence, step geometry,
        operand shapes/dtypes via XLA's own per-signature cache, or the
        baked value constants)."""
        with self._lock:
            # fused keys are the 9-tuples built below (10 with the
            # beyond-range marker); a plain launcher key for a user kernel
            # literally named "fused" is a 5-tuple (6) and must not count
            return sum(
                1 for k in self._cache
                if k and k[0] == "fused" and len(k) in (9, 10)
            )

    def compiled_counts_by_platform(self) -> dict[str, int]:
        """Distinct cached launch executables per dispatch platform —
        the heterogeneous-fleet compile-isolation probe: every launcher
        cache key carries its platform (plain/seq/fused alike), so a
        host-CPU lane joining a TPU fleet grows only the ``"cpu"``
        count while the ``"tpu"`` count stays PINNED — one kind can
        never evict or re-trace another kind's executables."""
        with self._lock:
            out: dict[str, int] = {}
            for k in self._cache:
                if k and k[0] == "fused" and len(k) in (9, 10):
                    p = k[7]
                elif k and k[0] == "seq" and len(k) in (9, 10):
                    p = k[8]
                elif len(k) in (5, 6):
                    p = k[4]
                else:  # future key shape: never miscount, bucket as ?
                    p = "?"
                out[str(p)] = out.get(str(p), 0) + 1
        return out

    def __contains__(self, name: str) -> bool:
        return name in self._c_kernels or name in self._py_kernels

    def array_param_count(self, name: str) -> int:
        if name in self._c_kernels:
            return sum(1 for p in self._c_kernels[name].params if p.is_pointer)
        return len(self._py_kernels[name].array_params)

    def value_param_names(self, name: str) -> list[str]:
        if name in self._c_kernels:
            return [p.name for p in self._c_kernels[name].params if not p.is_pointer]
        return list(self._py_kernels[name].value_params)

    def lowerings(self, name: str, platform: str | None) -> set[tuple]:
        """``{(lowering, veto), ...}`` over every launcher built so far
        for kernel ``name`` on ``platform`` — how a run asserts its
        routing (``{("pallas", None)}`` = every rung of the ladder went
        through Mosaic)."""
        with self._lock:
            infos = [
                info for key, (_fn, info) in self._cache.items()
                if len(key) in (5, 6) and key[0] == name and key[4] == platform
            ]
        return {(i.lowering, i.veto) for i in infos}

    # -- partition-safety verification (analysis/) ---------------------------
    def summaries(self) -> dict:
        """Per-kernel access summaries, built once per program (one
        abstract interpretation per C kernel; Python kernels map to
        ``None`` — outside the analyzable surface).  An analysis
        bail-out on one kernel degrades THAT kernel to unverifiable,
        never breaks the build."""
        out = self._analysis_summaries
        if out is None:
            from .. import analysis

            out = {}
            for name, kdef in self._c_kernels.items():
                try:
                    out[name] = analysis.summarize_kernel(kdef)
                except Exception:  # noqa: BLE001 - degrade, never break
                    out[name] = None
            for name in self._py_kernels:
                out[name] = None
            self._analysis_summaries = out
        return out

    def verify(self, kernel_names, flag_rows, window: bool = False):
        """Cached :class:`~..analysis.LaunchVerdict` for one launch
        shape.  ``flag_rows`` is a tuple of
        :func:`~..analysis.flag_row` tuples (positional, the call's
        parameter order).  Verification runs once per distinct
        (kernel sequence, flags, window) — every later call is one
        dict lookup."""
        key = (tuple(kernel_names), tuple(flag_rows), bool(window))
        v = self._verdict_cache.get(key)
        if v is None:
            from .. import analysis

            try:
                v = analysis.verify_launch(
                    self.summaries(), key[0], key[1], window=key[2])
            except Exception:  # noqa: BLE001 - verifier must never
                # sink a compute; an empty verdict is "nothing proven"
                v = analysis.LaunchVerdict(findings=())
            self._verdict_cache[key] = v
        return v

    def launcher(
        self,
        name: str,
        chunk: int,
        local_size: int,
        global_size: int,
        platform: str | None = None,
        in_range: bool = True,
    ) -> tuple[Callable, Any]:
        """Get (building if needed) the jitted launch function for one
        geometry.  Signature: ``fn(offset, arrays_tuple, values_tuple) ->
        updated arrays tuple``.

        ``in_range``: the caller's word that every launch of this function
        keeps its work items inside ``[0, global_size)``, from which the
        vectorized lowering proves affine accesses in bounds.  A launch
        that reaches beyond (a compute with a global offset:
        ``Worker.launch`` decides it from its own offset and size) gets the
        build without the proof, a key of its own.

        ``platform`` is the dispatch target's PJRT platform name
        (``"tpu"``/``"cpu"``): on TPU, C-subset kernels in the tile
        subset lower to Pallas (kernel/pallas_backend.py — VMEM-resident
        loop state, per-tile early exit); kernels outside it, or routed
        away by the measured policy, take the vectorized XLA lowering.
        ``info.lowering`` / ``info.veto`` record which was built and why
        — see :meth:`lowerings`."""
        key = (name, chunk, local_size, global_size, platform) + _beyond(in_range)
        with self._lock:
            hit = self._cache.get(key)
        if hit is not None:
            return hit

        if name in self._c_kernels:
            raw_fn = info = veto = None
            if platform == "tpu":
                from . import pallas_backend

                try:
                    raw_fn, info = pallas_backend.build_kernel_fn_pallas(
                        self._c_kernels[name], chunk, local_size, global_size,
                        in_range=in_range,
                    )
                except pallas_backend.PallasUnsupported as e:
                    veto = str(e)
            if raw_fn is None:
                raw_fn, info = codegen.build_kernel_fn(
                    self._c_kernels[name], chunk, local_size, global_size,
                    platform, in_range,
                )
                info.veto = veto
        elif name in self._py_kernels:
            pk = self._py_kernels[name]

            def raw_fn(offset, arrays: tuple, values: tuple = (), _pk=pk):
                gid = jnp.asarray(offset, jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
                kw = dict(zip(_pk.value_params, values))
                out = _pk.fn(gid, *arrays, **kw)
                if not isinstance(out, tuple):
                    out = (out,)
                if len(out) != len(arrays):
                    # python kernels may return only the modified arrays;
                    # pad by identity on the left-over inputs
                    out = tuple(out) + tuple(arrays[len(out):])
                return out

            info = codegen.KernelBuildInfo(
                name=name,
                array_params=list(pk.array_params),
                value_params=list(pk.value_params),
                array_ctypes={},
                stored_params=list(pk.array_params),
                lowering="python",
            )
        else:
            raise KernelCompileError(
                f"kernel {name!r} not found; available: {self.kernel_names}"
            )

        static = name in self._py_kernels and self._py_kernels[name].static_values
        # the array parameters a launch may replace: those a C kernel's
        # statements store to, every one of a Python kernel's
        stores = (codegen._stored_bufs(self._c_kernels[name].body)
                  if name in self._c_kernels else info.array_params)
        kept = tuple(i for i, p in enumerate(info.array_params) if p in stores)
        # the value arguments that key the executable: a C kernel's pitches,
        # where the vectorized lowering built it (which can use them)
        pitches = (codegen.pitch_params(self._c_kernels[name])
                   if name in self._c_kernels and info.lowering == "xla" else ())
        # whichever lowering built it: the XLA module reads jit_<kernel>
        raw_fn.__name__ = codegen.hlo_name(name)
        jitted = _KernelLauncher(
            raw_fn,
            f"{name} chunk={chunk} lr={local_size} g={global_size} "
            f"{platform}" + ("" if in_range else " beyond-range"),
            info, static, kept, pitches)
        with self._lock:
            self._cache[key] = (jitted, info)
        return jitted, info

    def sequence_launcher(
        self,
        names: tuple,
        chunks: tuple,
        local_size: int,
        global_size: int,
        repeats: int,
        sync_kernel: str | None,
        value_args,
        platform: str | None = None,
        in_range: bool = True,
    ) -> Callable | None:
        """One jitted function running the whole kernel sequence over the
        launch ladder ``repeats`` times as an on-device ``lax.fori_loop`` —
        O(1) dispatches regardless of repeat count (reference:
        computeRepeated / computeRepeatedWithSyncKernel run the repeat loop
        inside the native layer, Worker.cs:36-46, SURVEY.md §2.3).

        Scalar values are baked as compile-time constants (part of the
        cache key) — repeat mode recompiles when they change.  Returns
        ``None`` when the values are unhashable (caller falls back to the
        host loop).  ``in_range`` is the rung launchers' (:meth:`launcher`).
        """
        from jax import lax

        def vals_for(name: str) -> tuple:
            if isinstance(value_args, dict):
                return tuple(value_args.get(name, ()))
            return tuple(value_args)

        all_names = set(names) | ({sync_kernel} if sync_kernel else set())
        try:
            sig = tuple(sorted((n, vals_for(n)) for n in all_names))
            key = ("seq", names, chunks, local_size, global_size, repeats,
                   sync_kernel, sig, platform) + _beyond(in_range)
            with self._lock:
                hit = self._cache.get(key)
        except TypeError:
            return None  # unhashable values (e.g. traced arrays)
        if hit is not None:
            return hit[0]

        info = codegen.KernelBuildInfo(
            name="+".join(names), array_params=[], value_params=[],
            array_ctypes={}, stored_params=[], lowering="ladder",
        )
        rungs: dict = {}  # the rung launchers' infos, seen where traced

        def run_names(names_seq, offset0, bufs):
            for name in names_seq:
                off = offset0
                n_arr = self.array_param_count(name)
                for chunk in chunks:
                    fn, rungs[name, chunk] = self.launcher(
                        name, chunk, local_size, global_size, platform,
                        in_range)
                    out = fn(off, bufs[:n_arr], vals_for(name))
                    bufs = tuple(out) + bufs[n_arr:]
                    off = off + chunk
            info.rungs = tuple(rungs.values())
            return bufs

        def raw(offset, bufs: tuple):
            bufs = tuple(bufs)
            if repeats <= 1:
                return run_names(names, offset, bufs)
            if sync_kernel:
                def body(_, b):
                    b = run_names(names, offset, b)
                    return run_names((sync_kernel,), offset, b)

                bufs = lax.fori_loop(0, repeats - 1, body, bufs)
                return run_names(names, offset, bufs)
            return lax.fori_loop(
                0, repeats, lambda _, b: run_names(names, offset, b), bufs
            )

        raw.__name__ = "seq_" + codegen.hlo_name(*names)
        jitted = _Launcher(
            jax.jit(raw),
            f"seq:{'+'.join(names)} x{repeats} g={global_size} {platform}",
            info)
        with self._lock:
            self._cache[key] = (jitted, info)
        return jitted

    def fused_launcher(
        self,
        names: tuple,
        step: int,
        total_range: int,
        local_size: int,
        global_size: int,
        value_args,
        platform: str | None = None,
        donate: bool = False,
        build: bool = True,
        in_range: bool = True,
    ) -> Callable | None:
        """ONE executable for the fused-iteration dispatch path
        (core/cores.py): ``fn(offset, units, iters, bufs) -> bufs`` runs
        the kernel sequence over ``units·step`` work items starting at
        ``offset``, repeated ``iters`` times as an on-device
        ``lax.fori_loop`` — where **offset, units and iters are all
        runtime scalars**.

        The launch ladder is *predicated*: the body contains every binary
        chunk ``step·2^k`` up to the GLOBAL range and executes chunk ``k``
        under ``lax.cond`` iff bit ``k`` of ``units`` is set, advancing a
        runtime offset by the executed chunks.  Per element this applies
        exactly the per-iteration ladder's kernel functions in the same
        descending-chunk order, so results are bit-identical to the
        per-iteration path — while the executable itself is independent of
        the balancer's range-table row AND of the window's iteration
        count.  That independence IS the executable-cache invariant: a
        rebalance (range shift, unchanged shapes) or a different window
        size K hits this same cache entry; only a genuine shape change
        (program sequence, step/global geometry, baked values, platform)
        compiles a new one (``fused_compiled_count``).

        ``donate=True`` donates the buffer tuple (HBM residency across
        iterations without a transient double allocation) — the caller
        must drop every stale reference to the donated buffers
        (core/worker.py replaces its cache entries from the outputs).

        Scalar values are baked as compile-time constants, like
        :meth:`sequence_launcher`; returns ``None`` when they are
        unhashable (the caller falls back to per-iteration dispatch).

        ``build=False`` only PEEKS: the cached executable of exactly this
        key, or ``None`` when no fused window (or ``Cores.warmup``) has
        built it.  A multi-rung per-call launch (``Worker.launch``) rides
        the executable that way with ``iters=1``; it must never build
        one, because its values change freely from call to call and each
        new value would compile.

        ``in_range`` is the rung launchers' (:meth:`launcher`): whether the
        window's ``[offset, offset + units·step)`` stays inside the global
        range, as every window of a compute without a global offset does."""
        from jax import lax

        def vals_for(name: str) -> tuple:
            if isinstance(value_args, dict):
                return tuple(value_args.get(name, ()))
            return tuple(value_args)

        try:
            sig = tuple(sorted((n, vals_for(n)) for n in set(names)))
            key = ("fused", names, step, total_range, local_size,
                   global_size, sig, platform, donate) + _beyond(in_range)
            with self._lock:
                hit = self._cache.get(key)
        except TypeError:
            return None  # unhashable values (e.g. traced arrays)
        if hit is not None:
            return hit[0]
        if not build:
            return None

        nbits = max(1, (total_range // step).bit_length())
        info = codegen.KernelBuildInfo(
            name="fused:" + "+".join(names), array_params=[],
            value_params=[], array_ctypes={}, stored_params=[],
            lowering="ladder",
        )
        rungs: dict = {}  # the rung launchers' infos, seen where traced

        def run_ladder(offset, units, bufs):
            for name in names:
                n_arr = self.array_param_count(name)
                va = vals_for(name)
                off = jnp.asarray(offset, jnp.int32)
                for k in reversed(range(nbits)):
                    chunk = step << k
                    fn, rungs[name, chunk] = self.launcher(
                        name, chunk, local_size, global_size, platform,
                        in_range,
                    )
                    bit = (jnp.asarray(units, jnp.int32) >> k) & 1

                    def hit_branch(b, _fn=fn, _off=off, _va=va, _n=n_arr):
                        out = _fn(_off, tuple(b)[:_n], _va)
                        return tuple(out) + tuple(b)[_n:]

                    bufs = lax.cond(
                        bit != 0, hit_branch, lambda b: tuple(b), tuple(bufs)
                    )
                    off = off + bit * chunk
            info.rungs = tuple(rungs.values())
            return bufs

        def raw(offset, units, iters, bufs: tuple):
            bufs = tuple(bufs)
            return lax.fori_loop(
                0, iters, lambda _, b: run_ladder(offset, units, b), bufs
            )

        raw.__name__ = "fused_" + codegen.hlo_name(*names)
        jitted = _Launcher(
            jax.jit(raw, donate_argnums=(3,) if donate else ()),
            f"fused:{'+'.join(names)} step={step} g={global_size} "
            f"{platform}", info)
        with self._lock:
            self._cache[key] = (jitted, info)
        return jitted
