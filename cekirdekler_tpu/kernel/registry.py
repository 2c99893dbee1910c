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
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import KernelCompileError
from ..trace.spans import TRACER
from . import codegen, lang, pallas_backend

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
        return self._run(self._fn, *args)

    def _run(self, fn, *args):
        """``fn(*args)``, ``fn`` the jitted function or, where a launcher has
        one, its other entry: the launcher is warm once either has run."""
        if self._warm:
            return fn(*args)
        _tt = TRACER.t0("compile")
        try:
            out = fn(*args)
        finally:
            TRACER.record("compile", _tt, tag=self._tag,
                          **(lowering_meta((self.info,)) if _tt else {}))
        self._warm = not any(
            isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(out))
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _KeptViews:
    """The launch-invariant views (``codegen.ViewSpec``) of one program's
    launchers: what a launch derives from a read-only array ALONE is built
    when that array first meets a launcher and handed to every later launch
    as an argument.

    A view is built by a small jitted program of its own, kept under the
    array OBJECT's identity and dropped with the object: a ``jax.Array``
    cannot change, an upload makes a new one, and there is no other
    invalidation.  The launchers of one program share them (a product's
    four rungs are four launchers over the same two tables).

    The rule that bounds them comes from the device: views are kept while
    the bytes this program keeps on a device stay under ``SHARE`` of what
    its ``memory_stats()`` reports as the limit; a view that would pass it
    is left to the launch, which builds it as a temporary.  A device that
    reports no limit (the CPU rig) bounds nothing."""

    SHARE = 0.25

    def __init__(self):
        # callbacks of dying arrays re-enter on whichever thread drops the
        # last reference, this one while it holds the lock included
        self._lock = threading.RLock()
        # (id of the array, kind) -> (weak reference, view, device, bytes)
        self._kept: dict[tuple, tuple] = {}
        self._bytes: dict[Any, int] = {}    # device -> bytes kept there
        self._builders: dict[str, Callable] = {}
        # what a kernel's builds ask for: launcher signature -> specs
        self.asked: dict[tuple, tuple] = {}

    @staticmethod
    def limit(device) -> int | None:
        """The device's memory limit in bytes, where it reports one."""
        stats = device.memory_stats()
        return stats.get("bytes_limit") if stats else None

    def bytes_kept(self, device=None) -> int:
        with self._lock:
            return (sum(self._bytes.values()) if device is None
                    else self._bytes.get(device, 0))

    def _drop(self, key: tuple, ref) -> None:
        with self._lock:
            hit = self._kept.get(key)
            if hit is not None and hit[0] is ref:  # not a later object's
                del self._kept[key]
                self._bytes[hit[2]] -= hit[3]

    def views(self, arrays: tuple, specs) -> tuple[dict, int]:
        """``({(param, kind): view}, built)``: the kept views of ``arrays``
        among ``specs``, built now where an array is met for the first time
        (``built`` counts those); a spec whose view the memory rule refuses
        is left out."""
        out, built = {}, 0
        for spec in specs:
            arr = arrays[spec.param]
            key = (id(arr), spec.kind)
            hit = self._kept.get(key)
            if hit is None or hit[0]() is not arr:
                hit = self._build(key, arr, spec)
                if hit is None:
                    continue
                built += 1
            out[spec] = hit[1]
        return out, built

    def _build(self, key: tuple, arr, spec):
        devices = arr.devices()
        if len(devices) != 1:
            return None  # a sharded array: the launch builds its views
        (device,) = devices
        nbytes = spec.nbytes(arr.shape, arr.dtype.itemsize)
        with self._lock:
            hit = self._kept.get(key)
            if hit is not None and hit[0]() is arr:
                return hit  # another lane's thread was first
            limit = self.limit(device)
            if (limit is not None
                    and self._bytes.get(device, 0) + nbytes > self.SHARE * limit):
                return None
            build = self._builders.get(spec.kind)
            if build is None:
                build = self._builders[spec.kind] = jax.jit(spec.build)
            ref = weakref.ref(arr, lambda r, key=key: self._drop(key, r))
            hit = self._kept[key] = (ref, build(arr), device, nbytes)
            self._bytes[device] = self._bytes.get(device, 0) + nbytes
            return hit


def _concrete(arrays) -> bool:
    """Are these device arrays (and not the tracers of an enclosing trace,
    or host arrays), so that a view can be kept under their identity?"""
    return all(isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
               for a in arrays)


# -- a dispatch's run-time scalars, packed ---------------------------------
# A Python number handed to a jitted call is a host-to-device transfer of its
# own.  A per-call dispatch of a C kernel hands its offset and value arguments
# over as ONE vector of 32-bit words (``_KernelLauncher._pack``): each value
# converted on the host to what the launch makes of it, bit-cast back in the
# executable's entry.

#: a slot of a packed call's layout that holds no words: a value that rides
#: as a run-time argument of its own, and a launcher key (static)
LOOSE, KEYED = "", "key"


def _plain(v) -> bool:
    """A plain Python or numpy scalar: what a dispatch can pack."""
    return isinstance(v, (bool, int, float, np.bool_, np.integer, np.floating))


def _host_words(v, dtype: np.dtype) -> bytes | None:
    """The plain scalar ``v`` as the launch sees it, as the bytes of its
    32-bit words: first what ``jax.jit`` makes of the argument (a Python
    number takes the default dtype of its kind, a numpy scalar keeps its own;
    both canonical: 32 bits wide without x64, and a Python int beyond them is
    the OverflowError it was), then ``jnp.asarray(v, dtype)``, the
    parameter's.  numpy rounds, wraps and truncates as the device's convert
    does; where C leaves the cast undefined (a float that is no integer of
    the parameter's range) the two may differ: None, the value rides as it
    did.  A 64-bit value is two words, low first; a narrower one is widened
    by its bits."""
    with np.errstate(over="ignore"):  # a float beyond the narrower one's: inf
        seen = np.array(
            v, dtype=jax.dtypes.canonicalize_dtype(np.result_type(v)))
        if seen.dtype.kind == "f" and dtype.kind in "iu":
            lim = np.iinfo(dtype)
            # (as Python's exact integers: numpy compares in the float's width)
            if not (np.isfinite(seen) and lim.min <= int(seen) <= lim.max):
                return None
        a = seen.astype(dtype)
    if dtype.itemsize < 4:
        a = (a if dtype.kind == "b" else a.view(f"u{dtype.itemsize}")).astype(
            np.uint32)
    return a.tobytes()


def _from_words(words, dtype: np.dtype):
    """The value :func:`_host_words` packed, of ``dtype`` again."""
    if dtype.kind == "b":
        return words[0] != 0
    if dtype.itemsize == 8:
        return jax.lax.bitcast_convert_type(words, dtype)
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(words[0], dtype)
    return jax.lax.bitcast_convert_type(
        words[0].astype(f"uint{8 * dtype.itemsize}"), dtype)


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
    not compile a launcher a value.

    ``views``: what the vectorized lowering derives from a read-only array
    alone rides as one more argument (:class:`_KeptViews`).  A call on
    device arrays looks the views up under the arrays' identity (``frozen``:
    the positions no kernel of the caller's LAUNCH stores to; left out, this
    kernel's own); a call from inside a ladder's trace is handed them.

    ``ctypes``: a C kernel's value parameters' declared types.  A dispatch of
    such a kernel (a call on arrays that are no tracers) hands its run-time
    scalars over as ONE array: the offset and every value that is a plain
    Python or numpy scalar, each converted on the host to what the launch
    makes of it (:func:`_host_words`), cross as one vector of 32-bit words,
    and the executable's entry (``_packed``: the one a per-call dispatch
    compiles) bit-casts them back and runs the same function.  A launcher
    key stays static; a value that is anything else (a ``jax.Array``, a
    tracer) rides as an argument of its own; a Python kernel's values ride as
    they did (``ctypes`` None), and so does every call from inside another
    function's trace, where nothing crosses.  ``.trace`` / ``.lower`` are the
    unpacked function's, ``(offset, arrays, values[, keys[, views]])``, which
    compiles only where it is called.  ``info.scalars`` counts what the
    newest dispatch handed over: words in the vector, plain scalars one by
    one."""

    __slots__ = ("_kept", "_pitches", "_keyed", "_raw", "_views", "_sig",
                 "_dtypes", "_packed", "_static")
    KEYED_BUILDS = 4

    def __init__(self, raw_fn, tag: str, info, static: bool, kept: tuple,
                 pitches: tuple = (), views: _KeptViews | None = None,
                 sig: tuple = (), ctypes: tuple | None = None):
        self._kept, self._pitches = kept, pitches
        self._keyed: set = set()  # the key tuples that have a build
        # ``views``: the program's, for a build of the vectorized lowering
        # (the one that asks for any); ``sig``: what its builds share with
        # the kernel's other chunks
        self._raw, self._views, self._sig = raw_fn, views, sig

        def replaced(offset, arrays: tuple, values: tuple = (), keys=None,
                     views=None):
            out = (raw_fn(offset, arrays, values, keys, views)
                   if self._views is not None
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
        self._static = static
        self._dtypes = self._packed = None
        if ctypes is None:
            return
        # slot 0 is the offset (``jnp.asarray(offset, jnp.int32)`` in both
        # lowerings), slot i the value i - 1; each dtype with its name, which
        # numpy derives anew at every ask
        self._dtypes = tuple((d, d.name) for d in (
            np.dtype(np.int32),
            *(np.dtype(codegen.ctype_to_dtype(c)) for c in ctypes)))

        def packed(words, arrays: tuple, loose: tuple, layout: tuple,
                   keys=None, views=None):
            at, rest, slots = 0, iter(loose), []
            keyed = dict(zip(pitches, keys or ()))
            for i, kind in enumerate(layout):
                if kind == KEYED:
                    slots.append(keyed[i - 1])
                elif kind == LOOSE:
                    slots.append(next(rest))
                else:
                    dtype = np.dtype(kind)
                    n = max(dtype.itemsize // 4, 1)
                    slots.append(_from_words(words[at:at + n], dtype))
                    at += n
            return replaced(slots[0], arrays, tuple(slots[1:]), keys, views)

        packed.__name__ = raw_fn.__name__
        self._packed = jax.jit(packed, static_argnums=(3, 4))

    def _cache_size(self) -> int:
        """The builds of both entries (a test counts a call's traces by it)."""
        return self._fn._cache_size() + (
            self._packed._cache_size() if self._packed is not None else 0)

    def _pack(self, offset, values, keys) -> tuple:
        """``(words, loose, layout)`` of a dispatch: the vector, the values
        that ride beside it, and per slot the dtype's name the words hold
        (:data:`LOOSE`, :data:`KEYED` for the others)."""
        words, loose, layout = [], [], []
        for i, (v, (dtype, name)) in enumerate(
                zip((offset,) + values, self._dtypes)):
            if keys is not None and i - 1 in self._pitches:
                layout.append(KEYED)
                continue
            w = _host_words(v, dtype) if _plain(v) else None
            if w is None:
                loose.append(v)
                layout.append(LOOSE)
            else:
                words.append(w)
                layout.append(name)
        return (np.frombuffer(b"".join(words), np.uint32), tuple(loose),
                tuple(layout))

    def keys_of(self, values) -> tuple | None:
        """The launcher keys among ``values``, where this call has any."""
        if not self._pitches or not all(
                isinstance(values[i], (int, np.integer)) for i in self._pitches):
            return None
        keys = tuple(int(values[i]) for i in self._pitches)
        if keys not in self._keyed:
            if len(self._keyed) >= self.KEYED_BUILDS:
                return None
            self._keyed.add(keys)
        return keys

    def wants(self, arrays, values, keys) -> tuple:
        """The views (``codegen.ViewSpec``) a build of this kernel over
        ``arrays`` (anything with ``shape`` and ``dtype``) asks for, of the
        parameters the kernel itself never stores to.  Learnt from one
        abstract trace (nothing compiles, nothing runs) a signature, which
        the kernel's launchers of every chunk size share: what a kernel asks
        for is a matter of its accesses, not of the lanes."""
        if self._views is None:
            return ()
        sig = self._sig + (keys, tuple((a.shape, a.dtype) for a in arrays))
        specs = self._views.asked.get(sig)
        if specs is None:
            jax.eval_shape(
                lambda o, a, v: self._raw(o, a, v, keys),
                jax.ShapeDtypeStruct((), jnp.int32),
                tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays),
                tuple(values))
            specs = self._views.asked[sig] = self.info.views
        return specs

    def __call__(self, offset, arrays, values=(), views=None, frozen=None):
        arrays, keys = tuple(arrays), self.keys_of(values)
        if views is None:
            views, specs = {}, self.wants(arrays, values, keys)
            if specs:
                built = 0
                if _concrete(arrays):
                    views, built = self._views.views(
                        arrays, specs if frozen is None else
                        [s for s in specs if s.param in frozen])
                self.info.views_kept, self.info.views_built = len(views), built
        values = tuple(values)
        # inside another function's trace nothing is dispatched
        dispatch = not any(isinstance(x, jax.core.Tracer)
                           for x in (offset,) + arrays)
        if dispatch and self._packed is not None:
            words, loose, layout = self._pack(offset, values, keys)
            self.info.scalars = (len(words), sum(map(_plain, loose)))
            new = self._run(self._packed, words, arrays, loose, layout, keys,
                            views)
        else:
            if dispatch:
                crossing = (offset,) + (() if self._static else values)
                self.info.scalars = (0, sum(map(_plain, crossing)))
            new = super().__call__(offset, arrays, values, keys, views)
        out = list(arrays)
        for i, buf in zip(self._kept, new):
            out[i] = buf
        return tuple(out)


def _merged(moving, held: dict, n: int) -> tuple:
    """The ``n`` buffers of a ladder in their positions again: ``held``
    (position -> array) put back among the ``moving`` ones."""
    rest = iter(moving)
    return tuple(held[i] if i in held else next(rest) for i in range(n))


def _moving(bufs, held: dict) -> tuple:
    return tuple(b for i, b in enumerate(bufs) if i not in held)


class _LadderLauncher(_Launcher):
    """A ladder executable (repeat mode's, the fused window's):
    ``fn(*scalars, bufs) -> bufs``.  Its rungs' kept views
    (:class:`_KeptViews`) are looked up here, under the buffers' identity,
    and ride the executable as arguments that its loops close over: loop
    invariants, not carried.  A buffer that has a kept view is HELD: handed
    to the executable beside the others, neither donated nor returned, and
    put back as the object it was.  An array that comes back from an
    executable is a new object, read-only or not, and its views would be
    built again at every dispatch.  A ladder whose rungs ask for no view
    holds nothing and is the program it was before there were views.

    ``ask(bufs) -> specs``: the views the ladder's rungs ask for over
    ``bufs``, of the positions no kernel of the ladder stores to."""

    __slots__ = ("_ask", "_views", "_specs")

    def __init__(self, fn, tag: str, info, ask, views: _KeptViews):
        super().__init__(fn, tag, info)
        self._ask, self._views = ask, views
        self._specs: dict[tuple, tuple] = {}  # buffer signature -> specs

    def __call__(self, *args):
        bufs = tuple(args[-1])
        views, held = {}, {}
        if _concrete(bufs):
            sig = tuple((b.shape, b.dtype) for b in bufs)
            specs = self._specs.get(sig)
            if specs is None:
                specs = self._specs[sig] = self._ask(bufs)
            if specs:
                views, built = self._views.views(bufs, specs)
                held = {s.param: bufs[s.param] for s in views}
                self.info.views_kept, self.info.views_built = len(views), built
        out = super().__call__(*args[:-1], _moving(bufs, held), held, views)
        return _merged(out, held, len(bufs))


def _views_of(fn, arrays, values, views: dict) -> dict:
    """Of a ladder's kept ``views``, those its rung ``fn`` asks for."""
    if not views:
        return views
    return {s: views[s] for s in fn.wants(arrays, values, fn.keys_of(values))
            if s in views}


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
    per-lane mask, then ``;peeled:P`` where ``P > 0`` of the masked ones run
    the passes all their lanes make with no mask first
    (``codegen._common_walks``); joined the same way; no comma, which would
    end the value in a profiler annotation), where a TPU build was routed
    away from Pallas, ``veto`` with the reason, and where the launch read
    beyond its lane's own range, ``reach`` (``u1:16384``), and for a Pallas
    build ``tile`` (``64x128;grid=4;live=6``: the work items of a grid step's
    tile, the grid steps a launch makes and the tiles a counted loop of the
    kernel keeps alive, which the rows were fitted to; joined like
    ``loops``).  A ladder executable stands
    for its rungs.  ``views`` (``kept:K;built:B``) is of the DISPATCHES the
    span ran, one of ``infos`` each: the kept views they took as arguments
    (:class:`_KeptViews`) and how many of those were built on these calls;
    in a warm window ``built`` is 0.  ``scalars`` (``packed:W;loose:L``) is
    of the dispatches too, where a kernel's launcher made them: the 32-bit
    words of run-time scalars that crossed to the device in one vector a
    dispatch, and the Python or numpy scalars that crossed one by one.
    ``scatter`` (``stores:2;width:4+1``), where a kernel's build has any: the
    stores lowered to a scatter and the bytes of one element of each.
    ``compact`` (``loops:1;width:8192;gathered:3;scattered:0;ordered:1``),
    where a build made a masked loop compactable
    (``codegen._exec_compacted``): the loops, the lanes of a chunk, the reads
    and stores at the lane's own element that a chunk lowers as gathers and
    scatters, and the loops whose lanes go to their chunks by trip count.
    ``vector`` (``params:2;width:4;loads:1;gathers:1;stores:1``), where a
    kernel has ``__global floatN*`` parameters (kernel/vectors.py): how many,
    their ``N`` and the accesses of them that were BUILT, one an access
    whatever its width: loads by slice, strided window or uniform element,
    loads by per-lane gather (one row fetch a vector), stores (``access``
    counts each ONCE too, in the form it took).
    ``local`` (``arrays:1;bytes:1024;barriers:2;sites:shift:6,uniform:1,row:0``),
    where a kernel's work items cooperate inside their group: its ``__local``
    arrays, the bytes of them one work-group holds, its barrier statements,
    and the arrays' access sites by lowering (``codegen._local_load``);
    ``access`` then ends in ``;local:N;group:M;settled:S``: the sites' total,
    the buffer reads lowered to one window a work-group
    (``codegen._group_slice``) and, of those, the reads whose windows a loop
    settles once, before its passes (``codegen._settle``)."""
    infos = list(infos)
    leaves = [r for i in infos for r in (i.rungs or (i,))]
    meta = {"lowering": "+".join(sorted({i.lowering for i in leaves})),
            "loops": "+".join(sorted(
                {f"counted:{i.loops_counted};masked:{i.loops_masked}"
                 + (f";peeled:{i.loops_peeled}" if i.loops_peeled else "")
                 for i in leaves})),
            "views": f"kept:{sum(i.views_kept for i in infos)};"
                     f"built:{sum(i.views_built for i in infos)}"}
    scalars = [i.scalars for i in infos if i.scalars is not None]
    if scalars:
        meta["scalars"] = (f"packed:{sum(w for w, _l in scalars)};"
                           f"loose:{sum(l for _w, l in scalars)}")
    tiles = sorted({(i.tile_rows, i.tile_grid, i.loop_live)
                    for i in leaves if i.lowering == "pallas"})
    if tiles:
        meta["tile"] = "+".join(
            f"{rows}x{pallas_backend.LANES};grid={grid};live={live}"
            for rows, grid, live in tiles)
    vetoes = sorted({i.veto for i in leaves if i.veto})
    if vetoes:
        meta["veto"] = "; ".join(vetoes)
    # elements beyond the lane's own range that the launch's exchange kept
    # current, by array (``Worker.launch`` stamps the launchers it runs)
    reach = sorted({i.reach for i in infos + leaves if i.reach})
    if reach:
        meta["reach"] = ";".join(reach)
    # the access sites by how they were lowered, summed over the KERNELS
    # (the rungs of one kernel are builds of the same sites: the most of
    # each kind), and the value arguments that were launcher keys
    per_kernel: dict = {}
    for i in leaves:
        mine = per_kernel.setdefault(i.name, {})
        for kind, n in i.access.items():
            mine[kind] = max(mine.get(kind, 0), n)
    # work-group cooperation, summed over the kernels like ``access``: the
    # __local arrays, the bytes one group holds, the barrier statements, and
    # the arrays' access sites by lowering (``row`` is the fallback)
    coop = {i.name: i for i in leaves if i.local}
    if coop:
        arrays, nbytes, barriers = (sum(x) for x in zip(
            *(i.local for i in coop.values())))
        sites = {kind: sum(max([r.local_sites.get(kind, 0) for r in leaves
                                if r.name == name]) for name in coop)
                 for kind in codegen.LOCAL_KINDS}
        meta["local"] = (f"arrays:{arrays};bytes:{nbytes};barriers:{barriers};"
                         "sites:" + ",".join(f"{k}:{n}" for k, n in sites.items()))
    if any(per_kernel.values()):
        meta["access"] = ";".join(
            f"{kind}:{sum(k.get(kind, 0) for k in per_kernel.values())}"
            for kind in codegen.ACCESS_KINDS)
        if coop:
            group, settled = (sum(k.get(kind, 0) for k in per_kernel.values())
                              for kind in ("group", "settled"))
            meta["access"] += (f";local:{sum(sites.values())};group:{group}"
                               f";settled:{settled}")
    # the stores that became a scatter, summed over the kernels as
    # ``access`` is, with the bytes of one element of each (``+``-joined)
    scattered = {i.name: i.scattered for i in leaves if i.scattered}
    if scattered:
        widths = [w for name in sorted(scattered) for w in scattered[name]]
        meta["scatter"] = (f"stores:{len(widths)};"
                           f"width:{'+'.join(str(w) for w in widths)}")
    # vector parameters and the accesses of them that were built, summed
    # over the kernels as ``access`` is (of a kernel's rungs the most)
    wide: dict = {}
    for i in leaves:
        if i.vector:
            wide[i.name] = max(wide.get(i.name, ()), i.vector)
    if wide:
        params, widths, loads, gathers, stores = zip(*wide.values())
        meta["vector"] = (
            f"params:{sum(params)};"
            f"width:{'+'.join(str(w) for w in sorted({w for ws in widths for w in ws}))};"
            f"loads:{sum(loads)};gathers:{sum(gathers)};stores:{sum(stores)}")
    # the loops made compactable: of a kernel's rungs the most (a rung no
    # wider than a chunk compacts nothing), summed over the kernels
    compact: dict = {}
    for i in leaves:
        if i.compact:
            compact[i.name] = max(compact.get(i.name, ()), i.compact)
    if compact:
        loops, widths, gathered, scattered, ordered = zip(*compact.values())
        meta["compact"] = (
            f"loops:{sum(loops)};"
            f"width:{'+'.join(str(w) for w in sorted(set(widths)))};"
            f"gathered:{sum(gathered)};scattered:{sum(scattered)};"
            f"ordered:{sum(ordered)}")
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
        # the launch-invariant views of the arrays its kernels only read,
        # shared by its launchers, and the positions no kernel of a launch
        # stores to (kernel names -> positions)
        self.kept_views = _KeptViews()
        self._frozen: dict[tuple, frozenset] = {}
        self._cooperates: dict[str, bool] = {}
        self._vector_widths: dict[str, tuple] = {}
        # partition-safety/flag-soundness verification (analysis/):
        # access summaries build once per kernel on first verify();
        # launch verdicts cache per (names, flag rows, window).  Both
        # dicts are written lock-free by design — concurrent misses
        # recompute the same immutable value, and the serve submit hot
        # path must not grow a lock for a cache read.
        self._analysis_summaries: dict[str, Any] | None = None
        self._verdict_cache: dict[tuple, Any] = {}
        self._roaming: dict[tuple, frozenset] = {}  # roaming_stores' answers

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

    def vector_widths(self, name: str) -> tuple:
        """``N`` of every ``__global floatN*`` parameter of kernel ``name`` by
        its position among the array parameters (0 for a scalar pointer);
        ``()`` for a kernel with none (docs/KERNEL_LANGUAGE.md, *Vector
        types*)."""
        hit = self._vector_widths.get(name)
        if hit is None:
            kdef = self._c_kernels.get(name)
            hit = tuple((lang.vector_of(p.ctype) or (None, 0))[1]
                        for p in kdef.params if p.is_pointer) if kdef else ()
            hit = self._vector_widths[name] = hit if any(hit) else ()
        return hit

    def cooperates(self, name: str) -> bool:
        """Do the work items of kernel ``name`` cooperate inside their group
        (a ``__local`` array or a barrier)?  Its launches then cover whole
        work-groups (docs/KERNEL_LANGUAGE.md, *Work-group cooperation*)."""
        hit = self._cooperates.get(name)
        if hit is None:
            kdef = self._c_kernels.get(name)
            hit = self._cooperates[name] = (kdef is not None
                                            and codegen.cooperates(kdef))
        return hit

    def frozen(self, names: tuple) -> frozenset:
        """The positions of a launch's buffer tuple that NO kernel among
        ``names`` stores to (a C kernel's stores are its statements'; a
        Python kernel may replace every array it takes): the arrays whose
        views a launch of these kernels may keep."""
        hit = self._frozen.get(names)
        if hit is None:
            widest = max(self.array_param_count(n) for n in names)
            hit = self._frozen[names] = frozenset(range(widest)).difference(
                *(self._stored(n) for n in names))
        return hit

    def _stored(self, name: str) -> tuple:
        """The positions of the array parameters a launch of ``name`` may
        replace: those a C kernel's statements store to, every one of a
        Python kernel's."""
        if name not in self._c_kernels:
            return tuple(range(self.array_param_count(name)))
        kdef = self._c_kernels[name]
        stores = codegen._stored_bufs(kdef.body)
        return tuple(i for i, p in enumerate(
            p for p in kdef.params if p.is_pointer) if p.name in stores)

    def _ladder_asks(self, rungs, frozen: frozenset):
        """``ask(bufs) -> specs`` of a ladder (:class:`_LadderLauncher`)
        whose kernels' launchers are ``rungs()``: ``(launcher, values)``,
        one a kernel (a kernel's chunks ask alike)."""
        def ask(bufs) -> tuple:
            specs: set = set()
            for fn, values in rungs():
                n_arr = len(fn.info.array_params)
                specs.update(s for s in fn.wants(
                    bufs[:n_arr], values, fn.keys_of(values))
                    if s.param in frozen)
            return tuple(sorted(specs))

        return ask

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

    def verify(self, kernel_names, flag_rows, window: bool = False,
               exchange: bool = False, lanes: int | None = None):
        """Cached :class:`~..analysis.LaunchVerdict` for one launch
        shape.  ``flag_rows`` is a tuple of
        :func:`~..analysis.flag_row` tuples (positional, the call's
        parameter order).  Verification runs once per distinct
        (kernel sequence, flags, window, exchange, one lane or not) —
        every later call is one dict lookup.  ``exchange``: the caller keeps
        every proved reach current before the sequence runs
        (``verify_launch``); ``lanes``: how many lanes the launch runs on
        (one lane holds the whole range: no ``scatter-write``)."""
        key = (tuple(kernel_names), tuple(flag_rows), bool(window),
               bool(exchange), lanes == 1)
        v = self._verdict_cache.get(key)
        if v is None:
            from .. import analysis

            try:
                v = analysis.verify_launch(
                    self.summaries(), key[0], key[1], window=key[2],
                    exchange=key[3], lanes=1 if key[4] else None)
            except Exception:  # noqa: BLE001 - verifier must never
                # sink a compute; an empty verdict is "nothing proven"
                v = analysis.LaunchVerdict(findings=())
            self._verdict_cache[key] = v
        return v

    def roaming_stores(self, kernel_names, epws) -> frozenset:
        """Positions, among the call's array parameters, that some kernel
        of the sequence stores to at an index NOT confined to the work
        item's own elements (gathered, uniform, shifted, strided): a store
        of one chunk's launch may land in another chunk's elements.  The
        STREAM engine moves such an array whole, before the first launch
        and after the last (``Phases._streamed``): a chunk uploaded
        behind the launch that scattered into it would bury the store, a
        chunk downloaded before a later launch's store would miss it.
        ``epws``: the parameters' elements per work item, by position.
        Cached per sequence; a kernel outside the analyzable surface
        contributes nothing (its stores are unknown, as they were)."""
        key = (tuple(kernel_names), tuple(epws))
        hit = self._roaming.get(key)
        if hit is None:
            from .. import analysis

            found = set()
            summaries = self.summaries()
            for name in key[0]:
                s = summaries.get(name)
                if s is None:
                    continue
                for pos, pname in enumerate(s.array_params[:len(key[1])]):
                    if any(analysis.classify(acc.av, max(1, key[1][pos]))[0]
                           != "confined" for acc in s.writes.get(pname, ())):
                        found.add(pos)
            hit = self._roaming[key] = frozenset(found)
        return hit

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
        kept = self._stored(name)
        # the value arguments that key the executable: a C kernel's pitches,
        # where the vectorized lowering built it (which can use them)
        pitches = (codegen.pitch_params(self._c_kernels[name])
                   if name in self._c_kernels and info.lowering == "xla" else ())
        # whichever lowering built it: the XLA module reads jit_<kernel>
        raw_fn.__name__ = codegen.hlo_name(name)
        # the vectorized lowering is the one that asks for views; what it
        # asks for, the kernel's launchers of every chunk share
        xla = name in self._c_kernels and info.lowering == "xla"
        jitted = _KernelLauncher(
            raw_fn,
            f"{name} chunk={chunk} lr={local_size} g={global_size} "
            f"{platform}" + ("" if in_range else " beyond-range"),
            info, static, kept, pitches,
            self.kept_views if xla else None,
            (name, local_size, global_size, platform, in_range),
            tuple(p.ctype for p in self._c_kernels[name].params
                  if not p.is_pointer) if name in self._c_kernels else None)
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
        ordered = names + ((sync_kernel,) if sync_kernel else ())
        frozen = self.frozen(ordered)

        def rung(name: str, chunk: int):
            return self.launcher(name, chunk, local_size, global_size,
                                 platform, in_range)

        def run_names(names_seq, offset0, bufs, views):
            for name in names_seq:
                off = offset0
                n_arr = self.array_param_count(name)
                for chunk in chunks:
                    fn, rungs[name, chunk] = rung(name, chunk)
                    out = fn(off, bufs[:n_arr], vals_for(name),
                             _views_of(fn, bufs[:n_arr], vals_for(name), views))
                    bufs = tuple(out) + bufs[n_arr:]
                    off = off + chunk
            info.rungs = tuple(rungs.values())
            return bufs

        def raw(offset, bufs: tuple, held=None, views=None):
            # ``bufs``: the buffers that move through the ladder; ``held``
            # (position -> array) and the kept ``views`` are closed over
            held, views = held or {}, views or {}
            n = len(bufs) + len(held)

            def run(names_seq, b):
                return _moving(run_names(
                    names_seq, offset, _merged(b, held, n), views), held)

            bufs = tuple(bufs)
            if repeats <= 1:
                return run(names, bufs)
            if sync_kernel:
                def body(_, b):
                    return run((sync_kernel,), run(names, b))

                bufs = lax.fori_loop(0, repeats - 1, body, bufs)
                return run(names, bufs)
            return lax.fori_loop(0, repeats, lambda _, b: run(names, b), bufs)

        raw.__name__ = "seq_" + codegen.hlo_name(*names)
        jitted = _LadderLauncher(
            jax.jit(raw),
            f"seq:{'+'.join(names)} x{repeats} g={global_size} {platform}",
            info,
            self._ladder_asks(lambda: [(rung(n, chunks[0])[0], vals_for(n))
                                       for n in dict.fromkeys(ordered)],
                              frozen),
            self.kept_views)
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

        def rung(name: str, chunk: int):
            return self.launcher(name, chunk, local_size, global_size,
                                 platform, in_range)

        def run_ladder(offset, units, bufs, held, views):
            n = len(bufs) + len(held)
            for name in names:
                n_arr = self.array_param_count(name)
                va = vals_for(name)
                off = jnp.asarray(offset, jnp.int32)
                for k in reversed(range(nbits)):
                    chunk = step << k
                    fn, rungs[name, chunk] = rung(name, chunk)
                    bit = (jnp.asarray(units, jnp.int32) >> k) & 1

                    def hit_branch(b, _fn=fn, _off=off, _va=va, _n=n_arr):
                        b = _merged(b, held, n)
                        out = _fn(_off, b[:_n], _va,
                                  _views_of(_fn, b[:_n], _va, views))
                        return _moving(tuple(out) + b[_n:], held)

                    bufs = lax.cond(
                        bit != 0, hit_branch, lambda b: tuple(b), tuple(bufs)
                    )
                    off = off + bit * chunk
            info.rungs = tuple(rungs.values())
            return bufs

        def raw(offset, units, iters, bufs: tuple, held=None, views=None):
            # ``bufs``: the buffers that move through the ladder (donated
            # where ``donate``); ``held`` (position -> array) and the kept
            # ``views`` are closed over by the loop: invariants, not carried
            held, views = held or {}, views or {}
            bufs = tuple(bufs)
            return lax.fori_loop(
                0, iters,
                lambda _, b: run_ladder(offset, units, b, held, views), bufs
            )

        raw.__name__ = "fused_" + codegen.hlo_name(*names)
        jitted = _LadderLauncher(
            jax.jit(raw, donate_argnums=(3,) if donate else ()),
            f"fused:{'+'.join(names)} step={step} g={global_size} "
            f"{platform}", info,
            self._ladder_asks(lambda: [(rung(n, step)[0], vals_for(n))
                                       for n in dict.fromkeys(names)],
                              self.frozen(names)),
            self.kept_views)
        with self._lock:
            self._cache[key] = (jitted, info)
        return jitted
