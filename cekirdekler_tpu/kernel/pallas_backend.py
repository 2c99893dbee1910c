"""Pallas tile lowering for the kernel language — the TPU-native driver JIT.

The XLA lowering (codegen.py) vectorizes a kernel over the whole launch
chunk: every local variable becomes a ``(B,)`` array, and a ``while`` loop's
state streams through HBM on EVERY iteration — for iteration-heavy kernels
(mandelbrot's escape loop) that is HBM-bound and ~4-5x off the pace of a
hand-tiled Pallas kernel whose state lives in VMEM (ops/mandelbrot.py;
measured before the chip moved into the sandbox, its records deleted).

This backend closes that gap for kernels whose buffer accesses fall in
three statically-recognizable classes (discovered by a shape-only probe,
``jax.eval_shape`` — no device work):

1. **Elementwise** — ``buf[i]`` with ``i`` affine in ``get_global_id(0)``,
   stride 1, shift 0.  The work-item vector becomes a ``(rows, 128)`` VMEM
   block; loop carries stay on-chip; per-tile ``while`` loops exit early
   the moment their tile's items are all done (the XLA lowering must run
   every iteration until the LAST item of the whole chunk finishes).

2. **Shifted windows** — ``buf[i + c]`` with Python-int ``c`` (stencils,
   the waveEquation shape, Kamera.cs:233-268).  The array gets ONE extra
   halo input: the edge-padded buffer windowed per tile with
   element-granular row offsets (``pl.BlockSpec(pl.Element(rows + 2H))``),
   and the flat shift is realized entirely in VMEM as a lane roll
   (``pltpu.roll``) plus a lane-iota select between adjacent row slices —
   no per-shift HBM copies (the XLA lowering materializes one padded copy
   of the buffer per distinct shift).  Edge padding gives the same
   clamp-to-nearest out-of-bounds semantics as the other load paths.

3. **Lane-uniform gathers** — ``buf[j]`` where ``j`` is provably identical
   in every lane (codegen's ``_uniform_vars`` analysis; the n-body inner
   loop streaming a second buffer, Tester.cs:7682-7799).  The whole buffer
   rides as an SMEM operand and the load is ONE scalar read broadcast by
   the VPU — the tile's compute loop never touches HBM.  Buffers larger
   than :data:`SMEM_UNIFORM_LIMIT` bytes delegate the launch to the XLA
   lowering (decided at trace time from real shapes, inside the same
   jitted function).

Kernels outside the union (per-lane gathers ``x[idx[i]]``, traced shift
amounts, stores to an array that is also shift/uniform-read — the tile
would read stale neighbors) raise :class:`PallasUnsupported` during the
probe, and the registry falls back to the XLA lowering.  Mosaic
constraints handled here, matching the hand kernel's workarounds: no bool
arrays in while carries (masks ride as f32 0/1) and no replicated-layout
(constant) carries (scalars broadcast through a computed zero).

Reference mapping: this replaces the OpenCL driver JIT the reference
delegates to (ClProgram.cs:62-73 createProgram → clBuildProgram); the
tiling contract mirrors SURVEY.md §7 "step = 8*128 multiples".
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..errors import KernelCompileError
from . import codegen, lang
from .codegen import KVal, KernelBuildInfo, _Ctx, ctype_to_dtype

__all__ = ["PallasUnsupported", "build_kernel_fn_pallas", "LANES",
           "SMEM_UNIFORM_LIMIT"]

LANES = 128          # TPU lane width
DEFAULT_ROWS = 256   # most tile rows per grid step (matches ops/mandelbrot.py)
# Of the 64 vector registers (one holds 8 rows x 128 lanes of a tile), those
# the tiles that a counted loop keeps alive across its passes may take; the
# rest is the pass's temporaries.  48 is what nBody's loop runs at in a
# 64-row tile (six tiles x 8 registers: 55 cycles a pass, 6.9 a register);
# at 256 rows the six are 192 registers and every pass loads and stores them
# (301 cycles, 9.4 a register).  The sweep that set it: PERF.md, PR 36.
LOOP_LIVE_VREGS = 48
MAX_HALO_ROWS = 32   # largest halo: |shift| <= 32*128 = 4096 elements
# uniform-read buffers larger than this many BYTES fall back to the XLA
# lowering (512 KB verified to fit this chip's SMEM; headroom kept for
# scalars/offsets)
SMEM_UNIFORM_LIMIT = 512 * 1024


class PallasUnsupported(Exception):
    """Kernel is outside the Pallas tile subset — use the XLA path."""


@dataclass
class _Accesses:
    """Per-array access classes discovered by the probe pass."""

    elem: set[str] = field(default_factory=set)      # shift-0 loads
    shifts: dict[str, set[int]] = field(default_factory=dict)  # nonzero
    uniform: set[str] = field(default_factory=set)   # lane-uniform loads
    stored: set[str] = field(default_factory=set)
    # the most tiles a counted loop keeps alive across its passes
    live: int = 0


class _PallasCtx(_Ctx):
    """Interpreter context whose work-item vectors are (rows, 128) tiles.

    Runs in two modes: *record* (``record`` is an :class:`_Accesses`;
    every load/store classifies itself or raises) and *build* (``record``
    is None; loads consult the prepared halo blocks / SMEM refs)."""

    pallas = True

    def __init__(self, rows: int, offset, global_size, local_size: int, info: dict,
                 record: _Accesses | None = None, halo_h: int = 0):
        super().__init__(rows * LANES, offset, global_size, local_size, info)
        self.shape = (rows, LANES)
        self.rows = rows
        self.record = record
        self.halo_h = halo_h          # halo rows H (build mode)
        self.halo_blocks: dict[str, Any] = {}   # name -> (rows+2H, 128) value
        self.smem_refs: dict[str, tuple[Any, int]] = {}  # name -> (ref, length)
        # shifted-tile cache rides in _pad_cache[name][c]: the loop
        # machinery (codegen._exec_loop) clears _pad_cache at loop-body
        # entry and after the loop, which is exactly the tracer-leak
        # discipline the shift cache needs too
        r = lax.broadcasted_iota(jnp.int32, self.shape, 0)
        c = lax.broadcasted_iota(jnp.int32, self.shape, 1)
        # offset already includes program_id * rows * LANES (see _tile_kernel)
        self.gid = KVal(offset + r * LANES + c, "int", affine=(1, 0))
        # computed zero: a FLOAT zero derived from the runtime offset —
        # int x*0 folds algebraically back to a replicated constant, but
        # float x*0.0 cannot be folded without a finiteness proof (the same
        # trick as the hand kernel's `cx * 0.0`, ops/mandelbrot.py), so this
        # keeps a materialized Mosaic layout
        self._zero_f32 = self.gid.value.astype(jnp.float32) * 0.0

    def broadcast_scalar(self, val, dtype):
        # constant jnp.full gets a REPLICATED Mosaic layout that cannot be
        # relaid out to the loop body's computed carries; adding through a
        # computed zero forces a materialized layout
        return self._zero_f32.astype(dtype) + jnp.asarray(val, dtype)

    def force_computed(self, vec):
        return self._zero_f32.astype(vec.dtype) + vec

    def any_lane(self, mask):
        return jnp.sum(mask.astype(jnp.float32)) > 0.0

    def counted_loop(self, node, lane_vars: list, carried_bufs: list) -> None:
        # what a counted loop holds in registers from pass to pass, in tiles:
        # the locals it carries that differ from lane to lane, the tile-shaped
        # locals of outside that it reads (but the work item's id plus a
        # constant: an index, and an iota away), and the buffers whose tile
        # it stores to or reads at the lane's own index.  The same at any
        # rows: the probe counts it before the rows are settled (_fit_rows)
        if self.record is None:
            return
        held = set(lane_vars) | {
            k for k in codegen._vars_read(node) if k in self.env
            and self.env[k].affine is None
            and getattr(codegen._num(self.env[k]), "ndim", 0) > 0}
        tiles = set(carried_bufs) | {
            ix.base for ix in codegen._index_nodes(node)
            if not codegen._expr_uniform(ix.index, self.uniform_vars)}
        self.record.live = max(self.record.live, len(held) + len(tiles))

    # -- load/store classification ---------------------------------------

    def _uniform_index(self, node: lang.Index) -> bool:
        return codegen._expr_uniform(
            node.index, self.uniform_vars, frozenset(self.private)
        )

    def pallas_load(self, node: lang.Index, buf, ctype: str, idx: KVal) -> KVal:
        a = idx.affine
        if a is not None and a[0] == 1 and isinstance(a[1], int):
            c = a[1]
            if c == 0:
                if self.record is not None:
                    self.record.elem.add(node.base)
                    return KVal(buf, ctype)
                if node.base in self.halo_blocks:
                    # a shift-read array's center tap is served from its
                    # halo block too — the array then needs no separate
                    # tile window input (halving its HBM input traffic)
                    return KVal(self._shifted_tile(node.base, 0), ctype)
                return KVal(buf, ctype)
            if self.record is not None:
                self.record.shifts.setdefault(node.base, set()).add(c)
                return KVal(buf, ctype)  # placeholder: same tile shape
            return KVal(self._shifted_tile(node.base, c), ctype)
        if self._uniform_index(node):
            if self.record is not None:
                self.record.uniform.add(node.base)
                return KVal(buf[0, 0], ctype)  # scalar placeholder
            ref, n = self.smem_refs[node.base]
            iv = idx.value
            if hasattr(iv, "ndim") and iv.ndim > 0:
                iv = iv[(0,) * iv.ndim]  # provably uniform: take lane 0
            j = jnp.clip(jnp.asarray(iv, jnp.int32), 0, n - 1)
            return KVal(ref[j], ctype)
        raise PallasUnsupported(
            f"load {node.base}[...] is neither elementwise, statically "
            f"shifted, nor lane-uniform (Pallas tile path)"
        )

    def _shifted_tile(self, name: str, c: int):
        """The tile's window shifted by ``c`` flat elements, built from the
        halo block in VMEM: q rows + s lanes, s realized as a lane roll and
        a lane-iota select between adjacent row slices (proven on-device;
        no lane-granular slicing needed)."""
        cache = self._pad_cache.setdefault(name, {})
        if c in cache:
            return cache[c]
        from jax.experimental.pallas import tpu as pltpu

        H, rows = self.halo_h, self.rows
        blk = self.halo_blocks[name]     # (rows + 2H, LANES)
        q, s = divmod(c, LANES)          # python divmod: 0 <= s < LANES
        if s == 0:
            out = blk[H + q:H + q + rows, :]
        else:
            rolled = pltpu.roll(blk, LANES - s, axis=1)
            a_part = rolled[H + q:H + q + rows, :]
            b_part = rolled[H + q + 1:H + q + 1 + rows, :]
            lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
            out = jnp.where(lane < LANES - s, a_part, b_part)
        cache[c] = out
        return out

    def pallas_store(self, node: lang.Index, buf, ctype: str, idx: KVal, v) -> None:
        a = idx.affine
        if not (a is not None and a[0] == 1 and a[1] == 0):
            raise PallasUnsupported(
                f"store {node.base}[...] is not elementwise"
            )
        if self.record is not None:
            self.record.stored.add(node.base)
        # (dtype casting happens in codegen._store before this is called:
        # loads convert storage->declared ctype, stores convert back)
        m = self.active_mask()
        if m is not None:
            v = jnp.where(m, v, buf)
        self.bufs[node.base] = v
        self.stored.add(node.base)


def _probe(kernel: lang.KernelDef, rows: int, local_size: int, global_size: int,
           uniform_vars: set[str]) -> tuple[list[str], _Accesses]:
    """Shape-only dry run of the tile interpreter: classifies every buffer
    access (elementwise / shifted / uniform), discovers which params the
    kernel stores, and raises :class:`PallasUnsupported` for any access
    outside the subset.  No device work (jax.eval_shape)."""
    array_params = [p for p in kernel.params if p.is_pointer]
    value_params = [p for p in kernel.params if not p.is_pointer]
    stored: list[str] = []
    acc = _Accesses()

    def run(offset, arrays, values):
        ctx = _PallasCtx(rows, offset, global_size, local_size, {}, record=acc)
        ctx.adopt(kernel, uniform_vars)
        for p, arr in zip(array_params, arrays):
            ctx.bufs[p.name] = arr
            ctx.buf_ctypes[p.name] = p.ctype
        for p, v in zip(value_params, values):
            ctx.env[p.name] = KVal(v, p.ctype)
        codegen._exec_block(ctx, kernel.body)
        stored.extend(n for n in (p.name for p in array_params) if n in ctx.stored)
        return tuple(ctx.bufs[p.name] for p in array_params)

    shape = (rows, LANES)
    arrays = tuple(
        jax.ShapeDtypeStruct(shape, ctype_to_dtype(p.ctype)) for p in array_params
    )
    values = tuple(
        jax.ShapeDtypeStruct((), ctype_to_dtype(p.ctype)) for p in value_params
    )
    jax.eval_shape(run, jax.ShapeDtypeStruct((), jnp.int32), arrays, values)

    # a store into an array the kernel ALSO reads shifted or uniformly
    # would read stale neighbor data (other tiles' writes are unordered);
    # the XLA lowering sees in-chunk updates, so keep one semantics: bail
    mixed = acc.stored & (acc.uniform | set(acc.shifts))
    if mixed:
        raise PallasUnsupported(
            f"array(s) {sorted(mixed)} are stored AND shift/uniform-read; "
            "tile-parallel execution would read stale neighbors"
        )
    return stored, acc


def _mentions_half(kernel: lang.KernelDef) -> bool:
    """True if any declared ctype anywhere in the kernel (params, locals,
    casts, helpers) is 'half' — Mosaic rejects float16 tiles on this chip
    at compile time, PAST the registry's build-time fallback window, so
    half-typed kernels must be vetoed here even when no caller ARRAY is
    f16 (a half local or cast creates f16 tiles internally)."""
    return lang.any_node(kernel, lambda node: getattr(node, "ctype", None) == "half")


def _routing_veto(acc: _Accesses) -> None:
    """Measured routing policy (numbers from before the chip moved into the
    sandbox; their records are deleted and no ledger cell repeats them): kernels
    whose only non-elementwise accesses are shifted windows run FASTER
    through the XLA lowering (single-pass stencils are HBM-bound; XLA
    fuses the shifts into the consumer loop and across chained dispatches,
    while the halo path materializes a padded window copy per launch —
    wave 8-tap: 478 vs 255 GB/s effective).  Uniform-gather kernels are
    the opposite extreme (n-body: >20x for Pallas/SMEM).  So: shifted
    access WITHOUT any uniform access falls back to XLA; everything else
    stays on the tile path."""
    if acc.shifts and not acc.uniform:
        raise PallasUnsupported(
            "shift-only kernel routed to the XLA lowering "
            "(measured faster for single-pass stencils)"
        )


def _fit_rows(rows_total: int, cap: int, live: int = 0) -> int:
    """Rows of a grid step's tile for a chunk of ``rows_total`` rows: at most
    ``cap``, halved until they divide the chunk.  ``live`` is the tiles that
    the hungriest loop of a kernel whose loops are all counted keeps alive
    across its passes (0 for any other kernel): where they would overflow
    :data:`LOOP_LIVE_VREGS` every pass spills them, so such a kernel gets the
    largest power-of-two multiple of 8 rows that divides the chunk and at
    which they fit (8 where nothing fits), and the launch more grid steps.
    Each step runs the whole loop for its rows: no lane's operations change
    order.  A masked loop reduces its mask once a tile a pass and leaves
    early tile by tile, so it wants its tiles large (ops/mandelbrot.py)
    and is not fitted."""
    rows = min(cap, rows_total)
    while rows_total % rows != 0:
        rows //= 2
    if -(-rows // 8) * live > LOOP_LIVE_VREGS and rows_total % 8 == 0:
        fit = 8
        while (2 * fit <= rows and rows_total % (2 * fit) == 0
               and 2 * fit // 8 * live <= LOOP_LIVE_VREGS):
            fit *= 2
        rows = fit
    return rows


def _halo_rows(acc: _Accesses, rows: int, rows_total: int) -> int:
    """Halo depth H (rows) covering every shift; 0 when no shifts."""
    if not acc.shifts:
        return 0
    max_abs = max(abs(c) for cs in acc.shifts.values() for c in cs)
    h = -(-max_abs // LANES)  # ceil
    # block sublane dim (rows + 2H) must stay divisible by 8 unless the
    # block IS the whole array (grid == 1)
    if rows != rows_total:
        if rows % 8 != 0:
            raise PallasUnsupported(
                f"shifted access needs 8-row-aligned tiles (rows={rows})"
            )
        h = -(-h // 4) * 4
    if h > MAX_HALO_ROWS:
        raise PallasUnsupported(
            f"shift {max_abs} exceeds the halo budget "
            f"({MAX_HALO_ROWS * LANES} elements)"
        )
    return h


def _tile_kernel(kernel: lang.KernelDef, rows: int, local_size: int,
                 global_size: int, stored: list[str],
                 tile_names: list[str], halo_names: list[str],
                 smem_names: list[str], smem_lens: dict[str, int],
                 halo_h: int, uniform_vars: set[str]):
    """The pallas_call body: scalars arrive via SMEM (1,1) refs, array
    tiles / halo blocks via VMEM refs, uniform buffers via SMEM refs;
    stored params write to output refs."""
    array_params = [p for p in kernel.params if p.is_pointer]
    value_params = [p for p in kernel.params if not p.is_pointer]
    n_vals = len(value_params)
    n_tiles = len(tile_names)
    n_halos = len(halo_names)
    n_smem = len(smem_names)

    def body(*refs):
        offset_ref = refs[0]
        val_refs = refs[1:1 + n_vals]
        k = 1 + n_vals
        tile_refs = refs[k:k + n_tiles]
        halo_refs = refs[k + n_tiles:k + n_tiles + n_halos]
        smem_refs = refs[k + n_tiles + n_halos:k + n_tiles + n_halos + n_smem]
        out_refs = refs[k + n_tiles + n_halos + n_smem:]
        base = offset_ref[0, 0] + pl_program_id() * rows * LANES
        ctx = _PallasCtx(rows, base, global_size, local_size, {}, halo_h=halo_h)
        ctx.adopt(kernel, uniform_vars)
        for p in array_params:
            ctx.bufs[p.name] = None  # placeholder; real values set below
            ctx.buf_ctypes[p.name] = p.ctype
        for name, r in zip(tile_names, tile_refs):
            ctx.bufs[name] = r[:]
        for name, r in zip(halo_names, halo_refs):
            ctx.halo_blocks[name] = r[:]
        for name, r in zip(smem_names, smem_refs):
            ctx.smem_refs[name] = (r, smem_lens[name])
        for p, r in zip(value_params, val_refs):
            ctx.env[p.name] = KVal(r[0, 0], p.ctype)
        codegen._exec_block(ctx, kernel.body)
        for name, r in zip(stored, out_refs):
            r[:] = ctx.bufs[name]

    return body


def pl_program_id():
    from jax.experimental import pallas as pl

    return pl.program_id(0)


def _halo_window(arr, off, chunk: int, ph: int, halo_h: int):
    """The window ``arr[off-ph : off+chunk+ph]`` with clamp-to-edge
    out-of-bounds semantics, reshaped to ``(chunk/128 + 2*halo_h, 128)``,
    in O(window) work: clamped dynamic_slice + traced roll to realign +
    edge overwrite.  Falls back to a whole-buffer edge pad only when the
    buffer is smaller than the window."""
    n = arr.shape[0]
    L = chunk + 2 * ph
    rows_total = chunk // LANES
    if n < L:
        # covers whole-buffer launches too (n == chunk < L): the slice of
        # the length-L padded buffer clamps to offset 0 = the whole pad
        w = lax.dynamic_slice(jnp.pad(arr, (ph, ph), mode="edge"), (off,), (L,))
        return w.reshape(rows_total + 2 * halo_h, LANES)
    start = off - ph                      # may be < 0 or > n - L
    cs = jnp.clip(start, 0, n - L)
    w = lax.dynamic_slice(arr, (cs,), (L,))
    # realign so w[k] == arr[start + k] wherever start+k is in range
    w = jnp.roll(w, cs - start)
    k = jnp.arange(L, dtype=jnp.int32)
    w = jnp.where(start + k < 0, arr[0], w)
    w = jnp.where(start + k > n - 1, arr[n - 1], w)
    return w.reshape(rows_total + 2 * halo_h, LANES)


def build_kernel_fn_pallas(
    kernel: lang.KernelDef,
    chunk: int,
    local_size: int,
    global_size: int,
    block_rows: int | None = None,
    interpret: bool = False,
    force: bool = False,
    in_range: bool = True,
) -> tuple[Callable, KernelBuildInfo]:
    """Build the Pallas tile launch function for one kernel geometry.

    Same contract as :func:`codegen.build_kernel_fn`:
    ``fn(offset, arrays_tuple, values_tuple) -> updated arrays tuple`` over
    work items ``[offset, offset+chunk)`` with ``offset`` a runtime scalar.
    Raises :class:`PallasUnsupported` if the kernel is outside the tile
    subset, the chunk doesn't tile, or the measured routing policy prefers
    the XLA lowering for this access mix (``force=True`` skips the policy
    veto — used by tests and ``chip_smoke.py`` to exercise the halo path
    directly).  ``in_range`` is the XLA fallback's (codegen.build_kernel_fn).
    A tile's rows follow from the chunk and the kernel (:func:`_fit_rows`);
    ``block_rows`` caps them by hand and fits nothing (tests, sweeps)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if codegen.cooperates(kernel):
        # a tile is one grid step's work items, not a launch's work-groups:
        # the XLA half holds the group's row beside the lane vectors
        raise PallasUnsupported(
            "local-memory: the work items of a group cooperate (__local array "
            "or barrier); the vectorized lowering runs it")
    if lang.uses_vectors(kernel):
        # a tile is (rows, 128) work items of ONE scalar each: a vector's
        # planes and its N-element accesses are the XLA half's (vectors.py)
        raise PallasUnsupported(
            "vector-types: the kernel names a vector type (float4 ..); the "
            "vectorized lowering runs it")
    if chunk % LANES != 0:
        raise PallasUnsupported(f"chunk {chunk} not a multiple of {LANES}")
    if not interpret and _mentions_half(kernel):
        raise PallasUnsupported(
            "kernel declares 'half' types (Mosaic rejects f16 tiles)"
        )
    rows_total = chunk // LANES
    rows = _fit_rows(rows_total, block_rows or DEFAULT_ROWS)

    array_params = [p for p in kernel.params if p.is_pointer]
    value_params = [p for p in kernel.params if not p.is_pointer]
    uniform_vars = codegen._uniform_vars(
        kernel.body, {p.name for p in value_params}
    )
    stored, acc = _probe(kernel, rows, local_size, global_size, uniform_vars)
    if not force:
        _routing_veto(acc)
    counted, masked = codegen._loop_counts(kernel, uniform_vars)
    if block_rows is None and counted and not masked:
        rows = _fit_rows(rows_total, rows, acc.live)
    halo_h = _halo_rows(acc, rows, rows_total)

    # which inputs each array needs (an array can need several).  An
    # array with a halo block serves its center (shift-0) taps from that
    # block, so it takes a tile window only when stored (stores cannot
    # coexist with shift reads — probe's `mixed` check).
    halo_names = [p.name for p in array_params if p.name in acc.shifts]
    tile_names = [p.name for p in array_params
                  if (p.name in acc.elem and p.name not in acc.shifts)
                  or p.name in acc.stored]
    smem_names = [p.name for p in array_params if p.name in acc.uniform]

    info = KernelBuildInfo(
        name=kernel.name,
        array_params=[p.name for p in array_params],
        value_params=[p.name for p in value_params],
        array_ctypes={p.name: p.ctype for p in array_params},
        stored_params=list(stored),
        lowering="pallas",
    )
    info.loops_counted, info.loops_masked = counted, masked
    grid = rows_total // rows
    info.tile_rows, info.tile_grid, info.loop_live = rows, grid, acc.live
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    tile_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    halo_spec = pl.BlockSpec(
        (pl.Element(rows + 2 * halo_h), pl.Element(LANES)),
        lambda i, _r=rows: (i * _r, 0),
    )
    smem_full_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    stored_ix = {name: i for i, name in enumerate(info.array_params) if name in stored}
    name_ix = {p.name: i for i, p in enumerate(array_params)}
    ph = halo_h * LANES  # flat halo pad, elements

    # lazy XLA fallback for launches whose uniform-read buffers exceed the
    # SMEM budget — decided per concrete shape inside the traced fn
    _xla_fallback: list = []

    def xla_fn():
        if not _xla_fallback:
            f, _ = codegen.build_kernel_fn(
                kernel, chunk, local_size, global_size, "tpu", in_range)
            _xla_fallback.append(f)
        return _xla_fallback[0]

    def fn(offset, arrays: tuple, values: tuple = ()):
        if len(arrays) != len(array_params):
            raise KernelCompileError(
                f"kernel {kernel.name!r} takes {len(array_params)} array "
                f"argument(s), got {len(arrays)}"
            )
        # AGGREGATE budget: several uniform-read buffers share one SMEM,
        # so their sizes sum (3 x 480KB would pass a per-buffer check and
        # then fail Mosaic SMEM allocation at launch).  f16 arrays also
        # delegate: Mosaic rejects float16 tiles on this chip at compile
        # time — PAST the registry's build-time PallasUnsupported
        # fallback — so the dtype check must live here at trace time
        # (probed on-device, r4; bf16/f32/ints all compile fine).
        delegate = None
        if any(a.dtype == jnp.float16 for a in arrays):
            delegate = "float16 operand (Mosaic rejects f16 tiles)"
        elif sum(arrays[name_ix[n]].size * arrays[name_ix[n]].dtype.itemsize
                 for n in smem_names) > SMEM_UNIFORM_LIMIT:
            delegate = (f"uniform-read buffers exceed the "
                        f"{SMEM_UNIFORM_LIMIT}-byte SMEM budget")
        if delegate is not None:
            # recorded, not silent: the launcher's info names what ran
            info.lowering, info.veto = "xla", delegate
            return xla_fn()(offset, arrays, values)
        off = jnp.asarray(offset, jnp.int32)
        # window [offset, offset+chunk) of every elementwise/stored param
        windows = [
            lax.dynamic_slice(arrays[name_ix[n]], (off,), (chunk,))
            .reshape(rows_total, LANES)
            for n in tile_names
        ]
        # halo window [offset-ph, offset+chunk+ph) with out-of-range
        # elements clamped to the nearest valid one (same semantics as
        # the gather and padded-slice paths).  Built in O(window) work —
        # slice the unpadded buffer at a clamped start, realign by a
        # traced roll, and overwrite the (at most ph-deep) out-of-range
        # edges — NOT by edge-padding the whole buffer, which would cost
        # O(buffer) per launch on chunked multi-chip dispatches.
        halos = [
            _halo_window(arrays[name_ix[n]], off, chunk, ph, halo_h)
            for n in halo_names
        ]
        smem_bufs = [arrays[name_ix[n]] for n in smem_names]
        smem_lens = {n: arrays[name_ix[n]].shape[0] for n in smem_names}
        scalar_ops = [off.reshape(1, 1)] + [
            jnp.asarray(v, ctype_to_dtype(p.ctype)).reshape(1, 1)
            for p, v in zip(value_params, values)
        ]
        body = _tile_kernel(
            kernel, rows, local_size, global_size, stored,
            tile_names, halo_names, smem_names, smem_lens, halo_h,
            uniform_vars,
        )
        outs = pl.pallas_call(
            body,
            grid=(grid,),
            in_specs=(
                [scalar_spec] * len(scalar_ops)
                + [tile_spec] * len(windows)
                + [halo_spec] * len(halos)
                + [smem_full_spec] * len(smem_bufs)
            ),
            out_specs=[tile_spec] * len(stored),
            out_shape=[
                # the ACTUAL array dtype, not the declared ctype's: storage
                # keeps the caller's dtype when they differ (stores cast)
                jax.ShapeDtypeStruct(
                    (rows_total, LANES), arrays[name_ix[n]].dtype
                )
                for n in stored
            ],
            interpret=interpret,
            # the device operation carries the user's kernel's name
            name=codegen.hlo_name(kernel.name),
        )(*scalar_ops, *windows, *halos, *smem_bufs)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        result = list(arrays)
        for name, out in zip(stored, outs):
            i = stored_ix[name]
            flat = out.reshape(chunk)
            if arrays[i].shape[0] == chunk:
                result[i] = flat  # whole-buffer launch: the window IS the buffer
            else:
                result[i] = lax.dynamic_update_slice(arrays[i], flat, (off,))
        return tuple(result)

    return fn, info
