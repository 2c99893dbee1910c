"""The launchers of the benchmark's kernels are the programs they were.

Every kernel :data:`PINNED` names (a file of ``benchmark/configs`` and a
kernel of it) is built at a small size through ``KernelProgram.launcher`` and
the text of its lowering hashed.  The scalar kernels' hashes were taken ONCE,
on f630f00 (PR 50's parent) with :func:`build_sha` itself; none of those
kernels names a vector type, so a change to the language's vector forms that
moves one of them has changed a scalar kernel's program: it fails here, on the
CPU, not in the driver's check of the cells.  ``shoc_md.cl``'s is PR 50's own:
a later change to ``kernel/vectors.py`` that alters ``compute_lj_force``'s
program fails here too.  ``shoc_reduction.cl``'s was TAKEN ANEW by PR 51, which
means to change that lowering and no other (the passes every lane of its walk
makes run with no mask: ``codegen._common_walks``); the other eleven rows are
untouched, and tests/test_peeled_loops.py holds ``reduce`` with that analysis
switched off to the hash this row had.  ``rodinia_bfs.cl``'s ``BFS_1`` was TAKEN
ANEW by PR 53, which means to change that lowering and no other, and found
it the SAME: at this module's chunk (1024 lanes, under
``codegen._COMPACT_WIDTH``) no loop is compacted, and the order of the
compacted lanes is all PR 53 touches.  So the row stands and a second one,
:data:`WIDE`, holds ``BFS_1`` at a chunk of 16 384 lanes, where its adjacency
loop is built compacted and its entering lanes go to their chunks by trip
count: PR 53's own hash, and beside it the hash of the same build with the
key switched off, which is the parent's program (taken on 2dcc342).

A configuration that brings a new ``.cl`` file ADDS its rows (with an empty
hash first: the failing assertion shows the one built); a PR that means to
change a lowering takes the rows it moves anew and says so.  Nothing here
scans the directory: a new file breaks no test of this module.
"""

import hashlib
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
CHUNK, LOCAL, RANGE, ELEMENTS = 1024, 256, 4096, 4096

# (file, kernel) -> sha256 of the launcher's lowering: on f630f00 but for
# shoc_md.cl's, which is PR 50's, and shoc_reduction.cl's, which is PR 51's
PINNED = {
    ('hpcg_spmv.cl', 'spmv'):
        "8548704244454052be315cdd13728139443dbb4d92d7997ff4f9187006ac1d5d",
    ('mandelbrot_frame.cl', 'mandelbrot'):
        "a01732a99588a5bb04d8cb880cf849f8c15a3f6a74d3c7fb57f364750ffa63e1",
    ('nbody_direct.cl', 'nBody'):
        "fb5287b7eb1be3f0e8e421faa3f768f1db2a7116fc70c5fc48093f65fc9efd65",
    ('polybench_mvt.cl', 'mvt_kernel1'):
        "b0c65d80680c131b14ed608862984e1048e1b4667414f5288a11149e60327b97",
    ('polybench_mvt.cl', 'mvt_kernel2'):
        "265cf4eda58e1d980cf59153bf3792719fc2908084a097652180b1e09569c197",
    ('rodinia_bfs.cl', 'BFS_1'):
        "e7d0fb86a03281a97d1e190d8f12fbe2dbc7f1f312d28ae6cd09fcff6b5d0167",
    ('rodinia_bfs.cl', 'BFS_2'):
        "0adc02df78a0b6fc12581bbab5f427d34288958148f5f0e16e0dbf101d1c4ba3",
    ('shoc_reduction.cl', 'reduce'):
        "00948c1565b76c52c20fa6b31ac76352a45ba2eb7243cca10761f6fcc75c17b4",
    ('stream_triad.cl', 'triad'):
        "33e6146176337c7dafea58b30cc0764bd42102a66f7c0fe18c0e021677e75e6d",
    ('wave_membrane.cl', 'waveStep'):
        "fd9478c65d8b5015f791a06834ebdc500a86b8c8236c6db7cbd64ab195d21792",
    ('wave_membrane.cl', 'rotate'):
        "5572ea0966a36c1271b9dc7a99fc843ab4a8c9eeabbb8d1dde9c508e9f22e84d",
    ('shoc_md.cl', 'compute_lj_force'):
        "3ca20706575feeef5f45d08fb9662c8c112a220e7d28d4de829798b4d2bad60b",
}


def value_of(ctype: str):
    """A run-time scalar of the parameter's type; an ``int`` as a plain
    Python integer, as a caller hands a pitch (the launcher keys it)."""
    if ctype == "int":
        return 64
    return np.dtype(codegen.ctype_to_dtype(ctype)).type(
        1.5 if ctype in ("float", "double", "half") else 64)


# ``BFS_1`` over a chunk wider than ``_COMPACT_WIDTH``: as built by PR 53,
# and with ``_chunk_lanes`` handed no key, which is the program on 2dcc342,
# PR 53's parent
WIDE = 16384
PINNED_WIDE = {
    "keyed": "5c40ec551f8dac1e787d7860399b0cab021a7f4151f3cb97abed51d3ed9011d4",
    "rising": "319c58ec56de73def6c87b715f87c3afcef37f9bed8f00d9318f05ed6b2d7fae"}


def build_sha(src_file: str, kernel: str, chunk: int = CHUNK) -> str:
    with open(os.path.join(CONFIGS, src_file), encoding="utf-8") as f:
        source = f.read()
    kdef = next(k for k in lang.parse_kernels(source) if k.name == kernel)
    # a ``floatN*`` parameter binds N floats a work item
    typed = [(p, lang.vector_of(p.ctype) or (p.ctype, 1))
             for p in kdef.params if p.is_pointer]
    elements = max(ELEMENTS, chunk)
    arrays = tuple(
        jax.ShapeDtypeStruct((elements * n,), codegen.ctype_to_dtype(elem))
        for _p, (elem, n) in typed)
    values = tuple(value_of(p.ctype) for p in kdef.params if not p.is_pointer)
    fn, _info = KernelProgram(source).launcher(kernel, chunk, LOCAL,
                                               max(RANGE, chunk))
    text = fn.trace(0, arrays, values, fn.keys_of(values)).lower().as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("src_file,kernel", sorted(PINNED))
def test_a_pinned_kernel_builds_the_program_it_built(src_file, kernel):
    assert build_sha(src_file, kernel) == PINNED[src_file, kernel]



@pytest.mark.parametrize("order", sorted(PINNED_WIDE))
def test_the_compacted_bfs_launcher_is_pinned_with_and_without_its_key(
        order, monkeypatch):
    if order == "rising":
        real = codegen._chunk_lanes
        monkeypatch.setattr(codegen, "_chunk_lanes",
                            lambda entered, width, trips=None: real(entered, width))
    assert build_sha("rodinia_bfs.cl", "BFS_1", WIDE) == PINNED_WIDE[order]
