"""Where a Pallas kernel lowers: Mosaic on a TPU, the interpreter elsewhere.

One rule for every ``pallas_call`` the package ships
(``lax.platform_dependent``).  Under ``jit`` — every launcher the kernel
registry builds, and ``saxpy``, ``mandelbrot_pallas`` and the flash
kernels, which are jitted where they are defined — XLA picks the branch at
lowering time for the platform being lowered for: the device the
computation is DISPATCHED to, never the process's default backend.  A TPU +
host-CPU fleet running one kernel gets Mosaic on the chip lane and the
interpreter on the host lane from the same traced function.

Called EAGERLY, outside any jit (``map_blocks`` used directly),
``platform_dependent`` has no lowering to wait for and resolves against the
process's DEFAULT backend, not the operands' device: on host-CPU arrays in
a TPU process it asks for Mosaic, which fails to compile — loudly, never a
quiet interpreter.  Wrap the call in ``jax.jit`` or pass ``interpret``.

``interpret=True``/``False`` from a caller forces one lowering.
"""

from __future__ import annotations

from typing import Callable

from jax import lax

__all__ = ["call_by_platform"]


def call_by_platform(interpret: bool | None, make_call: Callable, *operands):
    """``make_call(interpret)(*operands)`` with ``interpret`` resolved per
    platform when the caller passed ``None`` (module docstring: the dispatch
    platform under ``jit``); only the chosen branch is compiled."""
    if interpret is not None:
        return make_call(bool(interpret))(*operands)
    return lax.platform_dependent(
        *operands, tpu=make_call(False), default=make_call(True))
