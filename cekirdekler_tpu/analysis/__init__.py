"""ckprove — kernel partition-safety & flag-soundness verification.

The framework's single riskiest user contract is invisible to every
runtime check: a kernel plus its per-array transfer flags
(``arrays/clarray.py`` ``TransferFlags``) is *assumed* safe to split
across lanes.  A mis-declared flag (``partial_read`` on an array the
kernel gathers from; ``write_only`` on an array it reads first) or a
non-gid-confined access (a write landing outside the caller's
partition) silently corrupts results or wastes H2D bytes — the exact
failure mode the reference's ``partialRead`` hints carry, and one the
serving tier now accepts from untrusted tenants.

This package is a pure-AST abstract interpreter over the kernel
language's parse tree (``kernel/lang.py`` nodes — **no jax import**,
the ckcheck run-anywhere discipline): it tracks index provenance from
``get_global_id(0)`` through arithmetic, loops, branches and helper
calls to every ``Index`` read/write site, summarizes each array's
access pattern (gid-affine interval with halo width / uniform /
gather / read-before-write), and proves or refutes split-safety and
flag soundness against the declared :class:`TransferFlags`.

Three consumers:

- :class:`~cekirdekler_tpu.kernel.registry.KernelProgram` summarizes
  once per source and caches launch verdicts;
- ``Cores.compute`` gates on the verdict (advisory by default;
  ``CK_KERNEL_VERIFY=strict`` raises
  :class:`~cekirdekler_tpu.errors.KernelVerifyError` with the named
  finding and source line);
- serve admission rejects unsafe jobs with a named ``ServeRejected``
  reason, recorded replayably (``ckreplay verify``).

The CLI is ``python -m tools.ckprove`` (ratcheted baseline, ``--json``,
``--explain``, ``// ckprove: ok`` source suppressions).  The
correctness anchor is the differential oracle in
``tests/kernel_corpus.py``: every verdict is checked against ground
truth by running each corpus kernel split across virtual lanes vs
unsplit and comparing bit-exactly.

The package's other analyzer, :mod:`.model` (``tools/ckmodel``), is
deliberately NOT imported here: it is the bounded exhaustive model
checker for the pure controller state machines, and it imports the
LIVE runtime (driving the real `drain_transition`/`Membership`/
`admit_decision`/`plan_coalesce`/`load_balance` is its whole point) —
keeping it out of this namespace preserves ckprove's jax-free
stub-load path.
"""

from .interp import AV, Access, KernelSummary, summarize_kernel
from .verdict import (
    ADVISORY_KINDS,
    ERROR_KINDS,
    VERDICT_KINDS,
    Finding,
    FlagRow,
    LaunchVerdict,
    Reach,
    classify,
    flag_row,
    reach_of,
    structural_findings,
    suppressed_lines,
    verify_launch,
)

__all__ = [
    "AV",
    "Access",
    "ADVISORY_KINDS",
    "ERROR_KINDS",
    "Finding",
    "FlagRow",
    "KernelSummary",
    "LaunchVerdict",
    "Reach",
    "VERDICT_KINDS",
    "classify",
    "flag_row",
    "reach_of",
    "structural_findings",
    "summarize_kernel",
    "suppressed_lines",
    "verify_launch",
]
