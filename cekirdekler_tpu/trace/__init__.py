"""``cekirdekler_tpu.trace`` — span-based attribution: explain every
lost millisecond.

Five pieces (see ``docs/OBSERVABILITY.md`` for the guided tour):

- :mod:`.spans` — the process-global :data:`TRACER`: a lock-free-ish
  ring buffer of typed spans (enqueue, split, rebalance, launch, fence,
  upload, download, pipeline-stage, pool-task, dcn-exchange, ...)
  recorded by every runtime layer, and the same spans as ``ck/<kind>``
  annotations in any running ``jax.profiler`` session; a no-op when
  neither is on (<1 µs/span, pinned by test).
- :mod:`.attribution` — per-window "where did the time go" reports
  reconciling host wall time against span totals and device-busy time,
  plus the per-compute-id fence split that fixes the one-fence-time-
  for-all-cids balancer distortion.
- :mod:`.export` — Chrome-trace (``chrome://tracing`` / Perfetto) JSON
  export and the plain-text table.
- :mod:`.ceiling` — the overlap ceiling re-derived from same-rep duplex
  probes with a witness clamp, so ``achieved_vs_ceiling`` is a real
  ratio-to-a-bound (≤ 1 structurally) with per-rep spread.
- :mod:`.aggregate` — cluster-wide aggregation: DCN worker processes
  ship span batches + metric snapshots with RTT-symmetric clock-offset
  estimation, producing ONE merged, alignment-checked Perfetto trace
  for an N-process job.
- :mod:`.device` — device-timeline attribution on the ``jax.profiler``
  capture seam: launch marks (``MARKS``), per-kernel device profiles
  reconciled against the host window, roofline rows, the unified
  host+device Perfetto export, and the persistent on-disk kernel-
  profile store (``CK_PROFILE_STORE``).

None of these import jax at module level: enabling tracing costs no
backend initialization.
"""

from .aggregate import (
    ClusterSnapshot,
    collective_consistency,
    estimate_clock_offsets,
    gather_cluster,
    merged_chrome_trace,
)
from .attribution import AttributionReport, split_fence_benches, window_report
from .ceiling import RepSample, ceiling_report, rep_ceiling
from .device import (
    DEVICE_SPAN_KINDS,
    MARKS,
    STORE,
    DeviceCapture,
    DeviceWindowReport,
    ProfileStore,
    capture_device,
    profilez_payload,
    roofline_row,
    split_unified_trace,
    unified_chrome_trace,
)
from .export import (
    from_chrome_trace,
    save_chrome_trace,
    text_table,
    to_chrome_trace,
)
from .spans import SPAN_KINDS, TRACER, Span, Tracer, tracing

__all__ = [
    "AttributionReport",
    "ClusterSnapshot",
    "DEVICE_SPAN_KINDS",
    "DeviceCapture",
    "DeviceWindowReport",
    "MARKS",
    "ProfileStore",
    "RepSample",
    "SPAN_KINDS",
    "STORE",
    "Span",
    "TRACER",
    "Tracer",
    "capture_device",
    "ceiling_report",
    "collective_consistency",
    "estimate_clock_offsets",
    "from_chrome_trace",
    "gather_cluster",
    "merged_chrome_trace",
    "profilez_payload",
    "rep_ceiling",
    "roofline_row",
    "save_chrome_trace",
    "split_fence_benches",
    "split_unified_trace",
    "text_table",
    "to_chrome_trace",
    "tracing",
    "window_report",
]
