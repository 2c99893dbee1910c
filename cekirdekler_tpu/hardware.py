"""Hardware query DSL — platform/device discovery and fluent selection.

TPU-native analogue of the reference's ``Hardware.ClPlatforms`` /
``Hardware.ClDevices`` (ClObjectApi.cs:36-109,158-775,781-1272): a fluent,
copy-on-select device query API whose results feed the ``NumberCruncher``
constructor.  Platforms map to JAX/PJRT backends (``tpu``, ``cpu``, …);
devices map to ``jax.Device`` chips.  The reference's vendor filters
(intel/amd/nvidia/altera/xilinx) become backend/device-kind filters; its
micro-benchmark ranking ``devicesWithHighestDirectNbodyPerformance``
(ClObjectApi.cs:1222-1244) is reproduced by running the nbody workload on each
chip.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, Sequence

import jax

from .errors import DeviceSelectionError

__all__ = [
    "AcceleratorType",
    "Device",
    "Devices",
    "Platform",
    "Platforms",
    "platforms",
    "all_devices",
    "chip_devices",
    "DEVICE_PEAKS",
    "device_peaks",
    "HOST_PEAKS",
    "RATE_PRIORS",
    "rate_prior",
    "device_rank",
]


#: Device-kind → (peak dense-matmul Tflop/s at the native narrow dtype,
#: peak HBM GB/s), keyed on ``jax.Device.device_kind`` strings (public
#: chip specs).  THE source of roofline/MFU peaks:
#: ``trace/device.roofline_row`` defaults from here via
#: :func:`device_peaks` — an MFU printed on a v4 or v6e rig must be
#: judged against THAT chip's roof.  A kind the table doesn't know is an
#: error (:func:`device_peaks` raises), never a default.  Source: Google
#: Cloud TPU documentation, per-generation system architecture pages.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    # bf16 peaks for the TPU generations JAX reports by these kinds
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),
    "TPU v6e": (918.0, 1640.0),
}

#: The accelerator kind :func:`rate_prior` seeds an UNRECOGNIZED chip
#: from (a balancer prior is a starting guess the measured split
#: replaces; roofline peaks get no such default).
DEFAULT_PRIOR_KIND = "TPU v5e"

#: Host-CPU peaks in the same (Tflop/s, GB/s) shape as
#: :data:`DEVICE_PEAKS` — a few DDR channels' streaming bandwidth, the
#: anchor every accelerator prior is expressed against.  Keyed on the
#: kinds XLA:CPU actually reports (``jax.Device.device_kind`` is
#: ``"cpu"`` on the host backend).
HOST_PEAKS: dict[str, tuple[float, float]] = {
    "cpu": (1.0, 50.0),
    "host": (1.0, 50.0),
}

#: The host-CPU anchor kind (prior == 1.0 by construction).
HOST_PRIOR_KIND = "cpu"

#: Device-kind → relative throughput prior for BANDWIDTH-BOUND work,
#: normalized to host CPU == 1.0.  Derived from the SAME peak tables
#: that drive roofline/MFU (:data:`DEVICE_PEAKS`) — the ISSUE 20 rule:
#: ranking (:func:`device_rank`) and the balancer's seed
#: (:func:`rate_prior`) read ONE table, so they cannot drift apart.
#: The mixed-fleet balancer seeds its first split from these ratios
#: (``core/balance.prior_split``) instead of discovering a ~25x-slower
#: host lane from equal shares over many re-shard iterations.
RATE_PRIORS: dict[str, float] = {
    kind: round(gb / HOST_PEAKS["cpu"][1], 3)
    for kind, (_tf, gb) in {**DEVICE_PEAKS, **HOST_PEAKS}.items()
}


def rate_prior(device_kind: str) -> float:
    """Relative throughput prior for one device kind (host CPU == 1.0).

    Pure over :data:`RATE_PRIORS` (model-checked purity contract:
    ``tools/ckmodel/purity.py``) — no jax, no clock, no environment.
    Unknown kinds: anything CPU/host-flavored anchors at the host
    prior, anything else is seeded from the :data:`DEFAULT_PRIOR_KIND`
    accelerator prior, so an unrecognized chip starts as "an
    accelerator", never as a host lane — the measured balancer takes
    over from there."""
    kind = str(device_kind)
    if kind in RATE_PRIORS:
        return RATE_PRIORS[kind]
    low = kind.lower()
    if "cpu" in low or "host" in low:
        return RATE_PRIORS[HOST_PRIOR_KIND]
    return RATE_PRIORS[DEFAULT_PRIOR_KIND]


def device_rank(device_kind: str) -> int:
    """Rank of a device kind by descending prior (0 == fastest band).

    The machine-readable face of the
    ``devicesWithHighestDirectNbodyPerformance`` idiom: kinds sharing a
    prior share a rank band.  Reads the SAME table as
    :func:`rate_prior`, so the ranking a selector sorts by and the seed
    the balancer splits by cannot disagree."""
    p = rate_prior(device_kind)
    return sum(1 for v in set(RATE_PRIORS.values()) if v > p)


def device_peaks(device_kind: str | None = None) -> tuple[float, float, str]:
    """``(peak_tflops, peak_gbps, kind)`` for a device kind; ``None``
    resolves the running process's first device.  Raises
    :class:`DeviceSelectionError` for a kind :data:`DEVICE_PEAKS` does
    not list (including the host CPU): a roofline share against an
    assumed roof is a wrong number, not a measurement."""
    kind = device_kind
    if kind is None:
        kind = str(jax.devices()[0].device_kind)
    if kind not in DEVICE_PEAKS:
        raise DeviceSelectionError(
            f"no roofline peaks for device kind {kind!r}; "
            f"DEVICE_PEAKS lists {sorted(DEVICE_PEAKS)}")
    tf, gb = DEVICE_PEAKS[kind]
    return tf, gb, kind


class AcceleratorType(enum.IntFlag):
    """Device-type selection flags (reference: AcceleratorType used by the
    ClNumberCruncher ctor, ClNumberCruncher.cs:199-248).

    ``GPU`` and ``ACC`` both select TPU chips on this platform; ``CPU``
    selects host (CPU backend) devices — including the virtual multi-device
    CPU rig used for testing multi-chip scheduling.
    """

    NONE = 0
    CPU = 1
    GPU = 2   # historical alias: on a TPU system the "GPU-class" device is the TPU
    ACC = 4   # accelerators == TPU
    TPU = 8
    ALL = CPU | GPU | ACC | TPU


_ACCEL_BACKENDS = ("tpu",)


def _backend_matches(platform_name: str, want: AcceleratorType) -> bool:
    is_accel = platform_name in _ACCEL_BACKENDS
    if want & (AcceleratorType.TPU | AcceleratorType.GPU | AcceleratorType.ACC):
        if is_accel:
            return True
    if want & AcceleratorType.CPU and platform_name == "cpu":
        return True
    return False


@dataclass(frozen=True)
class Device:
    """One compute chip (reference: ClDevice, ClDevice.cs:29-240).

    Wraps a ``jax.Device``.  ``dedicated_memory`` mirrors the reference's
    ``deviceGDDR`` flag (dedicated vs host-shared memory,
    ClDevice.cs:105-108): True for real TPU HBM, False for CPU backend
    devices.
    """

    jax_device: jax.Device
    partition_cores: int = 0  # >0 => virtual sub-device (CPU fission analogue)
    partition_id: int = 0     # lane index among partitions of one chip

    @property
    def platform(self) -> str:
        return self.jax_device.platform

    @property
    def name(self) -> str:
        base = f"{self.jax_device.device_kind} #{self.jax_device.id}"
        if self.partition_cores:
            return f"{base}/p{self.partition_id}"
        return base

    @property
    def vendor(self) -> str:
        return "Google" if self.is_tpu else "host"

    @property
    def is_tpu(self) -> bool:
        return self.jax_device.platform in _ACCEL_BACKENDS

    @property
    def is_cpu(self) -> bool:
        return self.jax_device.platform == "cpu"

    @property
    def dedicated_memory(self) -> bool:
        return self.is_tpu

    @property
    def compute_units(self) -> int:
        """Core count analogue (reference: deviceComputeUnits)."""
        if self.partition_cores:
            return self.partition_cores
        try:
            return int(getattr(self.jax_device, "num_cores", 1) or 1)
        except Exception:
            return 1

    @property
    def memory_bytes(self) -> int:
        """Device memory capacity (reference: deviceMemSize)."""
        try:
            stats = self.jax_device.memory_stats()
            if stats and "bytes_limit" in stats:
                return int(stats["bytes_limit"])
        except Exception:
            pass
        return 0

    @property
    def memory_available_bytes(self) -> int:
        try:
            stats = self.jax_device.memory_stats()
            if stats and "bytes_limit" in stats:
                return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
        except Exception:
            pass
        return 0

    def copy(self) -> "Device":
        return Device(self.jax_device, self.partition_cores, self.partition_id)

    def as_partitions(self, num: int) -> "Devices":
        """Split this chip into ``num`` virtual sub-devices (reference:
        ``createDeviceAsPartition`` — CPU device fission into sub-devices,
        ClDevice.cs:85-95).  Each partition is a distinct scheduler lane
        dispatching to the SAME chip: the balancer splits the range across
        them and XLA interleaves their async streams — the TPU-idiomatic
        reading of device fission (SURVEY.md §2.3: subslice / virtual-device
        counts)."""
        if num <= 0:
            raise ValueError("partition count must be positive")
        cores = max(1, self.compute_units // num)
        return Devices(
            Device(self.jax_device, cores, i) for i in range(num)
        )

    @property
    def is_partition(self) -> bool:
        return self.partition_cores > 0

    def log_info(self) -> str:
        mem = self.memory_bytes
        mem_s = f"{mem / (1 << 30):.2f} GiB" if mem else "unknown"
        return (
            f"Device: {self.name} ({self.platform}), cores={self.compute_units}, "
            f"mem={mem_s}, dedicated={self.dedicated_memory}"
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Device({self.name!r})"


class Devices(Sequence[Device]):
    """An ordered device selection (reference: ClDevices,
    ClObjectApi.cs:781-1272).  All filters return new ``Devices`` with device
    copies; ``+`` concatenates selections (ClObjectApi.cs:813-829)."""

    def __init__(self, devices: Iterable[Device] = ()):  # noqa: D107
        self._devices: list[Device] = [d for d in devices]

    # -- Sequence protocol ---------------------------------------------------
    def __len__(self) -> int:
        return len(self._devices)

    def __iter__(self) -> Iterator[Device]:
        return iter(self._devices)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Devices(d.copy() for d in self._devices[idx])
        return self._devices[idx].copy()

    def __add__(self, other: "Devices") -> "Devices":
        seen: set[tuple] = set()
        out: list[Device] = []
        for d in list(self._devices) + list(other._devices):
            # partitions of one chip are DISTINCT lanes — dedup must not
            # collapse them (only true duplicates of the same lane)
            key = (id(d.jax_device), d.partition_cores, d.partition_id)
            if key not in seen:
                seen.add(key)
                out.append(d.copy())
        return Devices(out)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Devices([{', '.join(d.name for d in self._devices)}])"

    # -- filters -------------------------------------------------------------
    def _filtered(self, pred: Callable[[Device], bool]) -> "Devices":
        return Devices(d.copy() for d in self._devices if pred(d))

    def tpus(self) -> "Devices":
        return self._filtered(lambda d: d.is_tpu)

    # reference naming: gpus()/accelerators() select the accelerator class
    def gpus(self) -> "Devices":
        return self.tpus()

    def accelerators(self) -> "Devices":
        return self.tpus()

    def cpus(self) -> "Devices":
        return self._filtered(lambda d: d.is_cpu)

    def with_dedicated_memory(self) -> "Devices":
        """reference: devicesWithDedicatedMemory (ClObjectApi.cs:1118-1145)"""
        return self._filtered(lambda d: d.dedicated_memory)

    def with_host_memory_sharing(self) -> "Devices":
        """reference: devicesWithHostMemorySharing (ClObjectApi.cs:1150-1193)"""
        return self._filtered(lambda d: not d.dedicated_memory)

    def with_most_compute_units(self) -> "Devices":
        """reference: devicesWithMostComputeUnits (ClObjectApi.cs:1202-1212)"""
        if not self._devices:
            return Devices()
        best = max(d.compute_units for d in self._devices)
        return self._filtered(lambda d: d.compute_units == best)

    def with_highest_memory_available(self) -> "Devices":
        """reference: devicesWithHighestMemoryAvailable (ClObjectApi.cs:1150-1160)"""
        if not self._devices:
            return Devices()
        ranked = sorted(
            self._devices, key=lambda d: d.memory_available_bytes, reverse=True
        )
        return Devices(d.copy() for d in ranked)

    def with_highest_nbody_performance(self, n: int = 2048, iters: int = 3) -> "Devices":
        """Rank devices by a direct-nbody micro-benchmark, fastest first
        (reference: devicesWithHighestDirectNbodyPerformance runs
        ``Tester.nBody`` per device, ClObjectApi.cs:1222-1244)."""
        from .ops import nbody  # local import: ops depends on hardware

        timed = [(nbody.microbenchmark(d.jax_device, n=n, iters=iters), d) for d in self._devices]
        timed.sort(key=lambda t: t[0])
        return Devices(d.copy() for _, d in timed)

    def subset(self, count: int) -> "Devices":
        """First ``count`` devices (reference: numberOfGPUsToUse trimming)."""
        return self[:count]

    def jax_devices(self) -> list[jax.Device]:
        return [d.jax_device for d in self._devices]

    def log_info(self) -> str:
        lines = [d.log_info() for d in self._devices]
        text = "\n".join(lines) if lines else "(no devices)"
        print(text)
        return text

    def require_nonempty(self, what: str = "selection") -> "Devices":
        if not self._devices:
            raise DeviceSelectionError(f"no devices matched {what}")
        return self


@dataclass(frozen=True)
class Platform:
    """A PJRT backend (reference: ClPlatform, ClPlatform.cs:31-206)."""

    name: str
    _devices: tuple = field(repr=False, default=())

    @property
    def vendor(self) -> str:
        return "Google" if self.name in _ACCEL_BACKENDS else "host"

    def devices(self) -> Devices:
        return Devices(Device(d) for d in self._devices)

    def num_tpus(self) -> int:
        return len(self.devices().tpus())

    def num_cpus(self) -> int:
        return len(self.devices().cpus())

    # reference naming
    def num_gpus(self) -> int:
        return self.num_tpus()

    def num_accelerators(self) -> int:
        return self.num_tpus()

    def log_info(self) -> str:
        return f"Platform: {self.name} (vendor={self.vendor}, devices={len(self._devices)})"


class Platforms(Sequence[Platform]):
    """All available backends (reference: ClPlatforms, ClObjectApi.cs:158-775)."""

    def __init__(self, items: Iterable[Platform]):
        self._items = list(items)

    @staticmethod
    def all() -> "Platforms":
        """Enumerate every usable backend (reference: ClPlatforms.all(),
        ClObjectApi.cs:204-216).

        When ``JAX_PLATFORMS`` pins the process to specific backends, only
        those are probed: probing an excluded platform still initializes
        its client, and a chip belongs to one process at a time — a
        CPU-pinned child must never reach for its parent's TPU."""
        import os

        candidates: tuple[str, ...] = ("tpu", "cpu")
        pinned = os.environ.get("JAX_PLATFORMS", "")
        if pinned:
            allowed = {p.strip() for p in pinned.split(",") if p.strip()}
            candidates = tuple(b for b in candidates if b in allowed)
        found: list[Platform] = []
        for backend in candidates:
            try:
                devs = jax.devices(backend)
            except RuntimeError:  # backend not present in this process
                continue
            if devs:
                found.append(Platform(backend, tuple(devs)))
        if not found:
            found.append(Platform(jax.default_backend(), tuple(jax.devices())))
        return Platforms(found)

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self) -> Iterator[Platform]:
        return iter(self._items)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Platforms(self._items[idx])
        return self._items[idx]

    def with_most_devices(self) -> "Platforms":
        """reference: platformsWithMostDevices (ClObjectApi.cs:268-279)"""
        if not self._items:
            return Platforms([])
        best = max(len(p._devices) for p in self._items)
        return Platforms([p for p in self._items if len(p._devices) == best])

    def tpus(self) -> Devices:
        out = Devices()
        for p in self._items:
            out = out + p.devices().tpus()
        return out

    def gpus(self) -> Devices:
        return self.tpus()

    def accelerators(self) -> Devices:
        return self.tpus()

    def cpus(self) -> Devices:
        out = Devices()
        for p in self._items:
            out = out + p.devices().cpus()
        return out

    def devices(self) -> Devices:
        out = Devices()
        for p in self._items:
            out = out + p.devices()
        return out

    def log_info(self) -> str:
        text = "\n".join(p.log_info() for p in self._items)
        print(text)
        return text


def platforms() -> Platforms:
    """Convenience: ``platforms().tpus()`` etc."""
    return Platforms.all()


def all_devices() -> Devices:
    return Platforms.all().devices()


def chip_devices() -> Devices:
    """The devices a path that produces device numbers runs on — every
    TPU of this process.  ONE rule for the bench, the workloads, the
    examples and the tools: no TPU is an error, never a quiet switch to
    the host CPU.  The host CPU is used only when ``JAX_PLATFORMS=cpu``
    asks for it (the test rig, a debugging run), and callers print the
    platform beside whatever they measured."""
    import os

    devs = all_devices()
    tpus = devs.tpus()
    if len(tpus):
        return tpus
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return devs.cpus()
    raise DeviceSelectionError(
        f"no TPU found (jax sees {[str(d) for d in jax.devices()]}); "
        "set JAX_PLATFORMS=cpu to run on the host CPU on purpose")


def devices_for_type(flags: AcceleratorType, max_devices: int = 0) -> Devices:
    """Select devices by AcceleratorType flags (reference: Cores device
    discovery per type, Cores.cs:156-273)."""
    sel = Devices()
    plats = Platforms.all()
    for p in plats:
        if _backend_matches(p.name, flags):
            sel = sel + p.devices()
    if flags & (AcceleratorType.TPU | AcceleratorType.GPU | AcceleratorType.ACC):
        # accelerator-class request should not silently pick up host devices
        sel_acc = sel.tpus()
        if flags & AcceleratorType.CPU:
            sel_acc = sel_acc + sel.cpus()
        sel = sel_acc
    if max_devices > 0:
        sel = sel.subset(max_devices)
    return sel.require_nonempty(f"AcceleratorType {flags!r}")
