"""Hardware descriptions: roofline constants, kernel VMEM limits, and the
table that maps a JAX device to its spec.

The serving path runs its Pallas kernels on a TPU v5e. The analytical
decision flows in :mod:`repro.core.dispatch` take a :class:`HardwareSpec`
as their tuning target, and the kernels size their tiles against the
spec's scoped-VMEM limit. :func:`for_device` picks the spec of the chip a
run is on by ``device_kind``; a device that is not in the table is an
error, never a silent v5e. CPU runs (tests, interpret mode) name
:data:`TPU_V5E` explicitly as the target they emulate.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip hardware description.

    Attributes:
      peak_flops_bf16: peak bf16 FLOP/s of the MXU.
      peak_flops_vpu_f32: peak f32 FLOP/s of the vector unit (used by the
        GEMV/ImplA cost model — the VPU path does not touch the MXU).
      hbm_bw: HBM bandwidth, bytes/s.
      host_bw: host↔device link bandwidth, bytes/s (PCIe-class; what a
        KV page pays per direction to move between the device pool and
        the host tier of the KV hierarchy — the swap-vs-re-prefill
        roofline's denominator).
      ici_bw_per_link: per-link ICI bandwidth, bytes/s.
      ici_links: number of ICI links per chip taking part in a 2D torus.
      hbm_bytes: HBM capacity per chip.
      vmem_bytes: VMEM (on-chip vector memory) capacity per core.
      vmem_limit_bytes: the scoped-VMEM limit every Pallas kernel asks
        Mosaic for (``CompilerParams(vmem_limit_bytes=...)``); Mosaic's
        own default is far below the physical VMEM.
      mxu_dim: systolic array dimension (128 for all current TPUs).
      lane: vector lane count (last-dim tiling atom).
      sublane_f32 / sublane_bf16: second-minor tiling atom per dtype.
    """

    name: str
    peak_flops_bf16: float
    peak_flops_vpu_f32: float
    hbm_bw: float
    host_bw: float
    ici_bw_per_link: float
    ici_links: int
    hbm_bytes: int
    vmem_bytes: int
    vmem_limit_bytes: int
    mxu_dim: int = 128
    lane: int = 128
    sublane_f32: int = 8
    sublane_bf16: int = 16

    def sublane(self, dtype_bytes: int) -> int:
        return {4: self.sublane_f32, 2: self.sublane_bf16, 1: 32}.get(dtype_bytes, 8)

    @property
    def tile_budget(self) -> int:
        """Bytes a kernel's tile picker may claim for its double-buffered
        input tiles and accumulators: half the scoped limit, leaving the
        rest for output buffers, scratch and Mosaic's internal use."""
        return self.vmem_limit_bytes // 2


# Roofline constants: 197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link
# ICI (Google Cloud documentation, "TPU v5e").
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_vpu_f32=197e12 / 32,  # VPU is ~1/32 of MXU throughput at f32
    hbm_bw=819e9,
    host_bw=16e9,  # PCIe-gen4-class effective host link, per direction
    ici_bw_per_link=50e9,
    ici_links=4,  # 2D torus: 4 links (x+, x-, y+, y-)
    hbm_bytes=16 * 2**30,
    vmem_bytes=128 * 2**20,
    vmem_limit_bytes=64 * 2**20,
)

#: ``jax.Device.device_kind`` -> spec, for every chip the kernels support.
BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,
}


class UnknownDeviceError(RuntimeError):
    """The run's device has no entry in :data:`BY_DEVICE_KIND`."""


def for_device(device) -> HardwareSpec:
    """The spec of ``device`` (a ``jax.Device``), looked up by its
    ``device_kind``. Raises :class:`UnknownDeviceError` for any kind not
    in the table — including the CPU, which only runs kernels in
    interpret mode against an explicitly named target."""
    try:
        return BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no HardwareSpec for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known kinds: "
            f"{sorted(BY_DEVICE_KIND)}") from None


def matmul_flops(m: int, n: int, k: int) -> int:
    return 2 * m * n * k


def bytes_of(shape: tuple[int, ...], dtype_bytes: int = 2) -> int:
    n = dtype_bytes
    for s in shape:
        n *= s
    return n
