"""Hardware descriptors — the paper's Table III, adapted to TPU.

The paper's 5 GPU features ``(gm, sm, cc, mbw, l2c)`` map to:

  gm  -> mem_gib       device memory (HBM / host RAM), GiB
  sm  -> num_cores     parallel compute units (TensorCores / host cores)
  cc  -> clock_mhz     core clock
  mbw -> mem_bw_gbps   memory bandwidth, GB/s  (paper used bus width; the
                       bandwidth is the architecture-portable equivalent)
  l2c -> sram_kib      on-chip staging SRAM (VMEM for TPU, L2 for CPU), KiB

``peak_tflops``/``ici_gbps`` are *not* features (the paper uses exactly 5
hardware dims); they feed the analytic cost model and the roofline.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Tuple

__all__ = [
    "HardwareSpec",
    "TPU_V5E",
    "TPU_V4",
    "TPU_V5P",
    "SIMULATED_CHIPS",
    "DEVICE_KINDS",
    "device_spec",
    "target_spec",
    "host_spec",
]


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    mem_gib: float
    num_cores: int
    clock_mhz: float
    mem_bw_gbps: float
    sram_kib: float
    # cost-model-only attributes (not classifier features):
    peak_tflops_bf16: float
    peak_tflops_f32: float
    ici_gbps: float = 50.0
    launch_overhead_us: float = 2.0
    transpose_bw_frac: float = 0.80  # paper [20]: out-of-place hits ~80% peak

    def features(self) -> Tuple[float, float, float, float, float]:
        """The paper's 5 hardware feature dims."""
        return (
            self.mem_gib,
            float(self.num_cores),
            self.clock_mhz,
            self.mem_bw_gbps,
            self.sram_kib,
        )


# -- target TPU chips (the analytic-dataset "GPUs") -------------------------
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    mem_gib=16.0,
    num_cores=1,
    clock_mhz=940.0,
    mem_bw_gbps=819.0,
    sram_kib=128 * 1024,
    peak_tflops_bf16=197.0,
    peak_tflops_f32=98.5,
    ici_gbps=50.0,
)
TPU_V4 = HardwareSpec(
    name="tpu_v4",
    mem_gib=32.0,
    num_cores=2,
    clock_mhz=1050.0,
    mem_bw_gbps=1228.0,
    sram_kib=128 * 1024,
    peak_tflops_bf16=275.0,
    peak_tflops_f32=137.5,
    ici_gbps=100.0,
)
TPU_V5P = HardwareSpec(
    name="tpu_v5p",
    mem_gib=95.0,
    num_cores=2,
    clock_mhz=1750.0,
    mem_bw_gbps=2765.0,
    sram_kib=128 * 1024,
    peak_tflops_bf16=459.0,
    peak_tflops_f32=229.5,
    ici_gbps=100.0,
)

SIMULATED_CHIPS: Dict[str, HardwareSpec] = {
    c.name: c for c in (TPU_V5E, TPU_V4, TPU_V5P)
}

# ``device_kind`` as JAX reports it -> descriptor.  A TPU kind missing here
# is an error (``device_spec``), never a silent stand-in.  Add a kind only
# once a run on that chip has shown the string JAX reports for it.
DEVICE_KINDS: Dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def device_spec(device=None) -> HardwareSpec:
    """Descriptor of the device JAX computes on (default: the first).

    A TPU maps by its ``device_kind`` through ``DEVICE_KINDS`` and an
    unknown kind raises; the CPU backend gets ``host_spec()``.  Measured
    data (autotune caches, measured selector datasets) is keyed by this,
    so timings are never filed under a chip they were not taken on."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return host_spec()
    spec = DEVICE_KINDS.get(device.device_kind)
    if spec is None:
        raise ValueError(
            f"no HardwareSpec for {device.platform} device_kind "
            f"{device.device_kind!r}; add it to hardware.DEVICE_KINDS"
        )
    return spec


def target_spec() -> HardwareSpec:
    """The chip the analytic and learned policies model: the attached
    device's descriptor, or ``TPU_V5E`` on the CPU backend — there the
    kernels run in interpret mode as a rehearsal of the v5e path, and a
    TPU cost model fed CPU peaks would describe neither machine."""
    import jax

    device = jax.devices()[0]
    return TPU_V5E if device.platform == "cpu" else device_spec(device)


def host_spec() -> HardwareSpec:
    """Best-effort descriptor of the host CPU, for data measured on the
    CPU backend.  Its peaks are rough, not published numbers."""
    ncpu = os.cpu_count() or 1
    mem_gib = 16.0
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemTotal"):
                    mem_gib = float(line.split()[1]) / (1024**2)
                    break
    except OSError:
        pass
    clock = 2000.0
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if "cpu MHz" in line:
                    clock = float(line.split(":")[1])
                    break
    except OSError:
        pass
    return HardwareSpec(
        name="host_cpu",
        mem_gib=round(mem_gib, 1),
        num_cores=ncpu,
        clock_mhz=clock,
        mem_bw_gbps=50.0,
        sram_kib=1024.0,
        peak_tflops_bf16=ncpu * 0.05,
        peak_tflops_f32=ncpu * 0.05,
        ici_gbps=10.0,
    )
