"""Hardware constants for the TPU v5e target and roofline helpers.

These constants parameterize
  * the roofline analysis over the compiled dry-run artifacts, and
  * the analytic T_fwd / T_swap cost model that the InferCept scheduler and
    the discrete-event simulator share (the paper obtains the same mappings
    by offline profiling on A100).

``chip_of(device)`` maps a JAX device to its entry by ``device_kind``, the
name JAX reports; a non-CPU kind missing from ``DEVICE_KINDS`` is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bandwidth: float        # bytes/s per chip
    hbm_bytes: float            # HBM capacity per chip
    ici_link_bandwidth: float   # bytes/s per ICI link
    host_link_bandwidth: float  # bytes/s chip<->host (PCIe share), for swap


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    hbm_bytes=16e9,
    ici_link_bandwidth=50e9,
    # v5e hosts attach 4 chips per PCIe-gen4 host; ~8 GB/s effective per chip
    # is a conservative swap-path figure (the paper's A100 PCIe4 x16 ~= 25GB/s
    # shared). This number only shapes T_swap; it is configurable.
    host_link_bandwidth=8e9,
)

# The paper's evaluation hardware, used by the simulator to reproduce the
# paper's own numbers (A100-80GB SXM).
A100 = ChipSpec(
    name="a100",
    peak_flops_bf16=312e12,
    hbm_bandwidth=2.0e12,
    hbm_bytes=80e9,
    ici_link_bandwidth=300e9,   # NVLink per direction, aggregate approx
    host_link_bandwidth=25e9,   # PCIe gen4 x16
)

CHIPS = {c.name: c for c in (TPU_V5E, A100)}

# JAX ``device_kind`` -> published peaks (Google Cloud docs, "TPU v5e")
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def chip_of(device) -> ChipSpec:
    """The peaks of a JAX device, looked up by its ``device_kind``. On the
    CPU (tests and demos) the v5e the cost model targets stands in, so
    the virtual clock means the same thing on both; any other device must
    have an entry."""
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform}); known: "
            f"{sorted(DEVICE_KINDS)}") from None


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1,
            "float8_e4m3": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
            "int32": 4}[str(dtype)]
