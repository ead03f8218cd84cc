"""Peak rates of the chips the roofline model prices, keyed by the
``device_kind`` JAX reports for them.

A device with no entry is an error, not a default: pricing a span run on
one chip against another chip's peaks yields a number that names the
wrong device."""

from dataclasses import dataclass


@dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float  # per chip, FLOP/s
    hbm_bw: float  # per chip, B/s
    ici_link_bw: float  # per link, B/s
    hbm_bytes: float  # per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GiB HBM per chip; 1,600 Gbit/s of interconnect over four links.
TPU_V5E = HwSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    hbm_bytes=16 * 1024**3,
)

HW_BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,
}


def local_hw() -> HwSpec:
    """The spec of the device this process runs on; raises for a
    ``device_kind`` with no entry."""
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return HW_BY_DEVICE_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no peak rates for device kind {kind!r} (known: "
            f"{sorted(HW_BY_DEVICE_KIND)}); pass an HwSpec explicitly to "
            "price costs for another chip") from None
