"""Hardware constants of the chip this repo targets: one Google Cloud TPU v5e.

Source: Google Cloud documentation, "TPU v5e" — peak bf16 compute, HBM
size and bandwidth, and the chip's 1,600 Gbit/s of interconnect (four
links).  `VMEM_BYTES` is the per-core vector memory the v5e compiler
enforces (it refuses a 128 MiB + 1 byte allocation); the fused kernels
request all of it and the fusion gate budgets against it.  `HOST_LINK_BW`
is a PCIe gen4 x16 figure that no run has measured.

JAX reports this chip's `device_kind` as `DEVICE_KIND`; a device of any
other kind is not described here (`require_described`)."""

DEVICE_KIND = "TPU v5 lite"

PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_LINK_BW = 50e9              # bytes/s per link (1,600 Gbit/s over 4 links)
HOST_LINK_BW = 32e9             # bytes/s host<->device (PCIe gen4 x16 per
#                                 direction — the out-of-core streaming link
#                                 the overlap model in rsvd_model.py prices)
HBM_BYTES = 16 * 2**30          # 16 GiB per chip
VMEM_BYTES = 128 * 2**20        # 128 MiB vector memory per core

CHIPS_SINGLE_POD = 256
CHIPS_MULTI_POD = 512


def require_described(device_kind: str) -> None:
    """Raise unless `device_kind` is the chip these constants describe —
    a measurement on another chip must not be judged against v5e peaks."""
    if device_kind != DEVICE_KIND:
        raise ValueError(
            f"roofline/hw.py describes {DEVICE_KIND!r} (TPU v5e) only; "
            f"got device_kind {device_kind!r}")
