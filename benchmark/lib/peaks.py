"""Published peaks of the chips the benchmark may run on, by
``jax.devices()[0].device_kind``.  A device that is not in the table is
an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "int8_op_per_s": 393e12, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class UnknownDevice(LookupError):
    pass


def peak(device_kind: str, name: str) -> float:
    row = PEAKS.get(device_kind)
    if row is None:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row with its source to benchmark/lib/peaks.py")
    if name not in row:
        raise UnknownDevice(f"no peak {name!r} for {device_kind!r}")
    return float(row[name])
