"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default.

The arithmetic that turns a configuration's shapes into operations and
bytes is its family's (``perfbench/families/<family>.py``), kept with the
benchmark so that no later PR can move the yardstick.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks recorded for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]
