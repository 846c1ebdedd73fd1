"""The chip's published peaks, keyed by the exact ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s per chip. A device that is not in the table
is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def lookup(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; add a row with "
            "its source to perfbench/harness/peaks.py") from None
