"""The chip-to-chip interconnect's published rate, and the bytes a ring
all-reduce has to move, keyed by the exact ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of
inter-chip interconnect bandwidth a chip = 200e9 bytes/s. A device that
is not in the table is an error, never a default.
"""

ICI = {
    "TPU v5 lite": {"bytes_per_s": 200e9,
                    "source": "Google Cloud documentation, TPU v5e: "
                              "1,600 Gbit/s ICI a chip"},
}


def lookup(device_kind):
    try:
        return ICI[device_kind]
    except KeyError:
        raise KeyError(
            f"no ICI rate for device_kind {device_kind!r}; add a row "
            "with its source to perfbench/harness/ici.py") from None


def ring_allreduce_bytes(payload_bytes, devices):
    """Bytes each chip sends (and receives) to all-reduce
    ``payload_bytes`` over ``devices`` chips by the bandwidth-optimal
    ring: a reduce-scatter and an all-gather of ``(D-1)/D`` of the
    payload each. No algorithm moves fewer."""
    d = int(devices)
    return 2.0 * (d - 1) / d * payload_bytes


def least_seconds(payload_bytes, devices, ici):
    return ring_allreduce_bytes(payload_bytes, devices) / ici["bytes_per_s"]
