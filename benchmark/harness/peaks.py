"""Peaks of the chips this benchmark may run on, keyed by JAX's
``device_kind``. A copy of ``observability/utilization.py``'s v5e entries,
kept here so that the yardstick does not move with the program.

Source: Google Cloud documentation, "TPU v5e" system architecture: one chip
does 197 TFLOP/s in bf16, has 16 GB of HBM2e at 819 GB/s, and 1,600 Gbit/s
of chip-to-chip interconnect.
"""

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9}
# JAX reports the v5e as "TPU v5 lite"
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


class UnknownDeviceKind(KeyError):
    """The benchmark has no peaks for this chip: add the kind with its
    public source before measuring on it."""


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"device kind {device_kind!r} is not in "
            f"benchmark/harness/peaks.py ({sorted(PEAKS)})") from None
