"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""

from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,     # FLOP/s, bf16 matrix units
    "int8_ops": 393e12,       # OP/s, int8
    "hbm_bytes": 819e9,       # bytes/s, HBM bandwidth
    "hbm_capacity": 16e9,     # bytes
    "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; add them to bench/peaks.py "
                            f"with their source") from None
