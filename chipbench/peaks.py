"""Published peaks per ``jax.Device.device_kind``: (FLOP/s, HBM bytes/s).

TPU v5e: 197 TFLOP/s in bf16 and 819 GB/s of HBM bandwidth (Google Cloud
documentation, "TPU v5e"). The kernels' f32 dots run as six bf16 passes
at ``precision=HIGHEST``, so the bf16 peak is an upper bound on the rate
they can reach. A device the table does not know is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(device_kind: str):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
