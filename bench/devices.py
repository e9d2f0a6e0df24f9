"""The chips the benchmark runs on: published peaks, and the gate.

Peaks of one TPU v5e chip, from Google Cloud's documentation ("TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect).  JAX names the chip "TPU v5 lite".  A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                        int8_ops_per_s=393e12, hbm_bytes=16e9,
                        ici_bits_per_s=1600e9),
}


class NoChip(Exception):
    """JAX found no accelerator the benchmark can measure."""


def gate(chips: int) -> dict:
    """The devices a cell runs on, or NoChip naming what was found."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {d0.platform!r} "
                     f"({d0.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if d0.device_kind not in PEAKS:
        raise NoChip(f"no peaks known for {d0.device_kind!r}")
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs))


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))
