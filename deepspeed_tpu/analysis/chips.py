"""THE per-chip peak table, keyed by jax ``device_kind``.

One frozen :class:`ChipSpec` per TPU generation — peak bf16 matmul FLOP/s
(fp32 halves), peak HBM bytes/s and HBM capacity — shared by the live
accelerator (``accelerator/tpu_accelerator.py`` reads its peaks from here)
and the analytic roofline (``ds_roofline``). Pure stdlib: ``bin/ds_roofline``
prices a saved ``.hlo`` dump on a machine with no jax at all (the ``ds_prof``
contract).

Sources: the per-generation pages of the Google Cloud TPU documentation
("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s; likewise "TPU v2" …
"TPU v6e"), and for the ``device_kind`` spellings the installed jax's own
list (``jax/_src/pallas/mosaic/tpu_info.py``). A device that is in neither
the table nor the ``cpu`` platform is an ERROR (:func:`detect_chip_name`
raises): an MFU against a guessed peak is a wrong number, not a rough one.
The ``cpu-sim`` row is NOMINAL — it keeps MFU/MBU math finite on the
simulated CPU meshes tier-1 runs on and describes no hardware.

Adding a chip = adding one ``ChipSpec`` line here.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

__all__ = ["ChipSpec", "CHIPS", "ALIASES", "known_chips", "resolve_chip",
           "detect_chip_name"]


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak envelope of one chip generation (per chip, not per pod)."""

    name: str             # canonical key in CHIPS
    peak_flops: float     # bf16 matmul peak, FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int        # HBM capacity, bytes
    note: str = ""
    # the jax ``device.device_kind`` strings this row answers to
    device_kinds: Tuple[str, ...] = ()

    def peak_flops_for(self, dtype: Optional[str] = None) -> float:
        """Peak for a dtype string — fp32 runs the MXU at half rate
        (same convention as ``TPU_Accelerator.peak_flops``)."""
        if dtype and str(dtype).lower() in ("f32", "fp32", "float32"):
            return self.peak_flops / 2.0
        return self.peak_flops

    def ridge_flops_per_byte(self) -> float:
        """Arithmetic intensity (FLOPs/byte) above which a region is
        compute-bound on this chip."""
        if self.hbm_bytes_per_s <= 0:
            return float("inf")
        return self.peak_flops / self.hbm_bytes_per_s


_GIB = 1024 ** 3

CHIPS: Dict[str, ChipSpec] = {
    "v2": ChipSpec("v2", 45e12, 700e9, 8 * _GIB, "TPU v2 core",
                   ("TPU v2",)),
    "v3": ChipSpec("v3", 123e12, 900e9, 16 * _GIB, "TPU v3 core",
                   ("TPU v3",)),
    "v4": ChipSpec("v4", 275e12, 1228e9, 32 * _GIB, "TPU v4",
                   ("TPU v4",)),
    "v5e": ChipSpec("v5e", 197e12, 819e9, 16 * _GIB, "TPU v5e (lite)",
                    ("TPU v5 lite", "TPU v5e")),
    "v5p": ChipSpec("v5p", 459e12, 2765e9, 95 * _GIB, "TPU v5p",
                    ("TPU v5", "TPU v5p")),
    "v6e": ChipSpec("v6e", 918e12, 1640e9, 32 * _GIB, "TPU v6e (Trillium)",
                    ("TPU v6 lite", "TPU v6e")),
    "cpu-sim": ChipSpec("cpu-sim", 1e12, 100e9, 64 * _GIB,
                        "simulated CPU mesh (NOMINAL, not a measurement)"),
}

ALIASES: Dict[str, str] = {
    "v5lite": "v5e",
    "v5litepod": "v5e",
    "v5": "v5p",
    "v6": "v6e",
    "cpu": "cpu-sim",
    "cpu_sim": "cpu-sim",
    "host": "cpu-sim",
}

_BY_DEVICE_KIND: Dict[str, str] = {
    kind: spec.name for spec in CHIPS.values() for kind in spec.device_kinds}


def known_chips() -> Tuple[str, ...]:
    return tuple(sorted(CHIPS))


def resolve_chip(name: str) -> ChipSpec:
    """Chip spec for ``name`` (canonical or alias, case-insensitive).
    Raises ``KeyError`` naming the known chips — the schema cross-field
    check turns that into a config-time finding."""
    key = (name or "").strip().lower().replace(" ", "")
    key = ALIASES.get(key, key)
    if key not in CHIPS:
        raise KeyError(
            f"unknown chip {name!r}; known: {', '.join(known_chips())} "
            f"(aliases: {', '.join(sorted(ALIASES))})")
    return CHIPS[key]


def detect_chip_name(device_kind: str, platform: str = "") -> str:
    """Chip name for a jax ``device.device_kind`` string (e.g. ``"TPU v5
    lite"``), on plain strings so callers need no jax. The ``cpu`` platform
    is the nominal ``cpu-sim`` row; any other device the table does not
    list raises ``KeyError`` — there is no default chip."""
    if (platform or "").lower() == "cpu":
        return "cpu-sim"
    name = _BY_DEVICE_KIND.get((device_kind or "").strip())
    if name is None:
        raise KeyError(
            f"device_kind {device_kind!r} (platform {platform!r}) is not in "
            f"the peak table; known: {', '.join(sorted(_BY_DEVICE_KIND))}. "
            "Add its published peaks to deepspeed_tpu/analysis/chips.py")
    return name
