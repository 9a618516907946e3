"""Peaks of the chips the benchmark runs on, and the work counts set against them.

The table is keyed by ``device_kind`` as JAX reports it. A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, 'TPU v5e' (system architecture)"

# bf16 FLOP/s, HBM bytes/s, and chip-to-chip interconnect (ICI) bytes/s per
# chip, as published. The ICI figure, 1,600 Gbit/s, is the aggregate over
# all of a chip's ICI ports. A chip of a 2x2 slice has two neighbours, and
# no published figure says how much of the aggregate an allreduce there can
# use, so a share of the ICI bound is a lower bound on the share of what is
# reachable (PERF.md, "Layers").
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s": 1600e9 / 8},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"({SOURCE}) holds {sorted(PEAKS)}") from None


def allreduce_wire_bytes(n: int, world: int, elem_bytes: int = 4) -> float:
    """Bytes one chip sends in a bandwidth-optimal allreduce of ``n``
    elements over ``world`` chips: 2(W-1)/W of the payload (the
    nccl-tests bus-bandwidth factor)."""
    return 2.0 * (world - 1) / world * n * elem_bytes


def allreduce_hbm_bytes(n: int, elem_bytes: int = 4) -> float:
    """The least HBM traffic of an allreduce on one chip: read the
    operand once and write the result once."""
    return 2.0 * n * elem_bytes


def allreduce_least_s(n: int, world: int, peak: dict,
                      elem_bytes: int = 4) -> tuple[float, str]:
    """The least time one chip could take for the allreduce, and which
    bound sets it ("ici" or "hbm")."""
    ici = allreduce_wire_bytes(n, world, elem_bytes) / peak["ici_bytes_s"]
    hbm = allreduce_hbm_bytes(n, elem_bytes) / peak["hbm_bytes_s"]
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")
