"""Operations and bytes of the serving scan, from the cell's shapes.

The least time a pass could take is the larger of bytes / peak bandwidth
and operations / peak rate; the share of it that the measured kernel time
reaches is the roofline share. Counted conservatively, so that the share
is never overstated: bytes are what the algorithm must read (the item
matrix at its logical feature width, not the sublane-padded tile the
kernel stores), operations are charged at the bf16 peak although float32
passes on the MXU cost more."""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def scan_bytes(items: int, features: int, dtype: str, rows: float, k: int) -> float:
    """Bytes one exact top-k pass must move: every item row once, the
    item norms / scales row the kernel streams beside it (4 B an item),
    the query rows, and the [rows, k] scores and ids it returns."""
    item_bytes = _DTYPE_BYTES[dtype]
    return (
        float(items) * features * item_bytes
        + float(items) * 4
        + float(rows) * features * 4
        + float(rows) * k * 8
    )


def scan_flops(items: int, features: int, rows: float) -> float:
    """2 x rows x items x features multiply-adds of the dot products; the
    selection's comparisons are not counted."""
    return 2.0 * float(rows) * float(items) * features


def scan_least_seconds(config: dict, rows: float, k: int, peaks: dict) -> tuple[float, str]:
    """(least seconds for one pass, which bound applied)."""
    t_bytes = scan_bytes(config["items"], config["features"], config["dtype"], rows, k) / float(
        peaks["hbm_bytes_per_s"]
    )
    t_ops = scan_flops(config["items"], config["features"], rows) / float(
        peaks["bf16_flops_per_s"]
    )
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "operations")
