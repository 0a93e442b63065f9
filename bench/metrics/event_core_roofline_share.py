"""The event core's share of its bandwidth roofline, in percent.

Per simulated event the core needs one replica's S head lanes: it reads
the GPU and CPU residuals and allocations (four float64, the number format
the configuration states) and the availability flag, and writes the two
residuals and the started flag.  Those bytes over the chip's HBM peak
(``bench/peaks.json``, by device kind) are the least time the work could
take; divided by the device's busy time over the traced block."""
import json
import pathlib

FLOAT_BYTES = 8


def bytes_per_event(S: int) -> int:
    return S * (4 * FLOAT_BYTES + 1 + 2 * FLOAT_BYTES + 1)


def hbm_peak(root, device_kind: str) -> float:
    peaks = json.loads((pathlib.Path(root) / "bench" / "peaks.json")
                       .read_text())
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def read(ctx):
    if not ctx.get("busy_s"):
        return None
    least_s = (ctx["traced_events"] * bytes_per_event(ctx["S"])
               / hbm_peak(ctx["root"], ctx["device_kind"]))
    return 100.0 * least_s / ctx["busy_s"]
