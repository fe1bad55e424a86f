"""What one scan window has to move and compute, and the chip's peaks:
the roofline's yardstick, kept with the benchmark so that no PR that
claims a gain can change it.

``window_sizes`` and ``pad_k`` are copies of the program's own rules
(``serving_model._window_sizes`` / ``_pad_k``): the benchmark needs them
to warm the shapes a cell's traffic produces and to name the modal
window.  A traced run counts compile requests inside the window, so a
copy that drifts from the program shows there.
"""

from __future__ import annotations

import json
import os

WINDOW_LADDER = (8, 32, 256)
FULL_WINDOW = 256

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


class UnknownDevice(Exception):
    """The device is not in the table of peaks: an error, not a default."""


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {_PEAKS_FILE} "
            f"(it has {sorted(table)})")
    return table[device_kind]


def window_sizes(n: int) -> list[int]:
    """The static window shapes an ``n``-query drain is scored in: full
    windows plus the smallest ladder window that holds the tail."""
    out = [FULL_WINDOW] * (n // FULL_WINDOW)
    tail = n % FULL_WINDOW
    if tail:
        out.append(next(w for w in WINDOW_LADDER if w >= tail))
    return out


def pad_k(k: int) -> int:
    """The fetched top-k width for ``k`` wanted rows (how many + known
    items): the next power of two, at least 8."""
    return 1 << max(3, (k - 1).bit_length())


def scan_window(rows: int, device_features: int, itemsize: int,
                batch: int) -> tuple[float, float]:
    """(bytes, flops) one exact top-N window needs: every stored factor
    read once, one multiply-add per query, row and stored feature.  What
    a build reads besides (mirrors, block maxima, the gathered blocks of
    phase B) is overhead against this, not part of it."""
    return (float(rows) * device_features * itemsize,
            2.0 * batch * rows * device_features)


def least_time_s(n_bytes: float, flops: float,
                 peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    t_mxu = flops / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_mxu else (t_mxu, "compute")
