"""Operations and bytes of the machine-phase kernels, from the shapes they
are given, and their share of the chip's roofline.

The least time a kernel call can take is the larger of its FLOPs over the
peak FLOP/s and its bytes over the peak HBM bandwidth (``peaks.json``,
keyed by ``device_kind``).  The chip publishes no float32 peak, so the
compute term uses the bf16 peak: a float32 product at HIGHEST precision
takes several bf16 passes, which this share counts against the kernel."""
from __future__ import annotations

import json
import os
from typing import Tuple

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def dense_work(n: int, m: int, d: int) -> Tuple[float, float]:
    """``pair_scores`` over an (n, d) x (m, d) f32 grid: FLOPs of the
    products, bytes of both inputs read and the (n, m) scores written."""
    return 2.0 * n * m * d, 4.0 * (n * d + m * d + n * m)


def compact_work(tiles: int, bn: int, bm: int, d: int) -> Tuple[float, float]:
    """One ``pair_scores_compact`` call over ``tiles`` gathered (bn, bm)
    tiles: FLOPs of the tile products, bytes of the gathered rows read and
    the (tiles, bn, bm) thresholded scores written."""
    return (2.0 * tiles * bn * bm * d,
            4.0 * tiles * (bn * d + bm * d + bn * bm))


def least_time(flops: float, nbytes: float, device_kind: str
               ) -> Tuple[float, str]:
    """(seconds, bounding resource) of the roofline."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_per_s"], nbytes / p["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share(work: Tuple[float, float], calls: int, kernel_s: float,
          device_kind: str) -> float:
    """Percent of the roofline reached by ``calls`` calls of the same
    ``work`` that took ``kernel_s`` seconds of device time together."""
    t, _ = least_time(*work, device_kind)
    return 100.0 * calls * t / kernel_s
