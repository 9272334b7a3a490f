"""Least work a kernel must do, from shapes alone (no program code).

A suffix-sigma wave reads its tokens once and must materialise one packed
suffix record per position (``lanes`` uint32 words) before it can sort them:
``4 * wave_tokens * (1 + lanes)`` bytes.  Any implementation of the wave
moves at least that much, whatever its fold policy, so the share of the HBM
roofline computed from it is a lower bound and cannot pass 100%.
"""


def wave_min_bytes(wave_tokens: int, lanes: int) -> int:
    return 4 * int(wave_tokens) * (1 + int(lanes))


def hbm_share(min_bytes: float, seconds: float, hbm_bytes_per_s: float):
    """Percent of the HBM roofline: least time at peak bandwidth over the
    measured time; ``None`` where nothing was measured."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * (min_bytes / hbm_bytes_per_s) / seconds
