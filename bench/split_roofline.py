"""Least work of a head/tail split job's tail program, from shapes alone
(no program code).

The tail pass of a wave must at least read the wave's tokens once, to find
the positions whose head is frequent: ``4 * wave_tokens`` bytes.  Any
implementation moves at least that much, so the share of the HBM roofline
computed from it is a lower bound and cannot pass 100%.
"""


def tail_min_bytes(wave_tokens: int) -> int:
    return 4 * int(wave_tokens)
