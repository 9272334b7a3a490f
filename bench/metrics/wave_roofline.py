"""Share of the HBM roofline reached by the fused wave program: the least
bytes any implementation must move per wave (``roofline.wave_min_bytes``)
at the chip's peak bandwidth, over the device time per wave of the
``jit_wave_fn`` XLA module (trace)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import roofline  # noqa: E402

PROGRAM = "wave_fn"        # the jitted wave_fn: XLA module jit_wave_fn


def read(ctx):
    f = ctx["facts"]
    if ctx["runner"] != "jobs" or not ctx["peaks"] or not f["waves"]:
        return None
    progs = ctx["trace"]["programs"] if ctx["trace"] else {}
    s = sum(v for name, v in progs.items() if PROGRAM in name)
    return roofline.hbm_share(
        roofline.wave_min_bytes(f["wave_tokens"], f["lanes"]),
        s / f["waves"], ctx["peaks"]["hbm_bytes_per_s"])
