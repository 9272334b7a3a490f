"""Share of the HBM roofline reached by a split job's tail program: the
least bytes it must move per wave (``split_roofline.tail_min_bytes``) at
the chip's peak bandwidth, over the device time per wave of the
``jit_tail_fn`` XLA module (trace)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import roofline  # noqa: E402
import split_roofline  # noqa: E402

PROGRAM = "tail_fn"        # the jitted tail_fn: XLA module jit_tail_fn


def read(ctx):
    f = ctx["facts"]
    if ctx["runner"] != "jobs" or not ctx["peaks"] or not f["waves"]:
        return None
    progs = ctx["trace"]["programs"] if ctx["trace"] else {}
    s = sum(v for name, v in progs.items() if PROGRAM in name)
    if s <= 0:
        return None
    return roofline.hbm_share(split_roofline.tail_min_bytes(f["wave_tokens"]),
                              s / f["waves"], ctx["peaks"]["hbm_bytes_per_s"])
