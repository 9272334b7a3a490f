"""Share of the job window in which the feeder waited for room in the
bounded queue of dispatched waves, because the fold thread was behind: the
union of the program's ``wave.feed.wait`` spans (program spans)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import span_share  # noqa: E402

SPANS = ("wave.feed.wait",)


def read(ctx):
    return span_share.share(ctx, SPANS)
