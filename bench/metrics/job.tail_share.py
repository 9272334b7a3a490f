"""Share of the job window in which the tail pass of a head/tail split job
collected a wave on the host: the union of the program's ``wave.tail``
spans (program spans)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import span_share  # noqa: E402

SPANS = ("wave.tail",)


def read(ctx):
    return span_share.share(ctx, SPANS)
