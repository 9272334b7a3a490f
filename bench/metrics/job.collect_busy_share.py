"""Share of the job window in which the fold thread did its own collect
work: the union of the program's ``wave.collect.d2h``, ``wave.collect.rows``
and ``wave.collect.sort`` spans (program spans), without the collect's wait
for the device (``wave.collect.wait``)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import span_share  # noqa: E402

SPANS = ("wave.collect.d2h", "wave.collect.rows", "wave.collect.sort")


def read(ctx):
    return span_share.share(ctx, SPANS)
