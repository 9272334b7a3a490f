"""Share of the job window spent materializing wave outputs on the host:
the union of the program's ``wave.collect.d2h`` spans (program spans)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import span_share  # noqa: E402

SPANS = ("wave.collect.d2h",)


def read(ctx):
    return span_share.share(ctx, SPANS)
