"""Share of the job window spent in the host's stable byte-view sorts: the
union of the program's ``wave.collect.sort`` (each wave's segment order) and
``merge.kway.sort`` (the k-way merge at finalize) spans (program spans)."""
import sys
from pathlib import Path

_BENCH = str(Path(__file__).resolve().parents[1])
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import span_share  # noqa: E402

SPANS = ("wave.collect.sort", "merge.kway.sort")


def read(ctx):
    return span_share.share(ctx, SPANS)
