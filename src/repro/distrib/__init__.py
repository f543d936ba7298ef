"""Fleet-scale wisdom distribution (beyond-paper, builds on §4.4).

The paper's wisdom files are per-kernel JSON written by whoever tuned
last, on one machine; PR 1's online tuner promotes from live traffic but
each process still learns alone. This subsystem makes wisdom a *fleet*
asset:

* :mod:`.store` — :class:`WisdomStore`: a wisdom directory with schema
  versioning (``WISDOM_VERSION``), migration, validation, pruning;
* :mod:`.merge` — combine stores from many hosts, statistical winner per
  (device, problem, dtype) scenario, provenance preserved as lineage;
* :mod:`.sync`  — pluggable transports (directory, in-memory) with
  :class:`PushSync` (publish / promotion broadcast) and :class:`PullSync`
  (periodic fleet pull, ticked by the serving host between runs);
* :mod:`.cli`   — the ``python -m repro.wisdom`` operator tool
  (inspect/diff/merge/prune/validate/migrate).
"""

from .merge import MergeReport, better_record, merge_stores, merge_wisdom
from .store import CONTROL_PREFIX, PruneReport, ValidationIssue, WisdomStore
from .sync import (DirectoryTransport, MemoryTransport, PullSync, PushSync,
                   Transport, transport_wisdom)

__all__ = [
    "MergeReport", "better_record", "merge_stores", "merge_wisdom",
    "CONTROL_PREFIX", "PruneReport", "ValidationIssue", "WisdomStore",
    "DirectoryTransport", "MemoryTransport", "PullSync", "PushSync",
    "Transport", "transport_wisdom",
]
