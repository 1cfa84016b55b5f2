"""Trace-driven replay harness (ROADMAP item 5).

Flight-recorder dumps and post-mortem bundles convert
into portable, versioned workload files (``workload.py`` /
``extract.py``) that replay deterministically against the real engine
in virtual time (``harness.py``) and report the same SLI families
production exports, diffed against the source incident
(``report.py``).  CLI: ``tools/replay.py``.
"""

from tpuserve.replay.extract import (load_bundle, merge_engine_bundles,
                                     workload_from_bundle)
from tpuserve.replay.harness import (ReplayOptions, build_replay_engine,
                                     replay)
from tpuserve.replay.report import diff_report, render_diff, sli_summary
from tpuserve.replay.workload import (WORKLOAD_SCHEMA_VERSION, Workload,
                                      WorkloadRequest)

__all__ = [
    "WORKLOAD_SCHEMA_VERSION", "Workload", "WorkloadRequest",
    "load_bundle", "merge_engine_bundles", "workload_from_bundle",
    "ReplayOptions", "build_replay_engine", "replay",
    "diff_report", "render_diff", "sli_summary",
]
