"""tpulint: repo-native static analysis for tpuserve engine invariants.

Seven AST-based passes over ``tpuserve/``, each encoding a bug class that
a generic linter cannot see because it is a *property of this engine's
design*, not of Python:

- ``host-sync`` (P1): host synchronization (``jax.device_get`` /
  ``np.asarray`` / ``.item()`` / traced truthiness) inside jit/scan bodies
  and inside the pipelined dispatch path.  The fused-window pipeline's
  one-sync-per-S-tokens property is one stray sync away from silently
  degrading.
- ``thread-ownership`` (P2): engine-loop-owned state mutated from
  watchdog / gateway / health threads — the exact cross-thread bug class
  fixed by hand after PR 3's review.
- ``kv-leak`` (P3): path-sensitive check that every ``BlockManager``
  allocate is paired with a free / ownership transfer on all exit paths
  including exception edges.
- ``pallas`` (P4): Pallas kernel contracts — BlockSpec index-map arity vs
  grid rank, scalar-prefetch argument ordering/arity, dtype rules on the
  int8-dequant path, and a static VMEM budget estimate per kernel.
- ``metrics`` (P5): every metric registered in ``server/metrics.py`` is
  incremented somewhere and documented in README.md, and the README
  tables name only real metric families.
- ``protocol`` (P6): the control-plane wire protocol between server,
  gateway, autoscaler and provisioner — every endpoint a consumer dials
  is served, every JSON key a consumer indexes is written by that
  endpoint's payload builders, every header read is set by a peer (and
  the reverse directions are dead-surface warnings).
- ``config-surface`` (P7): the configuration surface — every
  ``TPUSERVE_*`` read is reachable from a DeployConfig field (or
  registered debug-only), every DeployConfig field lands in a
  provision-layer manifest, and the README flag tables agree with the
  argparse/env surface both directions.

Run: ``python -m tools.tpulint [paths...] [--json]``;
``--explain CODE`` prints a pass's (or one rule's) text and its
suppression-tag syntax.
Suppress a finding with a reasoned comment on (or one line above) the
flagged line::

    x = jax.device_get(toks)   # tpulint: sync-ok(the one designated
                               # window-flush sync point)

A suppression without a reason, an unused suppression, or a suppression
tag outside ``[tool.tpulint].suppression_allowlist`` is itself an error —
the shipped tree lints clean with zero unexplained suppressions.
"""

from __future__ import annotations

from tools.tpulint.core import (Config, Finding, collect_files, load_config,
                                run_lint, run_lint_sources)

__all__ = ["Config", "Finding", "collect_files", "load_config", "run_lint",
           "run_lint_sources", "PASS_NAMES"]

PASS_NAMES = ("host-sync", "thread-ownership", "kv-leak", "pallas",
              "metrics", "protocol", "config-surface")
