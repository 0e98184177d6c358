"""Canonical registry of fault-injection site names.

``resilience.faults.inject(site)`` gates are addressed by name from
``TPU_ML_FAULT_PLAN`` plans; a typo'd site in either place silently never
fires. Declaring the sites here gives the chaos tests, the docs, and the
linter (``tools/tpulint.py`` rule TPL005) one source of truth: a call-site
literal that does not resolve against this set is a lint error.

Import-pure (no package siblings) so the linter can load it standalone.
"""

from __future__ import annotations

# Site constants — call sites use these (or the equal literal; the linter
# accepts both, the constant is preferred for grep-ability).
WORKER_TASK = "worker.task"       # localspark worker / executor task entry
COLLECTIVE = "collective"         # cross-device collective dispatch
DEVICE_INIT = "device.init"       # backend/device initialization
FOLD_DISPATCH = "fold.dispatch"   # streamed-fit chunk dispatch
FOLD_WAIT = "fold.wait"           # streamed-fit device waits: a chunk landing, the terminal one
INGEST_CHUNK = "ingest.chunk"     # streamed-fit chunk staging
# driver-side elastic-scheduler gates: unlike worker.task (which every
# worker process counts independently), these count in the DRIVER, so a
# plan can fail exactly one dispatch / one rank of one epoch
SCHEDULER_TASK = "scheduler.task"  # one task dispatch by the work queue
SCHEDULER_RANK = "scheduler.rank"  # one rank launch of a barrier epoch
# serving/refresh plane: the closed-loop model-refresh chaos surface.
# serve.dispatch counts per process (a fleet replica counts its own
# dispatches, so a plan can kill exactly one replica mid-request);
# serve.swap fires BEFORE the atomic registry publish, so any injected
# death/hang leaves the old version serving consistently — never torn
SERVE_DISPATCH = "serve.dispatch"  # one compiled-kernel dispatch
SERVE_SWAP = "serve.swap"          # hot-swap barrier, pre-publish
REFRESH_FOLD = "refresh.fold"      # one delta partial_fit fold
REFRESH_CHECKPOINT = "refresh.checkpoint"  # one durable carry checkpoint

FAULT_SITES: frozenset[str] = frozenset({
    WORKER_TASK,
    COLLECTIVE,
    DEVICE_INIT,
    FOLD_DISPATCH,
    FOLD_WAIT,
    INGEST_CHUNK,
    SCHEDULER_TASK,
    SCHEDULER_RANK,
    SERVE_DISPATCH,
    SERVE_SWAP,
    REFRESH_FOLD,
    REFRESH_CHECKPOINT,
})
