"""``repro.dist`` — the distributed-runtime substrate.

The COUNTDOWN Slack core (``repro.core``) reasons about *when* ranks wait;
this package provides the machinery that makes ranks exist at scale:

``sharding``     the Auto-axis mesh factory and partition rules: FSDP/TP
                 parameter shardings (2-d and ZeRO-3), optimizer-state
                 mirroring, batch/cache layouts, tensor-parallel serving
                 rules, activation constraints.
``elastic``      :class:`ElasticMesh` (rebuildable device mesh that survives
                 node failures) and :class:`FailureInjector` (deterministic
                 fault injection for the recovery path).
``checkpoint``   :class:`CheckpointManager` — atomic, optionally async
                 save/restore with retention pruning; the restart substrate
                 for the mesh-epoch recovery loop.
``compression``  int8 gradient codec + :func:`compressed_psum`, the
                 wire-thrifty cross-pod reduction (beyond-paper knob).
``straggler``    :class:`StragglerDetector` — turns barrier-arrival events
                 into a per-rank laggard signal (the paper's critical-rank
                 analysis, §5, made online).

See DESIGN.md §3 for how these compose into the train/serve launchers.
"""
from repro.dist import sharding  # noqa: F401
from repro.dist.checkpoint import CheckpointManager  # noqa: F401
from repro.dist.compression import compressed_psum  # noqa: F401
from repro.dist.elastic import ElasticMesh, FailureInjector  # noqa: F401
from repro.dist.straggler import StragglerDetector  # noqa: F401
