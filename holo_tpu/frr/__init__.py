"""IP Fast Reroute (FRR): precomputed per-link backup next hops.

The subsystem the reference tree hangs off its TI-LFA work: after every
primary SPF the protocol layer hands its :class:`~holo_tpu.ops.graph.Topology`
to an :class:`~holo_tpu.frr.manager.FrrEngine`, which runs ONE batched device
dispatch computing

1. the distance rows and reversed-graph columns the selection reads —
   from the root, its first routers, the far ends, the PQ nodes and the
   TI-LFA successors — in blocks of fixed width (no all-roots matrix),
2. per protected link, the post-convergence SPF (the protected edge's
   slot masked), and
3. the vectorized RFC 5286 LFA inequalities, RFC 7490 remote-LFA P/Q-space
   intersection, and TI-LFA P/Q repair-segment selection over those
   distance planes.

The output is a :class:`~holo_tpu.frr.kernel.BackupTable`: for every
(protected link, destination vertex) the chosen loop-free alternate —
a direct LFA next hop, a remote-LFA PQ tunnel endpoint, or a TI-LFA
(P, Q) segment pair — as int32 tables that are bit-identical to the
scalar oracle (:mod:`holo_tpu.frr.scalar`), matching the repo's SPF
conformance discipline.

Consumers: OSPFv2/v3 and IS-IS attach resolved backup next hops to the
routes they publish; the RIB keeps them beside the primaries and flips to
them in O(1) on a BFD session-down or interface link-down event, before
flood-and-SPF reconvergence replaces the repair with the new primaries.
"""

from holo_tpu.frr.inputs import FrrInputs, marshal_frr
from holo_tpu.frr.kernel import BackupTable
from holo_tpu.frr.manager import (
    FrrConfig,
    FrrEngine,
    repair_picks,
    repair_plan,
)

__all__ = [
    "BackupTable",
    "FrrConfig",
    "FrrEngine",
    "FrrInputs",
    "marshal_frr",
    "repair_picks",
    "repair_plan",
]
