"""mpit_tpu.agg — hierarchical quantized aggregation under the PS model.

GRAD is wire-bound: once chunked streaming (§12) put the single-link
path at the link floor, the next order of magnitude has to come from
sending *fewer bytes upstream*.  This
package embeds a collective pre-reduction stage under the parameter-
server model (the MXNET-MPI direction, PAPERS.md 1802.06949): N
gradients become one before the server ever sees them.

- :mod:`mpit_tpu.agg.plan` — the deterministic reduction topology:
  colocated groups (dplane-fingerprint equivalence) electing min-rank
  representatives, and a seed-deterministic ``fanin``-ary tree over
  the representatives.  Fixed fold order is the bitwise-parity anchor.
- :mod:`mpit_tpu.agg.node` — the in-process group plane: single-writer
  ticket queue for on-device pre-reduction (the DevicePlane shape).
- :mod:`mpit_tpu.agg.wire` — the REDUCE hop frames: §12 chunk
  discipline plus ``nfold`` fan-in accounting and the LATE ack status
  that re-routes stragglers to direct pushes.
- :mod:`mpit_tpu.agg.client` — :class:`AggClient`, the ParamClientAPI
  front that runs the whole thing: arrival-order-tolerant folds,
  per-hop int8 error feedback, wall-bounded straggler deadlines,
  loud-never-hang rails.

docs/PROTOCOL.md §13 is normative.
"""

from mpit_tpu.agg.client import AggClient
from mpit_tpu.agg.node import (
    TICKET_LATE,
    TICKET_OK,
    AggPlane,
    AggPlaneClosed,
    AggTicket,
)
from mpit_tpu.agg.plan import AggConfig, ReductionPlan
from mpit_tpu.agg.wire import (
    RD_ACK_WORDS,
    RD_HDR_BYTES,
    RD_HDR_WORDS,
    RD_LATE,
    RD_OK,
    pack_reduce_header,
    reduce_ack_frame,
    unpack_reduce_header,
)

__all__ = [
    "AggClient",
    "AggConfig",
    "AggPlane",
    "AggPlaneClosed",
    "AggTicket",
    "ReductionPlan",
    "TICKET_LATE",
    "TICKET_OK",
    "RD_ACK_WORDS",
    "RD_HDR_BYTES",
    "RD_HDR_WORDS",
    "RD_LATE",
    "RD_OK",
    "pack_reduce_header",
    "reduce_ack_frame",
    "unpack_reduce_header",
]
