"""The parameter-client protocol the comm-aware optimizers drive.

Mirrors the reference pClient surface (reference asyncsgd/pclient.lua:84-179):
``start/reset`` register host-visible flat buffers, the ``async_*`` calls
enqueue per-server transfer tasks, ``ping`` single-steps I/O to overlap with
compute, ``wait`` drains, ``stop`` runs the shutdown protocol.

The real implementation is :class:`mpit_tpu.ps.client.ParamClient`; optimizer
unit tests substitute an in-process simulator.  Buffers are 1-D numpy arrays
the client slices per server shard (numpy views = the zero-copy analog of
``torch.Storage(grad, offset, size)``, reference pclient.lua:50-52).

``announce_plain(ranges)``, before ``start``, is asked of a client
only by a shell whose step has plain ranges (``models/flat.py``
``plain_ranges``; :meth:`mpit_tpu.ps.client.ParamClient.announce_plain`):
a client without it cannot serve such a vector and fails there.

Two optional extensions: ``sync_device`` (:class:`DeviceSyncAPI`, below)
and ``stream_shards(staged, landed)``, by which a client tells the sync
round how the vector is cut and takes its per-shard gate and sink, with
its second half ``stream_pieces(pieces)``, by which it says which shards'
GRAD sends read the payload's pieces where they land and takes their feed
(described on :meth:`mpit_tpu.ps.client.ParamClient.stream_shards` and
:meth:`~mpit_tpu.ps.client.ParamClient.stream_pieces`; used
by :mod:`mpit_tpu.optim.sync`, which tests for them by name, because
``isinstance`` on a protocol does not see through a front that forwards
with ``__getattr__``).
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np


@runtime_checkable
class ParamClientAPI(Protocol):
    def start(self, param: np.ndarray, grad: np.ndarray) -> None:
        """Register buffers, announce shard offsets to servers, and (first
        client only) seed the servers' shards from ``param``."""

    def reset(self, param: np.ndarray, grad: np.ndarray) -> None:
        """Retarget the transfer buffers (reference pclient.lua:138-151) —
        e.g. EASGD points them at its center/elastic-delta copies."""

    def async_send_grad(self) -> None: ...

    def async_recv_param(self) -> None: ...

    def async_send_param(self) -> None: ...

    def ping(self) -> None:
        """Make one unit of I/O progress without blocking."""

    def wait(self) -> None:
        """Block until all enqueued transfers complete."""

    def stop(self) -> None: ...


@runtime_checkable
class DeviceSyncAPI(ParamClientAPI, Protocol):
    """Optional extension (mpit_tpu.dplane.ExchangeClient): a PS round
    that stays in device memory.  ``sync_device(update)`` ships a flat
    ``jax.Array`` update and returns the refreshed parameter vector as
    a device array — no host mirrors touched for device-eligible
    servers (wire-fallback servers are staged through the mirrors
    transparently).  Trainers should feature-test with
    ``isinstance(pc, DeviceSyncAPI)`` and keep the mirror path as the
    universal fallback."""

    def sync_device(self, update, *, pull: bool = True): ...

