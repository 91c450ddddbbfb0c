"""Wire-protocol tags (analog of reference asyncsgd/init.lua:3-10).

Eight channels, renamed by direction and purpose rather than the
reference's server-perspective naming.  0-byte messages serve as the
rendezvous conventions the reference relies on: PARAM_REQ is the "header"
a client sends to request a shard read (reference pclient.lua:74-75 ->
pserver.lua:100-101); *_ACK are the "tail" completion acks after writes
(reference pserver.lua:85-86, pclient.lua:55-56)."""

INIT = 1  # client -> server: int64 shard announcement.  Three wire
#           generations, distinguished by payload length (docs/PROTOCOL.md):
#           v1 (16 B) [offset, size] = codec 'none', no fault tolerance;
#           v2 (24 B) [offset, size, codec_id];
#           v3 (40 B) [offset, size, codec_id, epoch, flags] — epoch is
#           the client incarnation number (bumped on restart/rejoin) and
#           flags bit0 enables FT frame headers (mpit_tpu/ft/wire.py).
GRAD = 2  # client -> server: gradient/delta frame for the shard, in the
#           negotiated codec's wire format (raw dtype bytes for 'none');
#           FT-framed clients prepend an int64 [epoch, seq] header
GRAD_ACK = 3  # server -> client: ack after the update is applied — 0-byte
#               legacy, int64 [epoch, seq] echo for FT-framed clients
PARAM_REQ = 4  # client -> server: request-to-read header — 0-byte legacy,
#                int64 [epoch, seq] for FT-framed clients
PARAM = 5  # server -> client: current shard snapshot frame (negotiated
#            codec); FT-framed replies echo the request's [epoch, seq]
PARAM_PUSH = 6  # client -> server: whole-shard parameter write frame
#                 (FT-framed clients prepend [epoch, seq])
PARAM_PUSH_ACK = 7  # server -> client: ack after the write lands — 0-byte
#                     legacy, [epoch, seq] echo for FT-framed clients
STOP = 8  # client -> server: 0-byte graceful-shutdown signal
HEARTBEAT = 9  # client -> server: int64 [epoch, seq] liveness beacon; the
#                server's lease registry (mpit_tpu/ft/leases.py) renews
#                the client's lease on every beat and evicts on expiry.
#                Under shardctl, servers also beat to the controller with
#                a per-shard load report appended (docs/PROTOCOL.md §7.4).
MAP_UPDATE = 10  # controller -> server/client (and server -> controller
#                  as the DONE echo): a shard-map directive
#                  [kind, shard_id, peer] + serialized ShardMap
#                  (mpit_tpu/shardctl/wire.py; docs/PROTOCOL.md §7.3)
SHARD_PULL = 11  # server(dst) -> server(src): int64 [shard_id] — "I was
#                  directed to acquire this shard; send its state"
SHARD_STATE = 12  # server(src) -> server(dst): the frozen shard's full
#                   state (meta json + param bytes + rule-state arrays),
#                   a multi-message sequence on this one FIFO channel
HEARTBEAT_ECHO = 13  # server -> client: int64 [epoch, seq, t_tx_echo,
#                      t_recv, t_ack] — the FLAG_TIMING reply to a timed
#                      HEARTBEAT beacon (docs/PROTOCOL.md §6.7).  NOT an
#                      ack tail: heartbeats stay fire-and-forget, and the
#                      client drains echoes opportunistically (iprobe in
#                      ping/wait) to refresh its clock-offset estimator
#                      while compute-bound; a lost echo costs nothing.
# 14 and 15 are unassigned and not to be reused: they were DIFF and
# DIFF_REQ of the multi-cell fabric (docs/PROTOCOL.md §11, retired).
REDUCE = 16  # client -> client: one partial-gradient chunk frame of the
#              hierarchical aggregation tree (docs/PROTOCOL.md §13):
#              int64 [epoch, seq, chunk_idx, chunk_count, nfold] then
#              the chunk's codec frame, padded to the uniform stride.
#              ``nfold`` is the number of leaf contributions already
#              folded into the partial; the receiving interior node
#              folds the decoded chunk into its own partial sum in
#              fixed child-rank order and forwards chunk k upstream
#              while chunk k+1 is still arriving.
REDUCE_ACK = 17  # client -> client: int64 [epoch, seq, chunk_idx,
#                  status] — per-admitted-chunk ack on the REDUCE hop.
#                  status OK means received (retries resend only
#                  unacked chunks, the §12 discipline); status LATE
#                  means the round already folded without this sender
#                  (straggler deadline fired) — the sender must fall
#                  back to a direct GRAD push of its partial, so a
#                  late contribution is counted and re-routed, never
#                  silently dropped and never double-folded.

EMPTY = b""  # the canonical 0-byte payload

# Protocol-conformance pairing table (machine-checked: mtlint MT-P5xx).
# Every tag above MUST have an entry naming its sender and receiver
# roles; client<->server rows are additionally cross-checked against the
# actual role-file call sites (MT-P102), while rows involving the
# controller or server<->server traffic are exempt from that binary
# role model and are validated against this table + docs/PROTOCOL.md.
TAG_PAIRS = {
    "INIT": ("client", "server"),
    "GRAD": ("client", "server"),
    "GRAD_ACK": ("server", "client"),
    "PARAM_REQ": ("client", "server"),
    "PARAM": ("server", "client"),
    "PARAM_PUSH": ("client", "server"),
    "PARAM_PUSH_ACK": ("server", "client"),
    "STOP": ("client", "server|controller"),
    "HEARTBEAT": ("client|server", "server|controller"),
    "MAP_UPDATE": ("controller|server", "server|client|controller"),
    "SHARD_PULL": ("server", "server"),
    "SHARD_STATE": ("server", "server"),
    "HEARTBEAT_ECHO": ("server", "client"),
    # Hierarchical aggregation (docs/PROTOCOL.md §13): reduction-tree
    # hops travel client<->client — like the server<->server shard
    # handoff, these rows live outside the binary client<->server role
    # model and are validated against this table + PROTOCOL.md.
    "REDUCE": ("client", "client"),
    "REDUCE_ACK": ("client", "client"),
}
