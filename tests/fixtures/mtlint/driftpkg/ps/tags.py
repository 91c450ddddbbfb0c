"""Seeded tag-registry drift (mtlint fixture — parsed, never imported).
Deviations from analysis/schema.py TAGS are deliberate."""

INIT = 1
GRAD = 2
GRAD_ACK = 3
PARAM_REQ = 4
PARAM = 5
PARAM_PUSH = 6
PARAM_PUSH_ACK = 7
STOP = 8
HEARTBEAT = 9
MAP_UPDATE = 10
SHARD_PULL = 11
SHARD_STATE = 12
HEARTBEAT_ECHO = 13
DIFF = 14  # MT-S603: a retired id (PROTOCOL.md §11) still assigned
DIFF_REQ = 15
REDUCE = 18  # MT-S603: schema says 16 — the id itself drifted
REDUCE_ACK = 17
SIDEBAND = 19  # MT-S603: a tag the schema registry does not declare

EMPTY = b""

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
    "DIFF": ("server", "server"),  # MT-S603: a row for a retired tag
    "DIFF_REQ": ("cell", "server"),
    "REDUCE": ("client", "client"),
    "REDUCE_ACK": ("client", "server"),  # MT-S603: schema says c -> c
    # MT-S603: SIDEBAND has a TAG_PAIRS row but no schema TagSpec
    "SIDEBAND": ("client", "server"),
}
