"""L3 shell + client: how much of the pull went up to the chip before
its shard was whole on the host: per round the ``bytes`` of the stream
thread's ``h2d`` piece spans (``mpit_tpu/optim/sync.py``: the dispatch of
a piece's ``device_put`` and paste) that began before their own shard's
PARAM ``rx`` span on the client ended (the shm wire's record of the
message landing in ``w_host``: ``mpit_tpu/comm/shm.py``), over all the
round's ``h2d`` bytes, in percent, the median over the first worker's
rounds that lie whole in the window.  0 says every shard went up after
its PARAM op was done, as before PR 49; all but each shard's last piece
says the upload followed the landing (``ShmTransport.filled``), so the
round ends a piece, not a shard's upload, after its last pull.  The twin
of ``push_early_pct``.  The servers in rank order are the shards in
order (one shard a server, the launchers list the servers by rank); a
round in which a shard's pieces are not its message's bytes (a shard
that went up twice after an aborted read) is left out.  None where the
program recorded no ``copy`` span (a program from before PR 48), no
``wire`` span, or no transport ran."""

from chipbench.layers import copytree, wiretree


def read(run):
    copies = copytree.load(run)
    wire = wiretree.load(run)
    if copies is None or wire is None:
        return None
    mono = wire.tree.mono
    pulls = {}  # round -> [(server, when its rx ended, bytes)]
    for op, k, _tx, rx in wire.messages:
        if op == "PARAM":
            pulls.setdefault(k, []).append(
                (rx.args.get("peer"), mono(rx, rx.t1),
                 int(rx.args["bytes"])))
    shares = []
    for r, _mine, pieces in copies.rounds:
        ups = {}  # shard -> its piece spans
        for s in pieces:
            if s.name == "h2d":
                ups.setdefault(int(s.args.get("shard", -1)), []).append(s)
        landed = sorted(pulls.get(r.args.get("round"), ()))
        if not ups or len(landed) != len(ups):
            continue
        early = total = 0
        for shard, (_server, end, nbytes) in zip(sorted(ups), landed):
            sizes = [int(s.args.get("bytes", 0)) for s in ups[shard]]
            if sum(sizes) != nbytes:
                break
            total += nbytes
            early += sum(size for s, size in zip(ups[shard], sizes)
                         if mono(s, s.t0) < end)
        else:
            shares.append(100.0 * early / total)
    return wiretree.median(shares)
