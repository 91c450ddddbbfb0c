"""mpit_tpu.dplane — device-resident data plane tests.

Three layers:

- the partition-rule engine's invariants (every leaf matched exactly
  once, scalars unpartitioned, specs valid for the mesh, aligned cuts
  tile at segment boundaries);
- HbmSlot mechanics (donation really consumes the old buffers, the
  per-version snapshot/pull caches really cache, pulls survive a later
  donated apply);
- **bitwise parity**: for msgd / DOWNPOUR / EAMSGD, a device-exchange
  run ends with exactly the bytes of the host-path run under a fixed
  reduction order — including a mixed gang where the wire-fallback
  server runs under a drop/dup FaultPlan (retry/dedup intact beside
  the device path).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.dplane import (
    ExchangeClient,
    ExchangeError,
    HbmSlot,
    PlaneConfig,
    aligned_cut,
    dedupe_state,
    flat_segments,
    match_partition_rules,
    match_report,
    plan_shard_map,
    tree_shardings,
)
from mpit_tpu.dplane.exchange import DevicePlane, DeviceTicket
from mpit_tpu.dplane.partition import Segment, shard_tree, validate_spec
from mpit_tpu.ft import FaultPlan, FaultyTransport, FTConfig
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.easgd import EAMSGD
from mpit_tpu.optim.rules import make as make_rule
from mpit_tpu.optim.shells import SingleWorker
from mpit_tpu.parallel.mesh import make_mesh
from mpit_tpu.ps import ParamClient, ParamServer, tags
from mpit_tpu.utils.platform import default_devices

DATA_TAGS = frozenset({tags.GRAD, tags.PARAM_REQ, tags.PARAM_PUSH})
FAST_FT = FTConfig(op_deadline_s=0.25, max_retries=8,
                   backoff_base_s=0.005, backoff_cap_s=0.02)


def mesh8():
    return make_mesh(default_devices(), dp=1)


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "role thread did not stop (hang)"


def _tree(seed: int):
    """A transformer-shaped random pytree (nested dicts, mixed ranks,
    a couple of scalars)."""
    rng = np.random.default_rng(seed)
    return {
        "embed": {"table": rng.normal(size=(16, 8)).astype(np.float32)},
        "layer_0": {
            "attn": {"q": rng.normal(size=(8, 8)).astype(np.float32),
                     "bias": rng.normal(size=8).astype(np.float32)},
            "mlp": {"w1": rng.normal(size=(8, 16)).astype(np.float32),
                    "w2": rng.normal(size=(16, 8)).astype(np.float32)},
        },
        "norm": {"scale": np.float32(rng.normal())},
        "step": np.zeros((), np.int32),
    }


RULES = [
    (r"embed/table", P("shard", None)),
    (r"attn/.*bias", P(None)),
    (r"attn", P(None, "shard")),
    (r"mlp/w1", P(None, "shard")),
    (r"mlp/w2", P("shard", None)),
    (r".*", P()),
]


class TestPartitionRules:
    def test_first_match_wins_and_scalars_unpartitioned(self):
        specs = match_partition_rules(RULES, _tree(0))
        assert specs["embed"]["table"] == P("shard", None)
        # attn/bias hits the bias rule before the broader attn rule
        assert specs["layer_0"]["attn"]["bias"] == P(None)
        assert specs["layer_0"]["attn"]["q"] == P(None, "shard")
        # scalars resolve to P() without consuming a rule
        assert specs["norm"]["scale"] == P()
        assert specs["step"] == P()

    def test_unmatched_leaf_raises_or_replicates(self):
        rules = [(r"embed", P("shard", None))]
        with pytest.raises(ValueError, match="no partition rule"):
            match_partition_rules(rules, _tree(0))
        specs = match_partition_rules(rules, _tree(0),
                                      on_unmatched="replicate")
        assert specs["layer_0"]["mlp"]["w1"] == P()

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_every_leaf_matched_exactly_once(self, seed):
        tree = _tree(seed)
        leaves = jax.tree_util.tree_leaves(tree)
        report = match_report(RULES, tree)
        # unique path per leaf => exactly one verdict per leaf
        assert len(report) == len(leaves)
        for name, idx in report.items():
            if name in ("norm/scale", "step"):
                assert idx == -1, name  # scalar: never partitioned
            else:
                assert 0 <= idx < len(RULES), name

    def test_specs_valid_for_mesh(self):
        mesh = mesh8()
        tree = _tree(0)
        specs = match_partition_rules(RULES, tree)
        shardings = tree_shardings(mesh, specs, tree)
        flat = jax.tree_util.tree_leaves(shardings)
        assert all(s.mesh.shape == mesh.shape for s in flat)
        # placement roundtrip preserves every byte
        placed = shard_tree(tree, shardings)
        for a, b in zip(jax.tree_util.tree_leaves(placed),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), b)

    def test_invalid_axis_and_indivisible_dims_fail_loudly(self):
        mesh = mesh8()
        with pytest.raises(ValueError, match="not in mesh axes"):
            validate_spec(mesh, P("bogus"), (8,), "x")
        with pytest.raises(ValueError, match="not divisible"):
            validate_spec(mesh, P("shard"), (9,), "x")
        with pytest.raises(ValueError, match="names 2 dims"):
            validate_spec(mesh, P("shard", None), (8,), "x")

    def test_naive_fallback_degrades_indivisible_dims(self):
        mesh = mesh8()
        tree = {"w": np.zeros((9, 8), np.float32)}
        specs = {"w": P("shard", None)}
        shardings = tree_shardings(mesh, specs, tree, naive_fallback=True)
        assert shardings["w"].spec == P(None, None)
        with pytest.raises(ValueError, match="not divisible"):
            tree_shardings(mesh, specs, tree)


class TestAlignedCut:
    @pytest.mark.parametrize("seed", [3, 4, 5])
    def test_cut_properties(self, seed):
        rng = np.random.default_rng(seed)
        sizes = rng.integers(1, 50, size=12)
        segments, off = [], 0
        for i, s in enumerate(sizes):
            segments.append(Segment(f"leaf{i}", off, int(s)))
            off += int(s)
        n = int(rng.integers(2, 6))
        shards = aligned_cut(off, segments, n)
        # tile [0, plong), nonempty, interior cuts on segment boundaries
        assert shards[0].offset == 0 and shards[-1].end == off
        boundaries = {s.offset for s in segments}
        pos = 0
        for sh in shards:
            assert sh.offset == pos and sh.size > 0
            assert sh.offset in boundaries or sh.offset == 0
            pos = sh.end
        # deterministic
        assert aligned_cut(off, segments, n) == shards

    def test_fewer_segments_than_shards_raises(self):
        segments = [Segment("a", 0, 10), Segment("b", 10, 10)]
        with pytest.raises(ValueError, match="never splits a parameter"):
            aligned_cut(20, segments, 3)

    def test_plan_shard_map_is_a_valid_layout_source(self):
        tree = _tree(1)
        smap = plan_shard_map(tree, [0, 1], shards_per_server=2)
        segments = flat_segments(tree)
        assert smap.plong == segments[-1].end
        assert smap.version == 0 and len(smap.entries) == 4
        assert [e.owner for e in smap.entries] == [0, 0, 1, 1]
        boundaries = {s.offset for s in segments}
        for e in smap.entries[1:]:
            assert e.shard.offset in boundaries


class TestHbmSlot:
    def test_donated_apply_consumes_old_buffers_bitwise(self):
        cfg = PlaneConfig(mesh=mesh8())
        slot = HbmSlot(16, make_rule("adam"), config=cfg)
        rng = np.random.default_rng(7)
        g = rng.normal(size=16).astype(np.float32)
        # reference: the same rule math, un-donated, on host arrays
        ref_rule = make_rule("adam")
        ref_p = jnp.zeros(16, jnp.float32)
        ref_s = ref_rule.init(ref_p)
        ref_p, ref_s = jax.jit(ref_rule.apply)(ref_p, jnp.asarray(g), ref_s)
        p0, m0 = slot.param, slot.rule_state["m"]
        slot.apply_grad(g)
        assert p0.is_deleted() and m0.is_deleted(), \
            "donation did not consume the old buffers"
        np.testing.assert_array_equal(slot.snapshot_host(),
                                      np.asarray(ref_p))
        assert slot.version == 1

    def test_snapshot_and_pull_caches_are_per_version(self):
        slot = HbmSlot(16, make_rule("add"), config=PlaneConfig(mesh=mesh8()))
        a, b = slot.snapshot_host(), slot.snapshot_host()
        assert a is b and int(slot._m_copies.value) == 1
        p1, p2 = slot.pull_device(), slot.pull_device()
        assert p1 is p2 and int(slot._m_gathers.value) == 1
        slot.apply_grad(np.ones(16, np.float32))
        assert slot.snapshot_host() is not a
        assert int(slot._m_copies.value) == 2

    def test_pull_survives_a_later_donated_apply(self):
        slot = HbmSlot(16, make_rule("add"), config=PlaneConfig(mesh=mesh8()))
        pulled = slot.pull_device()
        slot.apply_grad(np.ones(16, np.float32))
        # the old param buffer was donated away; the pull must not be it
        np.testing.assert_array_equal(np.asarray(pulled),
                                      np.zeros(16, np.float32))

    def test_dedupe_state_breaks_rule_init_aliasing(self):
        p = jnp.zeros(8, jnp.float32)
        state = make_rule("adam").init(p)
        assert state["m"] is state["v"], "fixture assumption: init aliases"
        fresh = dedupe_state(state)
        assert fresh["m"] is not fresh["v"]
        np.testing.assert_array_equal(np.asarray(fresh["m"]),
                                      np.asarray(fresh["v"]))


# ---------------------------------------------------------------------------
# the partition engine over a REAL TrainState (params + optimizer slots)


def _lm_train_state(rule="adam"):
    from mpit_tpu.lm import build, train_state_tree

    model = build(d_model=16, n_heads=2, n_layers=1, seq_len=16,
                  use_flash=False)
    params = model.flat.unravel(model.flat.w0)
    return params, train_state_tree(params, rule)


class TestTrainStatePartition:
    """The rule table must cover params AND the mirrored optimizer
    slots — the tree the LM shard plan is actually computed over."""

    @pytest.mark.parametrize("rule", ["adam", "rmsprop", "adagrad"])
    def test_every_trainstate_leaf_matched_exactly_once(self, rule):
        from mpit_tpu.lm import PARTITION_RULES, audit_rules

        params, ts = _lm_train_state(rule)
        leaves = jax.tree_util.tree_leaves(ts)
        report = audit_rules(ts)  # raises on any -2 (unmatched)
        assert len(report) == len(leaves)
        for name, idx in report.items():
            assert idx == -1 or 0 <= idx < len(PARTITION_RULES), name
        # optimizer slots mirror the param paths, so both halves of the
        # TrainState resolve through ONE table
        assert any(n.startswith("params/") and report[n] >= 0
                   for n in report)
        assert any(n.startswith("opt_state/") and report[n] >= 0
                   for n in report)
        # per-leaf step counters are scalars: unpartitioned, not errors
        assert all(report[n] == -1 for n in report if n.endswith("/t"))

    def test_unmatched_opt_leaf_is_loud(self):
        from mpit_tpu.lm import audit_rules

        _, ts = _lm_train_state("adam")
        # drop the kernel rule: every Dense kernel (params AND its m/v
        # slots) must be reported, not silently replicated
        rules = [(r"Embed_\d+/embedding", P("mdl", None)),
                 (r"Dense_\d+/bias", P()),
                 (r"LayerNorm_\d+/(scale|bias)", P())]
        with pytest.raises(ValueError, match="match no partition rule"):
            audit_rules(ts, rules)

    def test_optax_style_nested_opt_state(self):
        optax = pytest.importorskip("optax")
        from mpit_tpu.lm import PARTITION_RULES

        params, _ = _lm_train_state()
        state = optax.adam(1e-3).init(params)
        tree = {"params": params, "opt_state": state}
        report = match_report(PARTITION_RULES, tree)
        leaves = jax.tree_util.tree_leaves(tree)
        assert len(report) == len(leaves)
        assert not any(idx == -2 for idx in report.values()), \
            sorted(n for n, i in report.items() if i == -2)
        # optax nests the param tree under namedtuple fields (mu/nu);
        # the component-name rules still land because match is a search
        mu = [n for n in report if "/mu/" in n]
        assert mu and all(report[n] >= 0 for n in mu)
        assert report["opt_state/0/count"] == -1  # scalar step counter

    def test_shared_zero_slots_compose_with_dedupe_state(self):
        # train_state_tree keeps rule-init aliasing (m is v is one
        # zeros_like); dedupe_state must break it leaf-by-leaf without
        # changing bytes — the seam a donated apply depends on.
        _, ts = _lm_train_state("adam")
        aliased = 0
        for _path, sub in jax.tree_util.tree_leaves_with_path(
                ts["opt_state"],
                is_leaf=lambda x: isinstance(x, dict) and "m" in x):
            if not isinstance(sub, dict):
                continue
            if sub["m"] is sub["v"]:
                aliased += 1
                fresh = dedupe_state(sub)
                assert fresh["m"] is not fresh["v"]
                np.testing.assert_array_equal(np.asarray(fresh["m"]),
                                              np.asarray(sub["m"]))
        assert aliased > 0, "fixture assumption: adam init aliases m/v"


# ---------------------------------------------------------------------------
# optimizer parity: device exchange vs host path, bitwise


def _quadratic(target):
    def vgf(w):
        delta = w - target
        return 0.5 * jnp.sum(delta * delta), delta

    return vgf


def _single_client_gang(dplane, *, rule="add", single_mode=False,
                        seed_servers=True):
    router = LocalRouter(3)
    sranks, crank = [0, 1], 2
    cfg = PlaneConfig.auto() if dplane else None
    servers = [ParamServer(r, [crank], router.endpoint(r), rule=rule,
                           single_mode=single_mode, dplane=cfg)
               for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    pc = ParamClient(crank, sranks, router.endpoint(crank),
                     seed_servers=seed_servers)
    client = ExchangeClient(pc) if dplane else pc
    return servers, client, threads


def _run_optimizer(make_opt, dplane, steps=6, size=32):
    servers, client, threads = (
        _single_client_gang(dplane, rule="add"))
    rng = np.random.default_rng(21)
    w = jnp.asarray(rng.normal(size=size).astype(np.float32))
    target = jnp.asarray(rng.normal(size=size).astype(np.float32))
    opt = make_opt(_quadratic(target), client)
    w = opt.start(w)
    for _ in range(steps):
        w, _loss = opt.step(w)
    opt.stop()
    join_all(threads)
    if dplane:
        assert client.device_ranks == [0, 1]
    finals = [np.asarray(s.param) for s in servers]
    return np.asarray(w), np.concatenate(finals)


@pytest.mark.parametrize("name,make_opt", [
    ("downpour", lambda vgf, pc: Downpour(vgf, pc, lr=0.05, su=2)),
    ("eamsgd", lambda vgf, pc: EAMSGD(vgf, pc, lr=0.05, mom=0.5,
                                      mva=0.3, su=2)),
])
def test_optimizer_parity_device_vs_host(name, make_opt):
    """DOWNPOUR / EAMSGD: the device-exchange run must end bitwise
    equal to the host-path run — local params AND the servers' center."""
    w_host, center_host = _run_optimizer(make_opt, dplane=False)
    w_dev, center_dev = _run_optimizer(make_opt, dplane=True)
    np.testing.assert_array_equal(w_host, w_dev)
    np.testing.assert_array_equal(center_host, center_dev)


def _run_msgd(dplane, steps=5, size=32):
    servers, client, threads = _single_client_gang(
        dplane, single_mode=True, seed_servers=True)
    rng = np.random.default_rng(33)
    w = jnp.asarray(rng.normal(size=size).astype(np.float32))
    target = jnp.asarray(rng.normal(size=size).astype(np.float32))
    opt = SingleWorker(_quadratic(target), client, rule="msgd",
                       lr=0.05, mom=0.9)
    w = opt.start(w)
    for _ in range(steps):
        w, _loss = opt.step(w)
    w = opt.params(w)  # the committed vector: what the pushes carry
    opt.stop()
    join_all(threads)
    return np.asarray(w), np.concatenate(
        [np.asarray(s.param) for s in servers])


def test_msgd_parity_device_vs_host():
    """msgd (SingleWorker): whole-param pushes ride the device 'push'
    op; the mirrored server state must match the host run bitwise."""
    w_host, mirror_host = _run_msgd(dplane=False)
    w_dev, mirror_dev = _run_msgd(dplane=True)
    np.testing.assert_array_equal(w_host, w_dev)
    np.testing.assert_array_equal(mirror_host, mirror_dev)
    np.testing.assert_array_equal(w_dev, mirror_dev)


# ---------------------------------------------------------------------------
# mixed gang: device path beside the faulty wire fallback


def _mixed_gang_final(device_ranks, client_plans, rounds=4, size=64):
    """2 servers / 2 clients lockstep; server ranks in ``device_ranks``
    serve over the device path, the rest over the (possibly faulty)
    framed wire."""
    router = LocalRouter(4)
    sranks, cranks = [0, 1], [2, 3]
    cfg = PlaneConfig.auto() if device_ranks else None
    servers = [ParamServer(r, cranks, router.endpoint(r), rule="add",
                           ft=FAST_FT, dplane=cfg) for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    rng = np.random.default_rng(42)
    w0 = rng.normal(size=size).astype(np.float32)
    gtab = rng.normal(size=(2, rounds, size)).astype(np.float32)
    clients = []
    for r in cranks:
        ep = router.endpoint(r)
        if client_plans and r - 2 in client_plans:
            ep = FaultyTransport(ep, client_plans[r - 2])
        pc = ParamClient(r, sranks, ep, seed_servers=(r == cranks[0]),
                         ft=FAST_FT)
        clients.append(ExchangeClient(pc, device_ranks=device_ranks)
                       if device_ranks else pc)
    params = [w0.copy(), np.zeros(size, np.float32)]
    starters = [threading.Thread(target=c.start,
                                 args=(p, np.zeros(size, np.float32)),
                                 daemon=True)
                for c, p in zip(clients, params)]
    for t in starters:
        t.start()
    join_all(starters)
    for r in range(rounds):
        for i, c in enumerate(clients):
            c.grad[:] = gtab[i, r]
            c.async_send_grad()
            c.wait()
    clients[0].async_recv_param()
    clients[0].wait()
    final = clients[0].param.copy()
    retries = sum(c.retries for c in clients)
    for c in clients:
        c.stop()
    join_all(threads)
    return final, retries, servers


def test_faultplan_leg_mixed_device_and_faulty_wire_bitwise():
    """The ISSUE's drop/dup leg: server 0 serves on the device path,
    server 1 on the wire under a drop/dup FaultPlan.  Final params must
    equal the fault-free all-wire run bitwise — retry/dedup cover the
    wire half while the device half bypasses it entirely."""
    clean, _, _ = _mixed_gang_final(None, None)
    plans = {i: FaultPlan(seed=i, drop_every=3, dup_every=4,
                          tags=DATA_TAGS) for i in range(2)}
    faulty, retries, servers = _mixed_gang_final([0], plans)
    np.testing.assert_array_equal(clean, faulty)
    assert retries > 0, "the plan never actually bit"
    dev_ops = sum(int(c.value) for c in servers[0]._m_dp_ops.values())
    assert dev_ops > 0, "the device path was never exercised"
    assert servers[1]._hbm is None or not servers[1]._m_dp_ops, \
        "the faulty server must have served over the wire"


# ---------------------------------------------------------------------------
# exchange lifecycle: loud failures, honest fallbacks


class TestExchangeLifecycle:
    def test_closed_plane_fails_tickets_loudly(self):
        plane = DevicePlane(0, (0, "cpu"))
        ticket = plane.submit(DeviceTicket("grad", 1, 0, None))
        plane.close("test teardown")
        assert ticket.event.is_set()
        assert isinstance(ticket.error, ExchangeError)
        with pytest.raises(ExchangeError, match="closed"):
            plane.submit(DeviceTicket("grad", 1, 0, None))

    def test_non_identity_codec_falls_back_to_wire(self):
        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0), rule="add",
                             codec=None, dplane=PlaneConfig.auto())
        t = threading.Thread(target=server.start, daemon=True)
        t.start()
        pc = ParamClient(1, [0], router.endpoint(1), seed_servers=True,
                         codec="int8")
        client = ExchangeClient(pc)
        w = np.zeros(2048, np.float32)
        client.start(w, np.zeros_like(w))
        assert client.device_ranks == []  # quantized exchange: wire only
        client.grad[:] = 1.0
        client.async_send_grad()
        client.wait()
        client.stop()
        join_all([t])

    def test_require_device_raises_without_a_plane(self):
        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0), rule="add")
        t = threading.Thread(target=server.start, daemon=True)
        t.start()
        pc = ParamClient(1, [0], router.endpoint(1), seed_servers=True)
        client = ExchangeClient(pc, require_device=True)
        w = np.zeros(16, np.float32)
        with pytest.raises(ExchangeError, match="fell back to the wire"):
            client.start(w, np.zeros_like(w))
        client.stop()
        join_all([t])

    def test_sync_device_round_stays_on_device(self):
        servers, client, threads = _single_client_gang(True)
        w0 = np.ones(32, np.float32)
        client.start(w0.copy(), np.zeros(32, np.float32))
        update = jnp.full(32, 0.5, jnp.float32)
        out = client.sync_device(update)
        assert isinstance(out, jax.Array)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full(32, 1.5, np.float32))
        client.stop()
        join_all(threads)
