"""mpit_tpu.lm — the flagship LM workload.

Four layers:

- the packed token stream's determinism contract (bitwise-identical
  batches for equal ``(seed, step)`` — across calls, across a fresh
  *process*, and across the supervisor-restart pattern of recreating
  the stream object and resuming mid-run);
- the shard plan (aligned weighted cuts tile the flat vector on
  parameter boundaries; the footprint model prices optimizer slots);
- the static ``layout=`` seam on ParamClient/ReaderClient — the
  weighted cut replaces the equal split and composes with chunked
  streaming and the int8 error-feedback codec;
- the LmTrainer loop (local sgd learns; tokens/sec accounting).
"""

import hashlib
import subprocess
import sys
import threading

import numpy as np
import pytest

from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import FTConfig
from mpit_tpu.lm import (
    EOS,
    LmTrainer,
    PackedStream,
    audit_rules,
    build,
    packed_batch,
    plan,
    train_state_tree,
)
from mpit_tpu.ps import ParamClient, ParamServer
from mpit_tpu.ps.serve import ReaderClient
from mpit_tpu.utils.config import Config


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "gang thread did not stop (hang)"


# ---------------------------------------------------------------------------
# packed stream determinism (the data half of bitwise reproducibility)


class TestPackedStream:
    def test_shape_dtype_vocab(self):
        b = packed_batch(3, 0, batch=4, seq_len=32)
        assert b.shape == (4, 33) and b.dtype == np.int32
        assert b.min() >= 0 and b.max() < 256

    def test_eos_separators_present(self):
        # packing concatenates EOS-terminated docs: the grid must
        # contain separators but not be all-EOS
        b = packed_batch(3, 0, batch=4, seq_len=32)
        assert (b == EOS).any()
        assert (b != EOS).sum() > b.size // 2

    def test_bitwise_determinism_in_process(self):
        a = packed_batch(11, 7, batch=8, seq_len=64)
        b = packed_batch(11, 7, batch=8, seq_len=64)
        np.testing.assert_array_equal(a, b)
        assert a.tobytes() == b.tobytes()

    def test_steps_and_seeds_decorrelated(self):
        base = packed_batch(11, 7, batch=8, seq_len=64)
        assert packed_batch(11, 8, batch=8, seq_len=64).tobytes() \
            != base.tobytes()
        assert packed_batch(12, 7, batch=8, seq_len=64).tobytes() \
            != base.tobytes()

    def test_bitwise_determinism_across_processes(self):
        """The cross-process half of the contract: a fresh interpreter
        (fresh numpy, fresh global RNG state) produces the same bytes."""
        prog = (
            "import hashlib\n"
            "from mpit_tpu.lm import packed_batch\n"
            "h = hashlib.sha256()\n"
            "for step in (0, 1, 5):\n"
            "    h.update(packed_batch(11, step, batch=4,"
            " seq_len=32).tobytes())\n"
            "print(h.hexdigest())\n"
        )
        out = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        h = hashlib.sha256()
        for step in (0, 1, 5):
            h.update(packed_batch(11, step, batch=4, seq_len=32).tobytes())
        assert out.stdout.strip() == h.hexdigest()

    def test_restart_resumes_identically(self):
        """Supervisor-restart semantics: a NEW stream object (the dead
        incarnation's state is gone) resumes at step k with exactly the
        batch the old one would have produced — no replay needed."""
        first = PackedStream(5, 4, 32)
        want = [first.batch_at(k).tobytes() for k in range(8)]
        reborn = PackedStream(5, 4, 32)
        got = [reborn.batch_at(k).tobytes() for k in range(4, 8)]
        assert got == want[4:8]

    def test_global_rng_state_untouched(self):
        state = np.random.get_state()[1].copy()
        packed_batch(1, 0, batch=2, seq_len=16)
        np.testing.assert_array_equal(np.random.get_state()[1], state)

    def test_bad_shapes_raise(self):
        with pytest.raises(ValueError):
            packed_batch(0, 0, batch=0, seq_len=32)
        with pytest.raises(ValueError):
            packed_batch(0, 0, batch=2, seq_len=1)


# ---------------------------------------------------------------------------
# the shard plan


class TestLmPlan:
    def _params(self):
        model = build(d_model=16, n_heads=2, n_layers=1, seq_len=16,
                      use_flash=False)
        return model.flat.unravel(model.flat.w0), model.flat.size

    def test_layout_tiles_on_parameter_boundaries(self):
        params, plong = self._params()
        p = plan(params, 3)
        assert p.plong == plong
        boundaries = {s.offset for s in p.segments}
        pos = 0
        for sh in p.layout:
            assert sh.offset == pos and sh.size > 0
            assert sh.offset in boundaries or sh.offset == 0
            pos = sh.end
        assert pos == plong

    def test_weighted_cut_skews_toward_heavy_servers(self):
        # dense parameter boundaries so the weighted target can land
        # near its fraction (the real model's coarse leaves snap harder)
        params = {f"p{i:02d}": np.zeros(64, np.float32) for i in range(16)}
        even = plan(params, 2).layout
        skewed = plan(params, 2, server_weights=[3, 1]).layout
        assert even[0].size == even[1].size == 512
        assert skewed[0].size > even[0].size
        assert skewed[0].size > 2 * skewed[1].size  # 3:1 target, aligned

    def test_footprint_prices_optimizer_slots(self):
        params, plong = self._params()
        p_add = plan(params, 2, rule="add")
        p_adam = plan(params, 2, rule="adam")
        assert p_add.layout == p_adam.layout  # rule never moves the cut
        for i in range(2):
            assert p_add.footprint_bytes(i) == p_add.layout[i].size * 4
            assert p_adam.footprint_bytes(i) == p_add.footprint_bytes(i) * 3
        s = p_adam.summary()
        assert s["servers"] == 2 and s["slots"] == 2
        assert sum(s["shard_elems"]) == plong

    def test_shard_map_lift_is_valid(self):
        params, plong = self._params()
        smap = plan(params, 2).shard_map([0, 2])
        assert smap.plong == plong and smap.version == 0
        assert [e.owner for e in smap.entries] == [0, 2]

    def test_audit_covers_the_train_state(self):
        params, _ = self._params()
        report = audit_rules(train_state_tree(params, "adam"))
        assert report and not any(i == -2 for i in report.values())

    def test_bad_weights_raise(self):
        params, _ = self._params()
        with pytest.raises(ValueError):
            plan(params, 2, server_weights=[1, 2, 3])
        with pytest.raises(ValueError):
            plan(params, 2, server_weights=[1, 0])
        with pytest.raises(ValueError):
            plan(params, 0)


# ---------------------------------------------------------------------------
# the static layout= seam on the PS clients


def _gang_ft(chunk_bytes=0):
    return FTConfig(op_deadline_s=2.0, max_retries=8,
                    backoff_base_s=0.005, backoff_cap_s=0.02,
                    chunk_bytes=chunk_bytes)


class TestClientLayout:
    def _run(self, layout, size, *, codec=None, chunk_bytes=0,
             reader=False):
        """1 client (+ optional reader) against len(layout) servers; the
        client pushes one delta and pulls; returns (servers, param[,
        read])."""
        nserv = len(layout)
        n = nserv + 1 + (1 if reader else 0)
        router = LocalRouter(n)
        ft = _gang_ft(chunk_bytes)
        servers = [
            ParamServer(r, [nserv], router.endpoint(r), ft=ft,
                        reader_ranks=([nserv + 1] if reader else None))
            for r in range(nserv)
        ]
        threads = [threading.Thread(target=s.start, daemon=True)
                   for s in servers]
        for t in threads:
            t.start()
        client = ParamClient(nserv, list(range(nserv)),
                             router.endpoint(nserv), seed_servers=True,
                             codec=codec, ft=ft, layout=layout)
        param = np.arange(size, dtype=np.float32)
        grad = np.zeros(size, np.float32)
        client.start(param, grad)
        grad[:] = 1.0
        client.async_send_grad()
        client.async_recv_param()
        client.wait()
        read = None
        if reader:
            rc = ReaderClient(nserv + 1, list(range(nserv)),
                              router.endpoint(nserv + 1), codec=codec,
                              ft=ft, layout=layout)
            mirror = np.zeros(size, np.float32)
            rc.start(mirror)
            rc.read_params()
            read = mirror.copy()
            rc.stop()
        client.stop()
        for s in servers:
            s.live.stop()
        join_all(threads)
        return servers, param, read

    def test_servers_adopt_the_weighted_cut(self):
        params = {"a": np.zeros((6, 4), np.float32),
                  "b": np.zeros(40, np.float32),
                  "c": np.zeros((8, 2), np.float32)}
        layout = plan(params, 2, server_weights=[3, 1]).layout
        servers, param, _ = self._run(layout, 80)
        # each server holds exactly its planned shard, not the equal split
        for srv, shard in zip(servers, layout):
            assert (srv.offset, srv.size) == (shard.offset, shard.size)
        np.testing.assert_allclose(
            param, np.arange(80, dtype=np.float32) + 1.0, rtol=1e-6)

    def test_layout_composes_with_chunked_int8(self):
        # uneven cut + FLAG_CHUNKED streaming + int8 error feedback: the
        # flagship static composition, down to byte-exact pull of what
        # the servers hold
        params = {"a": np.zeros(96, np.float32),
                  "b": np.zeros((32, 8), np.float32),
                  "c": np.zeros(160, np.float32)}
        layout = plan(params, 2, server_weights=[5, 3]).layout
        servers, param, read = self._run(layout, 512, codec="int8",
                                         chunk_bytes=256, reader=True)
        held = np.concatenate([np.asarray(s.param) for s in servers])
        # writer pull and reader read decode the SAME served bytes ->
        # bitwise agreement; against the f32 shard the error is bounded
        # by the int8 quantization step
        np.testing.assert_array_equal(param, read)
        q = float(np.abs(held).max()) / 127.0
        np.testing.assert_allclose(param, held, atol=2 * q)

    def test_reader_layout_matches_writers(self):
        params = {"a": np.zeros(30, np.float32),
                  "b": np.zeros(34, np.float32)}
        layout = plan(params, 2, server_weights=[2, 1]).layout
        _, param, read = self._run(layout, 64, reader=True)
        np.testing.assert_array_equal(read, param)

    def test_layout_validation_is_loud(self):
        router = LocalRouter(2)
        params = {"a": np.zeros(64, np.float32)}
        layout = plan(params, 1).layout
        with pytest.raises(ValueError, match="exactly one each"):
            ParamClient(1, [0, 2], router.endpoint(1), layout=layout)
        with pytest.raises(ValueError, match="cannot combine"):
            ParamClient(1, [0], router.endpoint(1), layout=layout,
                        shardctl=True)
        with pytest.raises(ValueError, match="exactly one each"):
            ReaderClient(1, [0, 2], router.endpoint(1), layout=layout)
        # registered vector shorter than the layout: caught at start()
        client = ParamClient(1, [0], router.endpoint(1), layout=layout)
        with pytest.raises(ValueError, match="registered vector"):
            client.start(np.zeros(32, np.float32),
                         np.zeros(32, np.float32))


# ---------------------------------------------------------------------------
# the trainer loop


class TestLmTrainer:
    CFG = Config(d_model=32, n_heads=2, n_layers=1, seq_len=32, batch=4,
                 opt="sgd", lr=0.5, steps=30, eval_every=15,
                 eval_batches=1, seed=0, use_flash=0)

    def test_local_sgd_learns(self):
        res = LmTrainer(self.CFG).run()
        losses = [h["avg_loss"] for h in res["history"]]
        assert all(np.isfinite(x) for x in losses)
        # byte stream entropy floor is ln(256) ~ 5.545; training from a
        # random init must descend toward it
        assert losses[-1] < losses[0]
        assert res["final_eval_loss"] < 6.5

    def test_tokens_accounting(self):
        res = LmTrainer(self.CFG).run()
        assert res["tokens_total"] == 30 * 4 * 32
        assert res["tokens_per_s"] > 0
        assert res["train_seconds"] > 0
        # history rows carry the live tokens/sec trajectory
        assert all(h["tokens_per_s"] > 0 for h in res["history"])

    def test_server_opts_require_a_client(self):
        cfg = self.CFG.merged({"opt": "downpour"})
        with pytest.raises(ValueError, match="parameter client"):
            LmTrainer(cfg).run()

    def test_unknown_opt_raises(self):
        cfg = self.CFG.merged({"opt": "nope"})
        with pytest.raises(ValueError, match="unknown optimizer"):
            LmTrainer(cfg).run()


@pytest.mark.parametrize("lm_vocab, rows", [(0, 256), (320, 320)])
def test_lm_vocab_reaches_every_build(lm_vocab, rows):
    """``--lm_vocab`` sizes the token table and the head in the trainer,
    in the gang's layout and in a reader's vector alike; 0 leaves
    ``lm.model.build``'s own keyword default in force."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(lm=1, lm_d_model=32, lm_heads=2,
                                        lm_layers=1, lm_seq=16, opt="sgd",
                                        lm_vocab=lm_vocab, lm_use_flash=0)
    trainer = LmTrainer(launch.lm_trainer_cfg(cfg))
    assert trainer.model.vocab == rows
    size = int(trainer.model.flat.size)
    assert launch._serve_vec_len(cfg, rank=0) == size
    layout = launch.lm_layout(cfg, n_servers=2)
    assert sum(shard.size for shard in layout) == size
