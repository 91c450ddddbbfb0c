"""Trainer + launcher tests: single-process (claunch analog) and threaded
multi-role topologies (mlaunch analog) on the in-process router.
"""

import threading

import numpy as np
import pytest

from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.data.mnist import load_mnist
from mpit_tpu.train.launch import LAUNCH_DEFAULTS, assign_roles, run_rank, server_rule_for
from mpit_tpu.train.trainer import MnistTrainer, TRAINER_DEFAULTS
from mpit_tpu.utils.config import Config


@pytest.fixture(scope="module")
def small_data():
    (x_train, y_train, x_test, y_test), source = load_mnist(side=8)
    # keep it tiny for 1-CPU test speed
    return (x_train[:512], y_train[:512], x_test[:256], y_test[:256])


class TestAssignRoles:
    def test_parity_split(self):
        sranks, cranks, tester = assign_roles(12)
        assert sranks == [0, 2, 4, 6, 8, 10]
        assert cranks == [1, 3, 5, 7, 9, 11]
        assert tester is None

    def test_master_freq_3(self):
        sranks, cranks, _ = assign_roles(6, master_freq=3)
        assert sranks == [0, 3]
        assert cranks == [1, 2, 4, 5]

    def test_tester_last(self):
        sranks, cranks, tester = assign_roles(5, tester="last")
        assert tester == 4
        assert 4 not in sranks and 4 not in cranks

    def test_tester_first(self):
        sranks, cranks, tester = assign_roles(5, tester="first")
        assert tester == 0
        assert 0 not in sranks and 0 not in cranks

    def test_degenerate_raises(self):
        with pytest.raises(ValueError):
            assign_roles(1)


class TestServerRule:
    def test_stateful_rules_match_opt(self):
        assert server_rule_for(Config(opt="adam", lr=0.1)).apply is not None

    def test_delta_optimizers_use_add(self):
        from mpit_tpu.optim.rules import add_apply

        rule = server_rule_for(Config(opt="eamsgd", lr=0.1))
        assert rule.apply is add_apply


class TestLocalTrainer:
    def test_msgd_learns(self, small_data):
        cfg = TRAINER_DEFAULTS.merged(
            model="linear", opt="msgd", lr=0.3, mom=0.9, epochs=3,
            batch=64, side=8,
        )
        trainer = MnistTrainer(cfg, data=small_data)
        err0 = trainer.test_error()
        result = trainer.run()
        assert result["final_test_err"] < err0
        assert result["final_test_err"] < 0.5
        assert len(result["history"]) == 3
        assert "feval" in result["timers"]

    def test_comm_optimizer_without_client_raises(self, small_data):
        cfg = TRAINER_DEFAULTS.merged(opt="downpour", side=8, epochs=1)
        trainer = MnistTrainer(cfg, data=small_data)  # eval-only use is fine
        with pytest.raises(ValueError, match="parameter client"):
            trainer.run()


def run_topology(size, cfg, data, timeout=300):
    """Run all ranks of a topology on threads over the in-process router."""
    router = LocalRouter(size)
    results = {}
    errors = {}

    def target(rank):
        try:
            results[rank] = run_rank(rank, size, cfg, router.endpoint(rank), data=data)
        except BaseException as exc:  # noqa: BLE001
            errors[rank] = exc

    threads = [threading.Thread(target=target, args=(r,), daemon=True) for r in range(size)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    # A crashed rank starves its peers, so surface rank errors first.
    if errors:
        raise next(iter(errors.values()))
    assert not any(t.is_alive() for t in threads), f"topology hung; done={list(results)}"
    return results


class TestTopologies:
    def test_downpour_np4(self, small_data):
        cfg = LAUNCH_DEFAULTS.merged(
            np=4, opt="downpour", lr=0.2, su=1, epochs=1, batch=64, side=8,
        )
        results = run_topology(4, cfg, small_data)
        roles = {r: res["role"] for r, res in results.items()}
        assert roles == {0: "server", 1: "worker", 2: "server", 3: "worker"}
        for rank in (0, 2):
            assert results[rank]["grads_applied"] > 0
        for rank in (1, 3):
            assert results[rank]["final_test_err"] < 0.8

    def test_eamsgd_np4(self, small_data):
        cfg = LAUNCH_DEFAULTS.merged(
            np=4, opt="eamsgd", lr=0.2, mom=0.9, mva=0.45, su=5,
            epochs=1, batch=64, side=8,
        )
        results = run_topology(4, cfg, small_data)
        workers = [res for res in results.values() if res["role"] == "worker"]
        assert len(workers) == 2
        assert all(w["final_test_err"] < 0.8 for w in workers)

    def test_eamsgd_np4_int8_codec_converges(self, small_data):
        """The flagship EASGD topology with quantized shard transfer
        (codec=int8 pins the servers AND drives the clients) must reach
        the same test-error bar as the uncompressed run above — the
        client-held error-feedback residual carries the quantization
        error across sync rounds."""
        cfg = LAUNCH_DEFAULTS.merged(
            np=4, opt="eamsgd", lr=0.2, mom=0.9, mva=0.45, su=5,
            epochs=1, batch=64, side=8, codec="int8",
        )
        results = run_topology(4, cfg, small_data)
        workers = [res for res in results.values() if res["role"] == "worker"]
        assert len(workers) == 2
        assert all(w["final_test_err"] < 0.8 for w in workers)
        assert all(res["grads_applied"] > 0 for res in results.values()
                   if res["role"] == "server")

    def test_downpour_np4_bf16_codec(self, small_data):
        cfg = LAUNCH_DEFAULTS.merged(
            np=4, opt="downpour", lr=0.2, su=1, epochs=1, batch=64, side=8,
            codec="bf16",
        )
        results = run_topology(4, cfg, small_data)
        workers = [res for res in results.values() if res["role"] == "worker"]
        assert all(w["final_test_err"] < 0.8 for w in workers)

    def test_tester_role(self, small_data, tmp_path):
        cfg = LAUNCH_DEFAULTS.merged(
            np=3, opt="downpour", lr=0.2, su=1, epochs=1, batch=64, side=8,
            tester="last", tester_rounds=3, tester_interval=0.05,
            ckpt_dir=str(tmp_path),
        )
        results = run_topology(3, cfg, small_data)
        tester = results[2]
        assert tester["role"] == "tester"
        assert tester["best_test_err"] <= 1.0
        assert len(tester["history"]) == 3
        assert list(tmp_path.glob("ckpt_*.npz")), "tester should checkpoint"

    def test_adam_server_stateful_np2(self, small_data):
        cfg = LAUNCH_DEFAULTS.merged(
            np=2, opt="adam", lr=1e-3, su=1, epochs=1, batch=64, side=8,
        )
        results = run_topology(2, cfg, small_data)
        assert results[0]["role"] == "server" and results[0]["grads_applied"] > 0
        assert results[1]["role"] == "worker"


class TestDevicePolicy:
    """The per-rank device assignment the chip run depends on
    (train/gang.py assign_devices): a chip for each worker, host roles
    pinned to the CPU backend, all decided in the parent."""

    @staticmethod
    def _host(monkeypatch, chips, platforms=None):
        from mpit_tpu.train import gang

        monkeypatch.setattr(gang, "count_local_chips", lambda: chips)
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)

    def test_one_chip_one_worker(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

        self._host(monkeypatch, chips=1, platforms="tpu,cpu")
        ov = device_env_overrides(LAUNCH_DEFAULTS.merged(np=3), 3)
        # master_freq=2: servers 0 and 2 are host roles, worker 1 owns
        # the chip and sees no other.
        assert ov[0] == ov[2] == {"JAX_PLATFORMS": "cpu"}
        assert ov[1]["TPU_VISIBLE_CHIPS"] == "0"
        assert ov[1]["JAX_PLATFORMS"].split(",")[0] == "tpu"
        assert ov[1]["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert ov[1]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"

    def test_four_chips_four_workers(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

        self._host(monkeypatch, chips=4)
        cfg = LAUNCH_DEFAULTS.merged(np=6, master_freq=3)
        ov = device_env_overrides(cfg, 6)
        assert ov[0] == ov[3] == {"JAX_PLATFORMS": "cpu"}
        chips = [ov[r]["TPU_VISIBLE_CHIPS"] for r in (1, 2, 4, 5)]
        assert sorted(chips) == ["0", "1", "2", "3"]
        # a pure function of the roles: a supervisor restart re-reads
        # the same map, so a worker gets its predecessor's chip
        assert device_env_overrides(cfg, 6) == ov

    def test_tester_owns_a_chip_host_roles_do_not(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

        self._host(monkeypatch, chips=2)
        # ranks 0..3 split (workers 1 and 3), 4 = controller
        cfg = LAUNCH_DEFAULTS.merged(np=5, shardctl=True)
        ov = device_env_overrides(cfg, 5)
        assert [r for r in ov if "TPU_VISIBLE_CHIPS" in ov[r]] == [1, 3]
        assert ov[4] == {"JAX_PLATFORMS": "cpu"}
        cfg = LAUNCH_DEFAULTS.merged(np=3, tester="last")
        ov = device_env_overrides(cfg, 3)
        assert {r for r in ov if "TPU_VISIBLE_CHIPS" in ov[r]} == {1, 2}

    def test_more_workers_than_chips_fails_in_parent(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

        self._host(monkeypatch, chips=1)
        cfg = LAUNCH_DEFAULTS.merged(np=4, opt="downpour")
        spawned = []
        monkeypatch.setattr("mpit_tpu.train.gang.spawn_rank",
                            lambda *a, **k: spawned.append(a))
        with pytest.raises(ValueError, match=r"\[1, 3\].*has 1"):
            launch_processes(cfg, timeout=5)
        assert not spawned

    def test_cpu_parent_only_inherits(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

        # chips or not: a parent pinned to the CPU assigns nothing
        self._host(monkeypatch, chips=4, platforms="cpu")
        assert device_env_overrides(LAUNCH_DEFAULTS.merged(np=4), 4) == {}

    def test_cpu_policy_and_unknown_policy(self, monkeypatch):
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

        self._host(monkeypatch, chips=4)
        cfg = LAUNCH_DEFAULTS.merged(np=4, device_policy="cpu")
        ov = device_env_overrides(cfg, 4)
        assert set(ov) == {0, 1, 2, 3}
        assert all(v == {"JAX_PLATFORMS": "cpu"} for v in ov.values())
        with pytest.raises(ValueError, match="device_policy"):
            device_env_overrides(cfg.merged(device_policy="inherit"), 4)

    def test_bicnn_gang_goes_through_the_same_assignment(self, monkeypatch):
        from mpit_tpu.train import bicnn_launch, gang

        self._host(monkeypatch, chips=2)
        seen = {}
        monkeypatch.setattr(
            gang, "launch_gang",
            lambda module, cfg, env_overrides=None: seen.update(
                env=env_overrides) or {})
        bicnn_launch.main(["--np", "4", "--optimization", "downpour",
                           "--valid_mode", "none"])
        assert {r for r, e in seen["env"].items()
                if "TPU_VISIBLE_CHIPS" in e} == {1, 3}
        assert seen["env"][0] == seen["env"][2] == {"JAX_PLATFORMS": "cpu"}

    @pytest.mark.slow
    def test_gang_applies_policy(self, monkeypatch):
        """np=2 gang with device_policy=cpu: children report the forced
        platform.  The parent's inherited JAX_PLATFORMS is removed so the
        assertion can only pass through the env_overrides plumbing (on an
        accelerator host a broken override would surface as a non-cpu
        platform or a chip-contention failure)."""
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        cfg = LAUNCH_DEFAULTS.merged(
            np=2, opt="downpour", epochs=1, model="linear", side=8,
            batch=64, device_policy="cpu", master_freq=2,
        )
        results = launch_processes(cfg, timeout=600)
        assert set(results) == {0, 1}
        assert all(r.get("platform") == "cpu" for r in results.values())


class TestChipSmoke:
    def test_refuses_on_cpu_without_spawning(self):
        """JAX_PLATFORMS=cpu: non-zero, one line, no result line — and it
        returns before anything could have been spawned or compiled."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=repo,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=60)
        assert out.returncode != 0
        assert out.stdout == ""
        assert len(out.stderr.strip().splitlines()) == 1

    def test_launchers_import_no_backend(self):
        """The gang parent must stay off jax: a parent that initialised a
        backend would hold the chip its worker needs."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import chip_smoke, mpit_tpu.train.launch, "
            "mpit_tpu.train.bicnn_launch, mpit_tpu.train.gang\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
        subprocess.run([sys.executable, "-c", code], cwd=repo, check=True,
                       timeout=120)


@pytest.mark.slow
class TestServerCkptResumeGang:
    def test_two_session_resume(self, tmp_path):
        """Session 1 trains with periodic server checkpoints; session 2
        resumes from them (servers restore, no client seeding) and keeps
        training — the launcher-level resume flow the in-process PS tests
        cover at the API level."""
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

        base = LAUNCH_DEFAULTS.merged(
            np=3, opt="downpour", epochs=1, model="linear", side=8,
            batch=64, master_freq=2, device_policy="cpu",
            server_ckpt_dir=str(tmp_path), server_ckpt_interval=0.2,
        )
        r1 = launch_processes(base, timeout=600)
        servers1 = {r: v for r, v in r1.items() if v["role"] == "server"}
        assert servers1 and all(v["ckpts_written"] >= 1 for v in servers1.values())
        for r in servers1:
            assert (tmp_path / f"server{r}_latest.npz").exists()

        r2 = launch_processes(base.merged(resume=True), timeout=600)
        servers2 = {r: v for r, v in r2.items() if v["role"] == "server"}
        workers2 = [v for v in r2.values() if v["role"] == "worker"]
        # Restored moment/param state: grads_applied continues the count
        # from session 1 instead of restarting at the session's own total.
        for r, v in servers2.items():
            assert v["grads_applied"] > servers1[r]["grads_applied"]
        assert workers2 and all("final_test_err" in w for w in workers2)
