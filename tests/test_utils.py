"""Tests for config, timers, serialization utilities."""

import numpy as np
import pytest

from mpit_tpu.obs.timers import PhaseTimers
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.serialize import (
    decode,
    decode_array,
    encode_array,
    encode_object,
)


class TestConfig:
    def test_attribute_and_item_access(self):
        cfg = Config(lr=0.01, opt="easgd")
        assert cfg.lr == 0.01
        assert cfg["opt"] == "easgd"

    def test_get_default(self):
        cfg = Config(lr=0.01)
        assert cfg.get("missing", 7) == 7

    def test_merged_precedence(self):
        base = Config(lr=0.01, mom=0.99)
        out = base.merged({"lr": 0.1}, mom=0.5)
        assert out.lr == 0.1 and out.mom == 0.5
        assert base.lr == 0.01  # original untouched

    def test_parse_args_typed(self):
        cfg = Config(lr=0.01, epochs=10, cuda=False, name="sgd")
        out = cfg.parse_args(["--lr", "0.5", "--cuda", "true", "--epochs", "3"])
        assert out.lr == 0.5 and out.cuda is True and out.epochs == 3
        assert out.name == "sgd"

    def test_missing_attribute_raises(self):
        with pytest.raises(AttributeError):
            Config().nope


class TestTimers:
    def test_phase_accumulates(self):
        tm = PhaseTimers()
        with tm.phase("feval"):
            pass
        with tm.phase("feval"):
            pass
        assert tm.count["feval"] == 2
        assert tm.total["feval"] >= 0.0
        assert "feval" in tm.summary()


class TestSerialize:
    def test_array_roundtrip(self):
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        out = decode_array(encode_array(arr))
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == np.float32

    def test_array_into_preallocated(self):
        arr = np.linspace(0, 1, 8, dtype=np.float32)
        out = np.empty_like(arr)
        result = decode_array(encode_array(arr), out=out)
        assert result is out
        np.testing.assert_array_equal(out, arr)

    def test_bfloat16_via_jax(self):
        import jax.numpy as jnp

        arr = jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3)
        out = decode(encode_array(arr))
        np.testing.assert_array_equal(np.asarray(arr, dtype=np.float32),
                                      np.asarray(out, dtype=np.float32))

    def test_object_roundtrip(self):
        obj = {"offset": 3, "size": (5, 2), "name": "shard"}
        assert decode(encode_object(obj)) == obj

    def test_dispatch(self):
        arr = np.ones(4, dtype=np.int32)
        from mpit_tpu.utils.serialize import encode

        np.testing.assert_array_equal(decode(encode(arr)), arr)
        assert decode(encode({"a": 1})) == {"a": 1}


class TestCheckpoint:
    def test_flat_roundtrip(self, tmp_path):
        from mpit_tpu.utils.checkpoint import load_flat, save_flat

        w = np.linspace(-1, 1, 11, dtype=np.float32)
        path = save_flat(tmp_path, w, {"step": 7})
        w2, meta = load_flat(path)
        np.testing.assert_array_equal(w2, w)
        assert meta["step"] == 7
        w3, _ = load_flat(tmp_path / "ckpt_latest.npz")
        np.testing.assert_array_equal(w3, w)

    def test_flat_roundtrip_bfloat16(self, tmp_path):
        # np.savez alone would degrade ml_dtypes arrays to void records;
        # the raw-bytes layout must preserve the extension dtype.
        import ml_dtypes

        from mpit_tpu.utils.checkpoint import load_flat, save_flat

        w = np.arange(9, dtype=ml_dtypes.bfloat16).reshape(3, 3)
        path = save_flat(tmp_path, w, {"step": 1})
        w2, _ = load_flat(path)
        assert w2.dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            w2.astype(np.float32), w.astype(np.float32)
        )


class TestCompileCache:
    """utils/platform.enable_compile_cache: one function, and the cache
    can be placed from outside."""

    @pytest.fixture
    def cache_dir_config(self):
        import jax

        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path,
                                    cache_dir_config):
        import jax

        from mpit_tpu.utils.platform import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert enable_compile_cache() == str(tmp_path)
        # no directory set in code: jax reads the variable itself
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_default_is_the_checkout(self, monkeypatch, cache_dir_config):
        import pathlib

        import jax

        from mpit_tpu.utils.platform import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


class TestChipNodes:
    def test_chip_node_pattern(self):
        from mpit_tpu.utils.platform import _CHIP_NODE

        assert _CHIP_NODE.match("/dev/vfio/2")
        assert _CHIP_NODE.match("/dev/accel0")
        assert not _CHIP_NODE.match("/dev/vfio/vfio")
        assert not _CHIP_NODE.match("/dev/vfio/2/x")

    def test_cpu_process_holds_none(self):
        from mpit_tpu.utils.platform import device_report

        rep = device_report()
        assert rep["platform"] == "cpu" and rep["chip_nodes"] == []
        assert rep["device_count"] == len(rep["device_ids"])
