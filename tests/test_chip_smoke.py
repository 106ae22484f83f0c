"""chip_smoke.py's phases on the CPU mesh at ~100k rows, with the same
NumPy oracles the chip run uses — keeps the smoke script from rotting
between chip runs.  The script's entry itself refuses a non-TPU
platform (its health checks read HBM stats only a chip reports)."""

import numpy as np
import pytest

import chip_smoke as cs

ROWS = 1 << 17
SLICE = 1 << 15
SEED = 5


@pytest.fixture(scope="module")
def data():
    return cs.make_data(ROWS, SEED)


def _small_generations(mp):
    from geomesa_tpu.index.z3_lean import LeanZ3Index
    from geomesa_tpu.parallel.lean import ShardedLeanZ3Index
    mp.setattr(LeanZ3Index, "GENERATION_SLOTS", 1 << 16)
    mp.setattr(ShardedLeanZ3Index, "GENERATION_SLOTS", 1 << 14)


@pytest.fixture(scope="module")
def store(data):
    with pytest.MonkeyPatch.context() as mp:
        _small_generations(mp)
        ds = cs.open_store()
        cs.ingest(ds, data, SLICE)
        yield ds


@pytest.fixture(scope="module")
def sharded_store(data):
    from geomesa_tpu.parallel import device_mesh
    with pytest.MonkeyPatch.context() as mp:
        _small_generations(mp)
        ds = cs.open_store(device_mesh(4))
        cs.ingest(ds, data, SLICE)
        yield ds


ONE_CHIP_PHASES = ("bbox_queries", "attr_query", "count_stats", "density",
                   "knn", "fused", "web")
SHARDED_PHASES = ("bbox_queries", "attr_query", "count_stats", "density")


@pytest.mark.parametrize("phase", ONE_CHIP_PHASES)
def test_one_chip_phase_matches_oracle(phase, store, data):
    getattr(cs, phase)(store, data)


@pytest.mark.parametrize("phase", SHARDED_PHASES)
def test_sharded_phase_matches_oracle(phase, sharded_store, data):
    getattr(cs, phase)(sharded_store, data)


def test_kernels_phase_matches_oracle(data):
    """The Pallas kernels phase (interpret mode here) at a size interpret
    mode runs in seconds."""
    cs.kernels(data, n=1 << 13)


def test_sharded_rows_split_evenly(sharded_store):
    cs.shard_balance(sharded_store, ROWS, 4)


def test_oracle_catches_a_wrong_answer(store, data):
    """A phase fails loudly when the store's answer differs."""
    bad = dict(data, code=np.where(data["code"] == 4, 0, data["code"]))
    with pytest.raises(cs.SmokeFailure, match="attribute query"):
        cs.attr_query(store, bad)


def test_data_is_seeded():
    a, b = cs.make_data(1000, 3), cs.make_data(1000, 3)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["x"], cs.make_data(1000, 4)["x"])


def test_entry_refuses_a_non_tpu_platform(capsys):
    assert cs.main(["--rows", "1000"]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert out.out == ""


class _FakeDevice:
    """A device whose HBM report a test controls (CPU reports none)."""

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return {"bytes_in_use": 0, "peak_bytes_in_use": 0,
                "bytes_limit": self.limit}


@pytest.mark.parametrize("which", ["store", "sharded_store"])
def test_health_checks(which, request, monkeypatch):
    """The chip-only health checks run on both store layouts: they pass
    under a chip-sized limit and fail when the HBM budget exceeds it."""
    from geomesa_tpu.ops import pallas_kernels as pk
    ds = request.getfixturevalue(which)
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    cs.health(ds, [_FakeDevice(16 << 30)])
    with pytest.raises(cs.SmokeFailure, match="exceeds"):
        cs.health(ds, [_FakeDevice(1 << 30)])


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """The entry scripts' cache helper defers to JAX_COMPILATION_CACHE_DIR
    and otherwise fixes ``<checkout>/.jax_cache``; it never picks another
    directory.  (``jax.config.update`` is recorded, not applied, so the
    test process keeps its cache off.)"""
    import os

    import jax

    from geomesa_tpu import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    compile_cache.enable_compile_cache()
    dirs = updates.get("jax_compilation_cache_dir")
    if env_dir is None:
        assert dirs == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
        assert os.path.isdir(os.path.join(compile_cache.CHECKOUT,
                                          "geomesa_tpu"))
    else:
        assert dirs is None
