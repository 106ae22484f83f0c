"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so that every sharding/collective
path (shard_map, psum over the mesh) is exercised without TPU hardware —
the analog of the reference's in-memory `TestGeoMesaDataStore` +
Accumulo MockInstance strategy (SURVEY.md §4): full stack, zero infra.

``JAX_PLATFORMS`` is forced (not defaulted) to cpu, so a run on a machine
with a chip still tests the CPU mesh; the chip is exercised by
``chip_smoke.py`` and compiled for by ``test_tpu_compile.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# CI-sized attribute-index generations: the production 16M-slot default
# would make every CPU append sort a 16M-slot run per indexed attribute
# (the same sizing discipline as the multihost worker's sharded lean
# generations — ROUND4.md "CI at 10x speed"); rollover/spill paths get
# exercised MORE at this size, not less
from geomesa_tpu.index.attr_lean import LeanAttrIndex  # noqa: E402
from geomesa_tpu.parallel.attr_lean import ShardedLeanAttrIndex  # noqa: E402

LeanAttrIndex.GENERATION_SLOTS = 1 << 16
ShardedLeanAttrIndex.GENERATION_SLOTS = 1 << 13


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(574)


@pytest.fixture(scope="session")
def gm_lint_tree():
    """ONE timed gm-lint full-tree pass shared by every in-process
    analyzer assertion (the zzzz clean-tree gate, the metric-lint
    delegation test) — the pass is pure ast but still ~3 s, so tier-1
    pays it once."""
    import time

    from geomesa_tpu.analysis import analyze
    from geomesa_tpu.analysis.walker import PACKAGE_ROOT

    t0 = time.perf_counter()
    findings = analyze(PACKAGE_ROOT)
    return findings, time.perf_counter() - t0
