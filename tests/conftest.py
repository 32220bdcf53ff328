"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax imports.

Mirrors the reference's in-process multi-node test strategy
(test/test/InternalTestCluster.java:146 runs N nodes in one JVM over
LocalTransport): we run N "chips" in one process over XLA's host platform,
so every sharding/collective path is exercised without TPU hardware.
"""

import os

# Tests always run on the virtual CPU mesh, set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
# ...and never write a persistent compile cache (Node.start() points one
# into the checkout; a test run must leave the tree as it found it).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---- hang tripwire (the stall-tolerance PR's own honesty check) -----------
# The tier-1 gate runs under `timeout -k 10 870`; a genuine hang (a wait
# this PR failed to bound) would burn the whole wall and die with no
# evidence. Dump every thread's stack shortly BEFORE the outer timeout so
# the wedged wait is named in the log. repeat=False, exit=False: purely
# diagnostic — pytest (or the outer timeout) still owns the verdict.
import faulthandler  # noqa: E402

if hasattr(faulthandler, "dump_traceback_later"):
    faulthandler.dump_traceback_later(840, exit=False)


# ---- randomized-seed harness (ESTestCase / TESTING.asciidoc:1-60) ---------
# Every session draws a master seed (override: ESTPU_TEST_SEED=<n>); each
# test derives its own rng from (master seed, test id), so runs vary
# across sessions but any failure reproduces exactly from the printed
# seed. This is the reference's randomized-runner discipline: fixed-seed
# suites systematically miss order/timing/shape bugs.

import zlib

SESSION_SEED = int(os.environ.get("ESTPU_TEST_SEED",
                                  np.random.SeedSequence().entropy
                                  % (2 ** 31)))


def pytest_report_header(config):
    return (f"estpu randomized seed: {SESSION_SEED} "
            f"(reproduce: ESTPU_TEST_SEED={SESSION_SEED})")


def derive_seed(name: str) -> int:
    return (SESSION_SEED ^ zlib.crc32(name.encode())) % (2 ** 31)


@pytest.fixture
def rng(request):
    """Per-test rng derived from the session seed — deterministic given
    ESTPU_TEST_SEED, different across sessions."""
    return np.random.default_rng(derive_seed(request.node.nodeid))


@pytest.fixture
def test_random(request):
    """Python `random.Random` flavor of the same derivation (node
    counts, shard counts, op shuffles)."""
    import random
    return random.Random(derive_seed(request.node.nodeid))


@pytest.fixture
def tmp_index_path(tmp_path):
    p = tmp_path / "index0"
    p.mkdir()
    return p
