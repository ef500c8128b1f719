"""Test configuration: force an 8-device virtual CPU mesh so distributed
(DP/TP/SP) logic is exercised on CI machines without TPU hardware — the same
philosophy as the reference's Spark local[N] / DummyTransport fabric
(SURVEY.md §4.2). Must run before jax is imported anywhere."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)  # gradient-check tier runs fp64 (SURVEY §4.3)

assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8, "virtual 8-device CPU mesh required for parallel tests"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded():
    """Deterministic global RNG per test (ref: Nd4j.getRandom().setSeed)."""
    from deeplearning4j_tpu.ndarray import getRandom

    getRandom().setSeed(12345)
    yield


@pytest.fixture
def rtol():
    return 1e-5


@pytest.fixture(autouse=True)
def _leak_watch(request):
    """Zero-leak gate for the suites that stress shutdown paths (ISSUE
    18): after any test marked chaos/stress/soak tears down, every
    engine/RPC server it shut down must satisfy the ledger's shutdown
    law — allocator free list fully attributable, swap store empty,
    zero unresolved ops, no resident slot. See serving/ledger.py."""
    marked = any(request.node.get_closest_marker(m)
                 for m in ("chaos", "stress", "soak"))
    if not marked:
        yield
        return
    from deeplearning4j_tpu.serving.ledger import LeakWatch

    watch = LeakWatch()
    yield
    bad = watch.finish()
    assert not bad, (
        "leaked resources at engine/server shutdown:\n  "
        + "\n  ".join(bad))


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Cap in-process compiled-executable accumulation. Running the whole
    suite in one process leaves hundreds of XLA:CPU executables loaded, after
    which the NEXT very large compile (InceptionResNetV1's fused fit step in
    test_zoo) segfaults inside backend_compile — reproducibly in-suite,
    never in isolation. Dropping compilation caches at module boundaries
    keeps the live-executable population at per-module scale."""
    yield
    jax.clear_caches()
