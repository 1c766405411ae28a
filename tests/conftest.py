"""Test harness: force an 8-device virtual CPU platform so mesh/sharding
tests run anywhere — the TPU-native analog of the reference's DummyBackend
(dummy_backend.py), per SURVEY.md §4. Every test runs on the CPU: the
platform is ASSIGNED here (env for subprocesses, jax.config for this
process), so a chip host that exports JAX_PLATFORMS=tpu cannot point the
suite at its accelerator.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite is XLA-compile dominated (tiny shapes, hundreds of unique
# programs); skipping XLA's optimization pipeline cuts the cold full run
# ~35% without changing program semantics (measured: test_moe.py 85 -> 55 s).
# Runtime of the tiny test shapes is negligible either way; the TPU
# benchmark (benchmarks/) never imports this file and stays fully optimized.
# Exported via the environment so CLI-subprocess e2e tests and the
# multiprocess workers inherit it; set to 0 to override.
# The blanket disable means parity tests exercise the UNOPTIMIZED pipeline;
# the always-on counterweight is tests/test_optimized_smoke.py, a small
# tier-1 subset (decode parity + attention parity) that re-enables the
# optimization passes for its own compiles.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
jax.config.update(
    "jax_disable_most_optimizations",
    os.environ.get("JAX_DISABLE_MOST_OPTIMIZATIONS", "1") != "0",
)

# persistent compilation cache: the suite is dominated by XLA compiles
# (every jit at these tiny shapes is seconds), and re-runs hit the disk
# cache — measured ~5x faster grad compiles warm. Safe to delete any time.
# The one placement rule (dalle_pytorch_tpu/compile_cache.py):
# JAX_COMPILATION_CACHE_DIR if the environment sets it, else the fixed
# tests/.jax_cache — then exported, so the CLI subprocesses and
# multiprocess workers the tests spawn share the same directory.
from dalle_pytorch_tpu.compile_cache import (  # noqa: E402
    ENV_VAR as _CACHE_ENV,
    enable_compile_cache,
)

os.environ[_CACHE_ENV] = enable_compile_cache(
    default=os.path.join(os.path.dirname(__file__), ".jax_cache")
)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

assert jax.local_device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

# fault injection must be OFF unless a test arms it explicitly — an armed
# env var would silently poison every download/shard/checkpoint test
assert not os.environ.get("DALLE_TPU_FAULTS"), (
    f"DALLE_TPU_FAULTS={os.environ['DALLE_TPU_FAULTS']!r} is set; the test "
    "suite requires fault injection off (tests arm FAULTS programmatically)"
)

# ... and the registry itself must start inert, with every production site
# (including the serving sites PR 3 added) known to it — a site name typo'd
# out of KNOWN_SITES would arm nothing and silently test nothing
from dalle_pytorch_tpu.utils.faults import FAULTS as _FAULTS  # noqa: E402
from dalle_pytorch_tpu.utils.faults import KNOWN_SITES as _SITES  # noqa: E402

assert not _FAULTS.active(), "fault registry armed at session start"
for _site in ("page_exhaust", "prefill_fail", "decode_stall",
              "request_cancel", "download", "ckpt_corrupt",
              "telemetry_sink_fail",
              # fleet sites (serving/router.py, PR 6)
              "replica_crash", "replica_stall", "health_flap"):
    assert _site in _SITES, f"production fault site {_site!r} unregistered"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_resilience_registries():
    """Keep the process-wide fault registry, counters, gauges, histograms,
    and telemetry hermetic: a test that arms faults or trips metrics must
    not leak into the next."""
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters, gauges, histograms
    from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

    FAULTS.reset()
    counters.reset()
    gauges.reset()
    histograms.reset()
    TELEMETRY.reset()
    yield
    FAULTS.reset()
    counters.reset()
    gauges.reset()
    histograms.reset()
    TELEMETRY.reset()


def pytest_collection_modifyitems(config, items):
    """Data-driven slow tier: tests listed in tests/slow_tests.txt (measured
    > ~2 s cold on the reference 1-CPU box; regenerate from
    `pytest --durations=0`) get the ``slow`` marker in addition to any
    literal @pytest.mark.slow. `-m "not slow"` is the fast tier."""
    import pytest as _pytest

    listing = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    if not os.path.exists(listing):
        return
    with open(listing) as f:
        slow = {
            line.strip() for line in f
            if line.strip() and not line.startswith("#")
        }
    for item in items:
        if item.nodeid in slow:
            item.add_marker(_pytest.mark.slow)
