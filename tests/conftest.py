"""Test configuration.

Per the build contract: tests run JAX on CPU with 8 virtual devices so
multi-chip sharding is exercised without TPU hardware. The environment
variables alone select the platform (`JAX_PLATFORMS=cpu` plus the
`XLA_FLAGS` host-device count) and are inherited by every subprocess a
test spawns; the `jax.config` calls pin the same choice for THIS process
even when the caller's shell exported something else.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# NOTE: x64 deliberately NOT enabled — the kernels are int32 (radix-13
# limbs) and production runs with default dtypes; tests must match.

# NOTE: the persistent compile cache is placed by plenum_tpu.ops
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache) —
# kernels cache across runs automatically.

import json  # noqa: E402
import weakref  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def tdir(tmp_path):
    return str(tmp_path)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running e2e tests (process pools, fuzzing)")
    config.addinivalue_line(
        "markers", "soak: minutes-scale bounded-growth soaks "
                   "(tools/churn_soak.py; always also marked slow)")


# --- flight-recorder dump on failure ----------------------------------------
# Sim pools (test_pool.Pool) register here at construction; when a test
# fails, every still-alive registered pool's per-node flight-recorder ring
# (common/tracing.py) is appended to the test report, so a red test
# arrives with its last-seconds span/anomaly story instead of just an
# assertion message. Weak references: pools die with their tests, and a
# stale pool from an earlier (passed) test drops out as soon as it is
# collected.
FLIGHT_POOLS: "weakref.WeakSet" = weakref.WeakSet()


def register_pool_for_flight_dump(pool) -> None:
    FLIGHT_POOLS.add(pool)


def flight_ring_lines(max_events: int = 40) -> list[str]:
    """Render every registered pool's rings (newest events last)."""
    lines: list[str] = []
    for pool in list(FLIGHT_POOLS):
        for name, node in sorted(getattr(pool, "nodes", {}).items()):
            tracer = getattr(node, "tracer", None)
            if tracer is None or not getattr(tracer, "enabled", False):
                continue
            snap = tracer.snapshot()
            events = snap["events"][-max_events:]
            lines.append(f"--- {name}: {len(snap['events'])} ring events "
                         f"({snap['anomalies']} anomalies), last "
                         f"{len(events)} ---")
            lines.extend(json.dumps(ev, default=repr) for ev in events)
    return lines


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        try:
            lines = flight_ring_lines()
        except Exception:
            lines = []
        if lines:
            rep.sections.append(("flight recorder", "\n".join(lines)))
