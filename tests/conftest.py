# Test configuration: force jax onto a virtual 8-device CPU mesh BEFORE any
# jax import, so multi-chip sharding tests run without TPU hardware
# (SURVEY.md §4: TPU-less CI via the jax CPU backend).

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# Run the whole suite under the lock-order race detector (utils/lock.py):
# every diagnostic Lock acquisition feeds the global acquisition-order
# graph, so an ABBA inversion anywhere in the tests surfaces as a
# potential-deadlock report instead of a once-a-month CI hang.
os.environ.setdefault("AIKO_LOCK_CHECK", "1")
# The CPU's code at LLVM's -O0 (ISSUE 43; CHANGES.md, PR 43, has what this
# and the cache below were worth in a run of the driver's command): a model
# case's time is compilation, and a tiny model's program runs no slower for
# it; what the HLO passes make of a program, which is what the tests read,
# is the same at every level, and so is a program for the described v5e.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()
# One persistent compilation cache for the run: much of what a case
# compiles another case, file or worker compiles too.  The first process to
# load this file (xdist's controller, or a plain run) makes a directory
# under TMPDIR, names it in the environment, which the workers inherit, and
# removes it as it exits; one that the environment already names is used and
# left.  Every program is kept: the eager cases' are small and many.  (The
# `v5e` fixture turns the cache off around compiles for the described chip.)
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="aiko-tier1-jax-cache-")
    atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                    ignore_errors=True)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import pytest  # noqa: E402

from aiko_services_tpu.event import EventEngine, VirtualClock  # noqa: E402
from aiko_services_tpu.transport.memory import MemoryBroker  # noqa: E402
from aiko_services_tpu.process import ProcessRuntime  # noqa: E402
from aiko_services_tpu.transport.memory import MemoryMessage  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _lock_order_gate():
    """Fail the run if any test left a lock-order violation behind:
    the detector reporting without gating would reduce a potential
    deadlock to a log line nobody reads.  Tests that provoke
    violations on purpose (test_analysis ABBA fixtures) reset the
    checker before yielding control back."""
    yield
    from aiko_services_tpu.utils import lock_check_report
    violations = lock_check_report()
    assert not violations, (
        "lock-order violations detected during the test run:\n"
        + "\n".join(str(v) for v in violations))


@pytest.fixture(autouse=True)
def _no_metrics_snapshot_retained_from_another_test():
    """A runtime built without a transport of its own speaks through the
    process-wide default broker, and a MetricsPublisher there RETAINS a
    snapshot of the process-wide registry: a gauge that one test left
    high (a decoder with queued requests: serving_active_slots), a hop
    histogram.  An Autoscaler that a later test builds on that broker
    is handed the snapshot on subscribing and counts it as a process of
    its fleet, so which tests shared a worker (xdist's loadfile moves
    with every new test file) decided whether test_autoscaler and
    test_drain_migrate's shrink tests passed.  Forget such snapshots
    before every test."""
    from aiko_services_tpu.observe.export import METRICS_TOPIC_SUFFIX
    from aiko_services_tpu.transport.memory import default_broker
    broker = default_broker()
    with broker._lock:
        for topic in [topic for topic in broker._retained
                      if topic.endswith("/" + METRICS_TOPIC_SUFFIX)]:
            del broker._retained[topic]
    yield


@pytest.fixture(scope="module")
def v5e():
    """A described v5e 2x2 (the TPU compiler is installed here though no
    chip is); the persistent compilation cache is off around the module
    (an executable compiled for a described device cannot be read back
    without one: a warm cache would only add warnings)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:           # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e: {exc!r}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    """SingleDeviceSharding on one chip of the described v5e."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


@pytest.fixture
def engine():
    """A shared deterministic event engine (virtual clock)."""
    return EventEngine(VirtualClock())


@pytest.fixture
def assert_ledger_clean():
    """Shared KV leak audit (ISSUE 20): delegate to
    observe.ledger.assert_ledger_clean so every suite's drain check
    asserts the SAME invariants (pool refcount conservation, free-list
    integrity, cache/store byte bookkeeping, ledger audit findings)
    instead of each test hand-rolling used_blocks() == 0."""
    from aiko_services_tpu.observe.ledger import assert_ledger_clean \
        as check
    return check


@pytest.fixture
def broker():
    """A fresh in-memory broker per test."""
    return MemoryBroker()


@pytest.fixture
def make_runtime(engine, broker):
    """Factory for logical processes sharing one engine + broker, so a whole
    distributed system is driven deterministically by engine.step()."""
    created = []

    def factory(name=None, **kwargs):
        def transport_factory(on_message, lwt_topic, lwt_payload, lwt_retain):
            return MemoryMessage(
                on_message=on_message, broker=broker, lwt_topic=lwt_topic,
                lwt_payload=lwt_payload, lwt_retain=lwt_retain)
        runtime = ProcessRuntime(
            name=name, engine=engine, transport_factory=transport_factory,
            **kwargs)
        created.append(runtime)
        return runtime

    yield factory
    for runtime in created:
        try:
            if runtime.message is not None and runtime.message.connected():
                runtime.terminate()
        except Exception:
            pass
