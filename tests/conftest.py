# Test configuration: force jax onto a virtual 8-device CPU mesh BEFORE any
# jax import, so multi-chip sharding tests run without TPU hardware
# (SURVEY.md §4: TPU-less CI via the jax CPU backend).

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Run the whole suite under the lock-order race detector (utils/lock.py):
# every diagnostic Lock acquisition feeds the global acquisition-order
# graph, so an ABBA inversion anywhere in the tests surfaces as a
# potential-deadlock report instead of a once-a-month CI hang.
os.environ.setdefault("AIKO_LOCK_CHECK", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_force_host_platform_device_count=8").strip()

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
