"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding is validated on virtual devices (the CI host has at most
one real TPU chip); see SURVEY.md §4 for the test strategy.

The tier-1 command already sets JAX_PLATFORMS=cpu; the jax.config pin below
makes a bare ``pytest`` on a TPU host land on the CPU mesh too.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Keep the persistent compilation cache OUT of CPU test runs.  In-process
# CLI tests (test_cli drives cli.main directly) call
# enable_compilation_cache(), arming the on-disk cache for the whole
# pytest process; XLA:CPU's executable serialize/deserialize path then
# aborts/segfaults this host (observed: test_cli + test_elastic kills the
# run inside train_elastic's cached step_by_idx, reproducibly, at any
# commit — and never with the cache disabled).
os.environ.setdefault("NERRF_NO_COMPILE_CACHE", "1")

import jax  # noqa: E402

# the suite runs on the virtual CPU mesh wherever it is started; what needs
# the chip is chip_smoke.py's and chipbench/'s, run through the tool
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end tests")


import pathlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent.parent


def scope_paths(lowered_text: str) -> list:
    """The `jax.named_scope` / primitive path of every location in a
    lowered module's text (``.as_text(debug_info=True)``), e.g.
    ``jit(loss)/jvp(NerrfNet)/gnn/gnn_heads/row_gather/dot_general``."""
    import re

    return re.findall(r'^#loc\d+ = loc\("([^"]+)"', lowered_text, flags=re.M)


def make_service_shell(cfg, registry=None, journal=None):
    """The private-state skeleton the fake-service tests build
    `OnlineDetectionService` from (no model, no compile): every field the
    admission / demux / failure / lifecycle paths touch, EXCEPT the
    batcher — each caller wires its own score_fn and starts it.  ONE
    copy: a field added to __init__ is added here once, not in three
    hand-rolled constructors (test_serve / test_registry / test_chaos)."""
    import threading

    from nerrf_tpu.flight.journal import EventJournal
    from nerrf_tpu.flight.slo import SLOTracker
    from nerrf_tpu.observability import MetricsRegistry
    from nerrf_tpu.serve.alerts import AlertSink
    from nerrf_tpu.serve.service import OnlineDetectionService

    registry = registry or MetricsRegistry(namespace="test")
    svc = OnlineDetectionService.__new__(OnlineDetectionService)
    svc.cfg = cfg
    svc._params = None
    svc._model = None
    svc._reg = registry
    svc._journal = journal if journal is not None \
        else EventJournal(registry=registry)
    svc._slo = SLOTracker(cfg.window_deadline_sec, registry=registry,
                          journal=svc._journal)
    svc._flight = None
    svc._manager = None
    svc._live_version = None
    svc._shadow = None
    svc._boot_threshold = cfg.threshold
    svc.sink = AlertSink(cfg.alert_queue_slots, registry=registry,
                         journal=svc._journal)
    svc._lock = threading.Lock()
    svc._swap_lock = threading.Lock()
    svc._streams = {}
    svc._strikes = {}
    svc._quarantined = {}
    svc._warm = True
    svc._admission_open = False
    svc.warmup_seconds = {}
    svc.warmup_source = {}
    svc._window_log = None
    svc._quality = None
    svc._devtime = None
    svc._archive = None
    svc._respond = None
    svc._learn = None
    svc._devtime_thread = None
    svc._devtime_stop = threading.Event()
    return svc, registry
