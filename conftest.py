"""Root conftest: pin the CPU and an 8-device virtual mesh for all tests.

Multi-chip TPU hardware is not available in CI; all sharding/parallelism
tests run against 8 virtual CPU devices (the reference's analog is loopback
testing of its distributed layer, see SURVEY.md §4). The chip is exercised
by ``chip_smoke.py``, never by the test suite.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by every subprocess we spawn

import jax

# before first use: both settings are read at backend initialization
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# Per-test watchdog: one hung test must not stall the whole suite (the
# reference uses meson test timeouts; pytest-timeout is not in this image,
# so a SIGALRM in the main thread fails the test with a TimeoutError and a
# stack trace). Override per test with @pytest.mark.timeout_s(N) or
# globally with NNS_TEST_TIMEOUT (0 disables).
import signal
import threading
import time

import pytest

_DEFAULT_TEST_TIMEOUT = float(os.environ.get("NNS_TEST_TIMEOUT", "180"))

# ---------------------------------------------------------------------------
# tsan-lite: NNS_TSAN=1 runs the whole session with the runtime lock-order
# sanitizer enabled (CI runs the chaos/service/serving suites this way).
# Enabling happens at conftest import — BEFORE test modules construct any
# package object — so every named lock created during the session is
# instrumented. Each test then asserts no lock-order violation was
# observed during ITS span (see _tsan_check below).
# ---------------------------------------------------------------------------
_TSAN = os.environ.get("NNS_TSAN", "") == "1"
if _TSAN:
    from nnstreamer_tpu.analysis import sanitizer as _sanitizer

    _sanitizer.enable(
        hold_warn_s=float(os.environ.get("NNS_TSAN_HOLD_S", "5")))

# ---------------------------------------------------------------------------
# leakcheck: NNS_LEAKCHECK=1 runs the whole session with the paired-resource
# leak ledger enabled (calibration refcounts, spans, guard reservations,
# tracked threads, proc replicas, metrics registrations, the AOT writer
# lock — analysis/sanitizer.py second half). Enabled at conftest import so
# every acquisition of the session is recorded; each test then asserts the
# ledger returns to ITS baseline (zero NEW outstanding units) — the runtime
# twin of the NNL3xx release-on-all-paths lint.
# ---------------------------------------------------------------------------
_LEAKCHECK = os.environ.get("NNS_LEAKCHECK", "") == "1"
if _LEAKCHECK:
    from nnstreamer_tpu.analysis import sanitizer as _leak_sanitizer

    _leak_sanitizer.enable_leakcheck()

# ---------------------------------------------------------------------------
# xfercheck: NNS_XFERCHECK=1 runs the whole session with the transfer
# sanitizer enabled (analysis/sanitizer.py third half): the fused-dispatch
# and backend-invoke jit regions run under transfer-guard disallow scopes
# (any IMPLICIT device→host materialization inside them raises), and the
# choke points (backend puts, queue hand-off, wire encode/decode, explicit
# as_numpy pulls) feed a per-(stage,direction) byte ledger. Each test then
# asserts zero NEW guard violations during its span — the runtime twin of
# the NNL4xx transfer lint.
# ---------------------------------------------------------------------------
_XFERCHECK = os.environ.get("NNS_XFERCHECK", "") == "1"
if _XFERCHECK:
    from nnstreamer_tpu.analysis import sanitizer as _xfer_sanitizer

    _xfer_sanitizer.enable_xfercheck()

# ---------------------------------------------------------------------------
# wirefuzz: NNS_WIREFUZZ=1 runs the whole session with the frame-fuzz
# scorekeeper enabled (analysis/sanitizer.py fourth half): the wire codec
# choke points feed a frames-seen ledger and every fuzzed mutant records a
# typed/clean/hang/crash/silent outcome. Each test then asserts zero NEW
# hostile-peer contract violations during its span — the runtime twin of
# the NNL5xx wire-protocol lint.
# ---------------------------------------------------------------------------
_WIREFUZZ = os.environ.get("NNS_WIREFUZZ", "") == "1"
if _WIREFUZZ:
    from nnstreamer_tpu.analysis import sanitizer as _wire_sanitizer

    _wire_sanitizer.enable_wirefuzz()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout_s(n): per-test watchdog seconds (default 180)")
    config.addinivalue_line(
        "markers", "thread_leak_ok: opt out of the per-test leaked-thread "
                   "check (intentionally long-lived fixture threads)")
    config.addinivalue_line(
        "markers", "leak_ok: opt out of the per-test NNS_LEAKCHECK "
                   "zero-outstanding-resources check (intentionally "
                   "session-lived acquisitions)")
    config.addinivalue_line(
        "markers", "xfer_ok: opt out of the per-test NNS_XFERCHECK "
                   "zero-implicit-D2H check (tests that exercise the "
                   "violation path itself)")
    config.addinivalue_line(
        "markers", "wirefuzz_ok: opt out of the per-test NNS_WIREFUZZ "
                   "zero-contract-violations check (tests that exercise "
                   "the violation path itself)")


@pytest.fixture(autouse=True)
def _leakcheck(request):
    """Under NNS_LEAKCHECK=1: fail any test that ends with paired
    resources still outstanding beyond its entry baseline. A short grace
    window rides out teardown-time releases (joins, drain callbacks),
    mirroring thread_leak_check."""
    if not _LEAKCHECK:
        yield
        return
    if request.node.get_closest_marker("leak_ok"):
        yield
        return

    def keyed():
        return {(r["kind"], r["key"]): r["count"]
                for r in _leak_sanitizer.outstanding()}

    before = keyed()
    yield

    def fresh():
        return [
            {"kind": k, "key": key, "count": c}
            for (k, key), c in keyed().items()
            if c > before.get((k, key), 0)]

    deadline = time.monotonic() + 2.0
    rest = fresh()
    while rest and time.monotonic() < deadline:
        time.sleep(0.05)
        rest = fresh()
    assert not rest, (
        f"leakcheck: {len(rest)} paired resource(s) still outstanding "
        f"after this test (acquire without release): {rest}")


@pytest.fixture(autouse=True)
def _tsan_check(request):
    """Under NNS_TSAN=1: fail any test during which the sanitizer observed
    a lock-order violation (the observed acquisition graph went cyclic)."""
    if not _TSAN:
        yield
        return
    before = len(_sanitizer.violations())
    yield
    fresh = _sanitizer.violations()[before:]
    assert not fresh, (
        f"tsan-lite: {len(fresh)} lock-order violation(s) observed during "
        f"this test: {fresh}")


@pytest.fixture(autouse=True)
def _xfercheck(request):
    """Under NNS_XFERCHECK=1: fail any test during which a guarded jit
    region (fused dispatch, backend invoke) performed an implicit
    device→host transfer. Explicit ``device_get`` / ``as_numpy`` pulls
    stay legal — they are the accounted paths."""
    if not _XFERCHECK:
        yield
        return
    if request.node.get_closest_marker("xfer_ok"):
        yield
        return
    before = len(_xfer_sanitizer.xfer_violations())
    yield
    fresh = _xfer_sanitizer.xfer_violations()[before:]
    assert not fresh, (
        f"xfercheck: {len(fresh)} implicit device→host transfer(s) inside "
        f"guarded scopes during this test: {fresh}")


@pytest.fixture(autouse=True)
def _wirefuzz_check(request):
    """Under NNS_WIREFUZZ=1: fail any test during which a fuzzed mutant
    broke the hostile-peer contract (hang, crash, or silent wrong
    decode — anything but a typed error or a parity-clean decode)."""
    if not _WIREFUZZ:
        yield
        return
    if request.node.get_closest_marker("wirefuzz_ok"):
        yield
        return
    before = len(_wire_sanitizer.wirefuzz_violations())
    yield
    fresh = _wire_sanitizer.wirefuzz_violations()[before:]
    assert not fresh, (
        f"wirefuzz: {len(fresh)} hostile-peer contract violation(s) "
        f"during this test: {fresh}")


# thread names owned by the control plane / serving layers — all of them
# have an explicit stop+join path now, so a survivor is a real leak
_JOINED_THREAD_PREFIXES = (
    "svc:", "svc-http:", "serving:", "queue:", "src:", "qserver:",
    "mqtt-broker:", "broker:", "fabric:", "slo:", "autoscaler:",
    "procreplica:", "fleet:",
)


@pytest.fixture(autouse=True)
def thread_leak_check(request):
    """Snapshot live threads per test; fail on leaked non-daemon threads
    and on leaked control-plane threads (which must be joined on stop).
    Opt out with @pytest.mark.thread_leak_ok."""
    if request.node.get_closest_marker("thread_leak_ok"):
        yield
        return
    before = set(threading.enumerate())
    yield

    def leaked():
        return [
            t for t in threading.enumerate()
            if t not in before and t.is_alive()
            and (not t.daemon or t.name.startswith(_JOINED_THREAD_PREFIXES))
        ]

    # grace: teardown-time stops may still be joining
    deadline = time.monotonic() + 2.0
    rest = leaked()
    while rest and time.monotonic() < deadline:
        time.sleep(0.05)
        rest = leaked()
    assert not rest, (
        "leaked threads (not joined by the test's teardown): "
        + ", ".join(f"{t.name}{'' if t.daemon else ' [non-daemon]'}"
                    for t in rest))


@pytest.fixture(autouse=True)
def _test_watchdog(request):
    marker = request.node.get_closest_marker("timeout_s")
    limit = float(marker.args[0]) if marker else _DEFAULT_TEST_TIMEOUT
    use_alarm = (
        limit > 0
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )
    if not use_alarm:
        yield
        return

    def _on_timeout(signum, frame):
        raise TimeoutError(
            f"test exceeded {limit:.0f}s watchdog (NNS_TEST_TIMEOUT)")

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
