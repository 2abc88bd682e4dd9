"""Unified control-plane retry policy: taxonomy, backoff, deadline.

Every control-plane HTTP call site (peer.fetch_url/put_url, elastic
propose, discovery self-resolve) rides `kungfu_tpu.retrying` — these
tests pin the policy's contract: transient faults retry with bounded
jittered backoff, permanent faults surface immediately, and deadlines
beat attempt budgets.
"""

import errno
import io
import socket
import threading
import urllib.error

import pytest

from kungfu_tpu import retrying
from kungfu_tpu.retrying import NO_RETRY, RetryPolicy, is_transient


def _http_error(code: int) -> urllib.error.HTTPError:
    return urllib.error.HTTPError("http://x/get", code, "boom", {},
                                  io.BytesIO(b""))


def test_taxonomy_transient_vs_fatal():
    # refused/reset/timeout and server-side HTTP failures heal
    assert is_transient(urllib.error.URLError("refused"))
    assert is_transient(ConnectionResetError())
    assert is_transient(TimeoutError())
    for code in (404, 408, 429, 500, 502, 503, 504):
        assert is_transient(_http_error(code)), code
    # client errors and malformed input never heal
    for code in (400, 401, 403, 405):
        assert not is_transient(_http_error(code)), code
    assert not is_transient(ValueError("bad json"))
    assert not is_transient(KeyError("version"))


def test_taxonomy_disk_errnos_are_permanent():
    # a full or read-only disk cannot heal within a retry budget —
    # retrying burns the deadline then fails with a misleading timeout
    for eno in (errno.ENOSPC, errno.EROFS):
        exc = OSError(eno, "disk")
        assert not is_transient(exc), errno.errorcode[eno]
        assert not retrying.is_conn_failure(exc), errno.errorcode[eno]
    # ...including when the socket layer wraps it in a URLError
    wrapped = urllib.error.URLError(OSError(errno.ENOSPC, "disk"))
    assert not is_transient(wrapped)
    assert not retrying.is_conn_failure(wrapped)
    # other errnos keep their transient classification (refused, reset)
    for eno in (errno.ECONNREFUSED, errno.ECONNRESET, errno.ETIMEDOUT):
        assert is_transient(OSError(eno, "net")), errno.errorcode[eno]
    # errno-less OSError stays transient: no evidence it is the disk
    assert is_transient(OSError("plain"))


def test_permanent_errno_raises_without_retry():
    p = RetryPolicy(attempts=5, base_ms=1, _sleep=lambda s: None)
    calls = []

    def full_disk():
        calls.append(1)
        raise OSError(errno.ENOSPC, "No space left on device")

    with pytest.raises(OSError) as ei:
        p.run(full_disk)
    assert ei.value.errno == errno.ENOSPC  # real errno, not a timeout
    assert len(calls) == 1


def test_retries_transient_until_success():
    sleeps = []
    p = RetryPolicy(attempts=4, base_ms=10, _sleep=sleeps.append)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    assert p.run(flaky) == "ok"
    assert len(calls) == 3
    assert len(sleeps) == 2  # backed off twice


def test_fatal_raises_immediately():
    p = RetryPolicy(attempts=5, base_ms=1, _sleep=lambda s: None)
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("malformed")

    with pytest.raises(ValueError):
        p.run(bad)
    assert len(calls) == 1  # no retry burned on an unhealable error


def test_attempts_exhausted_reraises_last():
    p = RetryPolicy(attempts=3, base_ms=1, _sleep=lambda s: None)
    calls = []

    def always():
        calls.append(1)
        raise ConnectionError(f"fail {len(calls)}")

    with pytest.raises(ConnectionError, match="fail 3"):
        p.run(always)
    assert len(calls) == 3


def test_backoff_sequence_grows_and_caps():
    p = RetryPolicy(attempts=6, base_ms=50, max_ms=300, multiplier=2.0)
    assert list(p.delays_ms()) == [50, 100, 200, 300, 300]


def test_jitter_bounds():
    p = RetryPolicy(base_ms=100, jitter=0.5)
    for attempt in range(1, 6):
        s = p.backoff_s(attempt)
        full = min(100 * 2.0 ** (attempt - 1), p.max_ms) / 1e3
        assert full * 0.5 <= s <= full, (attempt, s)


def test_deadline_beats_attempts():
    sleeps = []
    # deadline 0: the first backoff would already overshoot it
    p = RetryPolicy(attempts=10, base_ms=50, deadline_s=0.0,
                    _sleep=sleeps.append)
    calls = []

    def always():
        calls.append(1)
        raise ConnectionError("x")

    with pytest.raises(ConnectionError):
        p.run(always)
    assert len(calls) == 1
    assert sleeps == []  # never slept past the deadline


def test_no_retry_is_single_shot():
    calls = []

    def always():
        calls.append(1)
        raise ConnectionError("x")

    with pytest.raises(ConnectionError):
        NO_RETRY.run(always)
    assert len(calls) == 1


def test_env_knobs_configure_default_policy(monkeypatch):
    monkeypatch.setenv("KF_RETRY_ATTEMPTS", "7")
    monkeypatch.setenv("KF_RETRY_BASE_MS", "11")
    monkeypatch.setenv("KF_RETRY_MAX_MS", "222")
    monkeypatch.setenv("KF_RETRY_DEADLINE_MS", "4000")
    p = retrying.control_plane_policy(name="x")
    assert p.attempts == 7
    assert p.base_ms == 11
    assert p.max_ms == 222
    assert p.deadline_s == 4.0


def test_fetch_url_rides_policy_through_transients(tmp_path):
    """fetch_url + the shared policy: a file:// target that appears
    between attempts (the 'config server restarting' shape)."""
    from kungfu_tpu.peer import fetch_url

    target = tmp_path / "stage.json"
    sleeps = []

    def _sleep_then_recover(s):
        sleeps.append(s)
        target.write_text("READY")  # the dependency comes back

    policy = RetryPolicy(attempts=4, base_ms=1,
                         _sleep=_sleep_then_recover)
    assert fetch_url(f"file://{target}", retry=policy) == "READY"
    assert len(sleeps) == 1  # exactly one backoff bridged the gap


def test_answer_cut_short_is_a_reset_the_policy_rides():
    """A server stopped between its headers and its body (the config
    server restarting under a worker's poll) must reach the caller as
    an OSError like every other connection fault — http.client's
    IncompleteRead is not one, and the per-step poll in
    Peer.resize_from_url catches OSError only."""
    from kungfu_tpu.peer import fetch_url

    body = b'{"version": 1}'
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(4)

    def serve():
        for whole in (False, False, True):
            conn, _ = lst.accept()
            with conn:
                conn.recv(4096)
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n"
                             b"Connection: close\r\n\r\n" % len(body))
                if whole:
                    conn.sendall(body)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{lst.getsockname()[1]}/get"
    try:
        with pytest.raises(ConnectionResetError, match="cut short"):
            fetch_url(url, retry=NO_RETRY)
        # the second cut answer is retried, the third is whole
        assert fetch_url(url, retry=RetryPolicy(
            attempts=3, base_ms=1)) == body.decode()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        lst.close()
