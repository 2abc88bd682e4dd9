"""ffi.load() builds libkf.so where it loads it (kungfu_tpu/ffi.py).

A clean checkout has no libkf.so (`*.so` is ignored), and the first
load() of each process — six xdist workers, the N workers of a kfrun —
may find it absent together. Every test here works on a temporary copy
of kungfu_tpu/native/ (Makefile + sources), loaded from a child process
whose ffi._LIB_DIR points at the copy: the tree's own library is never
touched. `make` and `$(CXX)` are shims that log each call and hand over
to the real tool; -O0 keeps a build to a few seconds.
"""

import os
import shutil
import stat
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "kungfu_tpu", "native")

_CHILD = """
import sys
import kungfu_tpu.ffi as ffi
ffi._LIB_DIR = sys.argv[1]
lib = ffi.load()
assert lib.kf_peer_new.restype is not None
print("LOADED", lib._name, flush=True)
"""

_JUNK = b"half a library"

_CXX_SHIM = """
out=
prev=
for a in "$@"; do [ "$prev" = -o ] && out=$a; prev=$a; done
echo "$out" >> {log}
printf '{junk}' > "$out"
"""


def _script(path, body):
    path.write_text("#!/bin/sh\n" + body)
    path.chmod(path.stat().st_mode | stat.S_IXUSR)
    return str(path)


@pytest.fixture
def native(tmp_path):
    """(copy of native/ without products, env for a child, call logs)"""
    copy = tmp_path / "native"
    copy.mkdir()
    shutil.copy(os.path.join(NATIVE, "Makefile"), copy)
    for sub in ("include", "src"):
        shutil.copytree(os.path.join(NATIVE, sub), copy / sub)
    bin_ = tmp_path / "bin"
    bin_.mkdir()
    make_log = tmp_path / "make.log"
    cxx_log = tmp_path / "cxx.log"
    _script(bin_ / "make",
            f'echo "$@" >> {make_log}\nexec {shutil.which("make")} "$@"\n')
    # the compiler shim names its -o target in the log, fills it with
    # junk and dawdles before the real compile: a rule that links in
    # place shows the junk under the final name for that long
    cxx = _script(bin_ / "kf-test-cxx",
                  _CXX_SHIM.format(log=cxx_log, junk=_JUNK.decode())
                  + f'sleep 0.5\nexec {shutil.which("g++")} "$@"\n')
    env = {k: v for k, v in os.environ.items() if k != "KF_LIB"}
    env.update(
        PATH=f"{bin_}{os.pathsep}{env['PATH']}",
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
        CXX=cxx, CXXFLAGS="-std=c++17 -O0 -fPIC -pthread")
    return copy, env, make_log, cxx_log


def _spawn(copy, env):
    return subprocess.Popen([sys.executable, "-c", _CHILD, str(copy)],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _finish(proc, timeout=240):
    out, err = proc.communicate(timeout=timeout)
    return proc.returncode, out, err


def _lines(log):
    return log.read_text().splitlines() if log.exists() else []


def _leftovers(copy):
    return sorted(p.name for p in copy.iterdir()
                  if p.name.startswith("libkf.so"))


def test_load_builds_a_missing_library(native):
    copy, env, make_log, _ = native
    rc, out, err = _finish(_spawn(copy, env))
    assert rc == 0, err[-3000:]
    assert f"LOADED {copy / 'libkf.so'}" in out
    assert len(_lines(make_log)) == 1
    assert _leftovers(copy) == ["libkf.so"]


def test_concurrent_loaders_build_once(native):
    copy, env, make_log, cxx_log = native
    procs = [_spawn(copy, env) for _ in range(4)]
    for rc, out, err in [_finish(p) for p in procs]:
        assert rc == 0, err[-3000:]
        assert "LOADED" in out
    # the three that lost the flock found the file and never ran make
    assert len(_lines(make_log)) == 1, _lines(make_log)
    assert len(_lines(cxx_log)) == 1
    assert _leftovers(copy) == ["libkf.so"]


def test_readers_never_see_a_partial_library(native):
    copy, env, _, cxx_log = native
    final = copy / "libkf.so"
    builder = _spawn(copy, env)
    first = None
    deadline = time.monotonic() + 240
    while builder.poll() is None and time.monotonic() < deadline:
        time.sleep(0.01)
        try:
            seen = final.read_bytes()
        except FileNotFoundError:
            continue
        # whatever shows under the final name is the finished product
        assert not seen.startswith(_JUNK), "junk under the final name"
        first = first or seen
    rc, _, err = _finish(builder, timeout=10)
    assert rc == 0, err[-3000:]
    whole = final.read_bytes()
    assert whole[:4] == b"\x7fELF"
    assert first is None or first == whole
    # the shim did run, and was handed a name that is not the final one
    (target,) = _lines(cxx_log)
    assert target and os.path.basename(target) != "libkf.so"


def test_kf_lib_override_is_not_built(native):
    copy, env, make_log, _ = native
    env["KF_LIB"] = str(copy / "deployed" / "libkf.so")
    rc, _, err = _finish(_spawn(copy, env))
    assert rc != 0
    assert "OSError" in err and "deployed/libkf.so" in err
    assert _lines(make_log) == []
    assert _leftovers(copy) == []


def test_build_failure_raises_with_compiler_tail(native):
    copy, env, make_log, cxx_log = native
    # dies with half a product written: the rule takes that away too
    env["CXX"] = _script(
        copy.parent / "bin" / "kf-test-cxx-no",
        _CXX_SHIM.format(log=cxx_log, junk=_JUNK.decode())
        + 'echo "kf-test: the compiler says no" >&2\nexit 1\n')
    rc, _, err = _finish(_spawn(copy, env))
    assert rc != 0
    assert "RuntimeError: libkf.so build failed rc=2" in err
    assert "kf-test: the compiler says no" in err
    assert len(_lines(make_log)) == 1
    assert _leftovers(copy) == []


def test_existing_library_is_loaded_without_make(native):
    copy, env, make_log, _ = native
    # built ahead of first use, as setup.py and scripts/run-all.sh do
    r = subprocess.run([shutil.which("make"), "-C", str(copy), "libkf.so"],
                       env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    rc, out, err = _finish(_spawn(copy, env))
    assert rc == 0, err[-3000:]
    assert "LOADED" in out
    assert _lines(make_log) == []
    assert not (copy / ".libkf.lock").exists()
