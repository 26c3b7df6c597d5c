"""ASan/UBSan harness for the native layer.

The static lifecycle pass (ORX5xx) covers the Python side; the C++ side
gets the real thing: the adversarial-frame parity suite from
test_parse.py re-runs in a subprocess whose native library was compiled
with ``-fsanitize=address,undefined``. A heap overflow, use-after-free,
or UB in parse.cpp/feature_store.cpp aborts that subprocess and fails
here with the sanitizer report in the assertion message.

Skips cleanly (never fails) when g++ or the ASan runtime is absent —
the pure-Python-fallback environments the native layer already supports.

The subprocess needs:
  - LD_PRELOAD=<libasan.so>: a sanitized .so dlopen()ed into an
    uninstrumented CPython requires the ASan runtime loaded first;
  - ASAN_OPTIONS=detect_leaks=0: CPython itself is not LSan-clean, so
    leak checking would drown real reports in interpreter noise;
  - ORYX_NATIVE_SANITIZE=1: makes oryx_tpu.native load the sanitized
    build variant instead of the production -O3 artifact.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent.parent

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="g++ unavailable"
)


@pytest.fixture(scope="module")
def sanitized_env():
    from oryx_tpu import native

    so_path = native.build_sanitized_library()
    if so_path is None:
        pytest.skip("sanitized native build unavailable")
    runtime = native.find_asan_runtime()
    if runtime is None:
        pytest.skip("libasan.so not found; cannot preload the ASan runtime")
    env = dict(os.environ)
    env.update(
        {
            "LD_PRELOAD": runtime,
            "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
            "UBSAN_OPTIONS": "halt_on_error=1:print_stacktrace=1",
            "ORYX_NATIVE_SANITIZE": "1",
            "ORYX_NATIVE": "1",
            "JAX_PLATFORMS": "cpu",
        }
    )
    return env


def _run(env, *pytest_args, timeout=600):
    return subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q",
            "-p", "no:cacheprovider", "-p", "no:randomly",
            *pytest_args,
        ],
        cwd=REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_parity_suite_clean_under_asan_ubsan(sanitized_env):
    """Every parity/fallback case from test_parse.py — including the
    adversarial frames the native grammar must decline — runs against
    the instrumented library without a single sanitizer report."""
    proc = _run(
        sanitized_env,
        "tests/native/test_parse.py",
        "-k", "parity or fallback or empty_batch",
    )
    output = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"sanitized parity run failed:\n{output[-8000:]}"
    # belt and braces: a recovered (non-fatal) report still fails
    assert "ERROR: AddressSanitizer" not in output, output[-8000:]
    assert "runtime error:" not in output, output[-8000:]
    # prove the sanitized variant actually loaded (did not silently fall
    # back to pure Python, which would vacuously pass)
    probe = _run(
        sanitized_env,
        "tests/native/test_parse.py::test_parity_basic_with_ts",
        "-rs",
        timeout=300,
    )
    assert "native library unavailable" not in probe.stdout, probe.stdout


def test_feature_store_suite_clean_under_asan_ubsan(sanitized_env):
    """The concurrent feature-store suite (set/get/remove/pack under
    threads) against the instrumented library: the races ASan's
    use-after-free checks are built for."""
    proc = _run(sanitized_env, "tests/native/test_feature_store.py")
    output = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"sanitized store run failed:\n{output[-8000:]}"
    assert "ERROR: AddressSanitizer" not in output, output[-8000:]
    assert "runtime error:" not in output, output[-8000:]


def test_httpfront_suite_clean_under_asan_ubsan(sanitized_env):
    """The native HTTP front under the instrumented build: the byte-parity
    suite (real sockets, pipelining, keep-alive concurrency, slowloris
    reaping, oversized-frame rejection, mid-request disconnects) replays
    against an httpfront.cpp compiled with ASan+UBSan. The epoll loop,
    per-connection buffer arithmetic, and the teardown path (hf_shutdown
    unblocking every hf_take, then hf_close freeing connections) are exactly
    the code ASan's heap checks and UBSan's overflow checks target."""
    proc = _run(
        sanitized_env,
        "tests/serving/test_native_front.py",
        "-k", "not fleet and not tenants and not hf_take",
        timeout=600,
    )
    output = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"sanitized httpfront run failed:\n{output[-8000:]}"
    assert "ERROR: AddressSanitizer" not in output, output[-8000:]
    assert "runtime error:" not in output, output[-8000:]
    # prove the native front actually ran (skipif would vacuously pass if
    # the sanitized variant silently failed to load)
    probe = _run(
        sanitized_env,
        "tests/serving/test_native_front.py::test_native_rejects_bad_wire",
        "-rs",
        timeout=300,
    )
    assert "native toolchain unavailable" not in probe.stdout, probe.stdout


def test_httpfront_take_respond_shutdown_race_clean_under_asan_ubsan(sanitized_env):
    """The queue between the parser and the serving threads, on the
    library's handle alone (the `hf_take` cases of test_native_front.py):
    threads blocked in `hf_take` woken one a request, a record left at the
    head for want of room, takers answering through `hf_respond` while
    clients send and `hf_shutdown` lands among them, `hf_close` after the
    last taker has left. A frame written past its buffer, a request moved
    out of the queue twice or a front freed under a taker is what ASan
    reports here."""
    proc = _run(
        sanitized_env,
        "tests/serving/test_native_front.py",
        "-k", "hf_take",
        "-rs",
        timeout=300,
    )
    output = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"sanitized hf_take run failed:\n{output[-8000:]}"
    assert "ERROR: AddressSanitizer" not in output, output[-8000:]
    assert "runtime error:" not in output, output[-8000:]
    assert "native toolchain unavailable" not in proc.stdout, proc.stdout
    assert " passed" in proc.stdout and "skipped" not in proc.stdout, proc.stdout


def test_tier_store_suite_clean_under_asan_ubsan(sanitized_env):
    """The tiered cell store (ts_* in feature_store.cpp) under the
    instrumented build: the concurrent suite — readers racing the
    prefetch worker and drop_ram churn over the mmap'd cold tier and the
    RAM LRU — plus the residency/eviction/prefetch cases. The mmap
    lifecycle (remap on put_cell supersede, unmap on close), the LRU
    list splices, and the prefetch queue handoff are exactly where a
    use-after-free or torn index computation would hide. (The JAX
    scan-parity case is excluded: XLA's compiler aborts under a
    preloaded ASan runtime, same as every other sanitizer leg here —
    the instrumented target is the store, not XLA.)"""
    proc = _run(
        sanitized_env,
        "tests/native/test_tier_store.py",
        "-k", "not scan_parity",
    )
    output = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"sanitized tier-store run failed:\n{output[-8000:]}"
    assert "ERROR: AddressSanitizer" not in output, output[-8000:]
    assert "runtime error:" not in output, output[-8000:]
    # prove the native variant actually exercised (the suite parametrizes
    # python+native; a silent fallback would skip the native leg)
    probe = _run(
        sanitized_env,
        "tests/native/test_tier_store.py::test_concurrent_readers_and_prefetch",
        "-rs",
        timeout=300,
    )
    assert "native library unavailable" not in probe.stdout, probe.stdout


def test_build_native_cli_sanitize_exits_clean():
    """The CI entry point: `build_native.py --sanitize` succeeds with a
    toolchain present and exits 0 (clean skip) without one — never a
    hard failure CI has to special-case."""
    proc = subprocess.run(
        [sys.executable, "tools/build_native.py", "--sanitize"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sanitized library:" in proc.stdout or "skipping" in proc.stdout
