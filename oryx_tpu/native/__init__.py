"""Native (C++) components: build-on-demand via g++, bound with ctypes.

The reference outsources its hot CPU paths to the JVM's concurrent
collections; here the serving/speed vector store is real C++ (SURVEY.md:
"the serving layer's concurrent hash-partitioned vector store gets a C++
implementation bound into Python, not a Python stand-in"). The shared
library is compiled with -march=native into this package's _build/
directory, keyed by source hash and host CPU, so a tree copied to another
kind of machine rebuilds there instead of loading code for the wrong CPU;
set ORYX_NATIVE=0 to force the pure-Python fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["feature_store.cpp", "parse.cpp", "httpfront.cpp"]
_LOCK = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


def native_enabled() -> bool:
    return os.environ.get("ORYX_NATIVE", "1") != "0"


def _host_cpu() -> bytes:
    """What -march=native depends on: this host's CPU model and feature
    flags (the first processor's lines of /proc/cpuinfo)."""
    with open("/proc/cpuinfo", "rb") as f:
        lines = f.read().split(b"\n\n", 1)[0].splitlines()
    return b"\n".join(
        ln for ln in lines if ln.split(b":")[0].strip() in (b"model name", b"flags")
    )


def _library_target() -> str:
    """Where this host's build of the current sources lives: keyed by source
    hash (edits rebuild, repeat imports reuse) and by host CPU (a _build/
    directory that travelled with the tree from another machine is not
    loaded)."""
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_HERE, s), "rb") as f:
            h.update(f.read())
    h.update(_host_cpu())
    return os.path.join(_HERE, "_build", f"liboryx_native_{h.hexdigest()[:16]}.so")


def _build_library() -> str | None:
    """Compile the native sources to one .so, unless this host built these
    sources before."""
    so_path = _library_target()
    if os.path.exists(so_path):
        return so_path
    paths = [os.path.join(_HERE, s) for s in _SOURCES]
    os.makedirs(os.path.dirname(so_path), exist_ok=True)
    # build aside, rename into place: a process starting meanwhile never
    # loads a half-written library
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
        "-o", tmp_path, *paths, "-lpthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, FileNotFoundError) as e:
        err = getattr(e, "stderr", b"")
        log.warning(
            "native build failed (%s); falling back to pure Python: %s",
            e, (err or b"").decode("utf-8", "replace")[:500],
        )
        return None
    os.replace(tmp_path, so_path)
    return so_path


_SANITIZE_FLAGS = [
    # -O1 keeps stack traces honest; frame pointers make ASan reports
    # readable. detect_leaks is left to the harness (CPython itself is
    # not leak-clean, so LSan would drown real reports in interpreter
    # noise).
    "-fsanitize=address,undefined",
    "-fno-sanitize-recover=undefined",
    "-fno-omit-frame-pointer",
    "-g",
    "-O1",
]


def build_sanitized_library() -> str | None:
    """Compile an ASan+UBSan instrumented variant of the native sources.

    Kept as a SEPARATE artifact in _build/ (``liboryx_native_san_*``) so
    the production .so is never polluted with sanitizer runtime deps.
    Loading it into CPython requires the ASan runtime to be preloaded
    (see `find_asan_runtime`); the test harness runs the parity suite in
    a subprocess with LD_PRELOAD set. Returns None when the toolchain is
    unavailable — callers skip, they do not fail.
    """
    h = hashlib.sha256()
    paths = [os.path.join(_HERE, s) for s in _SOURCES]
    for path in paths:
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_SANITIZE_FLAGS).encode())
    build_dir = os.path.join(_HERE, "_build")
    os.makedirs(build_dir, exist_ok=True)
    so_path = os.path.join(
        build_dir, f"liboryx_native_san_{h.hexdigest()[:16]}.so"
    )
    if os.path.exists(so_path):
        return so_path
    cmd = [
        "g++", *_SANITIZE_FLAGS, "-std=c++17", "-shared", "-fPIC",
        "-o", so_path, *paths, "-lpthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=240)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, FileNotFoundError) as e:
        err = getattr(e, "stderr", b"")
        log.warning(
            "sanitized native build unavailable (%s): %s",
            e, (err or b"").decode("utf-8", "replace")[:500],
        )
        return None
    return so_path


def find_asan_runtime() -> str | None:
    """Absolute path to libasan.so for LD_PRELOAD, or None.

    A sanitized .so dlopen()ed into an uninstrumented CPython needs the
    ASan runtime loaded FIRST; g++ knows where its copy lives.
    """
    try:
        out = subprocess.run(
            ["g++", "-print-file-name=libasan.so"],
            check=True, capture_output=True, timeout=30,
        ).stdout.decode().strip()
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, FileNotFoundError):
        return None
    # when the runtime is missing g++ echoes the bare name back
    if out and os.path.isabs(out) and os.path.exists(out):
        return os.path.realpath(out)
    return None


def library_path() -> str | None:
    """Path of the native library this process loaded, or None when it
    runs the pure-Python twins (disabled, not yet needed, or the build
    failed). Never triggers a build: health endpoints report it."""
    return _lib._name if _lib is not None else None


def get_library() -> ctypes.CDLL | None:
    """The loaded native library, or None (disabled or build failure —
    callers fall back to Python implementations). With
    ORYX_NATIVE_SANITIZE=1 the ASan/UBSan build variant is loaded
    instead (the harness sets this in a subprocess whose LD_PRELOAD
    carries the ASan runtime)."""
    global _lib, _lib_failed
    if not native_enabled():
        return None
    with _LOCK:
        if _lib is not None or _lib_failed:
            return _lib
        if os.environ.get("ORYX_NATIVE_SANITIZE") == "1":
            so_path = build_sanitized_library()
        else:
            so_path = _build_library()
        if so_path is None:
            _lib_failed = True
            return None
        lib = ctypes.CDLL(so_path)
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.fs_create.restype = c.c_void_p
    lib.fs_create.argtypes = [c.c_int64, c.c_int64]
    lib.fs_destroy.argtypes = [c.c_void_p]
    lib.fs_dim.restype = c.c_int64
    lib.fs_dim.argtypes = [c.c_void_p]
    lib.fs_set.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.POINTER(c.c_float)]
    lib.fs_get.restype = c.c_int
    lib.fs_get.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.POINTER(c.c_float)]
    lib.fs_remove.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.fs_size.restype = c.c_int64
    lib.fs_size.argtypes = [c.c_void_p]
    lib.fs_recent_count.restype = c.c_int64
    lib.fs_recent_count.argtypes = [c.c_void_p]
    lib.fs_pack.restype = c.c_int64
    lib.fs_pack.argtypes = [
        c.c_void_p, c.POINTER(c.c_float), c.c_char_p, c.POINTER(c.c_int64),
        c.c_int64, c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int,
    ]
    lib.fs_vtv.argtypes = [c.c_void_p, c.POINTER(c.c_double)]
    lib.fs_retain.argtypes = [c.c_void_p, c.POINTER(c.c_int64), c.c_char_p, c.c_int64]
    lib.fs_get_batch.restype = c.c_int64
    lib.fs_get_batch.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.c_char_p, c.c_int64,
        c.POINTER(c.c_float), c.POINTER(c.c_uint8),
    ]
    lib.fs_fold_in.restype = c.c_int64
    lib.fs_fold_in.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.c_char_p, c.c_int64,
        c.POINTER(c.c_double), c.POINTER(c.c_double), c.POINTER(c.c_float),
        c.c_int32, c.POINTER(c.c_float),
    ]
    lib.fs_set_batch.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.c_char_p, c.c_int64,
        c.POINTER(c.c_float),
    ]
    lib.parse_float_csv.restype = c.c_int64
    lib.parse_float_csv.argtypes = [
        c.c_char_p, c.c_int64, c.POINTER(c.c_float), c.c_int64,
    ]
    lib.json_format_vectors.restype = c.c_int64
    lib.json_format_vectors.argtypes = [
        c.POINTER(c.c_float), c.c_int64, c.c_int64,
        c.POINTER(c.c_char), c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
    ]
    lib.als_update_row_cap.restype = c.c_int64
    lib.als_update_row_cap.argtypes = [c.c_int64, c.c_int64]
    lib.als_format_updates.restype = c.c_int64
    lib.als_format_updates.argtypes = [
        c.POINTER(c.c_float), c.c_int64, c.c_int64,
        c.POINTER(c.c_int64), c.c_char_p, c.POINTER(c.c_int64), c.c_char_p,
        c.c_char, c.c_int, c.c_int64, c.POINTER(c.c_char),
        c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
    ]
    lib.als_parse_text_block.restype = c.c_int64
    lib.als_parse_text_block.argtypes = [
        c.c_char_p, c.c_int64, c.c_int64,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_float),
        c.POINTER(c.c_int64), c.POINTER(c.c_uint8), c.POINTER(c.c_int32),
        c.c_int64,
    ]
    lib.als_format_updates_multi.restype = c.c_int64
    lib.als_format_updates_multi.argtypes = [
        c.POINTER(c.c_float), c.c_int64, c.c_int64,
        c.POINTER(c.c_int64), c.c_char_p,
        c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_char_p,
        c.c_char, c.c_int64, c.POINTER(c.c_char),
        c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
    ]
    # httpfront.cpp: epoll HTTP/1.1 front (serving/native_front.py owns
    # the handle; ctypes releases the GIL for the blocking hf_take)
    u8p = c.POINTER(c.c_uint8)
    lib.hf_create.restype = c.c_void_p
    lib.hf_create.argtypes = [
        c.c_int, c.c_int, c.c_int64, c.c_int64, c.c_double, c.c_int64,
    ]
    lib.hf_port.restype = c.c_int
    lib.hf_port.argtypes = [c.c_void_p]
    lib.hf_shutdown.argtypes = [c.c_void_p]
    lib.hf_close.argtypes = [c.c_void_p]
    lib.hf_take.restype = c.c_int64
    lib.hf_take.argtypes = [c.c_void_p, u8p, c.c_int64]
    lib.hf_respond.restype = c.c_int
    lib.hf_respond.argtypes = [
        c.c_void_p, c.c_uint32, c.c_uint32, u8p, c.c_int64, c.c_int,
    ]
    lib.hf_set_ladder.argtypes = [c.c_void_p, c.c_int, c.c_int, c.c_uint32]
    lib.hf_set_tenants.argtypes = [c.c_void_p, u8p, c.c_int64]
    lib.hf_set_exempt.argtypes = [c.c_void_p, u8p, c.c_int64]
    lib.hf_set_context.argtypes = [c.c_void_p, u8p, c.c_int64]
    lib.hf_set_shed_template.argtypes = [
        c.c_void_p, u8p, c.c_int64, u8p, c.c_int64, c.c_int64,
    ]
    lib.hf_set_snapshot.argtypes = [
        c.c_void_p, u8p, c.c_int64, u8p, c.c_int64, u8p, c.c_int64,
        c.c_int64, c.c_int,
    ]
    lib.hf_cache_cap.argtypes = [c.c_void_p, c.c_int64]
    lib.hf_cache_put.argtypes = [
        c.c_void_p, u8p, c.c_int64, u8p, c.c_int64, u8p, c.c_int64,
        c.c_int64,
    ]
    lib.hf_cache_clear.argtypes = [c.c_void_p]
    lib.hf_cache_size.restype = c.c_int64
    lib.hf_cache_size.argtypes = [c.c_void_p]
    lib.hf_stats.restype = c.c_int64
    lib.hf_stats.argtypes = [c.c_void_p, c.POINTER(c.c_uint64), c.c_int64, c.c_int]
    lib.hf_drain_trace.restype = c.c_int64
    lib.hf_drain_trace.argtypes = [c.c_void_p, u8p, c.c_int64]
    # tiered cell store (ts_*): RAM->disk item-plane tiers + async
    # prefetch (native/store.py TieredHostPlane owns the handle)
    i64p = c.POINTER(c.c_int64)
    lib.ts_create.restype = c.c_void_p
    lib.ts_create.argtypes = [c.c_char_p, c.c_int64, c.c_int64, c.c_int64]
    lib.ts_destroy.argtypes = [c.c_void_p]
    lib.ts_put_cell.restype = c.c_int64
    lib.ts_put_cell.argtypes = [c.c_void_p, c.c_int64, u8p, c.c_int64]
    lib.ts_cell_bytes.restype = c.c_int64
    lib.ts_cell_bytes.argtypes = [c.c_void_p, c.c_int64]
    lib.ts_read_cell.restype = c.c_int64
    lib.ts_read_cell.argtypes = [c.c_void_p, c.c_int64, u8p, c.c_int64]
    lib.ts_prefetch.restype = c.c_int64
    lib.ts_prefetch.argtypes = [c.c_void_p, i64p, c.c_int64]
    lib.ts_residency.restype = c.c_int64
    lib.ts_residency.argtypes = [c.c_void_p, i64p, c.c_int64]
    lib.ts_stats.argtypes = [c.c_void_p, i64p]
    lib.ts_drop_ram.argtypes = [c.c_void_p, c.c_int64]
