"""ctypes binding to libkoordsys (see ``native/koordsys.cpp``) — the native
fast path for batched cgroup reads and perf-counter CPI, mirroring the
reference's cgo touchpoints (libpfm perf groups, NVML).

Loading order: the library this process's ``native/koordsys.cpp`` builds
(``native/build/libkoordsys-<source hash>.so``) -> on-demand g++ build into
that location -> pure-Python fallback (``available() == False``; every
caller has one). The build happens at most once per process.  The source
hash in the name means a stale or foreign ``.so`` lying in the (git-ignored)
build directory is never loaded: it is simply not the file looked for.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "koordsys.cpp")
_LIB_DIR = os.path.join(_REPO_ROOT, "native", "build")


def _lib_path() -> str:
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        digest = "nosource"     # _build() reports the missing source
    return os.path.join(_LIB_DIR, f"libkoordsys-{digest}.so")


_LIB = _lib_path()

#: expected ks_version(); a source whose version disagrees is a bug, and
#: its library is not used
KS_VERSION = 2

_lock = threading.Lock()
#: serializes the g++ compile + dlopen; separate from _lock so fast-path
#: _load() calls never queue behind a running build
_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_build_thread: Optional[threading.Thread] = None


def _build() -> bool:
    if not os.path.exists(_SRC):
        return False
    os.makedirs(_LIB_DIR, exist_ok=True)
    # build beside the target and rename into place: another process
    # (tests spawn several) must never dlopen a half-written library
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-Wall", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load() -> Optional[ctypes.CDLL]:
    """Non-blocking: returns the lib if already loadable; if the .so is
    missing, kicks the g++ build in a background thread and returns None —
    callers use their Python fallback until the build lands. Never waits on
    a running build (the lock is only held for the quick dlopen, not the
    compile). Use :func:`ensure_built` to wait (tests, daemon init)."""
    global _build_thread
    if _lib is not None or _load_attempted:
        return _lib
    if os.path.exists(_LIB):
        return _load_blocking()
    with _lock:
        if _build_thread is None:
            _build_thread = threading.Thread(
                target=_load_blocking, name="koordsys-build", daemon=True
            )
            _build_thread.start()
    return None


def ensure_built() -> bool:
    """Blocking build+load; True when the native path is usable."""
    return _load_blocking() is not None


def _load_blocking() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    # The build runs under its own lock: concurrent ensure_built()/background
    # threads serialize here (two g++ runs on one .so corrupt it), while
    # fast-path _load() calls never touch this lock and keep falling back.
    with _build_lock:
        if not _load_attempted and not os.path.exists(_LIB):
            if not _build():
                with _lock:
                    _load_attempted = True
                    return None
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            return None
        lib.ks_version.restype = ctypes.c_int
        if lib.ks_version() != KS_VERSION:
            return None
        lib.ks_batch_read.restype = ctypes.c_int
        lib.ks_batch_read.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int, ctypes.POINTER(ctypes.c_long),
        ]
        lib.ks_cpi_open.restype = ctypes.c_int
        lib.ks_cpi_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.ks_cpi_read.restype = ctypes.c_int
        lib.ks_cpi_read.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong),
        ]
        lib.ks_cpi_close.restype = None
        lib.ks_cpi_close.argtypes = [ctypes.c_int]
        lib.ks_watch_open.restype = ctypes.c_int
        lib.ks_watch_open.argtypes = []
        lib.ks_watch_add.restype = ctypes.c_int
        lib.ks_watch_add.argtypes = [ctypes.c_int, ctypes.c_char_p]
        lib.ks_watch_poll.restype = ctypes.c_int
        lib.ks_watch_poll.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ]
        lib.ks_watch_close.restype = None
        lib.ks_watch_close.argtypes = [ctypes.c_int]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class BatchReader:
    """Reads a fixed set of small files in one native pass per tick.

    Collectors read the same cgroup files every tick, so the path array and
    the result buffer are built once and reused — the per-call cost is one C
    loop of open/read/close. (A naive per-call binding is slower than Python
    IO: the ctypes marshalling dominates.)
    """

    def __init__(self, paths: Sequence[str], max_bytes: int = 4096):
        self.paths = list(paths)
        self.max_bytes = max_bytes
        self._lib = _load()
        self._native_dead = False  # set when the lib stub rejects reads
        if self._lib is not None:
            self._marshal()

    def _marshal(self) -> None:
        n = len(self.paths)
        if n:
            self._c_paths = (ctypes.c_char_p * n)(
                *[p.encode() for p in self.paths]
            )
            self._buf = ctypes.create_string_buffer(n * self.max_bytes)
            self._sizes = (ctypes.c_long * n)()

    def _read_python(self) -> list[Optional[str]]:
        out: list[Optional[str]] = []
        for path in self.paths:
            try:
                with open(path) as f:
                    out.append(f.read(self.max_bytes))
            except OSError:
                out.append(None)
        return out

    def read(self) -> list[Optional[str]]:
        """Current content of every file; None where unreadable."""
        n = len(self.paths)
        if n == 0:
            return []
        if self._lib is None:
            if self._native_dead:
                return self._read_python()
            # the background build may have landed since construction —
            # re-probe so a long-lived reader upgrades to the native path
            self._lib = _load()
            if self._lib is None:
                return self._read_python()
            self._marshal()
        rc = self._lib.ks_batch_read(
            ctypes.cast(self._c_paths, ctypes.POINTER(ctypes.c_char_p)), n,
            self._buf, self.max_bytes, self._sizes,
        )
        if rc < 0:  # non-Linux stub: sizes are not populated
            self._lib = None
            self._native_dead = True
            return self._read_python()
        raw = self._buf.raw
        out = []
        for i in range(n):
            size = self._sizes[i]
            if size < 0:
                out.append(None)
            else:
                start = i * self.max_bytes
                out.append(raw[start: start + size].decode(errors="replace"))
        return out


def batch_read(paths: Sequence[str], max_bytes: int = 4096) -> list[Optional[str]]:
    """One-shot convenience over :class:`BatchReader`."""
    return BatchReader(paths, max_bytes).read()


class DirWatcher:
    """Inotify directory watcher (PLEG fast path; pleg.go's fsnotify role).

    ``open()`` returns False where inotify (or the native lib) is
    unavailable — callers keep their scan path.  ``poll`` returns a list of
    (wd, kind, name): kind "C" = entry appeared, "D" = vanished; a
    (-1, "C", "*") entry signals a kernel queue overflow — treat it as
    "anything may have changed" and rescan.
    """

    def __init__(self):
        self._fd: Optional[int] = None
        self._buf = ctypes.create_string_buffer(16384)

    def open(self) -> bool:
        lib = _load()
        if lib is None:
            return False
        fd = lib.ks_watch_open()
        if fd < 0:
            return False
        self._fd = fd
        return True

    def add(self, path: str) -> Optional[int]:
        """Watch a directory; returns the watch descriptor or None."""
        lib = _load()
        if lib is None or self._fd is None:
            return None
        wd = lib.ks_watch_add(self._fd, path.encode())
        return wd if wd >= 0 else None

    def poll(self, timeout_ms: int = 0) -> list[tuple[int, str, str]]:
        lib = _load()
        if lib is None or self._fd is None:
            return []
        n = lib.ks_watch_poll(self._fd, timeout_ms, self._buf,
                              len(self._buf))
        if n <= 0:
            return []
        out = []
        for line in self._buf.raw[:n].decode(errors="replace").splitlines():
            parts = line.split(" ", 2)
            if len(parts) == 3:
                out.append((int(parts[0]), parts[1], parts[2]))
        return out

    def close(self) -> None:
        lib = _load()
        if lib is not None and self._fd is not None:
            lib.ks_watch_close(self._fd)
        self._fd = None


class CPICounter:
    """Cycles/instructions counters for one cgroup (CPI collector source).

    ``open()`` returns False where perf is unavailable (permissions,
    container, non-Linux) — the CPI collector then disables itself, matching
    the reference's Libpfm4 feature-gate behavior.
    """

    def __init__(self, cgroup_dir: str, n_cpus: int):
        self.cgroup_dir = cgroup_dir
        self.n_cpus = n_cpus
        self._handle: Optional[int] = None

    def open(self) -> bool:
        lib = _load()
        if lib is None:
            return False
        handle = lib.ks_cpi_open(self.cgroup_dir.encode(), self.n_cpus)
        if handle < 0:
            return False
        self._handle = handle
        return True

    def read(self) -> Optional[tuple[int, int]]:
        """(cycles, instructions) cumulative, or None."""
        lib = _load()
        if lib is None or self._handle is None:
            return None
        cycles = ctypes.c_ulonglong()
        instructions = ctypes.c_ulonglong()
        if lib.ks_cpi_read(self._handle, ctypes.byref(cycles),
                           ctypes.byref(instructions)) != 0:
            return None
        return cycles.value, instructions.value

    def close(self) -> None:
        lib = _load()
        if lib is not None and self._handle is not None:
            lib.ks_cpi_close(self._handle)
        self._handle = None
