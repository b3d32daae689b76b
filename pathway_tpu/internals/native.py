"""Loader for the native runtime kernels (native/pathway_native.cc).

Imports `pathway_tpu._native` if already built; otherwise builds it once
with g++ (a few seconds) and caches the .so next to the package (the .so
is git-ignored: a fresh checkout always builds from the source). Every
caller has a pure-Python implementation too, so a missing toolchain
costs speed, never correctness — but never silently: the first load logs
which implementation is in use and why, and `native_status()` reports
it. Disable with PATHWAY_NO_NATIVE=1.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sysconfig

_native = None
_tried = False
_status = "not loaded yet"


def _build() -> str | None:
    """Compile to a temp file and swap in atomically: a failed build must
    never clobber (or have required deleting) a working cached kernel.
    Returns None on success, else why the build failed."""
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(os.path.dirname(pkg_dir), "native", "pathway_native.cc")
    if not os.path.exists(src):
        return f"source {src} is missing"
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    target = os.path.join(pkg_dir, "_native" + suffix)
    # per-process tmp: N processes of one spawn group may rebuild
    # concurrently — a shared tmp path would interleave linker writes
    tmp = f"{target}.{os.getpid()}.tmp"
    include = sysconfig.get_paths()["include"]
    cmd = [
        "g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
        f"-I{include}", src, "-o", tmp,
    ]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
        if res.returncode != 0 or not os.path.exists(tmp):
            tail = res.stderr.decode(errors="replace").strip()[-300:]
            return f"g++ exited {res.returncode}: {tail}"
        os.replace(tmp, target)
        return None
    except (OSError, subprocess.TimeoutExpired) as exc:
        return f"g++ did not run: {type(exc).__name__}: {exc}"
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _python_only(why: str, level: int = logging.WARNING):
    global _status
    _status = f"python ({why})"
    logging.getLogger("pathway_tpu").log(
        level, "native kernels not in use, running pure Python: %s", why
    )
    return None


def get_native():
    """The configured native module, or None (pure-Python callers).
    The first call logs which of the two this process runs on."""
    global _native, _tried, _status
    if _native is not None or _tried:
        return _native
    _tried = True
    if os.environ.get("PATHWAY_NO_NATIVE"):
        return _python_only("PATHWAY_NO_NATIVE is set", logging.INFO)
    # stale-cache guard: rebuild when the source is newer than the .so
    # (a cached kernel from an older source must not mask new entry
    # points). The rebuild goes via a temp file, so a box without g++
    # keeps its working cached kernel — callers feature-check new entry
    # points with hasattr.
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(os.path.dirname(pkg_dir), "native", "pathway_native.cc")
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    target = os.path.join(pkg_dir, "_native" + suffix)
    built = "cached build"
    try:
        if (
            os.path.exists(src)
            and os.path.exists(target)
            and os.path.getmtime(src) > os.path.getmtime(target)
        ):
            if _build() is None:
                built = "rebuilt from newer source"
    except OSError:
        pass
    try:
        from pathway_tpu import _native as mod  # type: ignore[attr-defined]
    except ImportError:
        failure = _build()
        if failure is not None:
            return _python_only(f"build failed: {failure}")
        built = "built from native/pathway_native.cc"
        try:
            from pathway_tpu import _native as mod  # type: ignore[attr-defined]
        except ImportError as exc:
            return _python_only(f"built module does not import: {exc}")
    from pathway_tpu.internals import api

    mod.configure(api.Pointer, api._value_bytes, api._SALT)
    # self-check: native hashing must agree with the python path, otherwise
    # persisted snapshots written by one would not resume under the other
    probe = (None, True, 7, 2.5, "x", b"y", (1, "z"))
    if mod.hash_value(probe) != api._hash_bytes(api._value_bytes(probe)):
        return _python_only("native hash self-check disagrees with Python")
    _native = mod
    _status = f"native ({os.path.basename(target)}, {built})"
    logging.getLogger("pathway_tpu").info("native kernels in use: %s", _status)
    return _native


def native_status() -> str:
    """Which implementation this process runs on and why (loads it)."""
    get_native()
    return _status
