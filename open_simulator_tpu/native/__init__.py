"""Native (C++) host runtime components, with transparent Python fallbacks.

The compute path of this framework is XLA-compiled (ops/kernels.py); this package
holds the native pieces of the HOST runtime around it. Currently:

- `_hashobj.canon_hash(obj)` — 128-bit canonical hash of JSON-ish object trees,
  used to key pod scheduling groups (simulator/encode.py), with `pod_sig` and
  `class_sigs` built on it. Compiled lazily from `_hashobj.cpp` with the
  toolchain's C++ compiler on first use; results are cached next to the
  source. Set SIMON_NO_NATIVE=1 to force the Python fallback.

Build strategy: no pybind11 in this environment, so the extension uses the raw
CPython C API and is compiled with a direct compiler invocation (no setuptools
temp-dir dance), which keeps cold-start under a second. The compiler writes
a temporary file beside the binary, which then replaces it in one rename, so
processes that rebuild at once never load a half-written binary.
"""

from __future__ import annotations

import importlib.util
import logging
import os
import subprocess
import sysconfig
import tempfile
from typing import Callable, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_hashobj.cpp")
_SO = os.path.join(_DIR, "_hashobj" + (sysconfig.get_config_var("EXT_SUFFIX") or ".so"))

_canon_hash: Optional[Callable] = None
_pod_sig: Optional[Callable] = None
_class_sigs: Optional[Callable] = None
_tried = False


def _build() -> bool:
    cc = os.environ.get("CXX", "g++")
    include = sysconfig.get_paths()["include"]
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(_SO),
                               prefix=".build-", suffix=os.path.basename(_SO))
    os.close(fd)
    cmd = [
        cc, "-O2", "-shared", "-fPIC", "-std=c++17",
        f"-I{include}", _SRC, "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            logging.debug("native build failed:\n%s", proc.stderr)
            return False
        os.chmod(tmp, 0o755)  # mkstemp made it private
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        logging.debug("native build failed to run: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    spec = importlib.util.spec_from_file_location("open_simulator_tpu.native._hashobj", _SO)
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ensure_built() -> None:
    global _canon_hash, _pod_sig, _class_sigs, _tried
    if _tried:
        return
    _tried = True
    if os.environ.get("SIMON_NO_NATIVE"):
        return
    try:
        # <= so equal mtimes (e.g. both stamped by a checkout) rebuild: loading a
        # stale binary would silently change signature semantics
        stale = (not os.path.exists(_SO)
                 or os.path.getmtime(_SO) <= os.path.getmtime(_SRC))
        if stale and not _build():
            return
        mod = _load()
        if mod is not None:
            _canon_hash = mod.canon_hash
            _pod_sig = getattr(mod, "pod_sig", None)
            _class_sigs = getattr(mod, "class_sigs", None)
    except Exception as e:  # any failure → Python fallback
        logging.debug("native hash unavailable: %s", e)
        _canon_hash = _pod_sig = _class_sigs = None


def canon_hash_fn() -> Optional[Callable]:
    """The native hash function, building it on first call; None when unavailable
    (missing compiler, SIMON_NO_NATIVE=1, ...)."""
    _ensure_built()
    return _canon_hash


def pod_sig_fn() -> Optional[Callable]:
    """The native one-call pod-signature function (extraction + hash); None when
    the extension is unavailable."""
    _ensure_built()
    return _pod_sig


def class_sigs_fn() -> Optional[Callable]:
    """The native scheduling-class keying, `class_sigs(templates, anno_keys,
    label_keys, keep_ns)`: per template, pod_sig of simulator/encode.py's
    class_template(t, label_keys, keep_ns) without building that copy; None
    when the extension is unavailable."""
    _ensure_built()
    return _class_sigs
