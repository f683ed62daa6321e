"""Lazy g++ build of the native library, cached next to the source.

No pybind11 in the image, so the library exposes a C ABI consumed via ctypes
(see ``matcha_tpu/native/__init__.py``).  The build is a single translation
unit — a plain ``g++ -O3 -shared`` is faster and simpler than dragging in
cmake for one file.  The cached ``.so`` is reused only when the hash stored
beside it is the source's: file times do not survive a copy or a checkout,
so an older ``.so`` can look newer than the source it was not built from.
Set ``MATCHA_TPU_NO_NATIVE=1`` to skip native entirely (pure-Python
fallbacks everywhere).
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from pathlib import Path
from typing import Optional

_SRC = Path(__file__).parent / "src" / "matcha_native.cpp"
_LIB = Path(__file__).parent / "_build" / "libmatcha_native.so"
_STAMP = _LIB.with_suffix(".so.sha256")


def build_native(force: bool = False) -> Optional[Path]:
    """Compile the native library if needed; returns its path or None."""
    if os.environ.get("MATCHA_TPU_NO_NATIVE"):
        return None
    if not _SRC.exists():
        return None
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()
    if (not force and _LIB.exists() and _STAMP.exists()
            and _STAMP.read_text().strip() == digest):
        return _LIB
    _LIB.parent.mkdir(parents=True, exist_ok=True)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        "-o", str(_LIB), str(_SRC),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.SubprocessError, FileNotFoundError):
        return None
    _STAMP.write_text(digest + "\n")
    return _LIB
