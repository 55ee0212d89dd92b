"""Build a csrc/ source into a shared library inside the checkout.

Every native backend (BLS, SHA-256 Merkle hashing, snappy, the KV
store) is compiled from the committed source at first use into
`LIB_DIR`, which git ignores. The file name carries a hash of the
source and the compiler command, so an edited source or changed flags
build anew, and nothing is written outside the checkout.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
LIB_DIR = REPO / ".native"


def build(src: Path, cmd: list[str], deps: tuple[Path, ...] = ()) -> Path:
    """Path of the shared library built from `src` by `cmd` (compiler
    and flags; the source and output are appended). Raises
    CalledProcessError/OSError when the build fails."""
    h = hashlib.sha256(" ".join(cmd).encode())
    for p in (src, *deps):
        h.update(p.read_bytes())
    path = LIB_DIR / f"{src.stem}_{h.hexdigest()[:16]}.so"
    if not path.exists():
        LIB_DIR.mkdir(exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        subprocess.run(
            [*cmd, "-shared", "-fPIC", str(src), "-o", str(tmp)],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, path)
    return path
