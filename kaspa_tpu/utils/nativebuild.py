"""Build a native helper library from ``native/`` and say where it is.

The library's file name carries a digest of the sources it was built from,
so what gets loaded is always what the checked-out sources compile to: an
ignored ``*.so`` left behind by an earlier build of other sources (a copied
tree, a checkout with rewound mtimes) is simply a different file that
nobody opens.  Same sources, same name — concurrent processes share one
build, published by atomic rename so none ever loads a half-written file.
"""

from __future__ import annotations

import functools
import hashlib
import os
import subprocess


@functools.lru_cache(maxsize=None)  # one digest + existence check per library per process
def build(src: str, stem: str, *, deps: tuple = (), opt: str = "-O2") -> str:
    """Compile ``src`` (a single translation unit that may include ``deps``)
    into ``<dir of src>/lib<stem>-<digest>.so`` unless it is already there;
    returns the path.  Raises if the toolchain is missing or the compile
    fails — the callers decide what a missing native engine means."""
    digest = hashlib.sha256()
    for path in (src, *deps):
        with open(path, "rb") as f:
            digest.update(f.read())
    digest.update(opt.encode())
    lib = os.path.join(os.path.dirname(src), f"lib{stem}-{digest.hexdigest()[:16]}.so")
    if not os.path.exists(lib):
        tmp = f"{lib}.tmp{os.getpid()}"
        subprocess.run(
            ["g++", opt, "-shared", "-fPIC", "-std=c++17", "-o", tmp, src], check=True, capture_output=True
        )
        os.replace(tmp, lib)
    return lib
