"""Shared build-on-demand loader for the native (C++) libraries.

One implementation of the compile/atomic-publish/ABI-check sequence, used
by both ``libnns_core`` (``__init__.py``) and ``libnns_q8`` (``q8.py``).
The library's file name carries a hash of its source and compile command
(``libnns_core-<hash>.so``), so staleness is decided by content: a binary
built from other source — an older checkout, a copied tree — has another
name and is never loaded. Concurrent processes may race to build; building
to a temp path and ``os.replace``-publishing keeps every reader consistent.
Callers keep their own per-module cache + failure latch and call
:func:`load_once` under their own lock.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
from typing import Callable, List, Optional, Sequence

from ..utils.log import logger

_HERE = os.path.dirname(os.path.abspath(__file__))


def _compile_cmd(src: str, extra_args: Sequence[str]) -> List[str]:
    return [
        os.environ.get("CXX", "g++"), "-O3", "-std=c++17", "-fPIC",
        "-shared", "-Wall", "-fvisibility=hidden", src, *extra_args,
    ]


def lib_path(src: str, stem: str, extra_args: Sequence[str] = ()) -> str:
    """``<native dir>/lib<stem>-<hash>.so`` for the source as it is on
    disk now, compiled the way :func:`build` compiles it."""
    h = hashlib.sha256()
    with open(src, "rb") as fh:
        h.update(fh.read())
    cmd = _compile_cmd(os.path.basename(src), extra_args)
    h.update("\0".join(cmd).encode())
    return os.path.join(_HERE, f"lib{stem}-{h.hexdigest()[:16]}.so")


def build(src: str, out_path: str, extra_args: Sequence[str] = (),
          timeout: float = 180.0) -> bool:
    tmp = f"{out_path}.{os.getpid()}.tmp"
    cmd = [*_compile_cmd(src, extra_args), "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            logger.warning("native build failed (%s):\n%s",
                           os.path.basename(src), proc.stderr)
            return False
        os.replace(tmp, out_path)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:  # g++ missing/hung
        logger.warning("native build unavailable (%s): %s",
                       os.path.basename(src), e)
        return False
    finally:
        # a failed/killed compile leaves its partial -o output behind;
        # one stranded .tmp per rebuild attempt adds up in shared caches
        try:
            os.remove(tmp)
        except OSError:
            pass


def load_once(src: str, stem: str, abi_version: int, abi_symbol: str,
              bind: Callable[[ctypes.CDLL], None],
              extra_args: Sequence[str] = ()) -> Optional[ctypes.CDLL]:
    """Build (if no library matches the source), dlopen, ABI-check, and
    bind. Returns the bound library or None; the caller latches the
    failure."""
    path = lib_path(src, stem, extra_args)
    if not os.path.exists(path):
        if not build(src, path, extra_args):
            return None
        # libraries of other source versions are dead weight now
        for old in glob.glob(os.path.join(_HERE, f"lib{stem}-*.so")):
            if old != path:
                try:
                    os.remove(old)
                except OSError:
                    pass
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        logger.warning("native load failed (%s): %s",
                       os.path.basename(path), e)
        return None
    abi_fn = getattr(lib, abi_symbol)
    abi_fn.restype = ctypes.c_uint64
    if abi_fn() != abi_version:
        # the name pins the source, so this is the binding and the source
        # disagreeing — a rebuild cannot fix it
        logger.warning("native ABI mismatch (%s): source says %d, binding "
                       "expects %d; native disabled",
                       os.path.basename(path), abi_fn(), abi_version)
        return None
    bind(lib)
    return lib
