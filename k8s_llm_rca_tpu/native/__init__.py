"""ctypes bindings for the native (C++) runtime components in csrc/.

The library builds on demand with the in-image g++ (``ensure_built``); every
consumer degrades gracefully to the pure-Python implementation when no
toolchain is available, so the hermetic test path never hard-requires a
compile.  ``NativePageAllocator`` and ``NativeJsonGrammar`` are drop-in
behind the same interfaces as engine/paged.PageAllocator and
engine/constrain.JsonGrammar; parity is asserted by tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from k8s_llm_rca_tpu.utils.logging import get_logger

log = get_logger(__name__)

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_STEM = "libk8s_rca_native"
_CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_PKG_DIR)), "csrc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None

# status codes (csrc/native.cpp)
OK = 0
ERR_OUT_OF_PAGES = 1
ERR_DOUBLE_FREE = 2
ERR_FOREIGN_PAGE = 3
ERR_TRASH_PAGE = 4
ERR_LEAK = 5
ERR_BAD_ARG = 6
ERR_GRAMMAR_VIOLATION = 7


def _existing_libs() -> List[str]:
    return sorted(os.path.join(_PKG_DIR, f) for f in os.listdir(_PKG_DIR)
                  if f.startswith(_LIB_STEM) and f.endswith(".so"))


def lib_path() -> Optional[str]:
    """Where the library built from the csrc/ sources ON DISK lives: the
    file name carries a digest of their content, so a library built from
    other sources (an earlier commit, another checkout copied over this
    one) is never mistaken for current — file times say nothing after a
    copy.  Installed without sources: whatever library is present."""
    try:
        names = sorted(os.listdir(_CSRC_DIR))
    except OSError:
        libs = _existing_libs()
        return libs[-1] if libs else None
    digest = hashlib.sha256()
    for name in names:
        digest.update(name.encode())
        with open(os.path.join(_CSRC_DIR, name), "rb") as f:
            digest.update(f.read())
    return os.path.join(_PKG_DIR,
                        f"{_LIB_STEM}-{digest.hexdigest()[:16]}.so")


def build_error() -> Optional[str]:
    """Why the last build or load failed (None: no failure so far)."""
    return _build_error


def ensure_built() -> bool:
    """Build csrc/ into the package tree unless the library for the
    current sources is already there; True when it is present.  The
    library is compiled to a process-unique temp path and atomically
    renamed, so concurrent first-builds from several processes can't hand
    each other a half-written file."""
    global _build_error
    if _build_error is not None:
        return False
    path = lib_path()
    if path is None:
        _build_error = (f"no library in {_PKG_DIR} and no sources in "
                        f"{_CSRC_DIR}")
        return False
    if os.path.exists(path):
        return True
    with _lock:
        if os.path.exists(path):
            return True
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            subprocess.run(["make", "-C", _CSRC_DIR, "-B", f"OUT={tmp}"],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, path)
        except (OSError, subprocess.SubprocessError) as e:
            stderr = getattr(e, "stderr", None) or b""
            _build_error = (f"{e}: "
                            f"{stderr.decode(errors='replace')[-2000:]}")
            log.warning("native build failed, using the Python "
                        "components: %s", _build_error)
            return False
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        for stale in _existing_libs():
            if stale != path:
                os.remove(stale)
    return True


def load_library() -> Optional[ctypes.CDLL]:
    """The loaded library, building it first if necessary; None when
    unavailable (callers fall back to Python)."""
    global _lib, _build_error
    if _lib is not None:
        return _lib
    if not ensure_built():
        return None
    with _lock:
        if _lib is None:
            try:
                lib = ctypes.CDLL(lib_path())
                _configure(lib)
            except OSError as e:     # corrupt/incompatible .so: fall back
                _build_error = f"load failed: {e}"
                log.warning("native library failed to load: %s", e)
                return None
            _lib = lib
    return _lib


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.pagealloc_create.restype = c.c_void_p
    lib.pagealloc_create.argtypes = [c.c_int32]
    lib.pagealloc_destroy.argtypes = [c.c_void_p]
    lib.pagealloc_n_free.restype = c.c_int32
    lib.pagealloc_n_free.argtypes = [c.c_void_p]
    lib.pagealloc_alloc.restype = c.c_int32
    lib.pagealloc_alloc.argtypes = [c.c_void_p, c.c_int32, c.c_int64,
                                    c.POINTER(c.c_int32)]
    lib.pagealloc_free.restype = c.c_int32
    lib.pagealloc_free.argtypes = [c.c_void_p, c.POINTER(c.c_int32),
                                   c.c_int32, c.c_int64]
    lib.pagealloc_transfer.restype = c.c_int32
    lib.pagealloc_transfer.argtypes = [c.c_void_p, c.POINTER(c.c_int32),
                                       c.c_int32, c.c_int64, c.c_int64]
    lib.pagealloc_pages_of.restype = c.c_int32
    lib.pagealloc_pages_of.argtypes = [c.c_void_p, c.c_int64,
                                       c.POINTER(c.c_int32), c.c_int32]
    lib.pagealloc_check.restype = c.c_int32
    lib.pagealloc_check.argtypes = [c.c_void_p]

    lib.jsongram_create.restype = c.c_void_p
    lib.jsongram_destroy.argtypes = [c.c_void_p]
    lib.jsongram_set_vocab.restype = c.c_int32
    lib.jsongram_set_vocab.argtypes = [c.c_void_p, c.c_char_p,
                                       c.POINTER(c.c_int32), c.c_int32]
    lib.jsongram_complete.restype = c.c_int32
    lib.jsongram_complete.argtypes = [c.c_void_p]
    lib.jsongram_can_terminate.restype = c.c_int32
    lib.jsongram_can_terminate.argtypes = [c.c_void_p]
    lib.jsongram_mask.restype = c.c_int32
    lib.jsongram_mask.argtypes = [c.c_void_p, c.POINTER(c.c_uint8)]
    lib.jsongram_advance_token.restype = c.c_int32
    lib.jsongram_advance_token.argtypes = [c.c_void_p, c.c_int32]
    lib.jsongram_accept_char.restype = c.c_int32
    lib.jsongram_accept_char.argtypes = [c.c_void_p, c.c_char]
    lib.jsongram_minimal_completion.restype = c.c_int32
    lib.jsongram_minimal_completion.argtypes = [c.c_void_p, c.c_char_p,
                                                c.c_int32]


def available() -> bool:
    return load_library() is not None


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------


class NativePageAllocator:
    """Drop-in for engine/paged.PageAllocator backed by csrc/native.cpp.
    Raises the same exception types on the same violations."""

    def __init__(self, n_pages: int):
        from k8s_llm_rca_tpu.engine.paged import AllocatorError

        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        lib = load_library()
        if lib is None:
            raise AllocatorError("native library unavailable")
        self._lib = lib
        self.n_pages = n_pages
        self._h = lib.pagealloc_create(np.int32(n_pages))

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.pagealloc_destroy(h)
            self._h = None

    def _raise(self, status: int) -> None:
        from k8s_llm_rca_tpu.engine.paged import AllocatorError, OutOfPages

        if status == ERR_OUT_OF_PAGES:
            raise OutOfPages(f"pool exhausted ({self.n_free} free)")
        messages = {
            ERR_DOUBLE_FREE: "double free",
            ERR_FOREIGN_PAGE: "page owned by another sequence",
            ERR_TRASH_PAGE: "attempt to free the trash page",
            ERR_LEAK: "leaked or aliased pages",
            ERR_BAD_ARG: "bad argument",
        }
        raise AllocatorError(messages.get(status, f"status {status}"))

    @property
    def n_free(self) -> int:
        return int(self._lib.pagealloc_n_free(self._h))

    def pages_of(self, owner: int) -> List[int]:
        cap = self.n_pages
        out = (ctypes.c_int32 * cap)()
        n = self._lib.pagealloc_pages_of(self._h, np.int64(owner), out, cap)
        return sorted(out[i] for i in range(min(n, cap)))

    def alloc(self, n: int, owner: int) -> List[int]:
        out = (ctypes.c_int32 * max(n, 1))()
        status = self._lib.pagealloc_alloc(self._h, np.int32(n),
                                           np.int64(owner), out)
        if status != OK:
            self._raise(status)
        return [out[i] for i in range(n)]

    def free(self, pages: Sequence[int], owner: int) -> None:
        arr = (ctypes.c_int32 * max(len(pages), 1))(*pages)
        status = self._lib.pagealloc_free(self._h, arr,
                                          np.int32(len(pages)),
                                          np.int64(owner))
        if status != OK:
            self._raise(status)

    def transfer(self, pages: Sequence[int], from_owner: int,
                 to_owner: int) -> None:
        arr = (ctypes.c_int32 * max(len(pages), 1))(*pages)
        status = self._lib.pagealloc_transfer(
            self._h, arr, np.int32(len(pages)), np.int64(from_owner),
            np.int64(to_owner))
        if status != OK:
            self._raise(status)

    def check(self) -> None:
        status = self._lib.pagealloc_check(self._h)
        if status != OK:
            self._raise(status)


class NativeJsonGrammar:
    """Drop-in for engine/constrain.JsonGrammar with the automaton, mask
    computation and minimal-completion logic in C++."""

    def __init__(self, tokenizer):
        from k8s_llm_rca_tpu.engine import constrain

        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.tokenizer = tokenizer
        self.eos_id = tokenizer.eos_id
        self._h = lib.jsongram_create()
        strings = constrain._token_strings(tokenizer)
        # flattened vocab buffer, cached on the tokenizer: grammars are
        # built once per serve request, so the O(V) encode must not repeat
        cached = getattr(tokenizer, "_native_vocab_cache", None)
        if cached is None:
            encoded = [s.encode("utf-8", errors="replace") for s in strings]
            buf = b"".join(encoded)
            offsets = np.zeros((len(strings) + 1,), np.int32)
            np.cumsum([len(e) for e in encoded], out=offsets[1:])
            cached = (buf, offsets)
            tokenizer._native_vocab_cache = cached
        buf, offsets = cached
        self._offsets = offsets            # keep alive for the C side setup
        status = lib.jsongram_set_vocab(
            self._h, buf, offsets.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)), np.int32(len(strings)))
        if status != OK:
            raise RuntimeError(f"set_vocab failed: {status}")
        self._strings = strings
        self._mask_buf = np.zeros((len(strings),), np.uint8)
        # force-close bookkeeping mirrors the Python grammar
        self._char_token: Dict[str, int] = {}
        max_chars = 1
        for t, s in enumerate(strings):
            if len(s) == 1 and s not in self._char_token:
                self._char_token[s] = t
            max_chars = max(max_chars, len(s))
        self._close_margin = 2 + 4 * (max_chars - 1)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.jsongram_destroy(h)
            self._h = None

    @property
    def done(self) -> bool:
        return bool(self._lib.jsongram_complete(self._h))

    def minimal_completion(self) -> str:
        out = ctypes.create_string_buffer(4096)
        n = self._lib.jsongram_minimal_completion(self._h, out, 4096)
        if n < 0:
            raise RuntimeError("minimal completion overflow")
        return out.raw[:n].decode()

    def constraint(self, remaining: Optional[int] = None):
        from k8s_llm_rca_tpu.engine.constrain import Constraint

        if self.done:
            return Constraint(force=self.eos_id)
        if remaining is not None:
            completion = self.minimal_completion()
            if remaining <= len(completion) + self._close_margin:
                if not completion:
                    return Constraint(force=self.eos_id)
                forced = self._char_token.get(completion[0])
                if forced is None:
                    if bool(self._lib.jsongram_can_terminate(self._h)):
                        return Constraint(force=self.eos_id)
                    forced = self.tokenizer.encode(completion[0])[0]
                return Constraint(force=forced)
        n_allowed = self._lib.jsongram_mask(
            self._h, self._mask_buf.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint8)))
        allow = self._mask_buf.astype(bool)   # fresh array each call
        if bool(self._lib.jsongram_can_terminate(self._h)):
            allow[self.eos_id] = True
            n_allowed += 1
        if n_allowed == 0:
            return Constraint(force=self.eos_id)
        return Constraint(allow=allow)

    def advance(self, token: int) -> None:
        if token == self.eos_id:
            return
        status = self._lib.jsongram_advance_token(self._h, np.int32(token))
        if status != OK:
            raise ValueError(
                f"token {token} ({self._strings[token]!r}) violates the "
                f"JSON grammar")
