"""ctypes bindings + build-on-demand for the native decoder.

Includes the alignchecker (analog of /root/reference/pkg/alignchecker:
verify at load time that the Python-side record layout byte-matches
the C++ struct — the ABI race detector between the two languages) and
NumPy fallbacks mirroring the C semantics exactly (used when g++ is
missing, and as the differential-testing oracle).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "tupledec.cpp")


def _lib_path() -> str:
    """The shared object built from THIS source: its name carries a
    hash of tupledec.cpp, so a copied tree or a stale build can never
    load a library compiled from other source (mtimes do not survive
    a copy)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_tupledec-{digest}.so")


# Python-side declaration of struct flow_record (must byte-match C++).
FLOW_RECORD_DTYPE = np.dtype(
    [
        ("ep_id", "<u4"),
        ("identity", "<u4"),
        ("saddr", "<u4"),
        ("daddr", "<u4"),
        ("sport", "<u2"),
        ("dport", "<u2"),
        ("proto", "u1"),
        ("direction", "u1"),
        ("flags", "u1"),
        ("pad", "u1"),
    ]
)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


class NativeUnavailable(RuntimeError):
    pass


def _build() -> Optional[ctypes.CDLL]:
    global _build_failed
    lib_path = _lib_path()
    if os.path.exists(lib_path):
        return ctypes.CDLL(lib_path)
    # build beside the target and rename into place: concurrent
    # builders (test workers) never load a half-written object
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [
                "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                _SRC, "-o", tmp,
            ],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, lib_path)
        return ctypes.CDLL(lib_path)
    except (subprocess.CalledProcessError, FileNotFoundError):
        _build_failed = True
        return None


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is None and not _build_failed:
            lib = _build()
            if lib is not None:
                _configure(lib)
                try:
                    alignment_check(lib)
                except NativeUnavailable:
                    # ABI skew: never serve the mismatched library —
                    # permanently fall back to the NumPy path (first
                    # call raises so the skew is loud, later calls
                    # degrade safely)
                    _build_failed = True
                    raise
                _lib = lib
        return _lib


def _configure(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u16p = ctypes.POINTER(ctypes.c_uint16)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.flow_record_size.restype = ctypes.c_size_t
    lib.flow_record_offset.restype = ctypes.c_size_t
    lib.flow_record_offset.argtypes = [ctypes.c_int]
    lib.decode_flow_records.restype = ctypes.c_size_t
    lib.decode_flow_records.argtypes = [
        u8p, ctypes.c_size_t, u32p, u32p, u32p, u32p, u16p, u16p,
        u8p, u8p, u8p,
    ]
    lib.parse_packets.restype = ctypes.c_size_t
    lib.parse_packets.argtypes = [
        u8p, u64p, ctypes.c_size_t, u32p, u32p, u16p, u16p, u8p, u8p,
        u8p, u32p,
    ]
    lib.encode_flow_records.restype = None
    lib.encode_flow_records.argtypes = [
        u8p, ctypes.c_size_t, u32p, u32p, u32p, u32p, u16p, u16p,
        u8p, u8p, u8p,
    ]


def alignment_check(lib: Optional[ctypes.CDLL] = None) -> None:
    """pkg/alignchecker analog: NumPy dtype layout == C++ struct."""
    lib = lib or _get_lib()
    if lib is None:
        return
    if int(lib.flow_record_size()) != FLOW_RECORD_DTYPE.itemsize:
        raise NativeUnavailable(
            f"flow_record size mismatch: C++ {lib.flow_record_size()} "
            f"vs Python {FLOW_RECORD_DTYPE.itemsize}"
        )
    for i, name in enumerate(
        ["ep_id", "identity", "saddr", "daddr", "sport", "dport",
         "proto", "direction", "flags"]
    ):
        c_off = int(lib.flow_record_offset(i))
        py_off = FLOW_RECORD_DTYPE.fields[name][1]
        if c_off != py_off:
            raise NativeUnavailable(
                f"flow_record.{name} offset mismatch: C++ {c_off} vs "
                f"Python {py_off}"
            )


def native_available() -> bool:
    return _get_lib() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------------------
# flow records
# ---------------------------------------------------------------------------


def decode_flow_records(buf: bytes):
    """Binary flow records → SoA dict of arrays.

    A buffer whose length is not a whole number of records is
    REJECTED with ValueError: a truncated/corrupt stream silently
    dropping its tail (native path) or crashing deep in numpy
    (fallback path) would either hide data loss or take the daemon
    down — the API server maps this to HTTP 400."""
    from cilium_tpu import faultinject

    faultinject.fire("native.decode")
    buf = faultinject.corrupt_bytes("native.decode", buf)
    if len(buf) % FLOW_RECORD_DTYPE.itemsize:
        raise ValueError(
            f"truncated flow record buffer: {len(buf)} bytes is not "
            f"a multiple of the {FLOW_RECORD_DTYPE.itemsize}-byte "
            f"record size"
        )
    n = len(buf) // FLOW_RECORD_DTYPE.itemsize
    out = {
        "ep_id": np.empty(n, np.uint32),
        "identity": np.empty(n, np.uint32),
        "saddr": np.empty(n, np.uint32),
        "daddr": np.empty(n, np.uint32),
        "sport": np.empty(n, np.uint16),
        "dport": np.empty(n, np.uint16),
        "proto": np.empty(n, np.uint8),
        "direction": np.empty(n, np.uint8),
        "is_fragment": np.empty(n, np.uint8),
    }
    lib = _get_lib()
    if lib is not None:
        raw = np.frombuffer(buf, dtype=np.uint8)
        lib.decode_flow_records(
            _ptr(raw, ctypes.c_uint8), n,
            _ptr(out["ep_id"], ctypes.c_uint32),
            _ptr(out["identity"], ctypes.c_uint32),
            _ptr(out["saddr"], ctypes.c_uint32),
            _ptr(out["daddr"], ctypes.c_uint32),
            _ptr(out["sport"], ctypes.c_uint16),
            _ptr(out["dport"], ctypes.c_uint16),
            _ptr(out["proto"], ctypes.c_uint8),
            _ptr(out["direction"], ctypes.c_uint8),
            _ptr(out["is_fragment"], ctypes.c_uint8),
        )
        return out
    rec = np.frombuffer(buf, dtype=FLOW_RECORD_DTYPE)
    for name in out:
        if name == "is_fragment":
            out[name] = (rec["flags"] & 1).astype(np.uint8)
        else:
            out[name] = rec[name].copy()
    return out


def encode_flow_records(
    ep_id, identity, saddr, daddr, sport, dport, proto, direction,
    is_fragment,
) -> bytes:
    n = len(ep_id)
    rec = np.zeros(n, dtype=FLOW_RECORD_DTYPE)
    rec["ep_id"] = ep_id
    rec["identity"] = identity
    rec["saddr"] = saddr
    rec["daddr"] = daddr
    rec["sport"] = sport
    rec["dport"] = dport
    rec["proto"] = proto
    rec["direction"] = direction
    rec["flags"] = np.asarray(is_fragment, np.uint8) & 1
    return rec.tobytes()


# ---------------------------------------------------------------------------
# raw packets
# ---------------------------------------------------------------------------


def parse_packets(buf: bytes, offsets: np.ndarray):
    """Raw Ethernet frames → tuple arrays.  `offsets` is [n+1] u64
    frame boundaries into buf."""
    n = len(offsets) - 1
    out = {
        "saddr": np.zeros(n, np.uint32),
        "daddr": np.zeros(n, np.uint32),
        "sport": np.zeros(n, np.uint16),
        "dport": np.zeros(n, np.uint16),
        "proto": np.zeros(n, np.uint8),
        "is_fragment": np.zeros(n, np.uint8),
        "valid": np.zeros(n, np.uint8),
        "pkt_len": np.zeros(n, np.uint32),
    }
    lib = _get_lib()
    offsets = np.ascontiguousarray(offsets, dtype=np.uint64)
    # The C++ decoder computes offsets[i+1]-offsets[i] as size_t and
    # indexes buf with it; validate here so bad input fails loudly in
    # Python instead of under/overflowing in native code.
    if n > 0:
        if (np.diff(offsets.astype(np.int64)) < 0).any():
            raise ValueError("packet offsets must be non-decreasing")
        if int(offsets[-1]) > len(buf):
            raise ValueError(
                f"packet offsets exceed buffer length ({int(offsets[-1])}"
                f" > {len(buf)})"
            )
    if lib is not None:
        raw = np.frombuffer(buf, dtype=np.uint8)
        lib.parse_packets(
            _ptr(raw, ctypes.c_uint8),
            _ptr(offsets, ctypes.c_uint64), n,
            _ptr(out["saddr"], ctypes.c_uint32),
            _ptr(out["daddr"], ctypes.c_uint32),
            _ptr(out["sport"], ctypes.c_uint16),
            _ptr(out["dport"], ctypes.c_uint16),
            _ptr(out["proto"], ctypes.c_uint8),
            _ptr(out["is_fragment"], ctypes.c_uint8),
            _ptr(out["valid"], ctypes.c_uint8),
            _ptr(out["pkt_len"], ctypes.c_uint32),
        )
        return out
    # NumPy fallback — semantics identical to the C++ (and used as its
    # differential-test oracle in tests/test_native.py)
    for i in range(n):
        pkt = buf[int(offsets[i]) : int(offsets[i + 1])]
        out["pkt_len"][i] = len(pkt)
        if len(pkt) < 34 or pkt[12:14] != b"\x08\x00":
            continue
        ip = pkt[14:]
        ihl = ip[0] & 0x0F
        if (ip[0] >> 4) != 4 or ihl < 5 or len(ip) < ihl * 4:
            continue
        frag_off = int.from_bytes(ip[6:8], "big")
        out["proto"][i] = ip[9]
        out["saddr"][i] = int.from_bytes(ip[12:16], "big")
        out["daddr"][i] = int.from_bytes(ip[16:20], "big")
        if frag_off & 0x3FFF:
            out["is_fragment"][i] = 1
        elif ip[9] in (6, 17) and len(ip) >= ihl * 4 + 4:
            l4 = ip[ihl * 4 :]
            out["sport"][i] = int.from_bytes(l4[0:2], "big")
            out["dport"][i] = int.from_bytes(l4[2:4], "big")
        out["valid"][i] = 1
    return out
