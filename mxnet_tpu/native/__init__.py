"""Native C++ runtime components, bound via ctypes.

The reference implements its data pipeline (RecordIO reader, image
normalization) in C++ (`src/io/`); this package provides the TPU
framework's native equivalents. The shared library is never committed:
it builds on first use from ``mxtpu_io.cc`` with the system toolchain
(g++ -O3) and is cached, untracked, alongside the source; every entry
point has a pure-Python fallback so the framework works without a
compiler.

API:
  recordio_scan(path) -> (offsets, lengths)   # index a .rec without .idx
  recordio_read(path, offsets, lengths) -> list[bytes]
  normalize_batch(u8_hwc, mean, std) -> f32 chw
  decode_jpeg_batch / decode_augment_batch  # OMP decode(+augment) loops
  available() -> bool, status() -> dict      # why the native path is off
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys

import numpy as _np

__all__ = ["available", "status", "recordio_scan", "recordio_read",
           "normalize_batch", "recordio_pack", "decode_jpeg_batch",
           "decode_augment_batch"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "mxtpu_io.cc")
_LIB_PATH = os.path.join(_HERE, "libmxtpu_io.so")
_lib = None
_tried = False
_error = None  # why the probe failed (cached; surfaced ONCE, see _load)


def _compile_to(out):
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-fopenmp",
           _SRC, "-o", out, "-ljpeg"]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
    except subprocess.CalledProcessError:
        # hosts without libjpeg/OpenMP: build without the decode path
        # (decode_jpeg_batch falls back to Python; the rest still works)
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
               "-DMXTPU_NO_JPEG", _SRC, "-o", out]
        subprocess.run(cmd, check=True, capture_output=True)


def _build():
    # tmp + rename: several processes (fleet workers, test children) may
    # reach first use at once, and none may load a library another is
    # still writing
    from ..checkpoint import atomic_write

    atomic_write(_LIB_PATH, _compile_to)


def _record_failure(exc):
    """Cache WHY the native path is off and surface it exactly once —
    a warning + telemetry counter instead of the old silent per-call
    degradation (every later call sees the cached probe result;
    tools/diagnose.py's "Data Plane" report prints the reason)."""
    global _error
    if isinstance(exc, subprocess.CalledProcessError):
        stderr = (exc.stderr or b"").decode(errors="replace").strip()
        _error = f"build failed (rc {exc.returncode}): {stderr[-400:]}"
    else:
        _error = f"{type(exc).__name__}: {exc}"
    try:
        from .. import log as _log

        _log.get_logger("mxnet_tpu.native").warning(
            "native IO library unavailable (%s); RecordIO/decode fall "
            "back to pure Python — see tools/diagnose.py 'Data Plane'",
            _error)
    except Exception:
        pass
    try:
        from ..telemetry import registry as _registry

        _registry.counter(
            "mxtpu_native_unavailable_total",
            "Native IO library probe/build failures (Python fallback "
            "active)").inc()
    except Exception:
        pass


def _load():
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        if not os.path.exists(_LIB_PATH) or \
                os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC):
            _build()
        lib = ctypes.CDLL(_LIB_PATH)
        lib.mxtpu_recordio_scan.restype = ctypes.c_longlong
        lib.mxtpu_recordio_scan.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_longlong]
        lib.mxtpu_recordio_read.restype = ctypes.c_int
        lib.mxtpu_recordio_read.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_uint8)]
        lib.mxtpu_normalize_hwc_u8_to_chw_f32.restype = None
        lib.mxtpu_recordio_pack.restype = ctypes.c_longlong
        if hasattr(lib, "mxtpu_decode_jpeg_batch"):
            lib.mxtpu_decode_jpeg_batch.restype = ctypes.c_longlong
            lib.mxtpu_decode_jpeg_batch.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_longlong,
                ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
        if hasattr(lib, "mxtpu_decode_augment_batch"):
            lib.mxtpu_decode_augment_batch.restype = ctypes.c_longlong
            lib.mxtpu_decode_augment_batch.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_longlong,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
        _lib = lib
    except Exception as e:
        _lib = None
        _record_failure(e)
    return _lib


def available():
    """True when the native library is built and loadable."""
    return _load() is not None


def status():
    """The data-plane probe result, for tools/diagnose.py and tests:
    availability of the lib and of each optional capability, plus the
    cached failure reason when the native path is off."""
    lib = _load()
    return {
        "available": lib is not None,
        "lib_path": _LIB_PATH,
        "built": os.path.exists(_LIB_PATH),
        "jpeg": bool(lib is not None
                     and hasattr(lib, "mxtpu_decode_jpeg_batch")),
        "augment": bool(lib is not None
                        and hasattr(lib, "mxtpu_decode_augment_batch")),
        "error": _error,
    }


def recordio_scan(path):
    """Index a .rec file: returns (offsets, lengths) numpy arrays of each
    record's payload. Native scan when available, else a Python walk."""
    lib = _load()
    if lib is not None:
        cap = 1024
        while True:
            offs = _np.zeros(cap, _np.uint64)
            lens = _np.zeros(cap, _np.uint64)
            n = lib.mxtpu_recordio_scan(
                path.encode(), offs.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint64)),
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                cap)
            if n >= 0:
                return offs[:n].copy(), lens[:n].copy()
            if n == -1:
                break  # IO/framing error: fall back to Python
            cap = -int(n) * 2
    return _py_scan(path)


def _py_scan(path):
    import struct

    offsets, lengths = [], []
    with open(path, "rb") as f:
        while True:
            pos = f.tell()
            head = f.read(8)
            if len(head) < 8:
                break
            magic, lrec = struct.unpack("<II", head)
            if magic != 0xCED7230A:
                raise ValueError(f"bad RecordIO magic at {pos}")
            if lrec >> 29:
                raise ValueError(
                    "multi-part RecordIO records (cflag != 0) are not "
                    "supported by the scanner; use the sequential reader")
            length = lrec & ((1 << 29) - 1)
            offsets.append(pos + 8)
            lengths.append(length)
            f.seek((length + 3) // 4 * 4, os.SEEK_CUR)
    return (_np.asarray(offsets, _np.uint64),
            _np.asarray(lengths, _np.uint64))


def recordio_read(path, offsets, lengths):
    """Read the payloads for (offsets, lengths); returns list[bytes]."""
    offsets = _np.ascontiguousarray(offsets, _np.uint64)
    lengths = _np.ascontiguousarray(lengths, _np.uint64)
    lib = _load()
    total = int(lengths.sum())
    if lib is not None:
        buf = _np.zeros(total, _np.uint8)
        rc = lib.mxtpu_recordio_read(
            path.encode(),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(offsets),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if rc == 0:
            out, p = [], 0
            for ln in lengths:
                out.append(buf[p:p + int(ln)].tobytes())
                p += int(ln)
            return out
    out = []
    with open(path, "rb") as f:
        for off, ln in zip(offsets, lengths):
            f.seek(int(off))
            out.append(f.read(int(ln)))
    return out


def normalize_batch(images_u8_hwc, mean=None, std=None, scale=1.0):
    """(N, H, W, C) uint8 -> (N, C, H, W) float32 with channel mean/std
    (the ImageRecordIter inner loop, native when available)."""
    images_u8_hwc = _np.ascontiguousarray(images_u8_hwc, _np.uint8)
    n, h, w, c = images_u8_hwc.shape
    lib = _load()
    if lib is not None:
        out = _np.empty((n, c, h, w), _np.float32)
        mean_arr = (_np.ascontiguousarray(mean, _np.float32)
                    if mean is not None else None)
        std_inv = (1.0 / _np.ascontiguousarray(std, _np.float32)
                   if std is not None else None)
        fptr = ctypes.POINTER(ctypes.c_float)
        lib.mxtpu_normalize_hwc_u8_to_chw_f32(
            images_u8_hwc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(fptr),
            ctypes.c_longlong(n), ctypes.c_longlong(h),
            ctypes.c_longlong(w), ctypes.c_longlong(c),
            mean_arr.ctypes.data_as(fptr) if mean_arr is not None
            else None,
            std_inv.ctypes.data_as(fptr) if std_inv is not None else None,
            ctypes.c_float(scale))
        return out
    out = images_u8_hwc.astype(_np.float32) * scale
    if mean is not None:
        out = out - _np.asarray(mean, _np.float32)
    if std is not None:
        out = out / _np.asarray(std, _np.float32)
    return out.transpose(0, 3, 1, 2).copy()


def recordio_pack(payloads):
    """Frame a list of payload bytes into RecordIO wire format; returns
    one bytes object (native single pass when available)."""
    lengths = _np.asarray([len(p) for p in payloads], _np.uint64)
    lib = _load()
    if lib is not None:
        src = _np.frombuffer(b"".join(payloads), _np.uint8)
        total = int(sum(8 + (int(l) + 3) // 4 * 4 for l in lengths))
        dst = _np.zeros(total, _np.uint8)
        n = lib.mxtpu_recordio_pack(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(payloads),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        return dst[:n].tobytes()
    import struct

    out = bytearray()
    for p in payloads:
        out += struct.pack("<II", 0xCED7230A, len(p))
        out += p
        out += b"\x00" * ((len(p) + 3) // 4 * 4 - len(p))
    return bytes(out)


def _blob_offsets(bufs):
    """Concatenate payloads + per-record (offsets, lengths) for the OMP
    decode entry points."""
    n = len(bufs)
    offsets = _np.zeros(n, _np.uint64)
    lengths = _np.zeros(n, _np.uint64)
    pos = 0
    for i, b in enumerate(bufs):
        offsets[i] = pos
        lengths[i] = len(b)
        pos += len(b)
    blob = _np.frombuffer(b"".join(bufs), _np.uint8)
    return blob, offsets, lengths


def decode_augment_batch(bufs, dh, dw, oh, ow, crop_y=None, crop_x=None,
                         mirror=None, jitter=None, n_threads=0):
    """Fused decode + augmentation (the streaming-data-plane hot path):
    decode each JPEG to (dh, dw), crop to (oh, ow) at per-image
    (crop_y[i], crop_x[i]), mirror where mirror[i], scale channels by
    jitter[i] — one pass per worker thread, producing training-ready
    HWC rows with no intermediate Python copy (parity: the augmenter
    chain inside iter_image_recordio_2.cc's OMP ParseChunk loop).
    Returns (batch, failed_idx) like :func:`decode_jpeg_batch`, or None
    when the native path is unavailable (caller falls back to the
    bit-compatible Python augmenter)."""
    lib = _load()
    if lib is None or not hasattr(lib, "mxtpu_decode_augment_batch"):
        return None
    n = len(bufs)
    blob, offsets, lengths = _blob_offsets(bufs)
    out = _np.empty((n, oh, ow, 3), _np.uint8)
    failed = _np.full(n, -1, _np.int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    cy = (_np.ascontiguousarray(crop_y, _np.int32)
          if crop_y is not None else None)
    cx = (_np.ascontiguousarray(crop_x, _np.int32)
          if crop_x is not None else None)
    mir = (_np.ascontiguousarray(mirror, _np.uint8)
           if mirror is not None else None)
    jit = (_np.ascontiguousarray(jitter, _np.float32)
           if jitter is not None else None)
    lib.mxtpu_decode_augment_batch(
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, dh, dw, oh, ow,
        cy.ctypes.data_as(i32p) if cy is not None else None,
        cx.ctypes.data_as(i32p) if cx is not None else None,
        mir.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        if mir is not None else None,
        jit.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        if jit is not None else None,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        int(n_threads))
    bad = [int(i) for i in failed if i >= 0]
    return out, bad


def decode_jpeg_batch(bufs, out_h, out_w, n_threads=0):
    """Decode a list of JPEG byte strings into an (N, out_h, out_w, 3)
    uint8 HWC array, resized bilinearly, OMP-parallel in C++ (parity:
    iter_image_recordio_2.cc ParseChunk). `n_threads` bounds the OMP
    team (0 = OMP default). Returns (batch, failed_idx list); None when
    the native decode path is unavailable (caller falls back to PIL)."""
    lib = _load()
    if lib is None or not hasattr(lib, "mxtpu_decode_jpeg_batch"):
        return None
    n = len(bufs)
    blob_arr, offsets, lengths = _blob_offsets(bufs)
    out = _np.empty((n, out_h, out_w, 3), _np.uint8)
    failed = _np.full(n, -1, _np.int64)
    lib.mxtpu_decode_jpeg_batch(
        blob_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, out_h, out_w,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        int(n_threads))
    bad = [int(i) for i in failed if i >= 0]
    return out, bad
