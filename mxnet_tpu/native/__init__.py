"""ctypes bindings for the native C++ I/O layer (``mxtpu_io.cc``).

The reference implements its data pipeline in C++ (recordio readers +
``ImageRecordIter`` OMP decode workers, ``src/io/iter_image_recordio_2.cc``);
this package is the TPU build's native equivalent.  pybind11 is not in the
image, so the library exposes a C ABI and we bind it with ctypes.

The shared library is compiled from ``mxtpu_io.cc`` on first use and cached
next to this file (git-ignored: a checkout always builds its own).  A host
with no ``g++`` uses the pure-Python readers; a host that HAS the compiler
and fails to build or load the library raises — a broken native layer must
not pass for a slow one.
"""
from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading

import numpy as onp

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "mxtpu_io.cc")
_SO = os.path.join(_DIR, "libmxtpu_io.so")

_lock = threading.Lock()
_lib = None
_tried = False

__all__ = ["lib", "available", "NativeRecordFile", "NativeImagePipeline"]


def _build():
    # per-process temp name: test workers that all find the library missing
    # build side by side, and each must replace it with a whole file
    tmp = os.path.join(_DIR, "libmxtpu_io.%d.tmp.so" % os.getpid())
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC,
           "-o", tmp, "-ljpeg", "-lpthread"]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _SO)


def lib():
    """Load (building if needed) the native library; None on a host with
    no C++ compiler.  A failed build or load raises."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            if shutil.which("g++") is None:
                return None
            try:
                _build()
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    "building %s failed:\n%s"
                    % (_SRC, e.stderr.decode(errors="replace"))) from e
        L = ctypes.CDLL(_SO)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        L.mxtpu_rec_open.restype = ctypes.c_void_p
        L.mxtpu_rec_open.argtypes = [ctypes.c_char_p]
        L.mxtpu_rec_close.argtypes = [ctypes.c_void_p]
        L.mxtpu_rec_at.restype = ctypes.c_int
        L.mxtpu_rec_at.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.POINTER(u8p),
                                   ctypes.POINTER(ctypes.c_uint64)]
        L.mxtpu_rec_scan.restype = ctypes.c_int64
        L.mxtpu_rec_scan.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_uint64),
                                     ctypes.c_int64]
        L.mxtpu_jpeg_decode.restype = ctypes.c_int64
        L.mxtpu_jpeg_decode.argtypes = [u8p, ctypes.c_uint64, u8p,
                                        ctypes.c_int64,
                                        ctypes.POINTER(ctypes.c_int),
                                        ctypes.POINTER(ctypes.c_int)]
        L.mxtpu_pipeline_create.restype = ctypes.c_void_p
        L.mxtpu_pipeline_create.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int]
        L.mxtpu_pipeline_next.restype = ctypes.c_int
        L.mxtpu_pipeline_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int)]
        L.mxtpu_pipeline_next_u8.restype = ctypes.c_int
        L.mxtpu_pipeline_next_u8.argtypes = [
            ctypes.c_void_p, u8p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int)]
        L.mxtpu_pipeline_borrow.restype = ctypes.c_int
        L.mxtpu_pipeline_borrow.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_int)]
        L.mxtpu_pipeline_release.argtypes = [ctypes.c_void_p,
                                             ctypes.c_void_p]
        L.mxtpu_pipeline_reset.argtypes = [ctypes.c_void_p]
        L.mxtpu_pipeline_nbatches.restype = ctypes.c_int
        L.mxtpu_pipeline_nbatches.argtypes = [ctypes.c_void_p]
        L.mxtpu_pipeline_destroy.argtypes = [ctypes.c_void_p]
        _lib = L
        return _lib


def available():
    return lib() is not None


class NativeRecordFile:
    """mmap-backed RecordIO reader (zero-copy record views)."""

    def __init__(self, path):
        L = lib()
        if L is None:
            raise RuntimeError("native library unavailable")
        self._lib = L
        self._h = L.mxtpu_rec_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def close(self):
        if self._h:
            self._lib.mxtpu_rec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def read_at(self, offset):
        """Record payload bytes at a byte offset (copies out of the mmap)."""
        data = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_uint64()
        if not self._lib.mxtpu_rec_at(self._h, int(offset),
                                      ctypes.byref(data), ctypes.byref(n)):
            raise IOError("bad record at offset %d" % offset)
        return ctypes.string_at(data, n.value)

    def scan(self):
        """All record offsets in file order (uint64 array)."""
        cap = 1 << 16
        while True:
            buf = onp.empty(cap, onp.uint64)
            n = self._lib.mxtpu_rec_scan(
                self._h, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                cap)
            if n < 0:
                raise IOError("corrupt recordio framing")
            if n <= cap:
                return buf[:n].copy()
            cap = int(n)


def jpeg_decode(buf):
    """Decode JPEG bytes → RGB u8 HWC array, or None if not decodable."""
    L = lib()
    if L is None:
        return None
    arr = onp.frombuffer(buf, onp.uint8)
    cap = 1 << 22
    h, w = ctypes.c_int(), ctypes.c_int()
    for _ in range(2):
        out = onp.empty(cap, onp.uint8)
        r = L.mxtpu_jpeg_decode(
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr.size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
            ctypes.byref(h), ctypes.byref(w))
        if r == 1:
            return out[:h.value * w.value * 3].reshape(h.value, w.value, 3)
        if r == 0:
            return None
        cap = -int(r)
    return None


class NativeImagePipeline:
    """Threaded decode+augment pipeline over a .rec file.

    Delivers (data NCHW float32, labels, pad, errors) batches in order;
    decode of batch N+1 overlaps Python/device work on batch N — the role
    the reference's prefetcher + OMP decoders play
    (``src/io/iter_image_recordio_2.cc``).
    """

    def __init__(self, rec_path, offsets, batch_size, data_shape,
                 label_width=1, resize=0, rand_crop=False, rand_mirror=False,
                 mean=None, std=None, shuffle=False, seed=0,
                 preprocess_threads=4, prefetch_buffer=3, u8_output=False):
        L = lib()
        if L is None:
            raise RuntimeError("native library unavailable")
        c, h, w = data_shape
        assert c == 3, "native pipeline is RGB-only"
        self._lib = L
        self.batch_size = batch_size
        self.data_shape = data_shape
        self.label_width = label_width
        self.u8_output = bool(u8_output)
        self._depth = max(2, int(prefetch_buffer))  # ring slots (C++ min 2)
        # kept for the consumer's on-device normalize in u8 mode
        self.mean = onp.asarray(
            mean if mean is not None else [0, 0, 0], onp.float32)
        self.std = onp.asarray(
            std if std is not None else [1, 1, 1], onp.float32)
        offs = onp.ascontiguousarray(offsets, onp.uint64)
        mean_a = onp.ascontiguousarray(self.mean, onp.float32)
        std_a = onp.ascontiguousarray(self.std, onp.float32)
        fp = ctypes.POINTER(ctypes.c_float)
        self._h = L.mxtpu_pipeline_create(
            rec_path.encode(),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(offs),
            batch_size, h, w, label_width, int(resize), int(bool(rand_crop)),
            int(bool(rand_mirror)), mean_a.ctypes.data_as(fp),
            std_a.ctypes.data_as(fp), int(bool(shuffle)), int(seed),
            int(preprocess_threads), int(prefetch_buffer),
            int(self.u8_output))
        if not self._h:
            raise RuntimeError("pipeline creation failed for %s" % rec_path)

    @property
    def num_batches(self):
        return self._lib.mxtpu_pipeline_nbatches(self._h)

    def next(self):
        """Next batch, or None when the epoch is exhausted.  Data is
        normalized float32 NCHW, or raw uint8 NCHW in ``u8_output`` mode
        (4x less host->device wire traffic; apply (x - mean) / std
        on-device)."""
        c, h, w = self.data_shape
        labels = onp.empty((self.batch_size, self.label_width), onp.float32)
        errs = ctypes.c_int()
        fp = ctypes.POINTER(ctypes.c_float)
        if self.u8_output:
            data = onp.empty((self.batch_size, c, h, w), onp.uint8)
            pad = self._lib.mxtpu_pipeline_next_u8(
                self._h, data.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint8)),
                labels.ctypes.data_as(fp), ctypes.byref(errs))
        else:
            data = onp.empty((self.batch_size, c, h, w), onp.float32)
            pad = self._lib.mxtpu_pipeline_next(
                self._h, data.ctypes.data_as(fp), labels.ctypes.data_as(fp),
                ctypes.byref(errs))
        if pad == -1:
            return None
        if pad < 0:
            raise RuntimeError("native pipeline failed")
        return data, labels, pad, errs.value

    def next_borrow(self):
        """Zero-copy variant of :meth:`next`: lend the next in-order
        batch's ring-slot buffers instead of copying them out.

        Returns ``(data, labels, pad, errors, token)`` where ``data`` /
        ``labels`` are numpy VIEWS of the slot (uint8 NCHW in
        ``u8_output`` mode, float32 otherwise; labels float32), valid
        only until :meth:`release`\\ (token) — release invalidates them
        and returns the slot to the decode workers.  Up to
        ``prefetch_buffer`` loans may be outstanding; each one shrinks
        the ring the workers can fill, so a consumer holding K batches
        in flight should size ``prefetch_buffer > K``.  Returns ``None``
        when the epoch is exhausted."""
        c, h, w = self.data_shape
        token = ctypes.c_void_p()
        dptr = ctypes.c_void_p()
        lptr = ctypes.POINTER(ctypes.c_float)()
        errs = ctypes.c_int()
        pad = self._lib.mxtpu_pipeline_borrow(
            self._h, ctypes.byref(token), ctypes.byref(dptr),
            ctypes.byref(lptr), ctypes.byref(errs))
        if pad == -1:
            return None
        if pad == -3:
            raise RuntimeError(
                "all %d ring slots are borrowed — release one first or "
                "create the pipeline with a larger prefetch_buffer"
                % self._depth)
        if pad < 0:
            raise RuntimeError("native pipeline failed")
        shape = (self.batch_size, c, h, w)
        if self.u8_output:
            data = onp.ctypeslib.as_array(
                ctypes.cast(dptr, ctypes.POINTER(ctypes.c_uint8)), shape)
        else:
            data = onp.ctypeslib.as_array(
                ctypes.cast(dptr, ctypes.POINTER(ctypes.c_float)), shape)
        labels = onp.ctypeslib.as_array(
            lptr, (self.batch_size, self.label_width))
        return data, labels, pad, errs.value, token

    def release(self, token):
        """Return a :meth:`next_borrow` slot to the ring (views die)."""
        self._lib.mxtpu_pipeline_release(self._h, token)

    def reset(self):
        self._lib.mxtpu_pipeline_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_pipeline_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
