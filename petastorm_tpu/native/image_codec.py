"""ctypes bindings for the batched PNG/JPEG decoder (image_codec.cpp).

One native call decodes a whole column's worth of encoded image cells with the
GIL released, replacing the reference's per-image Python+OpenCV loop
(reference codecs.py:92-111) — the measured input-pipeline bottleneck on the
image path. Availability is probed like the other native targets: any
build/load failure makes :func:`is_available` False and
``CompressedImageCodec`` stays on its per-image OpenCV path.

Threading: ``PSTPU_IMG_THREADS`` is the per-PROCESS native decode thread
budget (default: CPU count), shared cooperatively across concurrent calls
(:func:`_thread_grant`): a lone caller (dummy pool, a script) fans its
column out across all idle cores. A worker pool caps its own threads' share
(:func:`set_thread_share`: a thread pool gives each worker
``budget // width``), so its concurrent calls split the budget instead of
each bursting over it. Pass ``threads=N`` explicitly to bypass the
accounting.
"""

from __future__ import annotations

import contextlib
import ctypes
import logging
import os
import threading

import numpy as np

logger = logging.getLogger(__name__)

_lib = None
_lib_lock = threading.Lock()
_load_failed = False


class NativeDecodeError(RuntimeError):
    """Native probe/decode refused the payload; callers fall back to OpenCV."""

    def __init__(self, message, index=None):
        super().__init__(message)
        self.index = index


def _load_library():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            from petastorm_tpu.native.build import build_img
            lib = ctypes.CDLL(build_img(quiet=True))
        except Exception as e:  # noqa: BLE001 - fall back to the OpenCV path
            logger.info('native image codec unavailable (%s); using OpenCV per-image decode', e)
            _load_failed = True
            return None
        lib.pstpu_img_last_error.restype = ctypes.c_char_p
        lib.pstpu_img_probe_batch2.restype = ctypes.c_int64
        lib.pstpu_img_probe_batch2.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32]
        lib.pstpu_img_decode_batch2.restype = ctypes.c_int64
        lib.pstpu_img_decode_batch2.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.c_int32, ctypes.c_int32]
        lib.pstpu_img_decode_resize_batch.restype = ctypes.c_int64
        lib.pstpu_img_decode_resize_batch.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
        lib.pstpu_img_resize_area.restype = ctypes.c_int64
        lib.pstpu_img_resize_area.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32]
        lib.pstpu_img_resize_bilinear.restype = ctypes.c_int64
        lib.pstpu_img_resize_bilinear.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32]
        _lib = lib
        return _lib


def is_available():
    return _load_library() is not None


def batch_fn_addrs():
    """Raw C addresses of the batched probe/decode entry points, for the fused
    row-group kernel (``pstpu_read_fused``) to call THROUGH — image decode then
    happens inside the same native transition as the page scan and value
    decode, with no link-time coupling between the two libraries. Returns
    ``(probe_addr, decode_addr)`` or None when the codec is unavailable."""
    lib = _load_library()
    if lib is None:
        return None
    return (ctypes.cast(lib.pstpu_img_probe_batch2, ctypes.c_void_p).value,
            ctypes.cast(lib.pstpu_img_decode_batch2, ctypes.c_void_p).value)


def _default_threads():
    """The per-PROCESS native decode thread budget (``PSTPU_IMG_THREADS``).
    Not a per-call fan-out: concurrent callers share it through
    :func:`_thread_grant`.

    Unset: CPU count in a top-level process; 1 in a multiprocessing CHILD not
    configured by our own pool bootstrap (torch DataLoader workers, user
    process fan-outs) — sibling processes cannot see each other's grants, so
    each claiming the full budget would oversubscribe cores by the sibling
    count. Set-but-unparseable degrades to 1 (the safe floor), never to the
    full budget."""
    raw = os.environ.get('PSTPU_IMG_THREADS')
    if raw is not None:
        try:
            return max(1, int(raw))
        except ValueError:
            return 1
    import multiprocessing
    if multiprocessing.parent_process() is not None:
        return 1
    return max(1, os.cpu_count() or 1)


_budget_lock = threading.Lock()
_threads_in_use = 0

#: per-thread cap on the default fan-out (:func:`set_thread_share`); unset on
#: threads whose owner set none
_thread_share = threading.local()


def set_thread_share(threads):
    """Cap the default fan-out of native decodes made on the CALLING thread
    at ``threads`` (``None`` lifts the cap). Set by the worker pool that owns
    the thread, which knows how many such threads decode at once."""
    _thread_share.threads = threads


@contextlib.contextmanager
def _thread_grant(requested):
    """Cooperative intra-call fan-out: ``requested=None`` (the default) takes
    whatever share of the process-wide budget is currently free (floor 1, so
    callers always proceed) and returns it afterwards — a lone caller decoding
    a column fans out across all idle cores. A thread whose pool set a share
    (:func:`set_thread_share`) takes at most that share: the pool already runs
    that many calls at once, and a fan-out over the whole free budget inside
    each would burst up to budget threads at times unrelated to the rest of
    the process (the consumer's step loop and prefetch thread among them).
    The floor means N concurrent callers can transiently hold budget + (N - 1)
    threads (first caller takes the free budget, later ones still get 1) —
    bounded by the pool width and accepted so callers never block on the
    grant. An explicit integer bypasses the accounting (the caller's exact
    contract)."""
    if requested is not None:
        yield max(1, int(requested))
        return
    global _threads_in_use
    budget = _default_threads()
    share = getattr(_thread_share, 'threads', None)
    with _budget_lock:
        grant = max(1, budget - _threads_in_use)
        if share is not None:
            grant = min(grant, share)
        _threads_in_use += grant
    try:
        yield grant
    finally:
        with _budget_lock:
            _threads_in_use -= grant


def decode_images(buffers, threads=None, min_size=None):
    """Decode a list of encoded PNG/JPEG cells (bytes/memoryview) in one native
    call. Returns a list of numpy arrays — ``(H, W)`` for grayscale, ``(H, W, 3)``
    RGB otherwise; dtype uint8, or uint16 for 16-bit PNG.

    ``min_size=(min_h, min_w)`` enables scaled JPEG decode: each JPEG comes out
    at the smallest libjpeg m/8 DCT scale whose dims still cover the minimum
    (full size if the image is already smaller) — most pixels of a large photo
    are never computed, which is the cheapest possible "resize". PNGs ignore
    the hint (the format has no scaled decode).

    Raises :class:`NativeDecodeError` when any cell is an unsupported flavor
    (palette/alpha PNG, CMYK JPEG, corrupt data, non-image bytes) — the caller
    falls back to its per-image path.
    """
    lib = _load_library()
    if lib is None:
        raise NativeDecodeError('native image codec not available')
    n = len(buffers)
    if n == 0:
        return []
    min_h, min_w = (int(min_size[0]), int(min_size[1])) if min_size else (0, 0)
    # numpy views give stable base addresses for arbitrary (read-only) buffers
    views = [np.frombuffer(b, dtype=np.uint8) for b in buffers]
    ptrs = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
    lens = (ctypes.c_uint64 * n)(*[v.size for v in views])
    infos = np.empty((n, 4), dtype=np.int32)
    infos_p = infos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

    rc = lib.pstpu_img_probe_batch2(n, ptrs, lens, infos_p, min_w, min_h)
    if rc != -1:
        raise NativeDecodeError('unsupported or corrupt image at index {}'.format(rc), index=rc)

    outs = []
    out_ptrs = (ctypes.c_void_p * n)()
    for i in range(n):
        w, h, c, depth = (int(x) for x in infos[i])
        dtype = np.uint16 if depth == 16 else np.uint8
        shape = (h, w) if c == 1 else (h, w, c)
        arr = np.empty(shape, dtype=dtype)
        outs.append(arr)
        out_ptrs[i] = arr.ctypes.data

    with _thread_grant(threads) as fanout:
        rc = lib.pstpu_img_decode_batch2(n, ptrs, lens, out_ptrs, infos_p, fanout,
                                         min_w, min_h)
    if rc != -1:
        raise NativeDecodeError('image decode failed at index {}: {}'.format(
            rc, lib.pstpu_img_last_error().decode(errors='replace')), index=rc)
    return outs


def decode_images_auto(buffers, threads=None, min_size=None):
    """Decode a column of image cells with ONE header probe, into the best
    output layout the column admits:

      * every cell probes to the same dims/depth (the normal case for a
        prepared training store) -> ONE ``[N, H, W(, C)]`` array; the
        per-image out pointers simply walk the rows of a single allocation,
        so the per-image allocations and the column-stack copy that would
        follow them disappear;
      * mixed dims -> a list of per-image arrays (same outputs as
        :func:`decode_images`) WITHOUT re-probing the headers.

    Raises :class:`NativeDecodeError` like :func:`decode_images` for
    unsupported cells."""
    lib = _load_library()
    if lib is None:
        raise NativeDecodeError('native image codec not available')
    n = len(buffers)
    if n == 0:
        return []
    min_h, min_w = (int(min_size[0]), int(min_size[1])) if min_size else (0, 0)
    views = [np.frombuffer(b, dtype=np.uint8) for b in buffers]
    ptrs = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
    lens = (ctypes.c_uint64 * n)(*[v.size for v in views])
    infos = np.empty((n, 4), dtype=np.int32)
    infos_p = infos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

    rc = lib.pstpu_img_probe_batch2(n, ptrs, lens, infos_p, min_w, min_h)
    if rc != -1:
        raise NativeDecodeError('unsupported or corrupt image at index {}'.format(rc), index=rc)

    uniform = n == 1 or not (infos != infos[0]).any()
    if uniform:
        w, h, c, depth = (int(x) for x in infos[0])
        dtype = np.uint16 if depth == 16 else np.uint8
        shape = (n, h, w) if c == 1 else (n, h, w, c)
        result = np.empty(shape, dtype=dtype)
        stride = result.strides[0]
        base = result.ctypes.data
        out_ptrs = (ctypes.c_void_p * n)(*[base + i * stride for i in range(n)])
    else:
        result = []
        out_ptrs = (ctypes.c_void_p * n)()
        for i in range(n):
            w, h, c, depth = (int(x) for x in infos[i])
            dtype = np.uint16 if depth == 16 else np.uint8
            arr = np.empty((h, w) if c == 1 else (h, w, c), dtype=dtype)
            result.append(arr)
            out_ptrs[i] = arr.ctypes.data
    with _thread_grant(threads) as fanout:
        rc = lib.pstpu_img_decode_batch2(n, ptrs, lens, out_ptrs, infos_p, fanout,
                                         min_w, min_h)
    if rc != -1:
        raise NativeDecodeError('image decode failed at index {}: {}'.format(
            rc, lib.pstpu_img_last_error().decode(errors='replace')), index=rc)
    return result


def decode_images_block(buffers, threads=None, min_size=None):
    """:func:`decode_images_auto` restricted to the single-block layout:
    returns the ``[N, H, W(, C)]`` array, or ``None`` when dims differ."""
    result = decode_images_auto(buffers, threads=threads, min_size=min_size)
    return result if isinstance(result, np.ndarray) else None


def _resize_native(img, size, symbol_name):
    lib = _load_library()
    if lib is None:
        raise NativeDecodeError('native image codec not available')
    if img.dtype != np.uint8:
        raise ValueError('native resize supports uint8, got {}'.format(img.dtype))
    out_h, out_w = int(size[0]), int(size[1])
    c = img.shape[2] if img.ndim == 3 else 1
    src = np.ascontiguousarray(img)
    out = np.empty((out_h, out_w) + ((c,) if img.ndim == 3 else ()), np.uint8)
    rc = getattr(lib, symbol_name)(src.ctypes.data, img.shape[1], img.shape[0], c,
                                   out.ctypes.data, out_w, out_h)
    if rc != 0:
        raise NativeDecodeError('native resize failed: {}'.format(
            lib.pstpu_img_last_error().decode(errors='replace')))
    return out


def resize_area_image(img, size):
    """Area-resample one decoded uint8 image to ``size=(out_h, out_w)`` with
    the native resampler — the cv2 ``INTER_AREA`` stand-in for OpenCV-less
    deployments (within 1 LSB of cv2 when both axes downscale or both
    upscale; cv2's mixed down+up INTER_AREA is a non-separable special case
    this separable implementation does not chase). Returns a new array;
    raises :class:`NativeDecodeError` when the native library is
    unavailable."""
    return _resize_native(img, size, 'pstpu_img_resize_area')


def resize_bilinear_image(img, size):
    """Bilinear-resample one decoded uint8 image (half-pixel centers, cv2
    ``INTER_LINEAR`` semantics) — the mild-ratio half of the shared resize
    policy (see ``codecs._resize_image``)."""
    return _resize_native(img, size, 'pstpu_img_resize_bilinear')


def decode_images_resized(buffers, size, threads=None, min_size=None):
    """Fused decode + resize of a whole column into ONE
    ``[N, out_h, out_w(, C)]`` allocation. ``size`` is ``(out_h, out_w)``.
    Each image decodes at its probed dims (JPEG: at the smallest m/8 DCT scale
    covering the target, so most pixels of a large photo never exist) and is
    then resampled per the shared policy — bilinear below 2x decimation, area
    at >= 2x (see ``codecs._resize_image``) — into its output row: one
    GIL-released native call replaces a per-row Python resize transform.

    ``min_size=(min_h, min_w)`` overrides the DCT-scale floor (an explicit
    ``image_decode_hints`` entry wins over the resize target — e.g. decode at
    >= 2x the target for a supersampled downscale); default is the target
    itself.

    Returns ``None`` when the column mixes channel counts or carries 16-bit
    images (callers fall back to their per-image path); raises
    :class:`NativeDecodeError` for unsupported/corrupt cells."""
    lib = _load_library()
    if lib is None:
        raise NativeDecodeError('native image codec not available')
    n = len(buffers)
    if n == 0:
        return None
    out_h, out_w = int(size[0]), int(size[1])
    if out_h < 1 or out_w < 1:
        raise ValueError('resize target must be positive, got {}'.format(size))
    min_h, min_w = (int(min_size[0]), int(min_size[1])) if min_size else (out_h, out_w)
    views = [np.frombuffer(b, dtype=np.uint8) for b in buffers]
    ptrs = (ctypes.c_void_p * n)(*[v.ctypes.data for v in views])
    lens = (ctypes.c_uint64 * n)(*[v.size for v in views])
    infos = np.empty((n, 4), dtype=np.int32)
    infos_p = infos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

    rc = lib.pstpu_img_probe_batch2(n, ptrs, lens, infos_p, min_w, min_h)
    if rc != -1:
        raise NativeDecodeError('unsupported or corrupt image at index {}'.format(rc), index=rc)
    if (infos[:, 3] != 8).any() or (infos[:, 2] != infos[0, 2]).any():
        return None  # 16-bit or mixed gray/RGB column: per-image path
    c = int(infos[0, 2])
    shape = (n, out_h, out_w) if c == 1 else (n, out_h, out_w, c)
    out = np.empty(shape, dtype=np.uint8)
    stride = out.strides[0]
    base = out.ctypes.data
    out_ptrs = (ctypes.c_void_p * n)(*[base + i * stride for i in range(n)])
    with _thread_grant(threads) as fanout:
        rc = lib.pstpu_img_decode_resize_batch(n, ptrs, lens, out_ptrs, infos_p,
                                               fanout, min_w, min_h, out_w, out_h)
    if rc != -1:
        raise NativeDecodeError('image decode+resize failed at index {}: {}'.format(
            rc, lib.pstpu_img_last_error().decode(errors='replace')), index=rc)
    return out
