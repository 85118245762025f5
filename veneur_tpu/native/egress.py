"""ctypes bindings for the C++ egress library (veneur_egress.cpp).

The flush-egress twin of the ingest bindings in ``__init__.py``:

- ``dd_series_stream`` / ``dd_series_bodies`` — columnar flush block →
  Datadog ``/api/v1/series`` JSON bodies, deflated in C++ (the
  vectorized finalize+serialize of ``sinks/datadog/datadog.go:245-330``),
  handed over as they are made or as a list.
- ``decode_metric_list`` / ``MListInternTable`` — forwardrpc.MetricList
  bytes → struct-of-arrays batch + series interning (the import-side
  equivalent of ``parse_lines`` + ``InternTable``; reference path
  ``importsrv/server.go:101-132``).
- ``encode_digest_metrics`` — columnar digest planes → serialized
  MetricList chunks for the gRPC forward path (``flusher.go:424-473``).

``available()`` gates everything; callers fall back to the pure-Python
paths when no compiler is present.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

log = logging.getLogger("veneur.native.egress")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "veneur_egress.cpp")
_SO = os.path.join(_HERE, "libveneur_egress.so")

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None

MISS = 0xFFFFFFFF

# VtMetricBatch payload kinds
PAYLOAD_NONE = 0
PAYLOAD_COUNTER = 1
PAYLOAD_GAUGE = 2
PAYLOAD_HISTOGRAM = 3
PAYLOAD_SET = 4


class _VtBodies(ctypes.Structure):
    # ptr as void* — c_char_p would convert to bytes truncated at the
    # first NUL, and deflate bodies contain NULs
    _fields_ = [
        ("count", ctypes.c_uint32),
        ("ptr", ctypes.POINTER(ctypes.c_void_p)),
        ("len", ctypes.POINTER(ctypes.c_uint64)),
        ("impl", ctypes.c_void_p),
    ]


class _VtMetricBatch(ctypes.Structure):
    _fields_ = [
        ("count", ctypes.c_uint32),
        ("arena_len", ctypes.c_uint64),
        ("ncent", ctypes.c_uint64),
        ("topk_off", ctypes.c_uint64),
        ("topk_len", ctypes.c_uint64),
        ("type", ctypes.POINTER(ctypes.c_uint8)),
        ("payload", ctypes.POINTER(ctypes.c_uint8)),
        ("name_off", ctypes.POINTER(ctypes.c_uint32)),
        ("name_len", ctypes.POINTER(ctypes.c_uint32)),
        ("tags_off", ctypes.POINTER(ctypes.c_uint32)),
        ("tags_len", ctypes.POINTER(ctypes.c_uint32)),
        ("ivalue", ctypes.POINTER(ctypes.c_int64)),
        ("dvalue", ctypes.POINTER(ctypes.c_double)),
        ("compression", ctypes.POINTER(ctypes.c_double)),
        ("dmin", ctypes.POINTER(ctypes.c_double)),
        ("dmax", ctypes.POINTER(ctypes.c_double)),
        ("cent_off", ctypes.POINTER(ctypes.c_uint64)),
        ("cent_len", ctypes.POINTER(ctypes.c_uint32)),
        ("hll_off", ctypes.POINTER(ctypes.c_uint64)),
        ("hll_len", ctypes.POINTER(ctypes.c_uint64)),
        ("arena", ctypes.POINTER(ctypes.c_char)),
        ("means", ctypes.POINTER(ctypes.c_double)),
        ("weights", ctypes.POINTER(ctypes.c_double)),
        ("impl", ctypes.c_void_p),
    ]


def _build() -> Optional[str]:
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", _SO, _SRC, "-lz"],
            check=True, capture_output=True, timeout=120)
        return None
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.TimeoutExpired:
        return "native egress build timed out"
    except subprocess.CalledProcessError as e:
        return f"native egress build failed: {e.stderr.decode(errors='replace')}"


def _load():
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        if not os.path.exists(_SO) or (
                os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            err = _build()
            if err is not None:
                _build_error = err
                log.warning("native egress unavailable: %s", err)
                return None
        try:
            lib = _bind(ctypes.CDLL(_SO))
        except OSError as e:
            log.warning("native egress load failed (%s); rebuilding", e)
            err = _build()
            lib = None
            if err is None:
                try:
                    lib = _bind(ctypes.CDLL(_SO))
                except OSError as e2:
                    err = f"rebuilt library still unloadable: {e2}"
            if err is not None:
                _build_error = err
                log.warning("native egress unavailable: %s", err)
                return None
        _lib = lib
        return _lib


def _bind(lib):
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    f64p = ctypes.POINTER(ctypes.c_double)

    lib.vt_dd_stream_begin.restype = ctypes.c_void_p
    lib.vt_dd_stream_begin.argtypes = [
        ctypes.c_char_p, u32p, u32p,            # names
        ctypes.c_char_p, u32p, u32p,            # tags
        ctypes.c_uint32,                        # nrows
        ctypes.c_char_p, u32p, u32p, ctypes.c_uint32,  # suffixes
        u32p, u8p, f64p, u8p, ctypes.c_uint64,  # emissions
        ctypes.c_int64, ctypes.c_int32,         # timestamp, interval
        ctypes.c_char_p, ctypes.c_char_p,       # host, common tags json
        ctypes.c_uint32, ctypes.c_int,          # max_per_body, level
        ctypes.c_uint32,                        # workers
    ]
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.vt_dd_stream_next.restype = ctypes.c_int
    lib.vt_dd_stream_next.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64,       # handle, body index
        ctypes.POINTER(ctypes.c_void_p), u64p, u64p,  # ptr, len, ready ns
    ]
    lib.vt_dd_stream_end.argtypes = [ctypes.c_void_p, u64p]  # timing_ns[6]
    lib.vt_dd_stream_live.argtypes = [u64p]
    lib.vt_bodies_free.argtypes = [ctypes.POINTER(_VtBodies)]

    lib.vt_sfx_datapoints_json.restype = ctypes.POINTER(_VtBodies)
    lib.vt_sfx_datapoints_json.argtypes = [
        ctypes.c_char_p, u32p, u32p,            # names
        ctypes.c_char_p, u32p, u32p,            # tags
        ctypes.c_uint32,                        # nrows
        ctypes.c_char_p, u32p, u32p, ctypes.c_uint32,  # suffixes
        u32p, u8p, f64p, u8p, ctypes.c_uint64,  # emissions
        ctypes.c_int64,                         # timestamp ms
        ctypes.c_char_p, ctypes.c_char_p,       # hostname tag, hostname
        ctypes.c_char_p,                        # common dims json
        ctypes.c_char_p, u32p, u32p, ctypes.c_uint32,  # common keys
        ctypes.c_char_p, u32p, u32p, ctypes.c_uint32,  # excluded keys
    ]

    lib.vt_tsv_rows.restype = ctypes.POINTER(_VtBodies)
    lib.vt_tsv_rows.argtypes = [
        ctypes.c_char_p, u32p, u32p,            # names
        ctypes.c_char_p, u32p, u32p,            # tags
        ctypes.c_uint32,                        # nrows
        ctypes.c_char_p, u32p, u32p, ctypes.c_uint32,  # suffixes
        u32p, u8p, f64p, u8p, ctypes.c_uint64,  # emissions
        ctypes.c_char_p, ctypes.c_char_p,       # hostname, interval str
        ctypes.c_char_p, ctypes.c_char_p,       # timestamp, partition
    ]

    lib.vt_mlist_decode.restype = ctypes.POINTER(_VtMetricBatch)
    lib.vt_mlist_decode.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.vt_mbatch_free.argtypes = [ctypes.POINTER(_VtMetricBatch)]

    lib.vt_mintern_new.restype = ctypes.c_void_p
    lib.vt_mintern_free.argtypes = [ctypes.c_void_p]
    lib.vt_mintern_reset.argtypes = [ctypes.c_void_p]
    lib.vt_mintern_put.argtypes = [
        ctypes.c_void_p, ctypes.c_uint8, ctypes.c_uint8, ctypes.c_char_p,
        ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32]
    lib.vt_mintern_assign.restype = ctypes.c_uint32
    lib.vt_mintern_assign.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_VtMetricBatch), u32p, u32p]

    lib.vt_mlist_encode_digests.restype = ctypes.POINTER(_VtBodies)
    lib.vt_mlist_encode_digests.argtypes = [
        ctypes.c_char_p, u32p, u32p,            # names
        ctypes.c_char_p, u32p, u32p,            # tags
        f32p, f32p, ctypes.c_uint32,            # means, weights, K
        f32p, f32p,                             # dmins, dmaxs
        ctypes.c_uint32, ctypes.c_uint8,        # nrows, pb type
        ctypes.c_double, ctypes.c_uint64, ctypes.c_int,
    ]

    u16p = ctypes.POINTER(ctypes.c_uint16)
    lib.vt_mlist_encode_digests_packed.restype = ctypes.POINTER(_VtBodies)
    lib.vt_mlist_encode_digests_packed.argtypes = [
        ctypes.c_char_p, u32p, u32p,            # names
        ctypes.c_char_p, u32p, u32p,            # tags
        u16p, u16p, u16p,                       # counts, means_q, weights_bf
        f32p, f32p,                             # dmins, dmaxs
        ctypes.c_uint32, ctypes.c_uint8,        # nrows, pb type
        ctypes.c_double, ctypes.c_uint64, ctypes.c_int,
    ]
    return lib


def available() -> bool:
    return _load() is not None


def _take_bodies(lib, bp) -> List[bytes]:
    try:
        b = bp.contents
        return [ctypes.string_at(b.ptr[i], b.len[i])
                for i in range(b.count)]
    finally:
        lib.vt_bodies_free(bp)


def _u32a(a: np.ndarray) -> np.ndarray:
    """Contiguous u32 copy the CALLER must keep referenced across the C
    call (data_as on a temporary would dangle)."""
    return np.ascontiguousarray(a, np.uint32)


def _p(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------------------
# Datadog series JSON
# ---------------------------------------------------------------------------


def dd_workers(n_bodies: int) -> int:
    """Native threads one block's ``dd_series_stream`` spreads its bodies
    over: one a body, at most 8, and half the cores this process may run
    on, so a flush that runs under ingest leaves the readers and the
    merger theirs (the thread that takes the bodies, and POSTs them, is
    one more)."""
    return min(n_bodies, 8, max(1, len(os.sched_getaffinity(0)) // 2))


class DDSeriesStream:
    """The bodies of one columnar emission block as the native workers
    make them: an iterator of ``(body, ready_ns)``, body ``k`` as soon
    as it is made (``ready_ns``: when, on ``time.monotonic_ns``'s
    clock), while the workers go on with ``k+1..``. Each wait is a
    native call that releases the GIL. ``close()`` (the ``with``
    block's end, or the iterator's) stops the workers after the bodies
    in hand, joins them, frees the handle and adds to ``timing``; it
    runs on every way out, a consumer's exception included. The inputs
    stay referenced here until then: the workers read them."""

    def __init__(self, lib, handle, count: int, inputs: tuple,
                 timing: Optional[dict]):
        self._lib = lib
        self._handle = handle
        self._inputs = inputs
        self._timing = timing
        self._k = 0
        self.count = count

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[bytes, int]:
        if self._k >= self.count:
            self.close()
            raise StopIteration
        ptr, n, ready = ctypes.c_void_p(), ctypes.c_uint64(), \
            ctypes.c_uint64()
        self._lib.vt_dd_stream_next(self._handle, self._k,
                                    ctypes.byref(ptr), ctypes.byref(n),
                                    ctypes.byref(ready))
        self._k += 1
        return ctypes.string_at(ptr.value, n.value), ready.value

    def close(self) -> None:
        if self._handle is None:
            return
        spent = (ctypes.c_uint64 * 6)()
        handle, self._handle = self._handle, None
        self._lib.vt_dd_stream_end(handle, spent)
        self._inputs = None
        timing = self._timing
        if timing is not None:
            total, deflate = int(spent[0]), int(spent[1])
            for key, ns in (("deflate_ns", deflate),
                            ("encode_ns", total - deflate),
                            ("encode_cpu_ns", int(spent[2])),
                            ("deflate_cpu_ns", int(spent[3])),
                            ("bodies", int(spent[4]))):
                timing[key] = timing.get(key, 0) + ns
            timing["workers"] = max(timing.get("workers", 0),
                                    int(spent[5]))

    def __enter__(self) -> "DDSeriesStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        self.close()


def dd_series_stream(names: Tuple[bytes, np.ndarray, np.ndarray],
                     tags: Tuple[bytes, np.ndarray, np.ndarray],
                     suffixes: List[bytes],
                     em_rows: np.ndarray, em_suffix: np.ndarray,
                     em_values: np.ndarray, em_type: np.ndarray,
                     timestamp: int, interval: int, default_host: str,
                     common_tags_json: bytes = b"",
                     max_per_body: int = 0,
                     compress_level: int = 1,
                     timing: Optional[dict] = None,
                     workers: Optional[int] = None) -> DDSeriesStream:
    """Serialize one columnar emission block into chunked (optionally
    deflated) ``{"series": [...]}`` bodies, handed over in order as
    they are made (``DDSeriesStream``).

    names/tags: (arena bytes, offsets u32[S], lengths u32[S]).
    emissions: parallel arrays — row index u32, suffix index u8 (into
    ``suffixes``), finalized value f64 (counters already divided by the
    interval), type code u8 (0 gauge / 1 rate).

    The per-row pre-pass runs in this call, on the calling thread; the
    bodies are then encoded and deflated side by side by ``workers``
    native threads (``dd_workers`` of the body count where not given),
    byte for byte the one-worker bodies.

    ``timing``, where given, gains at ``close()`` a split of the
    serializer's wall, from this call to the last body made:
    ``deflate_ns`` (inside zlib's ``deflate``, clocked a slab at a time,
    on the worker that made the last body) and ``encode_ns`` (the rest:
    the JSON encoding); the same two summed over the workers' spans,
    ``deflate_cpu_ns`` and ``encode_cpu_ns``; ``bodies``; each added to
    what the key already holds. ``workers`` keeps the most a block ran.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    if len(suffixes) > 255:
        raise ValueError("more than 255 emission suffixes")
    suffix_blob, s_off, s_len, _ = _key_list(suffixes)
    em_rows = _u32a(em_rows)
    em_suffix = np.ascontiguousarray(em_suffix, np.uint8)
    em_values = np.ascontiguousarray(em_values, np.float64)
    em_type = np.ascontiguousarray(em_type, np.uint8)
    n = len(em_rows)
    if not (len(em_suffix) == len(em_values) == len(em_type) == n):
        raise ValueError("emission arrays differ in length")
    name_arena, name_off, name_len = names
    tags_arena, tags_off, tags_len = tags
    name_off, name_len = _u32a(name_off), _u32a(name_len)
    tags_off, tags_len = _u32a(tags_off), _u32a(tags_len)
    u32, u8, f64 = ctypes.c_uint32, ctypes.c_uint8, ctypes.c_double
    count = -(-n // max_per_body) if max_per_body else min(n, 1)
    if workers is None:
        workers = dd_workers(count)
    handle = lib.vt_dd_stream_begin(
        name_arena, _p(name_off, u32), _p(name_len, u32),
        tags_arena, _p(tags_off, u32), _p(tags_len, u32),
        len(name_off),
        suffix_blob, _p(s_off, u32), _p(s_len, u32), len(suffixes),
        _p(em_rows, u32), _p(em_suffix, u8), _p(em_values, f64),
        _p(em_type, u8),
        n, timestamp, interval, default_host.encode("utf-8"),
        common_tags_json, max_per_body, compress_level, workers)
    return DDSeriesStream(
        lib, handle, count,
        (name_arena, name_off, name_len, suffix_blob, s_off, s_len,
         em_rows, em_suffix, em_values, em_type), timing)


def dd_series_bodies(*args, **kw) -> List[bytes]:
    """``dd_series_stream``'s bodies drained into a list, in order;
    the same arguments, the same ``timing``."""
    with dd_series_stream(*args, **kw) as bodies:
        return [body for body, _ready_ns in bodies]


def dd_stream_live() -> Tuple[int, int]:
    """(serializer handles begun and not closed, native worker threads
    not joined) in this process."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    out = (ctypes.c_uint64 * 2)()
    lib.vt_dd_stream_live(out)
    return int(out[0]), int(out[1])


def _key_list(keys: List[bytes]):
    """(blob, off-array, len-array, count) for a small key set."""
    blob = b"".join(keys)
    n = max(len(keys), 1)
    offs = np.zeros(n, np.uint32)
    lens = np.zeros(n, np.uint32)
    pos = 0
    for i, k in enumerate(keys):
        offs[i] = pos
        lens[i] = len(k)
        pos += len(k)
    return blob, offs, lens, len(keys)


def sfx_datapoint_bodies(names: Tuple[bytes, np.ndarray, np.ndarray],
                         tags: Tuple[bytes, np.ndarray, np.ndarray],
                         suffixes: List[bytes],
                         em_rows: np.ndarray, em_suffix: np.ndarray,
                         em_values: np.ndarray, em_type: np.ndarray,
                         timestamp_ms: int, hostname_tag: str,
                         hostname: str,
                         common_dims_json: bytes = b"",
                         common_keys: Optional[List[bytes]] = None,
                         excluded_keys: Optional[List[bytes]] = None
                         ) -> List[bytes]:
    """Serialize one columnar emission block into a SignalFx
    ``/v2/datapoint`` body (``{"gauge": [...], "counter": [...]}``,
    uncompressed). Dimension semantics mirror SignalFxSink._dimensions;
    common_dims_json is the pre-escaped ``"k":"v",...`` fragment whose
    keys are listed in common_keys (tag dims with those keys are
    dropped — common dimensions override)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    if len(suffixes) > 255:
        raise ValueError("more than 255 emission suffixes")
    suffix_blob, s_off, s_len, _ = _key_list(suffixes)
    em_rows = _u32a(em_rows)
    em_suffix = np.ascontiguousarray(em_suffix, np.uint8)
    em_values = np.ascontiguousarray(em_values, np.float64)
    em_type = np.ascontiguousarray(em_type, np.uint8)
    n = len(em_rows)
    assert len(em_suffix) == n and len(em_values) == n and len(em_type) == n
    name_arena, name_off, name_len = names
    tags_arena, tags_off, tags_len = tags
    name_off, name_len = _u32a(name_off), _u32a(name_len)
    tags_off, tags_len = _u32a(tags_off), _u32a(tags_len)
    ck_blob, ck_off, ck_len, ck_n = _key_list(common_keys or [])
    ex_blob, ex_off, ex_len, ex_n = _key_list(excluded_keys or [])
    u32, u8, f64 = ctypes.c_uint32, ctypes.c_uint8, ctypes.c_double
    bp = lib.vt_sfx_datapoints_json(
        name_arena, _p(name_off, u32), _p(name_len, u32),
        tags_arena, _p(tags_off, u32), _p(tags_len, u32),
        len(name_off),
        suffix_blob, _p(s_off, u32), _p(s_len, u32), len(suffixes),
        _p(em_rows, u32), _p(em_suffix, u8), _p(em_values, f64),
        _p(em_type, u8), n, timestamp_ms,
        hostname_tag.encode("utf-8"), hostname.encode("utf-8"),
        common_dims_json,
        ck_blob, _p(ck_off, u32), _p(ck_len, u32), ck_n,
        ex_blob, _p(ex_off, u32), _p(ex_len, u32), ex_n)
    return _take_bodies(lib, bp)


def tsv_rows(names: Tuple[bytes, np.ndarray, np.ndarray],
             tags: Tuple[bytes, np.ndarray, np.ndarray],
             suffixes: List[bytes],
             em_rows: np.ndarray, em_suffix: np.ndarray,
             em_values: np.ndarray, em_type: np.ndarray,
             hostname: str, interval: int, timestamp_str: str,
             partition_str: str) -> bytes:
    """Serialize one columnar emission block into the archival TSV rows
    the s3/localfile plugins write (plugins/csv_encode.py column order;
    reference csv.go:17-92). Counter values must arrive already divided
    by the interval (em_type picks the rate/gauge column only)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    if len(suffixes) > 255:
        raise ValueError("more than 255 emission suffixes")
    suffix_blob, s_off, s_len, _ = _key_list(suffixes)
    em_rows = _u32a(em_rows)
    em_suffix = np.ascontiguousarray(em_suffix, np.uint8)
    em_values = np.ascontiguousarray(em_values, np.float64)
    em_type = np.ascontiguousarray(em_type, np.uint8)
    n = len(em_rows)
    assert len(em_suffix) == n and len(em_values) == n and len(em_type) == n
    name_arena, name_off, name_len = names
    tags_arena, tags_off, tags_len = tags
    name_off, name_len = _u32a(name_off), _u32a(name_len)
    tags_off, tags_len = _u32a(tags_off), _u32a(tags_len)
    u32, u8, f64 = ctypes.c_uint32, ctypes.c_uint8, ctypes.c_double
    bp = lib.vt_tsv_rows(
        name_arena, _p(name_off, u32), _p(name_len, u32),
        tags_arena, _p(tags_off, u32), _p(tags_len, u32),
        len(name_off),
        suffix_blob, _p(s_off, u32), _p(s_len, u32), len(suffixes),
        _p(em_rows, u32), _p(em_suffix, u8), _p(em_values, f64),
        _p(em_type, u8), n,
        hostname.encode("utf-8"), str(int(interval)).encode(),
        timestamp_str.encode(), partition_str.encode())
    (body,) = _take_bodies(lib, bp)
    return body


# ---------------------------------------------------------------------------
# MetricList decode + interning
# ---------------------------------------------------------------------------


class DecodedMetricList:
    """numpy views over a decoded MetricList. Arrays are COPIES by
    default; ``copy=False`` returns zero-copy VIEWS into the C++ batch —
    the import hot path uses it (saves a ~10 MB memcpy per 20k-digest
    message) but the views die with :meth:`close`. hll spans index into
    the ORIGINAL request bytes (keep them alive)."""

    __slots__ = ("count", "type", "payload", "name_off", "name_len",
                 "tags_off", "tags_len", "ivalue", "dvalue", "compression",
                 "dmin", "dmax", "cent_off", "cent_len", "hll_off",
                 "hll_len", "arena", "means", "weights", "topk_off",
                 "topk_len", "_ptr", "_lib")

    def __init__(self, lib, ptr, copy: bool = True):
        self._lib = lib
        self._ptr = ptr
        b = ptr.contents
        n = b.count
        self.topk_off = b.topk_off
        self.topk_len = b.topk_len

        def arr(p, dtype, count=n):
            if count == 0:
                return np.empty(0, dtype)
            return np.ctypeslib.as_array(p, shape=(count,)).astype(
                dtype, copy=copy)

        self.count = n
        self.type = arr(b.type, np.uint8)
        self.payload = arr(b.payload, np.uint8)
        self.name_off = arr(b.name_off, np.uint32)
        self.name_len = arr(b.name_len, np.uint32)
        self.tags_off = arr(b.tags_off, np.uint32)
        self.tags_len = arr(b.tags_len, np.uint32)
        self.ivalue = arr(b.ivalue, np.int64)
        self.dvalue = arr(b.dvalue, np.float64)
        self.compression = arr(b.compression, np.float64)
        self.dmin = arr(b.dmin, np.float64)
        self.dmax = arr(b.dmax, np.float64)
        self.cent_off = arr(b.cent_off, np.uint64)
        self.cent_len = arr(b.cent_len, np.uint32)
        self.hll_off = arr(b.hll_off, np.uint64)
        self.hll_len = arr(b.hll_len, np.uint64)
        self.arena = ctypes.string_at(b.arena, b.arena_len) \
            if b.arena_len else b""
        self.means = arr(b.means, np.float64, b.ncent)
        self.weights = arr(b.weights, np.float64, b.ncent)

    def name(self, i: int) -> str:
        o, l = self.name_off[i], self.name_len[i]
        return self.arena[o:o + l].decode("utf-8", "replace")

    def joined_tags(self, i: int) -> str:
        o, l = self.tags_off[i], self.tags_len[i]
        return self.arena[o:o + l].decode("utf-8", "replace")

    def raw_view(self) -> "_VtMetricBatch":
        """A struct borrowing this batch's numpy arrays for C calls
        (vt_mintern_assign). Keep self alive across the call."""
        b = _VtMetricBatch()
        b.count = self.count
        b.arena_len = len(self.arena)
        u8, u32 = ctypes.c_uint8, ctypes.c_uint32
        b.type = self.type.ctypes.data_as(ctypes.POINTER(u8))
        b.payload = self.payload.ctypes.data_as(ctypes.POINTER(u8))
        b.name_off = self.name_off.ctypes.data_as(ctypes.POINTER(u32))
        b.name_len = self.name_len.ctypes.data_as(ctypes.POINTER(u32))
        b.tags_off = self.tags_off.ctypes.data_as(ctypes.POINTER(u32))
        b.tags_len = self.tags_len.ctypes.data_as(ctypes.POINTER(u32))
        b.arena = ctypes.cast(ctypes.c_char_p(self.arena),
                              ctypes.POINTER(ctypes.c_char))
        return b

    def close(self):
        if self._ptr:
            self._lib.vt_mbatch_free(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def decode_metric_list(data: bytes, copy: bool = True) -> DecodedMetricList:
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    ptr = lib.vt_mlist_decode(data, len(data))
    return DecodedMetricList(lib, ptr, copy=copy)


class MListInternTable:
    """(metricpb type, payload kind, name, joined tags) -> store row,
    memoized in C++. Misses come back for Python to resolve and teach
    with put(). The payload kind is part of the key because row indices
    are only meaningful within one group and the applying group is chosen
    by the value-oneof: a repeated (type, name, tags) with a different
    oneof must MISS, not reuse a foreign group's row (ADVICE round-3)."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native egress unavailable: {_build_error}")
        self._lib = lib
        self._handle = lib.vt_mintern_new()

    def assign(self, batch: DecodedMetricList):
        n = batch.count
        rows = np.empty(n, np.uint32)
        miss = np.empty(n, np.uint32)
        view = batch.raw_view()
        nmiss = self._lib.vt_mintern_assign(
            self._handle, ctypes.byref(view),
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            miss.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
        return rows, miss[:nmiss]

    def put(self, pb_type: int, payload: int, name: bytes, tags: bytes,
            row: int):
        self._lib.vt_mintern_put(self._handle, pb_type, payload, name,
                                 len(name), tags, len(tags), row)

    def reset(self):
        self._lib.vt_mintern_reset(self._handle)

    def close(self):
        if self._handle:
            self._lib.vt_mintern_free(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# MetricList encode
# ---------------------------------------------------------------------------


def encode_digest_metrics(names: Tuple[bytes, np.ndarray, np.ndarray],
                          tags: Tuple[bytes, np.ndarray, np.ndarray],
                          means: np.ndarray, weights: np.ndarray,
                          dmins: np.ndarray, dmaxs: np.ndarray,
                          pb_type: int, compression: float = 100.0,
                          max_body_bytes: int = 0,
                          reference_compat: bool = False) -> List[bytes]:
    """Columnar digest planes → serialized MetricList chunks.

    means/weights: [S, K] float32 (weight <= 0 marks padding); each
    returned chunk is a complete MetricList serialization.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    means = np.ascontiguousarray(means, np.float32)
    weights = np.ascontiguousarray(weights, np.float32)
    dmins = np.ascontiguousarray(dmins, np.float32)
    dmaxs = np.ascontiguousarray(dmaxs, np.float32)
    nrows, k = means.shape
    assert weights.shape == (nrows, k)
    name_arena, name_off, name_len = names
    tags_arena, tags_off, tags_len = tags
    name_off, name_len = _u32a(name_off), _u32a(name_len)
    tags_off, tags_len = _u32a(tags_off), _u32a(tags_len)
    u32, f32 = ctypes.c_uint32, ctypes.c_float
    bp = lib.vt_mlist_encode_digests(
        name_arena, _p(name_off, u32), _p(name_len, u32),
        tags_arena, _p(tags_off, u32), _p(tags_len, u32),
        _p(means, f32), _p(weights, f32), k,
        _p(dmins, f32), _p(dmaxs, f32),
        nrows, pb_type, compression, max_body_bytes,
        1 if reference_compat else 0)
    return _take_bodies(lib, bp)


def encode_digest_metrics_packed(names: Tuple[bytes, np.ndarray, np.ndarray],
                                 tags: Tuple[bytes, np.ndarray, np.ndarray],
                                 planes, pb_type: int,
                                 compression: float = 100.0,
                                 max_body_bytes: int = 0,
                                 reference_compat: bool = False
                                 ) -> List[bytes]:
    """Device-compacted digest planes (core.store.PackedDigestPlanes) →
    serialized MetricList chunks. Non-compat chunks carry the quantized
    u16 arrays verbatim (tdigest fields 16/17, 4 bytes/centroid);
    reference_compat dequantizes in C++ and emits the reference's
    repeated-Centroid layout."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native egress unavailable: {_build_error}")
    counts = np.ascontiguousarray(planes.counts, np.uint16)
    means_q = np.ascontiguousarray(planes.means_q, np.uint16)
    weights_bf = np.ascontiguousarray(planes.weights_bf, np.uint16)
    dmins = np.ascontiguousarray(planes.dmin, np.float32)
    dmaxs = np.ascontiguousarray(planes.dmax, np.float32)
    nrows = len(counts)
    total = int(counts.astype(np.int64).sum())
    if not (total == len(means_q) == len(weights_bf)):
        # wire-boundary invariant: the C++ walker advances by counts and
        # would read out of bounds (must survive python -O)
        raise ValueError(
            f"packed planes inconsistent: sum(counts)={total}, "
            f"means={len(means_q)}, weights={len(weights_bf)}")
    name_arena, name_off, name_len = names
    tags_arena, tags_off, tags_len = tags
    name_off, name_len = _u32a(name_off), _u32a(name_len)
    tags_off, tags_len = _u32a(tags_off), _u32a(tags_len)
    u16, u32, f32 = ctypes.c_uint16, ctypes.c_uint32, ctypes.c_float
    bp = lib.vt_mlist_encode_digests_packed(
        name_arena, _p(name_off, u32), _p(name_len, u32),
        tags_arena, _p(tags_off, u32), _p(tags_len, u32),
        _p(counts, u16), _p(means_q, u16), _p(weights_bf, u16),
        _p(dmins, f32), _p(dmaxs, f32),
        nrows, pb_type, compression, max_body_bytes,
        1 if reference_compat else 0)
    return _take_bodies(lib, bp)
