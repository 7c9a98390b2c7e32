"""Binary flow captures: zero-copy ingest of fixed-size records.

Reference: the datapath's perf-ring events are fixed-size C structs
(``bpf/lib/events.h`` — PolicyVerdictNotify et al.) consumed by
``pkg/monitor`` (SURVEY.md §2.5, §2.7 "perf/ring buffer"). Ours mirrors
that split: L3/L4 flow tuples ride a packed 32-byte little-endian
record (written/validated by the native codec,
``native/capture/capture.cpp`` → ``libcilium_capture.so``), and the
Python side maps them STRAIGHT into a numpy structured array — no
per-record parsing between disk and the engine's ``encode_flows``.

Version 2 adds an L7 SIDECAR (the accesslog-path analog, columnar):
a shared string table (u32 offsets + one blob, string 0 = "") plus a
fixed 32-byte L7 record per flow referencing it, carrying
path/method/host/headers/qname/kafka fields. Strings are normalized at
WRITE time (host lowercased, qname sanitized, headers canonically
serialized) so replay featurizes with pure numpy gathers — zero
per-flow Python (``engine.verdict.encode_l7_records``). Version 3
adds a GENERIC section so ``l7proto`` records ride the binary
file→verdict path too (VERDICT r3 item 3): per flow, the proto name
and up to fmax (key, value) field pairs as indices into the SAME
string table; a capture with no generic flows stays byte-identical
v2.

The native library is built on demand (``make -C native/capture``,
same discipline as the proxylib shim); if the toolchain is missing, a
pure-numpy fallback reads/writes the identical format — the reference
likewise pairs its C event layout with a Go reader.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from cilium_tpu.core.flow import (
    Flow,
    L7Type,
    Protocol,
    TrafficDirection,
    Verdict,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(REPO, "native", "capture")
LIB_PATH = os.path.join(NATIVE_DIR, "libcilium_capture.so")

MAGIC = b"CTCAP1\x00\x00"
VERSION = 1
VERSION_L7 = 2
#: version 3 = v2 + a GENERIC section after the L7 records: one fixed
#: record per flow carrying the ``l7proto`` name and up to fmax
#: (key, value) field pairs as string-table indices (VERDICT r3 item
#: 3 — generic traffic rides the binary file→verdict path too). fmax
#: lives in the L7Header's reserved word; a capture with no generic
#: flows still writes byte-identical v2.
VERSION_L7G = 3
HEADER = np.dtype([("magic", "S8"), ("version", "<u4"),
                   ("count", "<u4")])
L7HEADER = np.dtype([("n_strings", "<u4"), ("reserved", "<u4"),
                     ("blob_bytes", "<u8")])


def gen_dtype(fmax: int) -> np.dtype:
    """Per-flow generic record: l7proto string index + fmax (key,
    value) string-index pairs (index 0 = "" = unused slot)."""
    return np.dtype([("proto", "<u4"), ("pairs", "<u4", (fmax, 2))])

#: numpy view of the C Record struct (keep in lockstep with
#: native/capture/capture.cpp)
RECORD = np.dtype([
    ("src_identity", "<u4"), ("dst_identity", "<u4"),
    ("dport", "<u2"), ("sport", "<u2"),
    ("proto", "u1"), ("direction", "u1"), ("l7_type", "u1"),
    ("verdict", "u1"),
    ("time", "<f8"),
    ("reserved0", "<u4"), ("reserved1", "<u4"),
])
assert RECORD.itemsize == 32

#: numpy view of the C L7Record struct (v2 sidecar; keep in lockstep
#: with native/capture/capture.cpp). Fields are indices into the
#: capture's shared string table; index 0 is always the empty string.
L7REC = np.dtype([
    ("path", "<u4"), ("method", "<u4"), ("host", "<u4"),
    ("headers", "<u4"), ("qname", "<u4"),
    ("kafka_client", "<u4"), ("kafka_topic", "<u4"),
    ("kafka_api_key", "<i2"), ("kafka_api_version", "<i2"),
])
assert L7REC.itemsize == 32

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False
#: True once this process rebuilt the native codec from its sources
native_built_this_run = False


def _sources_digest() -> str:
    """sha256 over the codec's sources — what the built library is
    keyed on (mtimes lie after a copy or a checkout)."""
    h = hashlib.sha256()
    for n in ("capture.cpp", "Makefile"):
        with open(os.path.join(NATIVE_DIR, n), "rb") as f:
            h.update(n.encode() + b"\0" + f.read())
    return h.hexdigest()


def _native() -> Optional[ctypes.CDLL]:
    """The native codec, built on demand; None if unbuildable."""
    global _lib, _lib_tried, native_built_this_run
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        # rebuild when missing OR built from other sources (a stale
        # pre-v3 library would reject version-3 files the Python
        # writer just produced): the library's stamp file holds the
        # digest of the sources it was built from
        stamp = LIB_PATH + ".sha256"
        # the file lock keeps concurrent processes (test workers) from
        # loading a library another one is halfway through writing
        try:
            lock = open(LIB_PATH + ".lock", "w")
        except OSError:
            return None
        with lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                with open(stamp) as f:
                    stale = (f.read().strip() != _sources_digest()
                             or not os.path.exists(LIB_PATH))
            except OSError:
                stale = True
            if stale:
                try:
                    subprocess.run(["make", "-B", "-C", NATIVE_DIR],
                                   check=True, capture_output=True)
                    with open(stamp, "w") as f:
                        f.write(_sources_digest())
                    native_built_this_run = True
                except (OSError, subprocess.CalledProcessError):
                    return None
        try:
            lib = ctypes.CDLL(LIB_PATH)
        except OSError:
            return None
        lib.ct_capture_record_size.restype = ctypes.c_int
        if lib.ct_capture_record_size() != RECORD.itemsize:
            return None  # layout drift: refuse rather than corrupt
        if not hasattr(lib, "ct_capture_write_l7g"):
            return None  # pre-v3 ABI: fall back to the numpy codec
        lib.ct_capture_write.restype = ctypes.c_int
        lib.ct_capture_write.argtypes = [ctypes.c_char_p,
                                         ctypes.c_void_p,
                                         ctypes.c_uint32]
        lib.ct_capture_count.restype = ctypes.c_int
        lib.ct_capture_count.argtypes = [ctypes.c_char_p]
        lib.ct_capture_read.restype = ctypes.c_int
        lib.ct_capture_read.argtypes = [ctypes.c_char_p,
                                        ctypes.c_void_p,
                                        ctypes.c_uint32,
                                        ctypes.c_uint32]
        lib.ct_capture_write_l7.restype = ctypes.c_int
        lib.ct_capture_write_l7.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_uint64]
        # the v3 writer was the ONE symbol bound without argtypes —
        # its calls hand-wrapped every scalar and nothing checked the
        # pointer marshaling (ctlint abi-surface); declared here with
        # the rest of the surface
        lib.ct_capture_write_l7g.restype = ctypes.c_int
        lib.ct_capture_write_l7g.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint32]
        if not hasattr(lib, "ct_capture_writer_open"):
            return None  # pre-batch-writer ABI: numpy codec instead
        # streaming columnar record-batch writer (ingest/columnar.py):
        # base records stream to disk per batch, trailing sections
        # buffer natively, finish() lays down the string table
        lib.ct_capture_writer_open.restype = ctypes.c_void_p
        lib.ct_capture_writer_open.argtypes = [ctypes.c_char_p,
                                               ctypes.c_uint32]
        lib.ct_capture_writer_batch.restype = ctypes.c_int
        lib.ct_capture_writer_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_uint32]
        lib.ct_capture_writer_finish.restype = ctypes.c_int
        lib.ct_capture_writer_finish.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_uint64]
        lib.ct_capture_writer_abort.restype = ctypes.c_int
        lib.ct_capture_writer_abort.argtypes = [ctypes.c_void_p]
        lib.ct_capture_l7_info.restype = ctypes.c_int
        lib.ct_capture_l7_info.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.ct_capture_read_l7.restype = ctypes.c_int
        lib.ct_capture_read_l7.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_void_p]
        _lib = lib
        return _lib


class CaptureError(ValueError):
    pass


_ERRORS = {-1: "io error", -2: "bad magic", -3: "unsupported version",
           -4: "truncated capture"}


def _check(rc: int) -> int:
    if rc < 0:
        raise CaptureError(_ERRORS.get(rc, f"error {rc}"))
    return rc


# -- record array ↔ Flow ----------------------------------------------------

def flows_to_records(flows: Iterable[Flow]) -> np.ndarray:
    flows = list(flows)
    rec = np.zeros(len(flows), dtype=RECORD)
    for i, f in enumerate(flows):
        # l7_type is recorded as NONE: the record carries no payload,
        # and a NONE-payload HTTP/Kafka flow would re-verdict
        # DIFFERENTLY than its source (empty path vs the real one) —
        # a converted capture must replay as the L3/L4 tuple it is
        rec[i] = (f.src_identity, f.dst_identity, f.dport, f.sport,
                  int(f.protocol), int(f.direction), int(L7Type.NONE),
                  int(f.verdict), f.time, 0, 0)
    return rec


def records_to_flows(rec: np.ndarray) -> List[Flow]:
    return [
        Flow(src_identity=int(r["src_identity"]),
             dst_identity=int(r["dst_identity"]),
             dport=int(r["dport"]), sport=int(r["sport"]),
             protocol=Protocol(int(r["proto"])),
             direction=TrafficDirection(int(r["direction"])),
             l7=L7Type(int(r["l7_type"])),
             verdict=Verdict(int(r["verdict"])),
             time=float(r["time"]))
        for r in rec
    ]


# -- file IO ---------------------------------------------------------------

def write_capture_records(path: str, rec: np.ndarray) -> int:
    """Write a v1 capture straight from a RECORD array (the columnar
    tooling path — no Flow objects)."""
    lib = _native()
    if lib is not None:
        buf = np.ascontiguousarray(rec)
        _check(lib.ct_capture_write(
            path.encode(), buf.ctypes.data_as(ctypes.c_void_p),
            len(buf)))
        return len(buf)
    header = np.zeros(1, dtype=HEADER)
    header[0] = (MAGIC, VERSION, len(rec))
    with open(path, "wb") as fp:
        fp.write(header.tobytes())
        fp.write(np.ascontiguousarray(rec).tobytes())
    return len(rec)


def write_capture(path: str, flows: Iterable[Flow]) -> int:
    rec = flows_to_records(flows)
    lib = _native()
    if lib is not None:
        buf = np.ascontiguousarray(rec)
        _check(lib.ct_capture_write(
            path.encode(), buf.ctypes.data_as(ctypes.c_void_p),
            len(buf)))
        return len(buf)
    header = np.zeros(1, dtype=HEADER)
    header[0] = (MAGIC, VERSION, len(rec))
    with open(path, "wb") as fp:
        fp.write(header.tobytes())
        fp.write(rec.tobytes())
    return len(rec)


def capture_count(path: str) -> int:
    lib = _native()
    if lib is not None:
        return _check(lib.ct_capture_count(path.encode()))
    with open(path, "rb") as fp:
        raw = fp.read(HEADER.itemsize)
        if len(raw) < HEADER.itemsize:
            raise CaptureError("truncated capture")
        h = np.frombuffer(raw, dtype=HEADER)[0]
        if bytes(h["magic"]).ljust(8, b"\x00") != MAGIC:
            raise CaptureError("bad magic")
        version, count = int(h["version"]), int(h["count"])
        if version not in (VERSION, VERSION_L7, VERSION_L7G):
            raise CaptureError("unsupported version")
        want = HEADER.itemsize + count * RECORD.itemsize
        if version in (VERSION_L7, VERSION_L7G):
            fp.seek(want)
            lraw = fp.read(L7HEADER.itemsize)
            if len(lraw) < L7HEADER.itemsize:
                raise CaptureError("truncated capture")
            lh = np.frombuffer(lraw, dtype=L7HEADER)[0]
            want += (L7HEADER.itemsize
                     + (int(lh["n_strings"]) + 1) * 4
                     + int(lh["blob_bytes"])
                     + count * L7REC.itemsize)
            if version == VERSION_L7G:
                fmax = int(lh["reserved"])
                if fmax <= 0:
                    raise CaptureError("truncated capture")
                want += count * gen_dtype(fmax).itemsize
        fp.seek(0, os.SEEK_END)
        if fp.tell() != want:
            raise CaptureError("truncated capture")
        return count


def read_records(path: str, start: int = 0,
                 limit: Optional[int] = None) -> np.ndarray:
    """Records as a structured array — the zero-parse ingest path."""
    total = capture_count(path)
    start = min(start, total)
    n = total - start if limit is None else min(limit, total - start)
    if n <= 0:
        return np.zeros(0, dtype=RECORD)
    lib = _native()
    if lib is not None:
        out = np.zeros(n, dtype=RECORD)
        got = _check(lib.ct_capture_read(
            path.encode(), out.ctypes.data_as(ctypes.c_void_p), n,
            start))
        return out[:got]
    with open(path, "rb") as fp:
        fp.seek(HEADER.itemsize + start * RECORD.itemsize)
        return np.frombuffer(fp.read(n * RECORD.itemsize),
                             dtype=RECORD).copy()


def read_capture(path: str, start: int = 0,
                 limit: Optional[int] = None) -> List[Flow]:
    return records_to_flows(read_records(path, start=start, limit=limit))


def map_capture(path: str):
    """Validate once, then expose the records as a read-only memmap —
    the chunked-replay path: one open, no per-chunk revalidation.
    Works for both versions: base records immediately follow the
    header either way."""
    total = capture_count(path)
    if total == 0:
        return np.zeros(0, dtype=RECORD)
    return np.memmap(path, dtype=RECORD, mode="r",
                     offset=HEADER.itemsize, shape=(total,))


# -- v2: L7 sidecar --------------------------------------------------------

def capture_version(path: str) -> int:
    with open(path, "rb") as fp:
        raw = fp.read(HEADER.itemsize)
    if len(raw) < HEADER.itemsize:
        raise CaptureError("truncated capture")
    return int(np.frombuffer(raw, dtype=HEADER)[0]["version"])


def flows_to_capture_l7(flows: Iterable[Flow]):
    """Flows → (records, l7_records, offsets, blob, gen, fmax): the
    v2/v3 capture sections (``gen`` is None and fmax 0 when no flow
    carries a generic payload — the file stays v2). String
    normalization happens HERE, at write time (host lowercased, qname
    sanitized, headers serialized canonically), so the replay hot path
    does zero per-string transformation — the same split the reference
    uses (accesslog entries arrive normalized from Envoy; the ring
    consumer never re-parses)."""
    from cilium_tpu.engine.verdict import serialize_headers
    from cilium_tpu.policy.compiler import matchpattern

    flows = list(flows)
    strings: List[bytes] = [b""]
    index: dict = {b"": 0}

    def intern(b: bytes) -> int:
        i = index.get(b)
        if i is None:
            i = index[b] = len(strings)
            strings.append(b)
        return i

    rec = np.zeros(len(flows), dtype=RECORD)
    l7 = np.zeros(len(flows), dtype=L7REC)
    gen_rows: List[Tuple[int, List[Tuple[int, int]]]] = []
    fmax = 0
    for i, f in enumerate(flows):
        g = f.generic
        carriable = (f.l7 >= L7Type.GENERIC and g is not None
                     and g.proto)
        # a GENERIC flow with no payload/proto can never match a rule;
        # flatten it to the L4 tuple (same invariant as v1: an
        # uncarriable payload must not re-verdict against EMPTY
        # fields). Frontend-family flows carry like GENERIC and
        # normalize to the canonical GENERIC code — replay re-derives
        # the family from the record's proto.
        if f.l7 >= L7Type.GENERIC:
            l7t = L7Type.GENERIC if carriable else L7Type.NONE
        else:
            l7t = f.l7
        rec[i] = (f.src_identity, f.dst_identity, f.dport, f.sport,
                  int(f.protocol), int(f.direction), int(l7t),
                  int(f.verdict), f.time, 0, 0)
        if carriable:
            pairs = [(intern(k.encode("utf-8")),
                      intern(v.encode("utf-8")))
                     for k, v in sorted(g.fields.items()) if k]
            gen_rows.append((intern(g.proto.encode("utf-8")), pairs))
            # a carriable flow forces the GENERIC section even with
            # zero field pairs — a proto-only flow written as v2 would
            # re-verdict against an ABSENT payload on replay
            fmax = max(fmax, len(pairs), 1)
        else:
            gen_rows.append((0, []))
        h = f.http
        if h is not None:
            l7[i]["path"] = intern(h.path.encode("utf-8"))
            l7[i]["method"] = intern(h.method.encode("utf-8"))
            l7[i]["host"] = intern(h.host.lower().encode("utf-8"))
            l7[i]["headers"] = intern(serialize_headers(h.headers))
        d = f.dns
        if d is not None and d.query:
            l7[i]["qname"] = intern(
                matchpattern.sanitize_name(d.query).encode("utf-8"))
        k = f.kafka
        if k is not None:
            l7[i]["kafka_client"] = intern(k.client_id.encode("utf-8"))
            l7[i]["kafka_topic"] = intern(k.topic.encode("utf-8"))
            l7[i]["kafka_api_key"] = k.api_key
            l7[i]["kafka_api_version"] = k.api_version
    lens = np.array([len(s) for s in strings], dtype=np.uint64)
    total = int(lens.sum())
    if total > 0xFFFFFFFF:
        # u32 offsets cap the string table at 4 GiB; wrapping silently
        # would gather garbage slices on replay
        raise CaptureError(f"string table too large ({total} bytes)")
    offsets = np.zeros(len(strings) + 1, dtype=np.uint32)
    offsets[1:] = np.cumsum(lens)
    blob = np.frombuffer(b"".join(strings), dtype=np.uint8)
    gen = None
    if fmax > 0:
        gen = np.zeros(len(flows), dtype=gen_dtype(fmax))
        for i, (proto, pairs) in enumerate(gen_rows):
            gen[i]["proto"] = proto
            for j, (k, v) in enumerate(pairs):
                gen[i]["pairs"][j] = (k, v)
    return rec, l7, offsets, blob, gen, fmax


class CaptureWriter:
    """Streaming columnar record-batch writer (the Python face of
    ``ct_capture_writer_*``; a pure-numpy fallback buffers batches and
    writes the identical layout when the native codec is unbuildable).

    Usage: ``write_batch`` per record batch (base records + aligned L7
    rows + — for ``fmax > 0`` — aligned GENERIC rows), then ``finish``
    with the shared string table. A writer abandoned without finish
    leaves a file readers reject as truncated, never misparse."""

    def __init__(self, path: str, fmax: int = 0):
        self.path = path
        self.fmax = int(fmax)
        self.n = 0
        self._lib = _native()
        self._handle = None
        self._batches: List[tuple] = []  # fallback buffering
        if self._lib is not None:
            self._handle = self._lib.ct_capture_writer_open(
                path.encode(), self.fmax)
            if not self._handle:
                raise CaptureError("io error")

    def write_batch(self, rec: np.ndarray, l7: np.ndarray,
                    gen: Optional[np.ndarray] = None) -> None:
        if len(rec) != len(l7) or (
                self.fmax > 0 and (gen is None or len(gen) != len(rec))):
            raise CaptureError("batch sections misaligned")
        if self._handle is not None:
            _check(self._lib.ct_capture_writer_batch(
                self._handle,
                np.ascontiguousarray(rec).ctypes.data_as(
                    ctypes.c_void_p),
                np.ascontiguousarray(l7).ctypes.data_as(
                    ctypes.c_void_p),
                (np.ascontiguousarray(gen).ctypes.data_as(
                    ctypes.c_void_p) if self.fmax > 0 else None),
                len(rec)))
        else:
            self._batches.append(
                (np.asarray(rec).copy(), np.asarray(l7).copy(),
                 None if gen is None else np.asarray(gen).copy()))
        self.n += len(rec)

    def finish(self, offsets: np.ndarray, blob: np.ndarray) -> int:
        offsets = np.ascontiguousarray(offsets, dtype=np.uint32)
        blob = np.ascontiguousarray(blob, dtype=np.uint8)
        if self._handle is not None:
            handle, self._handle = self._handle, None
            return _check(self._lib.ct_capture_writer_finish(
                handle,
                offsets.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint32)),
                len(offsets) - 1,
                blob.ctypes.data_as(ctypes.c_void_p),
                int(blob.size)))
        rec = (np.concatenate([b[0] for b in self._batches])
               if self._batches else np.zeros(0, dtype=RECORD))
        l7 = (np.concatenate([b[1] for b in self._batches])
              if self._batches else np.zeros(0, dtype=L7REC))
        gen = (np.concatenate([b[2] for b in self._batches])
               if self.fmax > 0 else None)
        header = np.zeros(1, dtype=HEADER)
        version = VERSION_L7 if self.fmax == 0 else VERSION_L7G
        header[0] = (MAGIC, version, len(rec))
        l7h = np.zeros(1, dtype=L7HEADER)
        l7h[0] = (len(offsets) - 1, self.fmax, int(blob.size))
        with open(self.path, "wb") as fp:
            fp.write(header.tobytes())
            fp.write(rec.tobytes())
            fp.write(l7h.tobytes())
            fp.write(offsets.tobytes())
            fp.write(blob.tobytes())
            fp.write(l7.tobytes())
            if gen is not None:
                fp.write(gen.tobytes())
        self._batches = []
        return len(rec)

    def abort(self) -> None:
        if self._handle is not None:
            handle, self._handle = self._handle, None
            self._lib.ct_capture_writer_abort(handle)
        self._batches = []

    def __enter__(self) -> "CaptureWriter":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        if self._handle is not None:
            self.abort()


def write_capture_columns(path: str, cols,
                          batch_size: int = 1 << 16) -> int:
    """Write :class:`~cilium_tpu.ingest.columnar.CaptureColumns`
    through the streaming record-batch writer (native when built),
    chunked at ``batch_size`` records."""
    w = CaptureWriter(path, fmax=cols.fmax)
    try:
        for s in range(0, len(cols.rec), batch_size):
            w.write_batch(
                cols.rec[s:s + batch_size],
                cols.l7[s:s + batch_size],
                (cols.gen[s:s + batch_size]
                 if cols.gen is not None else None))
        return w.finish(cols.offsets, cols.blob)
    except BaseException:
        w.abort()
        raise


def write_capture_l7(path: str, flows: Iterable[Flow]) -> int:
    """Write a version-2 capture (base records + L7 sidecar); version
    3 when any flow carries a generic ``l7proto`` payload (the extra
    GENERIC section, see ``VERSION_L7G``). Encoding is columnar
    (``ingest.columnar.flows_to_columns`` → the streaming batch
    writer): one batch intern per string column instead of per-record
    interleaved interning, so the string-table ORDER differs from the
    historical per-record writer (``flows_to_capture_l7``, kept as the
    differential reference) while every resolved field is identical."""
    from cilium_tpu.ingest.columnar import flows_to_columns

    return write_capture_columns(path, flows_to_columns(flows))


def _write_capture_l7_rowmajor(path: str, flows: Iterable[Flow]) -> int:
    """The historical per-record write path (row-major intern order).
    Reference/differential use only — ``write_capture_l7`` is the
    product path."""
    rec, l7, offsets, blob, gen, fmax = flows_to_capture_l7(flows)
    lib = _native()
    if lib is not None and gen is None:
        _check(lib.ct_capture_write_l7(
            path.encode(),
            np.ascontiguousarray(rec).ctypes.data_as(ctypes.c_void_p),
            len(rec),
            np.ascontiguousarray(l7).ctypes.data_as(ctypes.c_void_p),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            len(offsets) - 1,
            blob.ctypes.data_as(ctypes.c_void_p),
            int(blob.size)))
        return len(rec)
    if lib is not None and gen is not None:
        # _native() guarantees the v3 symbol (pre-v3 ABIs load as
        # None) and declared its argtypes/restype with the rest
        _check(lib.ct_capture_write_l7g(
            path.encode(),
            np.ascontiguousarray(rec).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_uint32(len(rec)),
            np.ascontiguousarray(l7).ctypes.data_as(ctypes.c_void_p),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            ctypes.c_uint32(len(offsets) - 1),
            blob.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_uint64(int(blob.size)),
            np.ascontiguousarray(gen).ctypes.data_as(ctypes.c_void_p),
            ctypes.c_uint32(fmax)))
        return len(rec)
    header = np.zeros(1, dtype=HEADER)
    version = VERSION_L7 if gen is None else VERSION_L7G
    header[0] = (MAGIC, version, len(rec))
    l7h = np.zeros(1, dtype=L7HEADER)
    # the reserved word carries gen fmax in v3 (0 in v2)
    l7h[0] = (len(offsets) - 1, fmax, int(blob.size))
    with open(path, "wb") as fp:
        fp.write(header.tobytes())
        fp.write(rec.tobytes())
        fp.write(l7h.tobytes())
        fp.write(offsets.tobytes())
        fp.write(blob.tobytes())
        fp.write(l7.tobytes())
        if gen is not None:
            fp.write(gen.tobytes())
    return len(rec)


def sections_to_bytes(rec, l7, offsets, blob,
                      gen: Optional[np.ndarray] = None,
                      fmax: int = 0) -> bytes:
    """Capture sections → one in-memory v2/v3 capture image (byte-
    identical to what ``write_capture_l7`` puts on disk). The unit of
    the verdict socket's STREAM mode (runtime/stream.py): each frame's
    payload is a self-contained capture image, so the server parses
    chunks with the same zero-copy section readers as files."""
    header = np.zeros(1, dtype=HEADER)
    version = VERSION_L7 if gen is None else VERSION_L7G
    header[0] = (MAGIC, version, len(rec))
    l7h = np.zeros(1, dtype=L7HEADER)
    l7h[0] = (len(offsets) - 1, fmax, int(blob.size))
    parts = [header.tobytes(), np.ascontiguousarray(rec).tobytes(),
             l7h.tobytes(), np.ascontiguousarray(offsets).tobytes(),
             np.ascontiguousarray(blob).tobytes(),
             np.ascontiguousarray(l7).tobytes()]
    if gen is not None:
        parts.append(np.ascontiguousarray(gen).tobytes())
    return b"".join(parts)


def capture_to_bytes(flows: Iterable[Flow]) -> bytes:
    """Flows → in-memory v2/v3 capture image (client side of the
    stream protocol; columnar-encoded like :func:`write_capture_l7`)."""
    from cilium_tpu.ingest.columnar import flows_to_columns

    return flows_to_columns(flows).to_bytes()


def capture_from_bytes(buf: bytes):
    """Capture image → (rec, l7, offsets, blob, gen) views. Validates
    the full layout (magic, version, section sizes) like
    ``capture_count`` does for files; raises CaptureError on anything
    short, long, or misversioned — a stream server must fail a bad
    frame loudly, never gather garbage slices."""
    if len(buf) < HEADER.itemsize:
        raise CaptureError("truncated capture image")
    h = np.frombuffer(buf[:HEADER.itemsize], dtype=HEADER)[0]
    if bytes(h["magic"]).ljust(8, b"\x00") != MAGIC:
        raise CaptureError("bad magic")
    version, count = int(h["version"]), int(h["count"])
    if version not in (VERSION_L7, VERSION_L7G):
        raise CaptureError(f"unsupported stream version {version}")
    off = HEADER.itemsize
    want = off + count * RECORD.itemsize + L7HEADER.itemsize
    if len(buf) < want:
        raise CaptureError("truncated capture image")
    rec = np.frombuffer(buf, dtype=RECORD, count=count, offset=off)
    off += count * RECORD.itemsize
    lh = np.frombuffer(buf, dtype=L7HEADER, count=1, offset=off)[0]
    off += L7HEADER.itemsize
    n_strings = int(lh["n_strings"])
    blob_bytes = int(lh["blob_bytes"])
    fmax = int(lh["reserved"])
    want = (off + (n_strings + 1) * 4 + blob_bytes
            + count * L7REC.itemsize)
    if version == VERSION_L7G:
        if fmax <= 0:
            raise CaptureError("truncated capture image")
        want += count * gen_dtype(fmax).itemsize
    if len(buf) != want:
        raise CaptureError(
            f"capture image size {len(buf)} != expected {want}")
    offsets = np.frombuffer(buf, dtype="<u4", count=n_strings + 1,
                            offset=off)
    off += (n_strings + 1) * 4
    blob = np.frombuffer(buf, dtype=np.uint8, count=blob_bytes,
                         offset=off)
    off += blob_bytes
    l7 = np.frombuffer(buf, dtype=L7REC, count=count, offset=off)
    off += count * L7REC.itemsize
    gen = None
    if version == VERSION_L7G:
        gen = np.frombuffer(buf, dtype=gen_dtype(fmax), count=count,
                            offset=off)
    return rec, l7, offsets, blob, gen


def capture_field_widths(l7, offsets, cfg=None,
                         pad_multiple: int = 32) -> Dict[str, int]:
    """Per-field padded widths over a WHOLE capture — pass to the
    engine's ``encode_l7_records`` so every chunk of a chunked replay
    encodes to identical shapes (one jit compile for the stream).
    Lives here (pure numpy) so the replay cursor can compute it
    without touching jax."""
    from cilium_tpu.core.config import EngineConfig

    cfg = cfg or EngineConfig()
    caps = {"path": max(cfg.http_path_buckets),
            "method": cfg.http_method_len, "host": cfg.http_host_len,
            "headers": 1024, "qname": cfg.dns_name_len}
    widths = {}
    for field, cap in caps.items():
        idx = l7[field]
        lens = (offsets[idx + 1].astype(np.int64)
                - offsets[idx].astype(np.int64))
        longest = int(lens.max()) if len(lens) else 1
        widths[field] = min(
            cap, max(pad_multiple,
                     -(-max(longest, 1) // pad_multiple) * pad_multiple))
    return widths


def l7_info(path: str):
    """O(1) sidecar geometry: (n_strings, blob_bytes) from the 16-byte
    L7Header ((0, 0) for a v1 capture) — the ct_capture_l7_info analog."""
    total = capture_count(path)  # full-layout validation
    if capture_version(path) not in (VERSION_L7, VERSION_L7G):
        return 0, 0
    with open(path, "rb") as fp:
        fp.seek(HEADER.itemsize + total * RECORD.itemsize)
        lh = np.frombuffer(fp.read(L7HEADER.itemsize), dtype=L7HEADER)[0]
    return int(lh["n_strings"]), int(lh["blob_bytes"])


def read_l7_sidecar(path: str):
    """(l7_records, offsets, blob) of a v2/v3 capture — one sequential
    read per section, no per-record parsing."""
    total = capture_count(path)  # full-layout validation
    if capture_version(path) not in (VERSION_L7, VERSION_L7G):
        raise CaptureError("capture has no L7 sidecar (v1)")
    with open(path, "rb") as fp:
        fp.seek(HEADER.itemsize + total * RECORD.itemsize)
        lh = np.frombuffer(fp.read(L7HEADER.itemsize), dtype=L7HEADER)[0]
        n_strings = int(lh["n_strings"])
        blob_bytes = int(lh["blob_bytes"])
        offsets = np.fromfile(fp, dtype="<u4", count=n_strings + 1)
        blob = np.fromfile(fp, dtype=np.uint8, count=blob_bytes)
        l7 = np.fromfile(fp, dtype=L7REC, count=total)
    return l7, offsets, blob


def read_gen_sidecar(path: str):
    """The v3 GENERIC section as a ``gen_dtype(fmax)`` array, or None
    for v1/v2 captures (one sequential read, like the L7 sidecar)."""
    total = capture_count(path)  # full-layout validation
    if capture_version(path) != VERSION_L7G:
        return None
    with open(path, "rb") as fp:
        fp.seek(HEADER.itemsize + total * RECORD.itemsize)
        lh = np.frombuffer(fp.read(L7HEADER.itemsize), dtype=L7HEADER)[0]
        fmax = int(lh["reserved"])
        fp.seek((int(lh["n_strings"]) + 1) * 4 + int(lh["blob_bytes"])
                + total * L7REC.itemsize, os.SEEK_CUR)
        return np.fromfile(fp, dtype=gen_dtype(fmax), count=total)


def _table_get(offsets: np.ndarray, blob: np.ndarray, idx: int) -> bytes:
    return blob[int(offsets[idx]):int(offsets[idx + 1])].tobytes()


def read_capture_flows_l7(path: str) -> List[Flow]:
    """Object-path reconstruction of a v2 capture (tooling/tests; the
    hot path is engine.verdict.encode_l7_records over the raw
    sections)."""
    rec = read_records(path)
    l7, offsets, blob = read_l7_sidecar(path)
    return records_to_flows_l7(rec, l7, offsets, blob,
                               gen=read_gen_sidecar(path))


def records_to_flows_l7(rec: np.ndarray, l7: np.ndarray,
                        offsets: np.ndarray, blob: np.ndarray,
                        gen: Optional[np.ndarray] = None
                        ) -> List[Flow]:
    from cilium_tpu.core.flow import (
        DNSInfo,
        GenericL7Info,
        HTTPInfo,
        KafkaInfo,
    )

    flows = []
    for i, (r, s) in enumerate(zip(rec, l7)):
        f = Flow(src_identity=int(r["src_identity"]),
                 dst_identity=int(r["dst_identity"]),
                 dport=int(r["dport"]), sport=int(r["sport"]),
                 protocol=Protocol(int(r["proto"])),
                 direction=TrafficDirection(int(r["direction"])),
                 l7=L7Type(int(r["l7_type"])),
                 verdict=Verdict(int(r["verdict"])),
                 time=float(r["time"]))
        if f.l7 == L7Type.HTTP:
            hdr_block = _table_get(offsets, blob, int(s["headers"]))
            headers = tuple(
                tuple(line.split(":", 1))
                for line in hdr_block.decode("utf-8").splitlines() if line)
            f.http = HTTPInfo(
                method=_table_get(offsets, blob,
                                  int(s["method"])).decode("utf-8"),
                path=_table_get(offsets, blob,
                                int(s["path"])).decode("utf-8"),
                host=_table_get(offsets, blob,
                                int(s["host"])).decode("utf-8"),
                headers=headers)
        elif f.l7 == L7Type.DNS:
            f.dns = DNSInfo(query=_table_get(
                offsets, blob, int(s["qname"])).decode("utf-8"))
        elif f.l7 == L7Type.KAFKA:
            f.kafka = KafkaInfo(
                api_key=int(s["kafka_api_key"]),
                api_version=int(s["kafka_api_version"]),
                client_id=_table_get(offsets, blob,
                                     int(s["kafka_client"])).decode("utf-8"),
                topic=_table_get(offsets, blob,
                                 int(s["kafka_topic"])).decode("utf-8"))
        elif f.l7 == L7Type.GENERIC and gen is not None:
            g = gen[i]
            fields = {}
            for k_idx, v_idx in g["pairs"]:
                if k_idx:  # index 0 = "" = unused slot
                    fields[_table_get(offsets, blob,
                                      int(k_idx)).decode("utf-8")] = \
                        _table_get(offsets, blob,
                                   int(v_idx)).decode("utf-8")
            f.generic = GenericL7Info(
                proto=_table_get(offsets, blob,
                                 int(g["proto"])).decode("utf-8"),
                fields=fields)
        flows.append(f)
    return flows
