"""Native imzML + ibd reader/writer.

The reference parses imzML via the external ``pyimzML`` library inside
``sm/engine/imzml_txt_converter.py::ImzmlTxtConverter.convert`` [U]
(SURVEY.md #4) and round-trips through a line-per-spectrum text file for
Spark.  We parse the binary format natively and keep everything as numpy
arrays — there is no text intermediate; the cube builder (io/dataset.py)
consumes the arrays directly.

Format essentials (imzML 1.1, built on mzML 1.1):
- ``.imzML``: XML; file-level cvParam IMS:1000030 (continuous) or
  IMS:1000031 (processed); per-spectrum scan position IMS:1000050/51 (x/y);
  per-binaryDataArray external byte offset IMS:1000102, array length
  IMS:1000103, encoded length IMS:1000104; array kind MS:1000514 (m/z) /
  MS:1000515 (intensity); dtype MS:1000521/523/519/522 (f32/f64/i32/i64).
  Array kind + dtype commonly live in a referenceableParamGroup.
- ``.ibd``: 16-byte UUID (must match imzML IMS:1000080), then raw arrays.
  Continuous mode: one shared m/z array, per-spectrum intensity arrays.
"""

from __future__ import annotations

import io
import re
import threading
import uuid as uuid_mod
import xml.etree.ElementTree as ET
from pathlib import Path
from typing import NamedTuple

import numpy as np

from ..utils import tracing
from ..utils.failpoints import failpoint, register_failpoint

FP_IMZML_PARSE = register_failpoint(
    "io.imzml_parse", "start of imzML XML parse (corrupt/unreadable imzML)")
FP_IBD_READ = register_failpoint(
    "io.ibd_read", "per ibd read call (I/O error / truncation mid-ingest)")

_DTYPES = {
    "MS:1000521": np.dtype("<f4"),
    "MS:1000523": np.dtype("<f8"),
    "MS:1000519": np.dtype("<i4"),
    "MS:1000522": np.dtype("<i8"),
    # IMS legacy aliases seen in the wild
    "IMS:1000101": np.dtype("<f4"),
}
# the index keeps an array's dtype as a code: its place in this tuple
_CODE_DTYPES = tuple(dict.fromkeys(_DTYPES.values()))
_CODE_ITEMSIZE = np.array([dt.itemsize for dt in _CODE_DTYPES], dtype=np.int64)
_MZ_ARRAY = "MS:1000514"
_INT_ARRAY = "MS:1000515"
_CONTINUOUS = "IMS:1000030"
_PROCESSED = "IMS:1000031"
_UUID = "IMS:1000080"
_POS_X = "IMS:1000050"
_POS_Y = "IMS:1000051"
_EXT_OFFSET = "IMS:1000102"
_EXT_ARR_LEN = "IMS:1000103"

# The most one ibd read call moves, and the most a bulk ingest
# (``ImzMLReader.read_into``) allocates beside the arrays it fills: runs
# that can be read straight to their place take none of it; the rest is a
# read buffer of half of it and the decode's index arrays in the other half.
# Small files get an eighth of their data instead, so the bound also holds
# in proportion.
_IBD_CHUNK_BYTES = 32 << 20
_IBD_MIN_CHUNK_BYTES = 16 << 10
# Arrays that abut in the file, in CSR order, form a run.  A run at least
# this long is read by itself, straight to its place; shorter ones are
# decoded from a chunk of the data region they lie in.
_IBD_RUN_BYTES = 256 << 10
_GATHER_BYTES_PER_ELEMENT = 48      # index temporaries of _gather, an element

# How each ingest's index was made and how many ibd read calls were issued.
# Process-wide (scheduler workers share it); the service's metrics collector
# pulls it as sm_imzml_ingest_total{index=} / sm_imzml_ibd_reads_total.
_INGEST_EVENTS = {"scan": 0, "xml": 0, "ibd_reads": 0}
_INGEST_EVENTS_LOCK = threading.Lock()


def _count_ingest(what: str) -> None:
    with _INGEST_EVENTS_LOCK:
        _INGEST_EVENTS[what] += 1


def ingest_events() -> dict:
    with _INGEST_EVENTS_LOCK:
        return dict(_INGEST_EVENTS)


def _local(tag: str) -> str:
    return tag.rsplit("}", 1)[-1]


class ImzMLParseError(ValueError):
    pass


class _Index(NamedTuple):
    """One row a spectrum: where its two arrays lie in the ibd.  ``offset``,
    ``length`` (elements) and ``code`` (place in ``_CODE_DTYPES``) are
    (2, n_spectra): row 0 the m/z array, row 1 the intensity array."""

    x: np.ndarray           # (n,) i64 raw scan position
    y: np.ndarray           # (n,) i64
    offset: np.ndarray      # (2, n) i64
    length: np.ndarray      # (2, n) i64
    code: np.ndarray        # (2, n) u8

    @staticmethod
    def from_rows(rows) -> "_Index":
        """rows: (n, 8) of x, y, then offset, length, code of the m/z and of
        the intensity array."""
        r = np.asarray(rows, dtype=np.int64).reshape(-1, 8).T
        return _Index(r[0].copy(), r[1].copy(), r[[2, 5]], r[[3, 6]],
                      r[[4, 7]].astype(np.uint8))


# -- the scan: a spectrum block as a pattern of the file's first one -------

_SPECTRUM_OPEN = re.compile(rb"<spectrum(?=[\s>])")
_SPECTRUM_CLOSE = b"</spectrum>"
_TAG = re.compile(
    rb'<(/?)([A-Za-z_][\w.\-]*)((?:\s+[\w:.\-]+="[^"<>]*")*)\s*(/?)>')
_ATTR = re.compile(rb'\s+([\w:.\-]+)="([^"<>]*)"')
_VALUE_ATTR = re.compile(rb'\svalue="([^"<>]*)"')
_UINT = re.compile(rb"\d{1,18}")


def _block_pattern(block: bytes):
    """(compiled pattern, roles) from one ``<spectrum>...</spectrum>`` block,
    or None where the block is not plain tags.

    The pattern is the block itself, letter for letter, but for what the
    reader never looks at (the attributes of any element other than
    ``cvParam`` and ``referenceableParamGroupRef``, and the value of a
    ``cvParam`` whose accession it does not read), which may vary, and for
    the values it does read, which are captured: ``roles[j]`` says what
    group j+1 holds: "x", "y", or (k, "offset" | "length") of the block's
    k-th binaryDataArray.  A block that matches therefore means to the XML
    reader what the first block means, with its own numbers.
    """
    pieces, roles = [], []
    pos, k, in_array, n_tags = 0, -1, False, 0
    for n_tags, m in enumerate(_TAG.finditer(block), 1):
        gap = block[pos:m.start()]
        if gap.strip():
            return None                      # text, comment, CDATA, PI
        pieces.append(re.escape(gap))
        pos = m.end()
        closing, name, attrs, selfclose = m.groups()
        tag = m.group(0)
        if closing:
            if name == b"binaryDataArray":
                in_array = False
            pieces.append(re.escape(tag))
        elif name == b"cvParam":
            a = dict(_ATTR.findall(attrs))
            acc = a.get(b"accession", b"").decode("ascii", "replace")
            role = None
            if in_array and acc in (_EXT_OFFSET, _EXT_ARR_LEN):
                role = (k, "offset" if acc == _EXT_OFFSET else "length")
            elif not in_array and acc in (_POS_X, _POS_Y):
                role = "x" if acc == _POS_X else "y"
            found = list(_VALUE_ATTR.finditer(tag))
            if b"value" not in a and not found and role is None:
                pieces.append(re.escape(tag))
                continue
            if [v.group(1) for v in found] != [a.get(b"value")]:
                return None                  # no one value="..." to vary
            lo, hi = found[0].span(1)
            if role is None:
                mid = rb'[^"<>]*'
            elif role in roles or not _UINT.fullmatch(tag[lo:hi]):
                return None
            else:
                roles.append(role)
                mid = rb"(\d{1,18})"
            pieces.append(re.escape(tag[:lo]) + mid + re.escape(tag[hi:]))
        elif name == b"referenceableParamGroupRef":
            pieces.append(re.escape(tag))
        else:
            if name == b"binaryDataArray":
                if selfclose or in_array:
                    return None
                k, in_array = k + 1, True
            elif name == b"spectrum" and n_tags > 1:
                return None                  # a spectrum inside a spectrum
            pieces.append(b"<" + re.escape(name) + rb"(?=[\s/>])[^<>]*>")
    if block[pos:].strip():
        return None
    return re.compile(b"".join(pieces)), roles


class _Buffer:
    """The ingest's one read buffer: made at first use, as large as the
    plan's chunk allows, larger only for a single array that is."""

    def __init__(self, nbytes: int):
        self.nbytes = nbytes
        self._mem: np.ndarray | None = None

    def first(self, nbytes: int) -> np.ndarray:
        if self._mem is None or self._mem.size < nbytes:
            self._mem = None
            self._mem = np.empty(max(nbytes, self.nbytes), dtype=np.uint8)
        return self._mem[:nbytes]


def _gather(view: np.ndarray, start: np.ndarray, count: np.ndarray,
            out: np.ndarray, dest: np.ndarray) -> None:
    """``out[dest[j] : dest[j] + count[j]] = view[start[j] : start[j] +
    count[j]]`` for every j, as one indexed copy (cast to ``out``'s dtype
    as ``astype`` would).  A single array is a slice and needs no index."""
    if start.size == 1:
        a, b, n = int(start[0]), int(dest[0]), int(count[0])
        out[b:b + n] = view[a:a + n]
        return
    ends = np.cumsum(count)
    total = int(ends[-1])
    within = np.arange(total, dtype=np.int64)
    within -= np.repeat(ends - count, count)
    src = np.repeat(start, count)
    src += within
    if np.array_equal(dest[1:], dest[:-1] + count[:-1]):
        seg = out[int(dest[0]):int(dest[0]) + total]
        if view.dtype == out.dtype:
            np.take(view, src, out=seg, mode="clip")
        else:
            seg[:] = view[src]
    else:
        within += np.repeat(dest, count)
        out[within] = view[src]


def _decode_chunk(data: np.ndarray, c0: int, kind, code, length, offset,
                  dest, outs, per_gather: int) -> None:
    """The arrays (one row each of ``kind`` .. ``dest``) that lie in ``data``,
    the file's bytes from ``c0`` on, to their places in ``outs``: one view of
    the chunk for each (kind, dtype, alignment) in it, and a gather for every
    ``per_gather`` elements of it."""
    rel = offset - c0
    key = (kind * len(_CODE_DTYPES) + code) * 8 + rel % _CODE_ITEMSIZE[code]
    for q in np.unique(key):
        sel = np.flatnonzero(key == q)
        which, skew = divmod(int(q), 8)
        out, dt = outs[which // len(_CODE_DTYPES)], _CODE_DTYPES[
            which % len(_CODE_DTYPES)]
        view = data[skew:skew + (data.size - skew)
                    // dt.itemsize * dt.itemsize].view(dt)
        start = (rel[sel] - skew) // dt.itemsize
        count, to = length[sel], dest[sel]
        done = np.cumsum(count)
        b0 = 0
        while b0 < sel.size:
            b1 = max(b0 + 1, int(np.searchsorted(
                done, done[b0] - count[b0] + per_gather, side="right")))
            _gather(view, start[b0:b1], count[b0:b1], out, to[b0:b1])
            b0 = b1


class ImzMLReader:
    """An imzML/ibd pair as columns (one row a spectrum) over an open ibd.

    Usage::
        rd = ImzMLReader("ds.imzML")
        for i in range(rd.n_spectra):
            x, y = rd.coordinates[i]
            mzs, ints = rd.read_spectrum(i)

    or, for the whole dataset in a few large reads, ``read_into``
    (``SpectralDataset.from_imzml``).

    The index is made by a scan of the XML's bytes: the header and what
    follows the last spectrum go through the XML parser, the first
    ``<spectrum>`` block too, and every other block has to be that block
    again with other numbers (``_block_pattern``).  A file of which that is
    not true, exactly, is handed whole to the XML parser (``_parse_xml``),
    which also raises every ``ImzMLParseError`` there is to raise; both give
    the same columns.  ``index_kind`` says which one made them.
    """

    def __init__(self, imzml_path: str | Path, ibd_path: str | Path | None = None):
        self.imzml_path = Path(imzml_path)
        self.ibd_path = Path(ibd_path) if ibd_path else self.imzml_path.with_suffix(".ibd")
        if not self.ibd_path.exists():
            # handle .imzml/.IBD case variants
            for cand in self.imzml_path.parent.glob("*"):
                if cand.suffix.lower() == ".ibd" and cand.stem == self.imzml_path.stem:
                    self.ibd_path = cand
                    break
        if not self.ibd_path.exists():
            raise FileNotFoundError(f"ibd file for {self.imzml_path} not found")
        self.continuous: bool | None = None
        self.uuid: str | None = None
        self.index_kind = "scan"
        self._coordinates: np.ndarray | None = None
        self.reads = 0              # ibd read calls so far
        self.bytes_read = 0
        self._build_index()
        self._ibd = open(self.ibd_path, "rb")
        self._check_uuid()

    # -- parsing ---------------------------------------------------------

    def _build_index(self) -> None:
        failpoint(FP_IMZML_PARSE, path=self.imzml_path)
        with tracing.span("parse_index"):
            data = self.imzml_path.read_bytes()
            index = self._scan_index(data)
            if index is None:
                self.index_kind = "xml"
                index = self._parse_xml(io.BytesIO(data))
            self._index = index
            _count_ingest(self.index_kind)
            tracing.annotate(spectra=self.n_spectra, index=self.index_kind,
                             xml_bytes=len(data))

    def _scan_index(self, data: bytes) -> _Index | None:
        """The columns by one pass of a pattern over the XML's bytes, or
        None where the file is not a sequence of like blocks (and where it
        is at fault: the XML parser then says how)."""
        first = _SPECTRUM_OPEN.search(data)
        last = data.rfind(_SPECTRUM_CLOSE)
        if first is None or last < first.start():
            return None
        lo, hi = first.start(), last + len(_SPECTRUM_CLOSE)
        end0 = data.index(_SPECTRUM_CLOSE, lo) + len(_SPECTRUM_CLOSE)
        try:
            made = _block_pattern(data[lo:end0])
            if made is None:
                return None
            pattern, roles = made
            # what the XML parser makes of the file with spectra 1.. left out
            parser = ET.XMLPullParser(events=("start", "end"))
            parser.feed(data[:end0])
            parser.feed(data[hi:])
            parser.close()
            seen: list = []
            self.continuous, self.uuid = self._parse_events(
                parser.read_events(), lambda *a: seen.append(a))
            if self.continuous is None or len(seen) != 1:
                return None
            x0, y0, k_mz, k_int, refs = self._resolve_spectrum(0, *seen[0])
            want = ["x", "y", (k_mz, "offset"), (k_mz, "length"),
                    (k_int, "offset"), (k_int, "length")]
            if not set(want) <= set(roles):
                return None
            parts = pattern.split(data[lo:hi])
            step = len(roles) + 1
            if b"".join(parts[0::step]).strip():
                return None                  # something between the blocks
            cols = {role: np.array(parts[j + 1::step]).astype(np.int64)
                    for j, role in enumerate(roles)}
        except (ET.ParseError, re.error, ValueError, OverflowError):
            return None
        # the pattern's reading of block 0 against the XML parser's
        for k, (offset, length, _code) in enumerate(refs):
            if (cols.get((k, "offset"), [offset])[0] != offset
                    or cols.get((k, "length"), [length])[0] != length):
                return None
        if cols["x"][0] != x0 or cols["y"][0] != y0:
            return None
        n = cols["x"].size
        return _Index(
            x=cols["x"], y=cols["y"],
            offset=np.stack([cols[k_mz, "offset"], cols[k_int, "offset"]]),
            length=np.stack([cols[k_mz, "length"], cols[k_int, "length"]]),
            code=np.repeat(np.array(
                [[refs[k_mz][2]], [refs[k_int][2]]], dtype=np.uint8), n, axis=1))

    def _parse_xml(self, source) -> _Index:
        """The columns by the XML parser, an element at a time: what the
        scan falls back on, and the oracle it is tested against."""
        rows: list[tuple] = []

        def finish(pos_x, pos_y, arrays):
            x, y, k_mz, k_int, refs = self._resolve_spectrum(
                len(rows), pos_x, pos_y, arrays)
            rows.append((x, y, *refs[k_mz], *refs[k_int]))

        self.continuous, self.uuid = self._parse_events(
            ET.iterparse(source, events=("start", "end")), finish)
        if self.continuous is None:
            raise ImzMLParseError(
                f"{self.imzml_path}: neither continuous ({_CONTINUOUS}) nor "
                f"processed ({_PROCESSED}) file-content cvParam found"
            )
        if not rows:
            raise ImzMLParseError(f"{self.imzml_path}: no spectra")
        return _Index.from_rows(rows)

    @staticmethod
    def _parse_events(events, finish_spectrum):
        """(continuous, uuid) of a stream of (event, element) pairs, handing
        each spectrum's (pos_x, pos_y, arrays) to ``finish_spectrum``."""
        continuous = uuid = None
        param_groups: dict[str, list[tuple[str, str]]] = {}
        cur_group: str | None = None
        in_spectrum = False
        pos_x = pos_y = None
        arrays: list[dict] = []
        cur_array: dict | None = None

        for event, elem in events:
            tag = _local(elem.tag)
            if event == "start":
                if tag == "referenceableParamGroup":
                    cur_group = elem.get("id")
                    param_groups[cur_group] = []
                elif tag == "spectrum":
                    in_spectrum = True
                    pos_x = pos_y = None
                    arrays = []
                elif tag == "binaryDataArray" and in_spectrum:
                    cur_array = {"accessions": {}}
                continue

            # end events
            if tag == "cvParam":
                acc = elem.get("accession", "")
                val = elem.get("value", "")
                if cur_group is not None and not in_spectrum:
                    param_groups[cur_group].append((acc, val))
                elif cur_array is not None:
                    cur_array["accessions"][acc] = val
                elif in_spectrum:
                    if acc == _POS_X:
                        pos_x = int(float(val))
                    elif acc == _POS_Y:
                        pos_y = int(float(val))
                else:
                    if acc == _CONTINUOUS:
                        continuous = True
                    elif acc == _PROCESSED:
                        continuous = False
                    elif acc == _UUID:
                        uuid = val.strip("{}").replace("-", "").lower()
            elif tag == "referenceableParamGroupRef" and cur_array is not None:
                ref = elem.get("ref")
                for acc, val in param_groups.get(ref, []):
                    cur_array["accessions"].setdefault(acc, val)
            elif tag == "binaryDataArray" and cur_array is not None:
                arrays.append(cur_array)
                cur_array = None
            elif tag == "spectrum":
                finish_spectrum(pos_x, pos_y, arrays)
                in_spectrum = False
                elem.clear()
            elif tag in ("spectrumList", "run", "mzML"):
                elem.clear()
        return continuous, uuid

    def _resolve_spectrum(self, index: int, pos_x, pos_y, arrays):
        """(x, y, k_mz, k_int, [(offset, length, dtype code) of each
        binaryDataArray]) of spectrum ``index``; k_mz / k_int say which of
        the arrays are its m/z and its intensities."""
        if pos_x is None or pos_y is None:
            raise ImzMLParseError(
                f"{self.imzml_path}: spectrum {index} missing scan position"
            )
        k_mz = k_int = None
        refs = []
        for k, arr in enumerate(arrays):
            acc = arr["accessions"]
            dtype = None
            for code, dt in _DTYPES.items():
                if code in acc:
                    dtype = dt
                    break
            if dtype is None or _EXT_OFFSET not in acc or _EXT_ARR_LEN not in acc:
                raise ImzMLParseError(
                    f"{self.imzml_path}: binaryDataArray missing dtype/offset/length"
                )
            refs.append((int(acc[_EXT_OFFSET]), int(acc[_EXT_ARR_LEN]),
                         _CODE_DTYPES.index(dtype)))
            if _MZ_ARRAY in acc:
                k_mz = k
            elif _INT_ARRAY in acc:
                k_int = k
        if k_mz is None or k_int is None:
            raise ImzMLParseError(
                f"{self.imzml_path}: spectrum {index} lacks m/z or intensity array"
            )
        return pos_x, pos_y, k_mz, k_int, refs

    def _check_uuid(self) -> None:
        raw = self._ibd.read(16)
        if len(raw) != 16:
            raise ImzMLParseError(f"{self.ibd_path}: shorter than the 16-byte UUID header")
        if self.uuid and raw.hex() != self.uuid:
            raise ImzMLParseError(
                f"ibd UUID {raw.hex()} does not match imzML UUID {self.uuid}"
            )

    # -- access ----------------------------------------------------------

    @property
    def n_spectra(self) -> int:
        return int(self._index.x.size)

    @property
    def coordinates(self) -> np.ndarray:
        """(n_spectra, 2) int array of raw (x, y) scan positions."""
        if self._coordinates is None:
            self._coordinates = np.stack([self._index.x, self._index.y], axis=1)
        return self._coordinates

    def spectrum_lengths(self) -> np.ndarray:
        """(n_spectra,) int64 peak counts WITHOUT touching the ibd data —
        lengths come from the XML array metadata, which is what lets
        ingestion preallocate exact CSR arrays and fill them from a few
        large reads (SpectralDataset.from_imzml)."""
        return self._index.length[0].copy()

    def _readinto(self, offset: int, target) -> None:
        """One read call: ``target`` (a writable buffer) filled from
        ``offset``."""
        failpoint(FP_IBD_READ, path=self.ibd_path)
        _count_ingest("ibd_reads")
        self.reads += 1
        self._ibd.seek(offset)
        got = self._ibd.readinto(target)
        self.bytes_read += got
        if got != memoryview(target).nbytes:
            raise ImzMLParseError(f"{self.ibd_path}: truncated read at offset {offset}")

    def _read_array(self, kind: int, i: int) -> np.ndarray:
        ix = self._index
        out = np.empty(int(ix.length[kind, i]), dtype=_CODE_DTYPES[ix.code[kind, i]])
        self._readinto(int(ix.offset[kind, i]), out)
        return out

    def read_spectrum(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """(mzs float64, intensities float32) of spectrum i."""
        mzs = self._read_array(0, i).astype(np.float64)
        ints = self._read_array(1, i).astype(np.float32)
        if mzs.shape != ints.shape:
            raise ImzMLParseError(f"spectrum {i}: mz/intensity length mismatch")
        return mzs, ints

    # -- the whole dataset at once -----------------------------------------

    def read_into(self, mzs_out: np.ndarray, ints_out: np.ndarray,
                  dest_start: np.ndarray) -> None:
        """Every spectrum's arrays into ``mzs_out`` (f64) / ``ints_out``
        (f32), spectrum i at ``dest_start[i]``, in a few large reads.

        The plan comes from the index.  Arrays of one kind and dtype that
        abut in the file, in the order of their places in the output, form
        a run.  A long run (``_IBD_RUN_BYTES``) is read by itself: straight
        to its place when the file's dtype is the output's, through the
        buffer and a cast otherwise.  All other arrays (an interleaved
        file's, a continuous file's shared m/z axis, shuffled coordinates)
        are decoded, one gather a batch, from chunks of the data region
        they lie in, read in file order.  Beside the outputs this allocates
        at most the ``chunk_bytes`` its span reports (half of it the read
        buffer, half the gather's index arrays), or the largest single
        array where that is larger."""
        outs = (mzs_out, ints_out)
        for out, dt in zip(outs, (np.float64, np.float32)):
            if out.dtype != dt or out.ndim != 1 or not out.flags.c_contiguous:
                raise ValueError(f"read_into wants contiguous 1-D {dt.__name__}")
        with tracing.span("read_ibd"):
            self._check_extents()
            reads, nbytes = self.reads, self.bytes_read
            runs, chunk = self._read_all(outs, np.asarray(dest_start, np.int64))
            tracing.annotate(bytes=self.bytes_read - nbytes,
                             reads=self.reads - reads, runs=runs,
                             chunk_bytes=chunk)

    def _read_all(self, outs, dest_start) -> tuple[int, int]:
        """-> (runs, chunk bytes) of the plan it carried out."""
        ix = self._index
        # every non-empty array of the file in file order, both kinds
        length = ix.length.ravel()
        order = np.flatnonzero(length > 0)
        order = order[np.argsort(ix.offset.ravel()[order], kind="stable")]
        if not order.size:
            return 0, 0
        kind = np.repeat(np.arange(2), ix.x.size)[order]
        code = ix.code.ravel()[order].astype(np.int64)
        length = length[order]
        offset = ix.offset.ravel()[order]
        dest = np.tile(dest_start, 2)[order]
        nbytes = length * _CODE_ITEMSIZE[code]
        end = offset + nbytes
        chunk = int(min(_IBD_CHUNK_BYTES,
                        max(_IBD_MIN_CHUNK_BYTES, int(nbytes.sum()) // 8)))
        buf = _Buffer(chunk // 2)

        new_run = np.ones(order.size, dtype=bool)
        new_run[1:] = ~((offset[1:] == end[:-1]) & (kind[1:] == kind[:-1])
                        & (code[1:] == code[:-1])
                        & (dest[1:] == dest[:-1] + length[:-1]))
        first = np.flatnonzero(new_run)
        run_bytes = np.add.reduceat(nbytes, first)
        long_run = run_bytes >= _IBD_RUN_BYTES
        for r in np.flatnonzero(long_run):
            j = first[r]
            self._read_run(int(offset[j]), int(run_bytes[r]),
                           _CODE_DTYPES[code[j]], outs[kind[j]],
                           int(dest[j]), buf)

        # the rest, a chunk of the file at a time: as many arrays as end
        # within the buffer's reach of the first one's start
        rest = np.flatnonzero(~long_run[np.cumsum(new_run) - 1])
        cols = tuple(a[rest] for a in (kind, code, length, offset, dest))
        offset, end = offset[rest], end[rest]
        reach = np.maximum.accumulate(end) if rest.size else end
        per_gather = max(1, chunk // 2 // _GATHER_BYTES_PER_ELEMENT)
        j0 = 0
        while j0 < rest.size:
            c0 = int(offset[j0])
            j1 = max(j0 + 1, int(np.searchsorted(
                reach, c0 + buf.nbytes, side="right")))
            data = buf.first(int(end[j0:j1].max()) - c0)
            self._readinto(c0, data)
            _decode_chunk(data, c0, *(a[j0:j1] for a in cols), outs,
                          per_gather)
            j0 = j1
        return int(first.size), chunk

    def _read_run(self, offset: int, nbytes: int, dt: np.dtype,
                  out: np.ndarray, dest: int, buf: "_Buffer") -> None:
        """One run of the file to its place: ``nbytes`` at ``offset`` are
        ``out[dest:]`` in dtype ``dt``."""
        seg = out[dest:dest + nbytes // dt.itemsize]
        if dt == out.dtype:
            target = memoryview(seg).cast("B")
            for a in range(0, nbytes, _IBD_CHUNK_BYTES):
                self._readinto(offset + a, target[a:a + _IBD_CHUNK_BYTES])
            return
        step = max(1, buf.nbytes // dt.itemsize)
        for a in range(0, seg.size, step):
            piece = buf.first(min(step, seg.size - a) * dt.itemsize)
            self._readinto(offset + a * dt.itemsize, piece)
            seg[a:a + step] = piece.view(dt)

    def _check_extents(self) -> None:
        """What the per-spectrum reader would raise at the first spectrum it
        cannot read, before anything is read."""
        ix = self._index
        size = self._ibd.seek(0, io.SEEK_END)
        nbytes = ix.length * _CODE_ITEMSIZE[ix.code]
        short = (nbytes > 0) & (ix.offset + nbytes > size)
        bad = short[0] | short[1] | (ix.length[0] != ix.length[1])
        if not bad.any():
            return
        i = int(np.argmax(bad))
        for kind in (0, 1):
            if short[kind, i]:
                raise ImzMLParseError(
                    f"{self.ibd_path}: truncated read at offset "
                    f"{int(ix.offset[kind, i])}")
        raise ImzMLParseError(f"spectrum {i}: mz/intensity length mismatch")

    def close(self) -> None:
        self._ibd.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _ArrayRef(NamedTuple):
    """Where ImzMLWriter put one array."""
    offset: int
    length: int
    dtype: np.dtype


class ImzMLWriter:
    """Writes spectra to an imzML/ibd pair (both modes). Used by the synthetic
    fixture generator and by tests; also gives users a migration path off
    text dumps."""

    def __init__(self, path: str | Path, continuous: bool = False,
                 mz_dtype=np.float64, int_dtype=np.float32):
        self.imzml_path = Path(path)
        self.ibd_path = self.imzml_path.with_suffix(".ibd")
        self.continuous = continuous
        self.mz_dtype = np.dtype(mz_dtype)
        self.int_dtype = np.dtype(int_dtype)
        self._uuid = uuid_mod.uuid4()
        self._ibd = open(self.ibd_path, "wb")
        self._ibd.write(self._uuid.bytes)
        self._offset = 16
        self._shared_mz_ref: _ArrayRef | None = None
        self._entries: list[tuple[int, int, _ArrayRef, _ArrayRef]] = []

    def _write_array(self, data: np.ndarray, dtype: np.dtype) -> _ArrayRef:
        buf = np.ascontiguousarray(data, dtype=dtype).tobytes()
        self._ibd.write(buf)
        ref = _ArrayRef(offset=self._offset, length=len(data), dtype=dtype)
        self._offset += len(buf)
        return ref

    def add_spectrum(self, x: int, y: int, mzs: np.ndarray, ints: np.ndarray) -> None:
        if len(mzs) != len(ints):
            raise ValueError("mzs and ints must have equal length")
        if self.continuous:
            if self._shared_mz_ref is None:
                self._shared_mz_ref = self._write_array(mzs, self.mz_dtype)
            elif self._shared_mz_ref.length != len(mzs):
                raise ValueError("continuous mode requires identical m/z axes")
            mz_ref = self._shared_mz_ref
        else:
            mz_ref = self._write_array(mzs, self.mz_dtype)
        int_ref = self._write_array(ints, self.int_dtype)
        self._entries.append((x, y, mz_ref, int_ref))

    _DTYPE_CV = {
        np.dtype("<f4"): ('MS:1000521', '32-bit float'),
        np.dtype("<f8"): ('MS:1000523', '64-bit float'),
        np.dtype("<i4"): ('MS:1000519', '32-bit integer'),
        np.dtype("<i8"): ('MS:1000522', '64-bit integer'),
    }

    def close(self) -> None:
        self._ibd.close()
        mode_acc, mode_name = (
            (_CONTINUOUS, "continuous") if self.continuous else (_PROCESSED, "processed")
        )
        mz_cv, mz_cv_name = self._DTYPE_CV[self.mz_dtype]
        int_cv, int_cv_name = self._DTYPE_CV[self.int_dtype]
        xs = [e[0] for e in self._entries]
        ys = [e[1] for e in self._entries]
        out = []
        w = out.append
        w('<?xml version="1.0" encoding="ISO-8859-1"?>')
        w('<mzML xmlns="http://psi.hupo.org/ms/mzml" version="1.1">')
        w('  <cvList count="2">')
        w('    <cv id="MS" fullName="Proteomics Standards Initiative Mass Spectrometry Ontology"/>')
        w('    <cv id="IMS" fullName="Imaging MS Ontology"/>')
        w('  </cvList>')
        w('  <fileDescription><fileContent>')
        w(f'    <cvParam cvRef="IMS" accession="{mode_acc}" name="{mode_name}"/>')
        w(f'    <cvParam cvRef="IMS" accession="{_UUID}" name="universally unique identifier" '
          f'value="{{{self._uuid}}}"/>')
        w('  </fileContent></fileDescription>')
        w('  <referenceableParamGroupList count="2">')
        w('    <referenceableParamGroup id="mzArray">')
        w('      <cvParam cvRef="MS" accession="MS:1000514" name="m/z array"/>')
        w(f'      <cvParam cvRef="MS" accession="{mz_cv}" name="{mz_cv_name}"/>')
        w('    </referenceableParamGroup>')
        w('    <referenceableParamGroup id="intensityArray">')
        w('      <cvParam cvRef="MS" accession="MS:1000515" name="intensity array"/>')
        w(f'      <cvParam cvRef="MS" accession="{int_cv}" name="{int_cv_name}"/>')
        w('    </referenceableParamGroup>')
        w('  </referenceableParamGroupList>')
        w('  <scanSettingsList count="1"><scanSettings id="scan1">')
        w(f'    <cvParam cvRef="IMS" accession="IMS:1000042" name="max count of pixels x" '
          f'value="{max(xs) if xs else 0}"/>')
        w(f'    <cvParam cvRef="IMS" accession="IMS:1000043" name="max count of pixels y" '
          f'value="{max(ys) if ys else 0}"/>')
        w('  </scanSettings></scanSettingsList>')
        w('  <run id="run1">')
        w(f'  <spectrumList count="{len(self._entries)}">')
        for i, (x, y, mz_ref, int_ref) in enumerate(self._entries):
            w(f'    <spectrum id="spectrum={i}" index="{i}" defaultArrayLength="{mz_ref.length}">')
            w('      <scanList count="1"><scan>')
            w(f'        <cvParam cvRef="IMS" accession="{_POS_X}" name="position x" value="{x}"/>')
            w(f'        <cvParam cvRef="IMS" accession="{_POS_Y}" name="position y" value="{y}"/>')
            w('      </scan></scanList>')
            w('      <binaryDataArrayList count="2">')
            for group, ref in (("mzArray", mz_ref), ("intensityArray", int_ref)):
                w('        <binaryDataArray encodedLength="0">')
                w(f'          <referenceableParamGroupRef ref="{group}"/>')
                w(f'          <cvParam cvRef="IMS" accession="{_EXT_OFFSET}" '
                  f'name="external offset" value="{ref.offset}"/>')
                w(f'          <cvParam cvRef="IMS" accession="{_EXT_ARR_LEN}" '
                  f'name="external array length" value="{ref.length}"/>')
                w(f'          <cvParam cvRef="IMS" accession="IMS:1000104" '
                  f'name="external encoded length" value="{ref.length * ref.dtype.itemsize}"/>')
                w('          <binary/>')
                w('        </binaryDataArray>')
            w('      </binaryDataArrayList>')
            w('    </spectrum>')
        w('  </spectrumList>')
        w('  </run>')
        w('</mzML>')
        self.imzml_path.write_text("\n".join(out))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
