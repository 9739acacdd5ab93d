"""The columnar imzML ingest (ISSUE 31): ``ImzMLReader`` makes its index by
one pass of a pattern over the XML's bytes and ``SpectralDataset.from_imzml``
fills the CSR arrays from a few large reads of the ibd.  Held here against
the reader it replaced: the XML parser's index (``_parse_xml``, which stays
as the fallback) and a ``read_spectrum`` loop into ``from_arrays``: the
same five arrays, the same errors, over the layouts real files have; and the
mechanism itself, without a clock: read calls that do not grow with the
number of spectra, an allocation bounded by the chunk, the two spans and the
two counters on a served job.
"""

import io
import json
import sys
import tracemalloc
import urllib.request
import uuid
from pathlib import Path

import numpy as np
import pytest

from sm_distributed_tpu.io import imzml
from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.io.imzml import ImzMLParseError, ImzMLReader
from sm_distributed_tpu.utils import failpoints, tracing

_CV = {np.dtype("<f4"): "MS:1000521", np.dtype("<f8"): "MS:1000523",
       np.dtype("<i4"): "MS:1000519", np.dtype("<i8"): "MS:1000522"}
FIELDS = ("mzs_flat", "ints_flat", "row_ptr", "pixel_inds", "mask")


# ------------------------------------------------------- files of every layout
def _spectra(order, n_px=(5, 4), continuous=False, peaks=(20, 60), seed=3):
    """(coords, [(mzs, ints)]) of a ``n_px`` grid.  ``order``: "raster";
    "shuffled" (file order is not pixel order, and one pixel was never
    scanned); "unsorted" (raster, one row's m/z descending)."""
    rng = np.random.default_rng(seed)
    ncols, nrows = n_px
    coords = [(x + 1, y + 1) for y in range(nrows) for x in range(ncols)]
    shared = np.sort(rng.uniform(100, 900, peaks[0]))
    spectra = []
    for i in range(len(coords)):
        n = peaks[0] if continuous else int(rng.integers(*peaks))
        mzs = shared if continuous else np.sort(rng.uniform(100, 900, n))
        if i == 6 and not continuous:
            mzs = mzs[:0]                                 # an empty spectrum
        spectra.append((mzs, rng.exponential(5.0, mzs.size)))
    if order == "unsorted":
        if continuous:
            shared = shared[::-1].copy()
            spectra = [(shared, t) for _m, t in spectra]
        else:
            spectra[3] = (spectra[3][0][::-1].copy(), spectra[3][1])
    if order == "shuffled":
        keep = rng.permutation(len(coords))[:-1]          # one pixel missing
        coords = [coords[i] for i in keep]
        spectra = [spectra[i] for i in keep]
    return np.array(coords), spectra


def _write_pair(path, coords, spectra, *, layout="interleaved",
                continuous=False, mz_dtype="<f8", int_dtype="<f4",
                style="plain", uid=None, file_content=True):
    """An imzML/ibd pair.  ``layout``: "interleaved" (ImzMLWriter's: m/z then
    intensities, a spectrum at a time; a continuous file's shared axis
    first) or "two_run" (the benchmark's: every m/z array, then every
    intensity array; a continuous file's shared axis LAST).  ``style``
    varies how the XML says the same thing."""
    mz_dt, int_dt = np.dtype(mz_dtype), np.dtype(int_dtype)
    uid = uid or uuid.UUID(int=0x1234 + len(spectra))
    blob, refs = bytearray(uid.bytes), []

    def put(a, dt):
        off = len(blob)
        blob.extend(np.ascontiguousarray(a, dt).tobytes())
        return off, len(a)

    if continuous and layout == "interleaved":
        shared = put(spectra[0][0], mz_dt)
    if layout == "interleaved":
        for mzs, ints in spectra:
            mz_ref = shared if continuous else put(mzs, mz_dt)
            refs.append((mz_ref, put(ints, int_dt)))
    else:
        mz_refs = [None if continuous else put(m, mz_dt) for m, _t in spectra]
        int_refs = [put(t, int_dt) for _m, t in spectra]
        if continuous:
            mz_refs = [put(spectra[0][0], mz_dt)] * len(spectra)
        refs = list(zip(mz_refs, int_refs))
    Path(path).with_suffix(".ibd").write_bytes(bytes(blob))

    def cv(acc, value=None, name="n", flip=False):
        parts = [f'cvRef="X"', f'accession="{acc}"', f'name="{name}"']
        if value is not None:
            parts.append(f'value="{value}"')
        if flip:
            parts.reverse()
        return "<cvParam " + " ".join(parts) + "/>"

    def array(i, group, kind_acc, dt, ref):
        flip = style == "attrs_shuffled" or (style == "mixed" and i % 2)
        head = (cv(kind_acc) + cv(_CV[dt]) if style == "dtype_on_array"
                else f'<referenceableParamGroupRef ref="{group}"/>')
        return ('<binaryDataArray encodedLength="0">' + head
                + cv("IMS:1000102", ref[0], "external offset", flip)
                + cv("IMS:1000103", ref[1], "external array length", flip)
                + cv("IMS:1000104", ref[1] * dt.itemsize, "encoded", flip)
                + "<binary/></binaryDataArray>")

    out = ['<?xml version="1.0" encoding="ISO-8859-1"?>',
           '<mzML xmlns="http://psi.hupo.org/ms/mzml" version="1.1">',
           "<fileDescription><fileContent>"]
    if file_content:
        out.append(cv("IMS:1000030" if continuous else "IMS:1000031"))
    out += [cv("IMS:1000080", "{%s}" % uid, "uuid"),
            "</fileContent></fileDescription>",
            '<referenceableParamGroupList count="2">',
            '<referenceableParamGroup id="mzArray">' + cv("MS:1000514")
            + cv(_CV[mz_dt]) + "</referenceableParamGroup>",
            '<referenceableParamGroup id="intensityArray">' + cv("MS:1000515")
            + cv(_CV[int_dt]) + "</referenceableParamGroup>",
            "</referenceableParamGroupList>",
            f'<run id="r"><spectrumList count="{len(spectra)}">']
    for i, ((x, y), (mz_ref, int_ref)) in enumerate(zip(coords, refs)):
        flip = style == "attrs_shuffled" or (style == "mixed" and i % 2)
        pos = (cv("IMS:1000050", x, "position x", flip)
               + cv("IMS:1000051", y, "position y", flip))
        if style == "no_position" and i == 3:
            pos = cv("IMS:1000050", x, "position x")
        if style == "tic":                    # a value the reader never reads
            pos += cv("MS:1000285", f"{1.5e3 * (i + 1):.6e}", "total ion current")
        if style == "first_differs" and i == 0:
            pos += cv("MS:1000285", "1.0", "total ion current")
        out.append(
            f'<spectrum id="s={i}" index="{i}" defaultArrayLength="{mz_ref[1]}">'
            f'<scanList count="1"><scan>{pos}</scan></scanList>'
            '<binaryDataArrayList count="2">'
            + array(i, "mzArray", "MS:1000514", mz_dt, mz_ref)
            + array(i, "intensityArray", "MS:1000515", int_dt, int_ref)
            + "</binaryDataArrayList></spectrum>")
        if style == "comment" and i == 1:
            out.append("<!-- a note between two spectra -->")
    out.append("</spectrumList></run></mzML>")
    Path(path).write_text("\n".join(out))
    return Path(path)


# ------------------------------------------ the reader this one is held against
def _xml_reader(path) -> ImzMLReader:
    """A reader whose index the XML parser made, whatever the file."""
    real = ImzMLReader._scan_index
    ImzMLReader._scan_index = lambda self, data: None
    try:
        return ImzMLReader(path)
    finally:
        ImzMLReader._scan_index = real


def _per_spectrum(path) -> SpectralDataset:
    """The ingest as it was: the XML parser's index, two reads a spectrum."""
    with _xml_reader(path) as rd:
        assert rd.index_kind == "xml"
        spectra = [rd.read_spectrum(i) for i in range(rd.n_spectra)]
        return SpectralDataset.from_arrays(rd.coordinates, spectra)


def _assert_same_dataset(got: SpectralDataset, want: SpectralDataset):
    assert (got.nrows, got.ncols) == (want.nrows, want.ncols)
    for f in FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def _assert_same_index(path):
    with ImzMLReader(path) as rd, _xml_reader(path) as ref:
        assert (rd.continuous, rd.uuid) == (ref.continuous, ref.uuid)
        for a, b in zip(rd._index, ref._index):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        return rd.index_kind


@pytest.fixture(params=["shipped", "small"])
def plan_sizes(request, monkeypatch):
    """The plan's constants as shipped (a small file is all short runs, one
    chunk) and cut down so that a small file has long runs, many chunks and
    many gather batches."""
    if request.param == "small":
        monkeypatch.setattr(imzml, "_IBD_RUN_BYTES", 2048)
        monkeypatch.setattr(imzml, "_IBD_CHUNK_BYTES", 4096)
        monkeypatch.setattr(imzml, "_IBD_MIN_CHUNK_BYTES", 1024)
    return request.param


@pytest.mark.parametrize("order", ["raster", "shuffled", "unsorted"])
@pytest.mark.parametrize("layout", ["interleaved", "two_run"])
@pytest.mark.parametrize("int_dtype", ["<f4", "<f8"])
@pytest.mark.parametrize("mz_dtype", ["<f8", "<f4"])
@pytest.mark.parametrize("continuous", [False, True],
                         ids=["processed", "continuous"])
def test_bulk_ingest_equals_the_per_spectrum_reader(
        tmp_path, plan_sizes, continuous, mz_dtype, int_dtype, layout, order):
    coords, spectra = _spectra(order, continuous=continuous)
    path = _write_pair(tmp_path / "a.imzML", coords, spectra, layout=layout,
                       continuous=continuous, mz_dtype=mz_dtype,
                       int_dtype=int_dtype)
    before = imzml.ingest_events()
    got = SpectralDataset.from_imzml(path)
    after = imzml.ingest_events()
    assert after["scan"] == before["scan"] + 1            # the scan engaged
    assert after["xml"] == before["xml"]
    _assert_same_dataset(got, _per_spectrum(path))
    assert _assert_same_index(path) == "scan"
    if order == "unsorted":
        assert all(np.all(np.diff(got.mzs_flat[a:b]) >= 0)
                   for a, b in zip(got.row_ptr[:-1], got.row_ptr[1:]))


def test_imzml_writer_files_take_the_scan_and_integer_dtypes_cast(
        tmp_path, plan_sizes):
    """ImzMLWriter's own output (the fixtures', the smokes') and integer
    arrays, which ``astype`` casts as the per-spectrum reader did."""
    coords, spectra = _spectra("shuffled")
    path = tmp_path / "w.imzML"
    with imzml.ImzMLWriter(path, mz_dtype=np.int64, int_dtype=np.int32) as wr:
        for (x, y), (mzs, ints) in zip(coords, spectra):
            wr.add_spectrum(int(x), int(y), np.round(mzs * 1000), ints * 100)
    assert _assert_same_index(path) == "scan"
    _assert_same_dataset(SpectralDataset.from_imzml(path), _per_spectrum(path))


@pytest.mark.parametrize("style,index", [
    ("attrs_shuffled", "scan"),     # value= before accession=, every block
    ("dtype_on_array", "scan"),     # kind and dtype on the array, no group ref
    ("tic", "scan"),                # a float cvParam that differs a spectrum
    ("mixed", "xml"),               # every other block in another order
    ("comment", "xml"),             # something between two blocks
    ("first_differs", "xml"),       # block 0 is not what the others are
])
@pytest.mark.parametrize("layout", ["interleaved", "two_run"])
def test_same_columns_however_the_xml_says_it(tmp_path, layout, style, index):
    coords, spectra = _spectra("shuffled")
    path = _write_pair(tmp_path / "s.imzML", coords, spectra, layout=layout,
                       style=style)
    before = imzml.ingest_events()
    assert _assert_same_index(path) == index
    after = imzml.ingest_events()
    # one ingest by the file's own index, one by the forced XML parser
    assert after[index] - before[index] == (2 if index == "xml" else 1)
    _assert_same_dataset(SpectralDataset.from_imzml(path), _per_spectrum(path))


def test_block_pattern_declines_what_it_cannot_pin_down():
    ok = (b'<spectrum id="a"><cvParam accession="IMS:1000050" value="3"/>'
          b"</spectrum>")
    pattern, roles = imzml._block_pattern(ok)
    assert roles == ["x"] and pattern.fullmatch(ok.replace(b'"3"', b'"41"'))
    assert not pattern.fullmatch(ok.replace(b'"3"', b'"4.0"'))
    for bad in (ok.replace(b'"3"', b'"3.0"'),             # not a plain integer
                ok.replace(b'value="3"', b"value='3'"),   # single quotes
                ok.replace(b"</spectrum>", b"text</spectrum>"),
                ok.replace(b"<cvParam", b"<!-- c --><cvParam"),
                ok.replace(b"/></", b"/>" + ok[17:-11] + b"</")):  # x twice
        assert imzml._block_pattern(bad) is None, bad


# ------------------------------------------------------------------ the errors
def _message(fn, *args):
    with pytest.raises(ImzMLParseError) as err:
        fn(*args)
    return str(err.value)


@pytest.mark.parametrize("layout", ["interleaved", "two_run"])
@pytest.mark.parametrize("fault,match", [
    ("truncated_ibd", "truncated read at offset"),
    ("xml_longer_than_data", "truncated read at offset"),
    ("lengths_disagree", "spectrum 7: mz/intensity length mismatch"),
    ("uuid", "does not match imzML UUID"),
    ("no_position", "spectrum 3 missing scan position"),
    ("no_file_content", "neither continuous"),
])
def test_faulty_files_raise_what_the_per_spectrum_reader_raised(
        tmp_path, plan_sizes, layout, fault, match):
    coords, spectra = _spectra("raster")
    path = _write_pair(
        tmp_path / "f.imzML", coords, spectra, layout=layout,
        style="no_position" if fault == "no_position" else "plain",
        file_content=fault != "no_file_content")
    ibd = path.with_suffix(".ibd")
    if fault == "truncated_ibd":
        ibd.write_bytes(ibd.read_bytes()[:-300])
    if fault == "uuid":
        raw = bytearray(ibd.read_bytes())
        raw[3] ^= 0xFF
        ibd.write_bytes(bytes(raw))
    if fault in ("xml_longer_than_data", "lengths_disagree"):
        # the last spectrum's m/z array 1000 elements longer than the file
        # holds / the eighth's one longer than its intensities
        which, more = ((len(spectra) - 1, 1000)
                       if fault == "xml_longer_than_data" else (7, 1))
        n = len(spectra[which][0])
        text = path.read_text()
        block = text.index(f'<spectrum id="s={which}"')
        at = text.index(f'name="external array length" value="{n}"', block)
        path.write_text(text[:at] + text[at:].replace(
            f'value="{n}"', f'value="{n + more}"', 1))
    got = _message(SpectralDataset.from_imzml, path)
    assert match in got
    assert got == _message(_per_spectrum, path)


def test_malformed_xml_is_still_a_parse_error(tmp_path):
    import xml.etree.ElementTree as ET

    coords, spectra = _spectra("raster")
    path = _write_pair(tmp_path / "cut.imzML", coords, spectra)
    path.write_text(path.read_text()[:-30])               # upload cut short
    with pytest.raises(ET.ParseError):
        ImzMLReader(path)


# ------------------------------------------ the mechanism, without a clock
class _CountingFile:
    """The ibd, counting what is asked of it."""

    opened: list = []

    def __init__(self, path, mode):
        self._f = io.open(path, mode)
        self.calls, self.nbytes = [], 0
        _CountingFile.opened.append(self)

    def read(self, n=-1):
        self.calls.append(("read", n))
        return self._f.read(n)

    def readinto(self, target):
        got = self._f.readinto(target)
        self.calls.append(("readinto", got))
        self.nbytes += got
        return got

    def __getattr__(self, name):
        return getattr(self._f, name)


def _uniform(n_spectra, peaks, seed=5):
    rng = np.random.default_rng(seed)
    coords = np.array([(i % 32 + 1, i // 32 + 1) for i in range(n_spectra)])
    spectra = [(np.sort(rng.uniform(100, 900, peaks)),
                rng.exponential(5.0, peaks)) for _ in range(n_spectra)]
    return coords, spectra


def _ingest_counted(path, monkeypatch, tmp_path, name):
    """(dataset, the counting ibd, the ingest's two spans' attrs)."""
    _CountingFile.opened = []
    monkeypatch.setattr(imzml, "open", _CountingFile, raising=False)
    ctx = tracing.new_trace(job_id=name, trace_dir=tmp_path / "traces")
    with tracing.attach(ctx):
        ds = SpectralDataset.from_imzml(path)
    monkeypatch.undo()
    tracing.close_file(ctx.file)
    spans = {r["name"]: r["attrs"] for r in tracing.read_trace(ctx.file)
             if r["kind"] == "span"}
    (ibd,) = _CountingFile.opened
    return ds, ibd, spans


def test_read_calls_do_not_grow_with_the_number_of_spectra(
        tmp_path, monkeypatch):
    reads = {}
    for n in (64, 256, 1024):
        coords, spectra = _uniform(n, 200)
        path = _write_pair(tmp_path / f"n{n}.imzML", coords, spectra,
                           layout="two_run")
        ds, ibd, spans = _ingest_counted(path, monkeypatch, tmp_path, f"n{n}")
        _assert_same_dataset(ds, SpectralDataset.from_arrays(
            coords, [(m, t.astype(np.float32)) for m, t in spectra]))
        assert ibd.calls[0] == ("read", 16)               # the UUID, once
        assert all(kind == "readinto" for kind, _n in ibd.calls[1:])
        attrs = spans["read_ibd"]
        reads[n] = attrs["reads"]
        assert reads[n] == len(ibd.calls) - 1
        assert attrs["bytes"] == ibd.nbytes == n * 200 * 12
        assert attrs["runs"] == 2
        assert spans["parse_index"] == {
            "spectra": n, "index": "scan",
            "xml_bytes": path.stat().st_size}
    # an eighth of the data a chunk, half of a chunk a buffer: never more
    # than 16 + one a kind, and two reads once both runs are long
    assert max(reads.values()) <= 18, reads
    assert reads[1024] == 2 and reads[256] < reads[64], reads


def test_interleaved_reads_are_bounded_by_the_chunk(tmp_path, monkeypatch):
    coords, spectra = _uniform(512, 300)
    path = _write_pair(tmp_path / "i.imzML", coords, spectra)
    monkeypatch.setattr(imzml, "_IBD_CHUNK_BYTES", 64 << 10)
    ds, ibd, spans = _ingest_counted(path, monkeypatch, tmp_path, "i")
    attrs = spans["read_ibd"]
    assert attrs["chunk_bytes"] == 64 << 10
    assert attrs["runs"] == 2 * 512                       # nothing abuts
    assert attrs["bytes"] == ibd.nbytes == 512 * 300 * 12
    assert attrs["reads"] <= attrs["bytes"] // (attrs["chunk_bytes"] // 2) + 2
    assert attrs["reads"] == len(ibd.calls) - 1 < 512 // 4
    _assert_same_dataset(ds, _per_spectrum(path))


@pytest.mark.parametrize("layout,mz_dtype", [
    ("two_run", "<f8"), ("two_run", "<f4"), ("interleaved", "<f8")])
def test_bulk_read_allocates_at_most_one_chunk(
        tmp_path, monkeypatch, layout, mz_dtype):
    """Straight to their place (no allocation), through the buffer and a
    cast, through the buffer and the gather: none allocates more than the
    chunk it reports beside the arrays it fills."""
    coords, spectra = _uniform(256, 2000)                 # 6 MB of data
    path = _write_pair(tmp_path / "m.imzML", coords, spectra, layout=layout,
                       mz_dtype=mz_dtype)
    chunk = 256 << 10
    monkeypatch.setattr(imzml, "_IBD_CHUNK_BYTES", chunk)
    with ImzMLReader(path) as rd:
        total = int(rd.spectrum_lengths().sum())
        mzs = np.empty(total, np.float64)
        ints = np.empty(total, np.float32)
        starts = np.arange(256) * 2000
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        rd.read_into(mzs, ints, starts)
        _cur, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
    # the plan's own columns: a few int64 a spectrum, not a peak
    assert peak - base <= chunk + 256 * 8 * 24, (peak - base, chunk)
    want = _per_spectrum(path)
    np.testing.assert_array_equal(mzs, want.mzs_flat)
    np.testing.assert_array_equal(ints, want.ints_flat)


def test_failpoint_fires_once_a_read_call(tmp_path):
    coords, spectra = _uniform(1024, 200)
    path = _write_pair(tmp_path / "fp.imzML", coords, spectra,
                       layout="two_run")                  # two read calls
    try:
        failpoints.configure("io.ibd_read=raise:OSError@3")
        SpectralDataset.from_imzml(path)                  # never reached
        failpoints.configure("io.ibd_read=raise:OSError@2")
        with pytest.raises(OSError, match="io.ibd_read"):
            SpectralDataset.from_imzml(path)
        failpoints.configure("io.imzml_parse=raise:OSError@1")
        with pytest.raises(OSError, match="io.imzml_parse"):
            SpectralDataset.from_imzml(path)
    finally:
        failpoints.configure(None)
    before = imzml.ingest_events()["ibd_reads"]
    with ImzMLReader(path) as rd:
        rd.read_spectrum(5)
        assert rd.reads == 2
    assert imzml.ingest_events()["ibd_reads"] == before + 2


# ------------------------------- the served path: /metrics and the job's trace
def test_served_job_traces_the_ingest_and_counts_it(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from scripts import trace_report
    from scripts.load_sweep import Harness

    path, truth = generate_synthetic_dataset(
        tmp_path / "ds", nrows=6, ncols=6, present_fraction=0.5,
        noise_peaks=30, seed=31)
    h = Harness(tmp_path, "served")
    try:
        def count(text, line):
            return float(text.split("\n" + line + " ")[1].split()[0])

        texts, traces = [h.metrics_text()], []
        for msg_id in ("up-0", "up-1"):                   # a miss, then a hit
            status, _hd, body = h.submit({
                "ds_id": "served", "msg_id": msg_id, "input_path": str(path),
                "formulas": truth.formulas[:6],
                "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
            assert status == 202, body
            rows = h.wait_terminal([msg_id], timeout_s=120.0)
            assert rows[msg_id]["state"] == "done", rows[msg_id]
            texts.append(h.metrics_text())
            with urllib.request.urlopen(
                    f"{h.base}/jobs/{msg_id}/trace?raw=1", timeout=30.0) as r:
                traces.append(json.loads(r.read())["records"])
    finally:
        h.shutdown()
    scan = 'sm_imzml_ingest_total{index="scan"}'
    xml = 'sm_imzml_ingest_total{index="xml"}'
    assert [count(t, scan) - count(texts[0], scan) for t in texts] == [0, 1, 1]
    assert [count(t, xml) - count(texts[0], xml) for t in texts] == [0, 0, 0]
    reads = [count(t, "sm_imzml_ibd_reads_total") for t in texts]
    assert reads[2] == reads[1]

    def spans(records, name):
        return [r for r in records if r["kind"] == "span" and r["name"] == name]

    miss, hit = traces
    (phase,) = spans(miss, "read_dataset")
    (index,) = spans(miss, "parse_index")
    (read,) = spans(miss, "read_ibd")
    assert index["parent_id"] == read["parent_id"] == phase["span_id"]
    assert index["attrs"]["index"] == "scan" and index["attrs"]["spectra"] == 36
    # 26 KB of data in a buffer of 8 KiB (half the smallest chunk)
    assert read["attrs"]["reads"] == reads[1] - reads[0] <= 8
    assert read["attrs"]["runs"] == 72
    assert index["dur"] + read["dur"] <= phase["dur"]
    assert spans(hit, "read_dataset") and not spans(hit, "parse_index") \
        and not spans(hit, "read_ibd")
    # the report lists the two spans under the phase they split
    lines = trace_report.render(trace_report.summarize(miss)).splitlines()
    at = next(i for i, ln in enumerate(lines)
              if ln.strip().startswith("read_dataset"))
    assert [ln.split()[0] for ln in lines[at + 1:at + 3]] == [
        "parse_index", "read_ibd"]
