"""Live-acquisition streaming tests (ISSUE 19): the crash-safe chunk log
(duplicate idempotency, out-of-order seqs, CRC conflict detection, torn
trailing chunks on restart, fenced append rejection at the manifest-commit
seam), provisional-FDR monotone coverage through the partial channel, the
stream idle timeout + absolute-deadline exemption + watchdog-feeding
regressions, the drain hand-off to a peer resuming from the streaming
checkpoint, and bit-identical (``check_exact``) convergence of the
streaming path to the one-shot batch result on both backends."""

import dataclasses
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from sm_distributed_tpu.engine.daemon import annotate_callback
from sm_distributed_tpu.engine.storage import read_result_tables
from sm_distributed_tpu.engine.stream import (
    ChunkConflictError,
    ChunkLog,
    StreamEmptyError,
    StreamGapError,
    StreamIngest,
)
from sm_distributed_tpu.io.dataset import SpectralDataset
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset
from sm_distributed_tpu.io.imzml import ImzMLReader
from sm_distributed_tpu.service import AnnotationService
from sm_distributed_tpu.utils.config import (
    ServiceConfig,
    SMConfig,
    StreamConfig,
)

ADDUCTS = {"isotope_generation": {"adducts": ["+H"]}}


@pytest.fixture(scope="module")
def fixture_path(tmp_path_factory):
    # off-lattice 9x11 spheroid: both dims miss the shape-bucket lattice,
    # so streaming convergence is tested through the pad/bucket path too
    out = tmp_path_factory.mktemp("ds_stream")
    path, truth = generate_synthetic_dataset(
        out, nrows=9, ncols=11, formulas=None, present_fraction=0.5,
        noise_peaks=12, seed=41)
    return path, truth


def _read_spectra(path):
    """All (coords, (mzs, ints)) pairs from the fixture, file order."""
    with ImzMLReader(path) as rd:
        coords = rd.coordinates.tolist()
        spectra = [tuple(a.tolist() for a in rd.read_spectrum(i))
                   for i in range(rd.n_spectra)]
    return coords, spectra


def _chunked(coords, spectra, n_chunks):
    """Split the acquisition into n_chunks contiguous pixel runs."""
    edges = np.linspace(0, len(coords), n_chunks + 1).astype(int)
    out = []
    for seq in range(n_chunks):
        lo, hi = edges[seq], edges[seq + 1]
        out.append((seq, coords[lo:hi], spectra[lo:hi]))
    return out


# ------------------------------------------------------------- chunk log
def test_chunk_log_duplicate_and_out_of_order(tmp_path):
    log = ChunkLog(tmp_path, "ds1")
    c0 = ([[0, 0], [0, 1]], [([100.0, 200.0], [1.0, 2.0]), ([150.0], [3.0])])
    out = log.append(0, *c0)
    assert out == {"seq": 0, "committed": True, "duplicate": False}
    # duplicate delivery (lost ack): idempotent, nothing rewritten
    before = sorted(p.name for p in (tmp_path / "ds1").iterdir())
    out = log.append(0, *c0)
    assert out["duplicate"] is True
    assert sorted(p.name for p in (tmp_path / "ds1").iterdir()) == before
    # same seq, different payload: a real conflict, not idempotent
    with pytest.raises(ChunkConflictError):
        log.append(0, [[0, 0], [0, 1]],
                   [([100.0], [9.0]), ([150.0], [3.0])])
    # out-of-order arrival is fine; finish requires the gap filled
    log.append(2, [[1, 0]], [([120.0], [5.0])])
    with pytest.raises(StreamGapError, match=r"missing chunk seqs \[1\]"):
        log.finish()
    log.append(1, [[0, 2]], [([130.0], [4.0])])
    assert log.finish() == {"finished": True, "duplicate": False, "chunks": 3}
    assert log.finish()["duplicate"] is True          # finish is idempotent
    with pytest.raises(StreamGapError):               # post-finish append
        log.append(3, [[2, 0]], [([140.0], [6.0])])


def test_chunk_log_concurrent_appends_lose_nothing(tmp_path):
    """Regression: the manifest read-modify-write must be serialized (a
    per-dataset flock) — the admin API is a ThreadingHTTPServer and
    replicas share the stream root, so two concurrent appends that each
    read the old manifest would otherwise ack chunks whose entries then
    vanish, wedging finish() forever (the client never re-posts an acked
    seq)."""
    import concurrent.futures

    n_chunks = 24
    def post(seq):
        # a fresh ChunkLog per call models independent handler threads /
        # replica processes — no shared in-memory state to hide behind
        return ChunkLog(tmp_path, "ds1").append(
            seq, [[seq, 0]], [([100.0 + seq], [1.0])])

    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
        outs = list(ex.map(post, range(n_chunks)))
    assert all(o["committed"] and not o["duplicate"] for o in outs)
    log = ChunkLog(tmp_path, "ds1")
    # every acked append survives in the manifest: no lost entries
    assert log.committed_seqs() == list(range(n_chunks))
    assert log.finish()["finished"] is True
    ds = log.assemble_dataset()
    assert ds.n_spectra == n_chunks


def test_chunk_log_concurrent_same_seq_appends_commit_once(tmp_path):
    """Concurrent same-seq appends (redelivery racing the original) must
    commit exactly once with an uncorrupted chunk — unique tmp names plus
    the lock keep interleaved writers from publishing torn bytes."""
    import concurrent.futures

    payload = ([[0, 0], [0, 1]],
               [([100.0, 200.0], [1.0, 2.0]), ([150.0], [3.0])])

    def post(_):
        return ChunkLog(tmp_path, "ds1").append(0, *payload)

    with concurrent.futures.ThreadPoolExecutor(max_workers=6) as ex:
        outs = list(ex.map(post, range(6)))
    assert all(o["committed"] for o in outs)
    assert sum(not o["duplicate"] for o in outs) == 1  # exactly-once
    log = ChunkLog(tmp_path, "ds1")
    assert log.committed_seqs() == [0]
    coords, spectra = log.load_chunk(0)                # CRC-verified read
    assert coords.tolist() == [[0, 0], [0, 1]]


def test_chunk_log_finish_empty_rejected(tmp_path):
    """finish() with zero committed chunks must not seal an empty
    acquisition — [] passes the gap check vacuously, but the batch engine
    cannot annotate zero pixels."""
    log = ChunkLog(tmp_path, "ds1")
    with pytest.raises(StreamEmptyError, match="zero committed chunks"):
        log.finish()
    assert not log.finished()
    # the first real chunk unblocks the seal
    log.append(0, [[0, 0]], [([100.0], [1.0])])
    assert log.finish()["finished"] is True


def test_chunk_log_torn_trailing_chunk_on_restart(tmp_path):
    log = ChunkLog(tmp_path, "ds1")
    log.append(0, [[0, 0]], [([100.0], [1.0])])
    d = tmp_path / "ds1"
    # a crash between chunk write and manifest commit leaves (a) a torn
    # append tmp and (b) a renamed-but-unpublished chunk file
    (d / ".chunk_000001.npz.tmp").write_bytes(b"torn garbage")
    (d / "chunk_000001.npz").write_bytes(b"stranded, never committed")
    log2 = ChunkLog(tmp_path, "ds1")                  # restart
    assert log2.sweep_debris(max_age_s=0.0) == 1      # the tmp, nothing else
    assert log2.committed_seqs() == [0]               # manifest never lied
    assert not (d / ".chunk_000001.npz.tmp").exists()
    # the unacked chunk is re-posted: it overwrites the stranded file and
    # commits cleanly — the log reads back whole
    log2.append(1, [[0, 1]], [([150.0], [3.0])])
    assert log2.committed_seqs() == [0, 1]
    coords, spectra = log2.load_chunk(1)
    assert coords.tolist() == [[0, 1]]


def test_chunk_log_crc_detects_corruption(tmp_path):
    log = ChunkLog(tmp_path, "ds1")
    log.append(0, [[0, 0]], [([100.0, 200.0], [1.0, 2.0])])
    p = log.chunk_path(0)
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF                        # flip one byte
    p.write_bytes(bytes(raw))
    with pytest.raises(OSError):
        ChunkLog(tmp_path, "ds1").load_chunk(0)


def test_fenced_append_rejected_at_manifest_seam(tmp_path):
    """A fenced replica (a peer took over its shards) must not advance the
    manifest: the fence fires immediately before the manifest commit, so
    the chunk is never published — equivalent to a pre-commit crash."""
    log = ChunkLog(tmp_path, "ds1")
    log.append(0, [[0, 0]], [([100.0], [1.0])])

    def fence():
        raise RuntimeError("fenced: shards reassigned")

    with pytest.raises(RuntimeError, match="fenced"):
        log.append(1, [[0, 1]], [([150.0], [3.0])], fence=fence)
    assert log.committed_seqs() == [0]                # not published
    with pytest.raises(RuntimeError, match="fenced"):
        log.finish(fence=fence)
    assert not log.finished()
    # the surviving owner retries the same chunk: clean, exactly-once
    assert log.append(1, [[0, 1]], [([150.0], [3.0])])["duplicate"] is False
    assert log.committed_seqs() == [0, 1]
    assert log.finish()["finished"] is True


def test_assembled_dataset_bit_identical_to_from_imzml(fixture_path, tmp_path):
    """from_arrays over chunked spectra (arbitrary arrival order) and the
    batch from_imzml reader build the SAME canonical CSR, bit for bit —
    the invariant the streaming-vs-batch convergence rests on."""
    path, _truth = fixture_path
    coords, spectra = _read_spectra(path)
    log = ChunkLog(tmp_path, "ds1")
    chunks = _chunked(coords, spectra, 4)
    for seq, cc, ss in reversed(chunks):              # worst-case ordering
        log.append(seq, cc, ss)
    log.finish()
    got = log.assemble_dataset()
    want = SpectralDataset.from_imzml(path)
    for attr in ("mzs_flat", "ints_flat", "pixel_inds", "row_ptr", "mask"):
        assert np.array_equal(getattr(got, attr), getattr(want, attr)), attr
    assert (got.nrows, got.ncols) == (want.nrows, want.ncols)


def test_stream_ingest_counters(tmp_path):
    from sm_distributed_tpu.service.metrics import MetricsRegistry

    m = MetricsRegistry()
    ing = StreamIngest(tmp_path, metrics=m)
    ing.append_chunk("ds1", 0, [[0, 0], [0, 1]],
                     [([100.0], [1.0]), ([150.0], [3.0])])
    ing.append_chunk("ds1", 0, [[0, 0], [0, 1]],
                     [([100.0], [1.0]), ([150.0], [3.0])])   # duplicate
    text = m.expose()
    assert "sm_stream_chunks_total 1" in text         # duplicates don't count
    assert "sm_stream_pixels_total 2" in text
    st = ing.status("ds1")
    assert st["chunks"] == 1 and st["pixels"] == 2 and not st["finished"]


# ------------------------------------------------------- service harness
def _fast_cfg(**kw) -> ServiceConfig:
    base = dict(workers=2, poll_interval_s=0.02, job_timeout_s=60.0,
                max_attempts=3, backoff_base_s=0.05, backoff_max_s=0.5,
                backoff_jitter=0.0, heartbeat_interval_s=0.05,
                stale_after_s=2.0, drain_timeout_s=15.0, cancel_grace_s=5.0,
                http_port=0,
                stream=StreamConfig(idle_timeout_s=30.0,
                                    poll_interval_s=0.02,
                                    rescore_min_chunks=1))
    base.update(kw)
    return ServiceConfig(**base)


def _sm(tmp_path, backend="numpy_ref", **service_kw) -> SMConfig:
    return dataclasses.replace(
        SMConfig.from_dict({
            "backend": backend,
            "fdr": {"decoy_sample_size": 3, "seed": 2},
            "storage": {"results_dir": str(tmp_path / "res")},
            "work_dir": str(tmp_path / "work"),
        }),
        service=_fast_cfg(**service_kw))


def _service(tmp_path, sm):
    svc = AnnotationService(tmp_path / "q", annotate_callback(sm),
                            sm_config=sm)
    svc.start()
    host, port = svc.api.address
    return svc, f"http://{host}:{port}"


def _req(base, path, method="GET", payload=None):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(base + path, method=method, data=data,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10.0) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _wait_job(base, msg_id, want_states, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        status, body = _req(base, f"/jobs/{msg_id}")
        if status == 200 and body.get("state") in want_states:
            return body
        time.sleep(0.05)
    raise AssertionError(f"job {msg_id} never reached {want_states}: {body}")


def _post_chunk(base, ds_id, seq, coords, spectra):
    return _req(base, f"/datasets/{ds_id}/pixels", "POST", {
        "seq": seq, "coords": coords,
        "mzs": [list(s[0]) for s in spectra],
        "ints": [list(s[1]) for s in spectra]})


def _report(res_dir, ds_id):
    """annotations, all metrics and the decoy assignment they were ranked
    by (``RESULT_TABLES``)."""
    tables = read_result_tables(res_dir / ds_id)
    assert len(tables) == 3 and len(tables[2]) > 0
    return tables


# ----------------------------------------------- streaming-vs-batch e2e
@pytest.mark.parametrize("backend", ["numpy_ref", "jax_tpu"])
def test_stream_converges_bit_identical_to_batch(fixture_path, tmp_path,
                                                 backend):
    """The tentpole invariant: chunked live ingest + provisional re-ranks
    + POST finish produce EXACTLY the one-shot batch report
    (``check_exact=True``), with monotone provisional coverage and the
    sm_stream_* telemetry along the way."""
    path, truth = fixture_path
    formulas = truth.formulas[:8]
    sm = _sm(tmp_path, backend=backend)
    svc, base = _service(tmp_path, sm)
    try:
        # batch golden through the same service
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "golden", "input_path": str(path),
            "formulas": formulas, "ds_config": ADDUCTS})
        assert status == 202
        _wait_job(base, body["msg_id"], ("done",))

        # live acquisition: submit first, then feed 3 chunks
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "live", "mode": "stream",
            "formulas": formulas, "ds_config": ADDUCTS})
        assert status == 202
        msg_id = body["msg_id"]
        coords, spectra = _read_spectra(path)
        seen_pixels = []
        for seq, cc, ss in _chunked(coords, spectra, 3):
            status, out = _post_chunk(base, "live", seq, cc, ss)
            assert status == 200 and out["committed"], out
            # provisional FDR: wait for the re-rank covering this chunk
            deadline = time.time() + 30.0
            while time.time() < deadline:
                rec = _req(base, f"/jobs/{msg_id}")[1]
                part = rec.get("partial") or {}
                if (part.get("stream") or {}).get("chunks", 0) >= seq + 1:
                    break
                time.sleep(0.05)
            else:
                raise AssertionError(f"no provisional re-rank for seq {seq}")
            assert part["provisional"] is True
            assert part["n_ions"] > 0 and "fdr_10pct" in part
            seen_pixels.append(part["stream"]["pixels"])
        # coverage is monotone in committed chunks
        assert seen_pixels == sorted(seen_pixels)
        assert seen_pixels[-1] == len(coords)

        status, out = _req(base, "/datasets/live/finish", "POST", {})
        assert status == 200 and out["finished"], out
        _wait_job(base, msg_id, ("done",))

        got = _report(tmp_path / "res", "live")
        want = _report(tmp_path / "res", "golden")
        for g, w in zip(got, want):
            pd.testing.assert_frame_equal(g, w, check_exact=True)

        text = svc.metrics.expose()
        assert "sm_stream_chunks_total 3" in text
        assert f"sm_stream_pixels_total {len(coords)}" in text
        assert "sm_stream_reranks_total" in text
        slo = _req(base, "/slo")[1]
        assert "stream_partial" in slo["slos"]
        assert slo["slos"]["stream_partial"]["count"] >= 1
    finally:
        svc.shutdown()


def test_stream_idle_timeout_and_deadline_exemption(fixture_path, tmp_path):
    """Satellite 1: a stream job ignores the submit-pinned absolute
    deadline (acquisition length is unknowable at submit time) and is
    instead cancelled terminally by the chunk-silence idle timeout."""
    path, truth = fixture_path
    sm = _sm(tmp_path, stream=StreamConfig(idle_timeout_s=1.0,
                                           poll_interval_s=0.02))
    svc, base = _service(tmp_path, sm)
    try:
        # deadline_s would kill a batch job in 0.2 s; the stream job must
        # outlive it and die later to the idle timeout instead
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "live", "mode": "stream", "deadline_s": 0.2,
            "formulas": truth.formulas[:3], "ds_config": ADDUCTS})
        assert status == 202
        t0 = time.time()
        rec = _wait_job(base, body["msg_id"], ("cancelled",), timeout_s=30.0)
        assert time.time() - t0 >= 0.8                 # not the deadline
        assert "idle" in rec["error"]
        assert rec["attempts"] == 1                    # terminal, no retries
    finally:
        svc.shutdown()


def test_stream_idle_timeout_fires_below_rescore_threshold(fixture_path,
                                                           tmp_path):
    """Regression: with ``rescore_min_chunks > 1``, sub-threshold pending
    chunks must NOT refresh the idle clock every tick — a client that
    commits one chunk and dies would otherwise keep the job alive
    forever.  The idle clock resets only on a genuinely new commit."""
    path, truth = fixture_path
    sm = _sm(tmp_path, stream=StreamConfig(idle_timeout_s=1.0,
                                           poll_interval_s=0.02,
                                           rescore_min_chunks=4))
    svc, base = _service(tmp_path, sm)
    try:
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "live", "mode": "stream",
            "formulas": truth.formulas[:3], "ds_config": ADDUCTS})
        assert status == 202
        coords, spectra = _read_spectra(path)
        # one chunk — below the re-score threshold — then client death
        assert _post_chunk(base, "live", 0, coords[:2], spectra[:2])[0] == 200
        rec = _wait_job(base, body["msg_id"], ("cancelled",), timeout_s=30.0)
        assert "idle" in rec["error"]
        assert rec["attempts"] == 1                    # terminal, no retries
    finally:
        svc.shutdown()


def test_stream_outlives_per_attempt_timeout(fixture_path, tmp_path):
    """Satellite 1, attempt-timeout leg: ``job_timeout_s`` bounds one
    BATCH attempt's wall clock, but an acquisition's wall clock is
    unknowable — a stream job paced far past the per-attempt timeout
    must still converge on its FIRST attempt (liveness stays owned by
    the idle timeout + the progress-reset stall watchdog)."""
    path, truth = fixture_path
    sm = _sm(tmp_path, job_timeout_s=0.5,
             stream=StreamConfig(idle_timeout_s=30.0, poll_interval_s=0.02))
    svc, base = _service(tmp_path, sm)
    try:
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "live", "mode": "stream",
            "formulas": truth.formulas[:3], "ds_config": ADDUCTS})
        assert status == 202
        coords, spectra = _read_spectra(path)
        for seq, cc, ss in _chunked(coords, spectra, 2):
            time.sleep(0.6)                # each gap alone > job_timeout_s
            assert _post_chunk(base, "live", seq, cc, ss)[0] == 200
        assert _req(base, "/datasets/live/finish", "POST", {})[0] == 200
        rec = _wait_job(base, body["msg_id"], ("done",))
        assert rec["attempts"] == 1, rec   # never timed out / retried
    finally:
        svc.shutdown()


def test_stream_chunk_progress_feeds_watchdog(fixture_path, tmp_path):
    """Satellite 2: waiting for chunks counts as progress — a stall
    watchdog far shorter than the acquisition must not kill the job, and
    the stream still converges to done."""
    path, truth = fixture_path
    formulas = truth.formulas[:3]
    sm = _sm(tmp_path, watchdog_interval_s=0.05, watchdog_stall_s=0.3,
             stream=StreamConfig(idle_timeout_s=0.0,   # wait forever
                                 poll_interval_s=0.02))
    svc, base = _service(tmp_path, sm)
    try:
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "live", "mode": "stream",
            "formulas": formulas, "ds_config": ADDUCTS})
        assert status == 202
        time.sleep(1.0)                                # >> watchdog_stall_s
        rec = _req(base, f"/jobs/{body['msg_id']}")[1]
        assert rec["state"] == "running", rec
        coords, spectra = _read_spectra(path)
        assert _post_chunk(base, "live", 0, coords, spectra)[0] == 200
        assert _req(base, "/datasets/live/finish", "POST", {})[0] == 200
        _wait_job(base, body["msg_id"], ("done",))
    finally:
        svc.shutdown()


def test_stream_drain_hands_off_to_peer(fixture_path, tmp_path):
    """Drain hand-off: shutting a replica down mid-acquisition republishes
    the stream job without burning an attempt; a fresh peer over the same
    spool + work dir resumes from the chunk log and converges to the
    batch-identical report."""
    path, truth = fixture_path
    formulas = truth.formulas[:5]
    coords, spectra = _read_spectra(path)
    chunks = _chunked(coords, spectra, 2)

    sm = _sm(tmp_path)
    svc1, base1 = _service(tmp_path, sm)
    shutdown1 = True
    try:
        status, body = _req(base1, "/submit", "POST", {
            "ds_id": "live", "mode": "stream",
            "formulas": formulas, "ds_config": ADDUCTS})
        assert status == 202
        msg_id = body["msg_id"]
        seq, cc, ss = chunks[0]
        assert _post_chunk(base1, "live", seq, cc, ss)[0] == 200
        deadline = time.time() + 30.0                  # first re-rank landed
        while time.time() < deadline:
            rec = _req(base1, f"/jobs/{msg_id}")[1]
            if (rec.get("partial") or {}).get("provisional"):
                break
            time.sleep(0.05)
        svc1.shutdown()                                # controller drain
        shutdown1 = False
        pending = tmp_path / "q" / "sm_annotate" / "pending" / f"{msg_id}.json"
        assert pending.exists(), "drain must republish the live stream job"
        handed = json.loads(pending.read_text())
        assert handed["service"]["attempts"] == 0      # no attempt burned

        svc2, base2 = _service(tmp_path, sm)           # the peer
        try:
            _wait_job(base2, msg_id, ("running",))
            seq, cc, ss = chunks[1]
            assert _post_chunk(base2, "live", seq, cc, ss)[0] == 200
            assert _req(base2, "/datasets/live/finish", "POST", {})[0] == 200
            _wait_job(base2, msg_id, ("done",))
            status, body = _req(base2, "/submit", "POST", {
                "ds_id": "golden", "input_path": str(path),
                "formulas": formulas, "ds_config": ADDUCTS})
            assert status == 202
            _wait_job(base2, body["msg_id"], ("done",))
        finally:
            svc2.shutdown()
        got = _report(tmp_path / "res", "live")
        want = _report(tmp_path / "res", "golden")
        for g, w in zip(got, want):
            pd.testing.assert_frame_equal(g, w, check_exact=True)
    finally:
        if shutdown1:
            svc1.shutdown()


def test_stream_http_validation_and_conflicts(fixture_path, tmp_path):
    path, truth = fixture_path
    sm = _sm(tmp_path)
    svc, base = _service(tmp_path, sm)
    try:
        # invalid mode rejected up front
        status, body = _req(base, "/submit", "POST", {
            "ds_id": "x", "input_path": "/in", "mode": "wat"})
        assert status == 400
        # malformed chunk bodies
        for payload in ({"coords": [[0, 0]]},                 # no seq
                        {"seq": -1, "coords": [], "mzs": [], "ints": []},
                        {"seq": 0, "coords": [[0, 0]],
                         "mzs": [[1.0], [2.0]], "ints": [[1.0]]}):
            status, body = _req(base, "/datasets/d/pixels", "POST", payload)
            assert status == 400, (payload, body)
        # conflicting re-post of a committed seq -> structured 409
        ok = {"seq": 0, "coords": [[0, 0]], "mzs": [[100.0]], "ints": [[1.0]]}
        assert _req(base, "/datasets/d/pixels", "POST", ok)[0] == 200
        bad = dict(ok, mzs=[[999.0]])
        status, body = _req(base, "/datasets/d/pixels", "POST", bad)
        assert status == 409 and body["reason"] == "chunk_conflict"
        # finish with a gap -> structured 409
        gap = {"seq": 5, "coords": [[1, 0]], "mzs": [[100.0]],
               "ints": [[1.0]]}
        assert _req(base, "/datasets/d/pixels", "POST", gap)[0] == 200
        status, body = _req(base, "/datasets/d/finish", "POST", {})
        assert status == 409 and body["reason"] == "stream_gap"
        # finish with ZERO committed chunks -> distinct structured 409
        status, body = _req(base, "/datasets/nothing/finish", "POST", {})
        assert status == 409 and body["reason"] == "stream_empty"
    finally:
        svc.shutdown()


# ------------------------------------------------------------- retention
def test_governor_reaps_finished_and_abandoned_stream_logs(tmp_path):
    """Regression: an abandoned acquisition (client vanished, finish never
    posted) must not hold governed work_dir space forever — unfinished
    logs are reaped once idle past retention_age_s + idle_timeout_s, by
    which point the stream job is certainly terminal.  idle_timeout_s = 0
    (open-ended) keeps unfinished logs forever, and an in-flight log
    inside the abandonment window is untouched."""
    import os

    from sm_distributed_tpu.service.resources import ResourceGovernor
    from sm_distributed_tpu.utils.config import ResourcesConfig

    root = tmp_path / "work" / "stream"

    def mklog(ds_id, finished, idle_s):
        log = ChunkLog(root, ds_id)
        log.append(0, [[0, 0]], [([100.0], [1.0])])
        if finished:
            log.finish()
        old = time.time() - idle_s
        os.utime(log.manifest_path, (old, old))
        return log.dir

    done = mklog("done", finished=True, idle_s=20.0)
    abandoned = mklog("abandoned", finished=False, idle_s=45.0)
    inflight = mklog("inflight", finished=False, idle_s=20.0)

    gov = ResourceGovernor(ResourcesConfig(), work_dir=tmp_path / "work",
                           stream_dir=root, stream_retention_age_s=10.0,
                           stream_idle_timeout_s=30.0)
    gov._sweep_stream(time.time())
    assert not done.exists()                           # finished + idle
    assert not abandoned.exists()                      # idle past 10 + 30
    assert (inflight / "manifest.json").exists()       # inside the window

    # idle_timeout_s = 0: open-ended acquisitions, never auto-abandoned
    forever = mklog("forever", finished=False, idle_s=1e6)
    gov0 = ResourceGovernor(ResourcesConfig(), work_dir=tmp_path / "work",
                            stream_dir=root, stream_retention_age_s=10.0,
                            stream_idle_timeout_s=0.0)
    gov0._sweep_stream(time.time())
    assert (forever / "manifest.json").exists()
