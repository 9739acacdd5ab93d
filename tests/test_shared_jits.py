"""One set of scoring jits per metric geometry, not per backend (ISSUE 34).

``models/msm_jax.make_flat_jits`` and ``make_extract_jit`` hand every backend
of one geometry the SAME ``jax.jit`` objects, so a fresh backend (a new upload
of a like-sized section) calls what the last one traced, lowered and loaded.
Held here: the second backend is quiet in ``analysis/retrace``'s census and
bit-identical to one with jits of its own; geometries do not mix; the primer
lowers the very objects a backend calls; eight builders at once get one set;
the registry holds scalars only, so an evicted backend still frees its
arrays; the LRU is bounded; the programs are the same programs; and the
counter and the span attr say which backend shared.
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import urllib.request
import weakref
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))

from serve import metric_sum  # noqa: E402  (benchmarks/serve.py)
from scripts.load_sweep import Harness  # noqa: E402
from sm_distributed_tpu.analysis import retrace  # noqa: E402
from sm_distributed_tpu.io.dataset import SpectralDataset  # noqa: E402
from sm_distributed_tpu.io.fixtures import generate_synthetic_dataset  # noqa: E402
from sm_distributed_tpu.models import msm_jax  # noqa: E402
from sm_distributed_tpu.models.msm_basic import _slice_table  # noqa: E402
from sm_distributed_tpu.models.msm_jax import (  # noqa: E402
    JaxBackend,
    make_extract_jit,
    make_flat_jits,
    named_partial,
)
from sm_distributed_tpu.ops.buckets import (  # noqa: E402
    export_chunk_rows,
    peak_bucket,
)
from sm_distributed_tpu.ops.imager_jax import export_image_chunks  # noqa: E402
from sm_distributed_tpu.ops.isocalc import IsocalcWrapper  # noqa: E402
from sm_distributed_tpu.service import primer  # noqa: E402
from sm_distributed_tpu.service.metrics import MetricsRegistry  # noqa: E402
from sm_distributed_tpu.service.server import AnnotationService  # noqa: E402
from sm_distributed_tpu.utils.config import (  # noqa: E402
    DSConfig,
    IsotopeGenerationConfig,
    SMConfig,
)

NROWS, NCOLS = 7, 9          # 7 rows bucket to 8: zero-row padding is real
COMMON = dict(nrows=8, ncols=NCOLS, nlevels=30, do_preprocessing=False, q=99.0)
DC = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                         "image_generation": {"ppm": 3.0}})
# what forces each extraction variant (JaxBackend._variant_for)
VARIANTS = {
    "plain": {"peak_compaction": "off", "band_slice": "off"},
    "compact": {"peak_compaction": "on", "band_slice": "off"},
    "band": {"peak_compaction": "off", "band_slice": "on"},
}
# the parent's construction, stated apart from the module under test
STATICS = {
    "plain": (msm_jax.fused_score_fn_flat_banded, ("gc_width", "b", "k")),
    "compact": (msm_jax.fused_score_fn_flat_banded_compact,
                ("n_keep", "gc_width", "b", "k")),
    "band": (msm_jax.fused_score_fn_flat_banded_sliced,
             ("w_cap", "gc_width", "b", "k")),
}


@pytest.fixture(autouse=True)
def fresh_registry():
    """The registry is the process's: every case starts from an empty one,
    whatever earlier tests of this worker built."""
    with msm_jax._SHARED_JITS_LOCK:
        msm_jax._SHARED_JITS.clear()
    yield


@pytest.fixture(scope="module")
def sections(tmp_path_factory):
    """Two sections of one geometry from two seeds (different spectra, peak
    counts in one lattice bucket) and the ion table both are scored with."""
    out = tmp_path_factory.mktemp("shared_jits")
    made = [generate_synthetic_dataset(
        out / f"s{seed}", nrows=NROWS, ncols=NCOLS, formulas=None,
        present_fraction=0.5, noise_peaks=noise, seed=seed)
        for seed, noise in ((71, 25), (72, 28))]
    truth = made[0][1]
    adducts = ("+H", "+Na")
    table = IsocalcWrapper(
        IsotopeGenerationConfig(adducts=adducts)).pattern_table(
        [(sf, ad) for sf in truth.formulas for ad in adducts])
    ds_a, ds_b = (SpectralDataset.from_imzml(path) for path, _ in made)
    assert ds_a.n_peaks != ds_b.n_peaks
    assert peak_bucket(ds_a.n_peaks) == peak_bucket(ds_b.n_peaks)
    return ds_a, ds_b, table


def _sm(variant: str = "plain") -> SMConfig:
    return SMConfig.from_dict({
        "backend": "jax_tpu",
        "parallel": {"formula_batch": 32, **VARIANTS[variant]}})


def _batches(backend, table):
    b = backend.batch
    return [_slice_table(table, s, min(s + b, table.n_ions))
            for s in range(0, table.n_ions, b)]


def _private(variant: str, common: dict):
    fn, statics = STATICS[variant]
    return jax.jit(named_partial(fn, **common), static_argnames=statics)


def _with_private_jits(backend) -> JaxBackend:
    """``backend`` as the parent built it: jits of its own."""
    for variant, (attr, *_rest) in msm_jax._VARIANTS.items():
        setattr(backend, attr, _private(variant, backend._common))
    backend._extract_fn = _private_export(backend._n_pix_b)
    return backend


def _private_export(n_pixels: int):
    return jax.jit(named_partial(
        export_image_chunks, n_pixels=n_pixels,
        chunk_rows=export_chunk_rows(n_pixels)))


def _bits(arrays):
    return [np.ascontiguousarray(a).view(np.uint64 if a.dtype == np.float64
                                         else np.uint32) for a in arrays]


@pytest.mark.parametrize("variant", ["plain", "compact", "band"])
def test_second_backend_is_quiet_and_bit_identical(sections, variant):
    """The second upload of a geometry: nothing is traced, lowered, loaded
    or compiled, and the answers are those of a backend with private jits."""
    ds_a, ds_b, table = sections
    sm = _sm(variant)
    first = JaxBackend(ds_a, DC, sm)
    kept = _slice_table(table, 0, 5)
    first.score_batches(_batches(first, table))
    first.extract_ion_images(kept)
    ran = {getattr(first, attr)._cache_size()
           for attr, *_r in msm_jax._VARIANTS.values()}
    assert ran == {0, 1}, ran            # one variant ran, one signature

    second = JaxBackend(ds_b, DC, sm)
    retrace.enable()
    retrace.reset()
    try:
        got = second.score_batches(_batches(second, table))
        got_images = second.extract_ion_images(kept)
        census = retrace.snapshot()
    finally:
        retrace.disable()
        retrace.reset()
    assert census["events_total"] == 0, census
    assert census["cache_hits_total"] == 0, census
    assert census["durations"] == dict.fromkeys(
        ("trace_s", "lower_s", "cache_load_s", "backend_compile_s"), 0.0)
    assert census["sites"] == {}

    alone = _with_private_jits(JaxBackend(ds_b, DC, sm))
    want = alone.score_batches(_batches(alone, table))
    want_images = alone.extract_ion_images(kept)
    for g, w in zip(_bits(got + [got_images]), _bits(want + [want_images])):
        np.testing.assert_array_equal(g, w)
    assert got_images.any() and any(g.any() for g in got)


@pytest.mark.parametrize("key,other", [
    ("ncols", NCOLS + 1), ("q", 95.0), ("nlevels", 20),
    ("nrows", 16), ("do_preprocessing", True)])
def test_geometries_do_not_mix(key, other):
    mine = make_flat_jits(dict(COMMON))
    theirs = make_flat_jits({**COMMON, key: other})
    assert set(mine) == set(theirs) == set(STATICS)
    for variant in STATICS:
        assert mine[variant] is not theirs[variant]
        assert mine[variant] is make_flat_jits(dict(COMMON))[variant]
        # the closure is the geometry asked for, and scalars alone
        assert theirs[variant].__wrapped__.keywords == {**COMMON, key: other}
    assert make_extract_jit(72) is make_extract_jit(72)
    assert make_extract_jit(72) is not make_extract_jit(80)


@pytest.mark.parametrize("variant", ["plain", "compact", "band"])
def test_primer_lowers_the_objects_a_backend_calls(sections, variant):
    """``service/primer.py`` rebuilds a recorded BucketSpec's call through
    ``make_flat_jits``: it gets the backend's own jit, not an equal one."""
    ds_a, _ds_b, table = sections
    backend = JaxBackend(ds_a, DC, _sm(variant))
    batch = _batches(backend, table)[0]
    ran, args, statics = backend._flat_call(batch)
    assert ran == variant
    spec = backend._bucket_spec(ran, args, statics)
    fn, avals, prime_statics = primer._flat_lower_call(spec)
    assert fn is getattr(backend, msm_jax._VARIANTS[variant][0])
    assert fn is make_flat_jits(backend._common)[variant]
    assert prime_statics == statics
    assert [(a.shape, a.dtype) for a in avals] == [
        (a.shape, a.dtype) for a in (backend._px_s, backend._in_s, *args)]
    assert backend._export_images(_slice_table(table, 0, 3))[1] == 32
    assert backend._extract_fn is make_extract_jit(backend._n_pix_b)


def test_eight_builders_at_once_get_one_set(sections):
    """Scheduler workers build backends at the same time: one geometry, one
    set of jits, one ``built`` and seven ``shared``."""
    ds_a, ds_b, _table = sections
    before = msm_jax.scoring_jit_events()
    gate, built, errors = threading.Barrier(8), [], []

    def build(i):
        try:
            gate.wait(timeout=60.0)
            built.append(JaxBackend(ds_a if i % 2 else ds_b, DC, _sm()))
        except Exception as exc:                        # noqa: BLE001
            errors.append(exc)

    workers = [threading.Thread(target=build, args=(i,)) for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and len(built) == 8, errors
    for attr, *_rest in msm_jax._VARIANTS.values():
        assert len({id(getattr(b, attr)) for b in built}) == 1, attr
    after = msm_jax.scoring_jit_events()
    assert (after["built"] - before["built"],
            after["shared"] - before["shared"]) == (1, 7)


def test_a_backend_is_collectable_while_the_registry_lives(sections):
    """An evicted backend frees its chip memory: the shared callables close
    over scalars, never over a backend, a dataset or a device array."""
    ds_a, _ds_b, table = sections
    backend = JaxBackend(ds_a, DC, _sm())
    backend.score_batches(_batches(backend, table))
    backend.extract_ion_images(_slice_table(table, 0, 3))
    dead = [weakref.ref(o) for o in (backend, backend._px_s, backend._in_s)]
    fns = make_flat_jits(backend._common)
    del backend
    gc.collect()
    assert [r() for r in dead] == [None, None, None]
    assert fns["plain"]._cache_size() == 1      # and the executable stays
    with msm_jax._SHARED_JITS_LOCK:
        entries = list(msm_jax._SHARED_JITS.values())
    jits = [f for e in entries for f in (e.values() if isinstance(e, dict)
                                         else [e])]
    assert len(jits) == 4
    for fn in jits:
        closure = fn.__wrapped__
        assert closure.args == () and closure.func.__closure__ is None
        assert all(type(v) in (int, float, bool)
                   for v in closure.keywords.values()), closure.keywords


def test_the_registry_drops_its_oldest_geometry_at_the_bound():
    bound = msm_jax.SHARED_JITS_MAX
    first = make_flat_jits({**COMMON, "ncols": 100})
    gone = weakref.ref(first["plain"])
    second = make_flat_jits({**COMMON, "ncols": 101})
    for i in range(2, bound):
        make_flat_jits({**COMMON, "ncols": 100 + i})
    assert make_flat_jits({**COMMON, "ncols": 100}) is first     # touched
    make_flat_jits({**COMMON, "ncols": 100 + bound})              # one over
    with msm_jax._SHARED_JITS_LOCK:
        assert len(msm_jax._SHARED_JITS) == bound
    # the least recently used went, not the oldest made
    assert make_flat_jits({**COMMON, "ncols": 100}) is first
    assert make_flat_jits({**COMMON, "ncols": 101}) is not second
    # a dropped geometry's jits die with their last holder
    del first
    for i in range(bound):
        make_extract_jit(1000 + i)
    gc.collect()
    assert gone() is None


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_the_programs_are_the_same_programs(sections, variant):
    """Lowered text of one batch equals a private ``jax.jit`` of the same
    partial: same module name, same statics, same HLO, so the persistent
    cache entries and warm-up manifests of the parent stay valid."""
    ds_a, _ds_b, table = sections
    backend = JaxBackend(ds_a, DC, _sm(variant))
    batch = _batches(backend, table)[0]
    ran, args, statics = backend._flat_call(batch)
    assert ran == variant
    call = (backend._px_s, backend._in_s, *args)
    shared = getattr(backend, msm_jax._VARIANTS[variant][0])
    text = shared.lower(*call, **statics).as_text()
    assert text == _private(variant, backend._common).lower(
        *call, **statics).as_text()
    assert f"module @jit_{STATICS[variant][0].__name__} " in text
    # the export's program too
    grid, r_lo, r_hi, _i, _n = backend._padded_windows(batch, 32)
    pos = msm_jax.flat_bound_ranks(backend._mz_host, grid)
    ext = (backend._px_s, backend._in_s, pos, r_lo, r_hi,
           np.ones(r_lo.shape, np.float32))
    ext_text = make_extract_jit(backend._n_pix_b).lower(*ext).as_text()
    assert ext_text == _private_export(
        backend._n_pix_b).lower(*ext).as_text()
    assert "module @jit_export_image_chunks " in ext_text


def test_counter_and_span_say_which_backend_shared(tmp_path, sections):
    """Through a real service: four uploads of one geometry (two sections,
    each under two ds_ids past a residency of one) build four backends;
    ``sm_scoring_jits_total`` reads built 1 then shared 3, and each job's
    ``backend_build`` span says ``jits_shared`` accordingly."""
    made = [generate_synthetic_dataset(
        tmp_path / f"up{seed}", nrows=NROWS, ncols=NCOLS, formulas=None,
        present_fraction=0.5, noise_peaks=25, seed=seed) for seed in (81, 82)]
    h = Harness(tmp_path, "shared", sm_overrides={
        "backend": "jax_tpu", "service": {"workers": 1},
        "parallel": {"formula_batch": 32, "resident_datasets": 1}})
    shared, built, spans = [], [], []

    def scrape():
        text = h.metrics_text()
        shared.append(metric_sum(
            text, "sm_scoring_jits_total", 'result="shared"') or 0)
        built.append(metric_sum(
            text, "sm_scoring_jits_total", 'result="built"') or 0)

    try:
        scrape()
        for i in range(4):
            path, truth = made[i % 2]
            status, _hd, body = h.submit({
                "ds_id": f"up-{i}", "msg_id": f"up-{i}",
                "input_path": str(path), "formulas": truth.formulas[:4],
                "ds_config": {"isotope_generation": {"adducts": ["+H"]}}})
            assert status == 202, body
            row = h.wait_terminal([f"up-{i}"], timeout_s=120.0)[f"up-{i}"]
            assert (row["state"], row["attempts"]) == ("done", 1), row
            scrape()
            with urllib.request.urlopen(
                    f"{h.base}/jobs/up-{i}/trace?raw=1", timeout=30.0) as r:
                records = json.loads(r.read())["records"]
            spans += [r for r in records if r["kind"] == "span"
                      and r["name"] == "backend_build"]
    finally:
        h.shutdown()
    assert [s["attrs"]["cache_hit"] for s in spans] == [False] * 4
    assert [s["attrs"]["jits_shared"] for s in spans] == [
        False, True, True, True]
    assert [b - built[0] for b in built] == [0, 1, 1, 1, 1]
    assert [s - shared[0] for s in shared] == [0, 0, 1, 2, 3]


def test_the_collector_exposes_what_the_registry_counted(sections):
    ds_a, ds_b, _table = sections
    m = MetricsRegistry()
    AnnotationService._collect_scoring_jits(m)
    text0 = m.expose()
    for ds in (ds_a, ds_b, ds_a):
        JaxBackend(ds, DC, _sm())
    AnnotationService._collect_scoring_jits(m)
    text1 = m.expose()

    def delta(result):
        label = f'result="{result}"'
        return (metric_sum(text1, "sm_scoring_jits_total", label) or 0) - (
            metric_sum(text0, "sm_scoring_jits_total", label) or 0)

    assert (delta("built"), delta("shared")) == (1, 2)
    assert "# TYPE sm_scoring_jits_total counter" in text1
