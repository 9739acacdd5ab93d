"""One residency under two budgets in bytes (ISSUE 51).

(a) ``engine/residency.ResidentStore`` against the plain reference
(``tests/residency_reference.py``: a list and a loop) on seeded random
sequences of get / put / pin / release; (b) an integer
``parallel.resident_datasets`` is the three LRUs by count it has always
been, on the same kind of sequence; (c) the knob through ``SMConfig``;
(d) at 8x8 px, a catalogue of six sections under a device limit that fits
four: hits, misses and evictions as the reference says, stored tables the
same bytes whether the job hit, missed or followed an eviction, a backend a
job holds not freed under it and one nobody holds freed at its eviction;
(e) what ``/metrics`` and a job's trace say of it; (f) the deployment's
files (``benchmarks/configs/maldi-section-128-workingset.json``, the traffic
mix, the cell and its two readers).
"""

from __future__ import annotations

import importlib.util
import json
import random
import sys
import types
from pathlib import Path

import pandas as pd
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from residency_reference import ByteLRU, CountLRU  # noqa: E402  (tests/)
from sm_distributed_tpu.engine import residency as res  # noqa: E402
from sm_distributed_tpu.engine.residency import (  # noqa: E402
    HOST,
    DatasetResidency,
    ResidentStore,
)
from sm_distributed_tpu.engine.search_job import SearchJob  # noqa: E402
from sm_distributed_tpu.engine.storage import read_result_tables  # noqa: E402
from sm_distributed_tpu.io.fixtures import (  # noqa: E402
    generate_synthetic_dataset,
)
from sm_distributed_tpu.service.metrics import MetricsRegistry  # noqa: E402
from sm_distributed_tpu.service.server import AnnotationService  # noqa: E402
from sm_distributed_tpu.utils import tracing  # noqa: E402
from sm_distributed_tpu.utils.config import DSConfig, SMConfig  # noqa: E402

CONFIGS = REPO / "benchmarks" / "configs"
WORKINGSET = json.loads(
    (CONFIGS / "maldi-section-128-workingset.json").read_text())
SECTION = json.loads((CONFIGS / "maldi-section-128.json").read_text())
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL, SIBLING = "section128-workingset-reannotate", "section128-reannotate"


# ------------------------------------- (a) the store against the reference
@pytest.mark.parametrize("seed", range(8))
def test_store_agrees_with_the_plain_reference(seed):
    """Hits, misses, the evicted keys in order and the bytes held after
    EVERY step of 600, under a budget a third of what the keys weigh."""
    rng = random.Random(5100 + seed)
    weights = {k: rng.randrange(1, 200) for k in range(24)}
    budget = sum(weights.values()) // 3
    store = ResidentStore({"backend": None}, lambda tier: budget)
    ref = ByteLRU(budget)
    pinned: list = []                        # (key, the store's entry)
    evicted: list = []
    for step in range(600):
        key = rng.randrange(len(weights))
        pin = rng.random() < 0.4
        op = rng.random()
        if op < 0.45:
            entry = store.get("backend", key, pin=pin)
            assert (entry is not None) == ref.get(key, pin), (seed, step)
            if entry is not None and pin:
                pinned.append((key, entry))
        elif op < 0.8:
            entry, gone = store.put("backend", key, f"value {key}",
                                    {"device:0": weights[key]}, pin=pin)
            ref.put(key, weights[key], pin)
            evicted += [e.key for e in gone]
            assert entry.value == f"value {key}"
            if pin and entry.pins:
                pinned.append((key, entry))
        elif pinned:
            key, entry = pinned.pop(rng.randrange(len(pinned)))
            evicted += [e.key for e in store.unpin([entry])]
            ref.release(key)
        assert evicted == ref.evicted, (seed, step)
        n, tiers = store.held("backend")
        assert (n, tiers.get("device:0", 0)) == \
            (len(ref.items), ref.held()), (seed, step)
        assert sorted(store.view("backend")) == \
            sorted(item[0] for item in ref.items)
    assert store.hits["backend"] > 50 and store.misses["backend"] > 50
    assert len(evicted) > 50
    assert store.evictions == {("backend", "bytes"): len(evicted)}
    # over its budget only while what is left is held
    if ref.held() > budget:
        assert all(item[2] for item in ref.items)


def test_an_entry_weighs_on_every_tier_it_names_and_the_reserve_comes_off():
    """A backend charges its chip AND the host; a tier's room is its
    budget less the largest reserve an entry on it asks for."""
    budgets = {"device:0": 100, "device:1": 100, HOST: 50}
    store = ResidentStore({}, budgets.get)
    store.put("backend", "a", "A", {"device:0": 40, HOST: 10},
              {"device:0": 30})
    store.put("backend", "b", "B", {"device:1": 90, HOST: 10})
    assert store.room("device:0") == 70 and store.room("device:1") == 100
    # 40 + 40 > 70: chip 0 evicts its oldest, chip 1's entry stays
    _e, gone = store.put("backend", "c", "C", {"device:0": 40, HOST: 10})
    assert [e.key for e in gone] == ["a"]
    assert store.room("device:0") == 100      # the reserve left with "a"
    # the host fills: the oldest entry that weighs on the HOST leaves
    _e, gone = store.put("dataset", "d", "D", {HOST: 35})
    assert [e.key for e in gone] == ["b"]
    assert store.held("backend") == (1, {"device:0": 40, HOST: 10})
    assert store.evictions == {("backend", "bytes"): 2}


# ------------------------------ (b) an integer is the three LRUs it was
@pytest.mark.parametrize("seed,cap", [(0, 1), (1, 2), (2, 2), (3, 3)])
def test_integer_cap_behaves_as_the_three_lrus_by_count(seed, cap):
    rng = random.Random(5150 + seed)
    residency = DatasetResidency(max_datasets=cap, max_backends=cap)
    refs = {c: CountLRU(cap) for c in res.CACHES}
    entry = types.SimpleNamespace       # an ion table's stand-in
    for step in range(400):
        cache = rng.choice(res.CACHES)
        key = (cache, rng.randrange(6))
        ref = refs[cache]
        if cache == "ion_table":
            if rng.random() < 0.5:
                got = residency.ion_table(key)
                want = ref.get(key)
                assert (got is None) == (want is None)
                assert got is None or got.table == want
            else:
                kept = residency.keep_ion_table(key, entry(table=step))
                assert kept.table == ref.put(key, step)
        else:
            lookup = getattr(residency, cache)
            got = lookup(key, lambda: object())
            want = ref.get(key)
            if want is None:
                ref.put(key, got)
            else:
                assert got is want, (seed, step)
        stats = residency.stats
        for c in res.CACHES:
            assert (stats[f"{c}_hits"], stats[f"{c}_misses"]) == \
                (refs[c].hits, refs[c].misses), (seed, step, c)
            held = getattr(residency, f"_{c}s").data
            assert list(held) == [k for k, _v in refs[c].items]
            assert stats[f"{c}_entries"] == len(refs[c].items)
            assert stats["evictions"].get((c, "count"), 0) == \
                len(refs[c].evicted)
    assert all(len(r.evicted) > 20 for r in refs.values())
    assert not any(cause == "bytes" for _c, cause in stats["evictions"])
    assert stats["budget_bytes"] == {"device": None, HOST: None}


# -------------------------------------------- (c) the knob through SMConfig
@pytest.mark.parametrize("value", [0, 1, 2, 16, "auto"])
def test_resident_datasets_values_that_load(value, monkeypatch):
    sm = SMConfig.from_dict({"parallel": {"resident_datasets": value}})
    assert sm.parallel.resident_datasets == value
    monkeypatch.setattr(res, "_host_available_bytes", lambda: 1000)
    residency = DatasetResidency.from_config(sm.parallel.resident_datasets)
    if value == 0:
        assert residency is None
    elif value == "auto":
        # no count, half of the memory available now, no chip asked yet
        assert residency._store._caps == dict.fromkeys(res.CACHES, None)
        assert residency.stats["budget_bytes"] == {"device": None, HOST: 500}
    else:
        assert residency._store._caps == dict.fromkeys(res.CACHES, value)
        assert residency.stats["budget_bytes"] == {"device": None,
                                                   HOST: None}


@pytest.mark.parametrize("value", [-1, "many", "", 1.5, True, None, [2]])
def test_resident_datasets_values_that_do_not_load(value):
    with pytest.raises(ValueError, match="resident_datasets"):
        SMConfig.from_dict({"parallel": {"resident_datasets": value}})


# ------------------ (d) six sections under a device limit that fits four
N_SECTIONS, FIT = 6, 4
ORDER = [0, 1, 2, 3, 0, 4, 5, 3, 1, 0, 2, 5]
DS_CONFIG = DSConfig.from_dict({"isotope_generation": {"adducts": ["+H"]},
                                "image_generation": {"ppm": 3.0}})


def _sm(tmp, name):
    return SMConfig.from_dict({
        "backend": "jax_tpu",
        "fdr": {"decoy_sample_size": 3, "seed": 2},
        "storage": {"results_dir": str(tmp / name / "res")},
        "work_dir": str(tmp / name / "work"),
        "parallel": {"formula_batch": 32, "pixels_axis": 1,
                     "formulas_axis": 1, "resident_datasets": "auto"}})


def _run(tmp, sm, residency, k, section, name):
    path, truth = section
    job = SearchJob(f"ds{k}", f"section {k}", path, DS_CONFIG, sm,
                    formulas=truth.formulas, residency=residency)
    ctx = tracing.new_trace(job_id=name, trace_dir=tmp / "traces" / name)
    with tracing.attach(ctx):
        job.run()
    tracing.close_file(ctx.file)
    return (tracing.read_trace(ctx.file),
            read_result_tables(Path(sm.storage.results_dir) / f"ds{k}"))


def _chip_residents(residency, sections):
    """Catalogue indices of the sections whose backends are resident,
    oldest first, with the backends."""
    by_ds = {}
    for backend in residency._backends.data.values():
        k, = [i for i, (path, _t) in enumerate(sections)
              if backend.ds.n_peaks == _peaks(path)]
        by_ds[k] = backend
    return by_ds


_PEAKS: dict = {}


def _peaks(path) -> int:
    from sm_distributed_tpu.io.dataset import SpectralDataset

    if path not in _PEAKS:
        _PEAKS[path] = SpectralDataset.from_imzml(path).n_peaks
    return _PEAKS[path]


@pytest.fixture(scope="module")
def six(tmp_path_factory):
    """The twelve jobs of ``ORDER`` over six 8x8 sections, one at a time,
    under a device limit of the scoring reserve + four and a half backends;
    then a seventh kind of job: one that holds a backend while two
    newcomers are admitted."""
    tmp = tmp_path_factory.mktemp("workingset")
    sections = [generate_synthetic_dataset(
        tmp / f"in{k}", nrows=8, ncols=8, formulas=None,
        present_fraction=0.5, noise_peaks=40 + 3 * k, seed=5100 + k)
        for k in range(N_SECTIONS)]
    assert len({_peaks(p) for p, _t in sections}) == N_SECTIONS
    # what one backend weighs and wants free beside it, from a job of its own
    probe = DatasetResidency(max_datasets=None, max_backends=None)
    _run(tmp, _sm(tmp, "probe"), probe, 0, sections[0], "probe")
    backend, = probe._backends.data.values()
    weigh, reserve = backend.resident_bytes, backend.scoring_reserve_bytes
    assert weigh > 0 and reserve > backend.build_attrs["hist_scratch_bytes"]
    limit = reserve + FIT * weigh + weigh // 2
    residency = DatasetResidency(max_datasets=None, max_backends=None,
                                 device_limit_bytes=limit)
    sm = _sm(tmp, "six")
    ref = ByteLRU(limit - reserve)
    out = {"jobs": [], "weigh": weigh, "reserve": reserve, "limit": limit,
           "residency": residency, "sections": sections}
    for n, k in enumerate(ORDER):
        before = residency.stats
        hit = ref.get(k, pin=True)
        if not hit:
            ref.put(k, weigh, pin=True)
        ref.release(k)
        trace, tables = _run(tmp, sm, residency, k, sections[k], f"job{n}")
        after = residency.stats
        out["jobs"].append({
            "k": k, "trace": trace, "tables": tables, "ref_hit": hit,
            "ref_resident": [item[0] for item in ref.items],
            "ref_evicted": list(ref.evicted), "before": before,
            "after": after,
            "resident": list(_chip_residents(residency, sections))})
    out["ref"] = ref
    # a job's hold, by hand: a resident backend looked up and held, the
    # other three scored again (so the held one is the OLDEST, the bytes
    # rule's first choice), then two sections that are not resident
    held_k = out["jobs"][-1]["resident"][0]
    hold = residency.job()
    key, = [key for key, b in residency._backends.data.items()
            if b is _chip_residents(residency, sections)[held_k]]
    held = hold.backend(key, lambda: pytest.fail("a resident backend"))
    absent = [k for k in range(N_SECTIONS)
              if k not in out["jobs"][-1]["resident"]]
    loose = {k: b for k, b in _chip_residents(residency, sections).items()
             if k != held_k}
    for n, k in enumerate(list(loose) + absent):
        _run(tmp, sm, residency, k, sections[k], f"held{n}")
    assert next(iter(_chip_residents(residency, sections))) == held_k
    out["hold"] = {"k": held_k, "backend": held, "loose": loose,
                   "resident": _chip_residents(residency, sections),
                   "absent": absent, "stats": residency.stats}
    hold.release()
    return out


def test_six_sections_hit_miss_and_evict_as_the_reference_says(six):
    assert six["weigh"] < six["reserve"]
    for n, job in enumerate(six["jobs"]):
        b, a = job["before"], job["after"]
        hit = a["backend_hits"] - b["backend_hits"]
        assert (hit, a["backend_misses"] - b["backend_misses"]) == \
            ((1, 0) if job["ref_hit"] else (0, 1)), n
        assert job["resident"] == job["ref_resident"], n
        assert a["evictions"].get(("backend", "bytes"), 0) == \
            len(job["ref_evicted"]), n
        assert a["backend_bytes"] == len(job["resident"]) * six["weigh"]
        assert a["backend_bytes"] <= a["budget_bytes"]["device"] == \
            six["limit"] - six["reserve"]
        # every section stays on the host: its tier has no limit here
        assert a["dataset_entries"] == len(set(ORDER[:n + 1]))
        assert a["ion_table_entries"] == 1
    last = six["jobs"][-1]["after"]
    assert last["backend_hits"] == 2 and last["backend_misses"] == 10
    assert last["evictions"] == {("backend", "bytes"): 6}
    assert last["dataset_hits"] == 6 and last["dataset_misses"] == 6
    assert last["ion_table_hits"] == 11 and last["ion_table_misses"] == 1
    assert last["dataset_bytes"] > 0 and last["backend_host_bytes"] > 0
    assert last["budget_bytes"][HOST] is None


def test_stored_tables_are_the_same_bytes_on_hit_miss_and_after_eviction(six):
    """Section 0 was scored cold, on a hit and after its eviction; 3 cold
    and on a hit; 1, 2 and 5 cold and after an eviction."""
    by_k: dict = {}
    for job in six["jobs"]:
        by_k.setdefault(job["k"], []).append(job)
    kinds = {k: [("hit" if j["ref_hit"] else "miss") for j in jobs]
             for k, jobs in by_k.items()}
    assert kinds[0] == ["miss", "hit", "miss"] and kinds[3] == ["miss", "hit"]
    assert kinds[1] == kinds[2] == kinds[5] == ["miss", "miss"]
    for k, jobs in by_k.items():
        for job in jobs[1:]:
            for got, want in zip(job["tables"], jobs[0]["tables"]):
                assert len(want) > 0
                pd.testing.assert_frame_equal(got, want, check_exact=True)
    # and the six sections are six different answers
    firsts = [jobs[0]["tables"][1] for jobs in by_k.values()]
    assert not any(a.equals(b) for i, a in enumerate(firsts)
                   for b in firsts[i + 1:])


def test_a_held_backend_is_not_freed_and_a_loose_one_is_at_its_eviction(six):
    hold = six["hold"]
    # two newcomers came while the oldest entry was held: it stayed, the
    # next oldest two left, and their device arrays went AT the eviction
    assert list(hold["resident"])[0] == hold["k"]
    assert hold["k"] in hold["resident"] and \
        hold["resident"][hold["k"]] is hold["backend"]
    assert not hold["backend"]._px_s.is_deleted()
    assert not hold["backend"]._in_s.is_deleted()
    gone = [k for k in hold["loose"] if k not in hold["resident"]]
    assert len(gone) == len(hold["absent"]) == 2
    for k in gone:
        assert "_px_s" not in vars(hold["loose"][k])
        assert "_in_s" not in vars(hold["loose"][k])
    for k, backend in hold["resident"].items():
        assert not backend._px_s.is_deleted(), k
    assert hold["stats"]["backend_entries"] == FIT


def test_a_count_cap_drops_a_held_entry_and_frees_it_when_it_is_let_go():
    freed = []
    value = types.SimpleNamespace(release=lambda: freed.append("a"),
                                  resident_bytes=8)
    residency = DatasetResidency(max_datasets=1, max_backends=1)
    hold = residency.job()
    assert hold.backend("a", lambda: value) is value
    other = residency.job()
    other.backend("b", lambda: types.SimpleNamespace(resident_bytes=8))
    # the count rule dropped "a" under its holder, as it always has ...
    assert list(residency._backends.data) == ["b"] and freed == []
    assert residency.stats["evictions"] == {("backend", "count"): 1}
    assert other.span_attrs("backend") == {
        "residency_entries": 1, "residency_bytes": 8,
        "residency_evicted": 1}
    # ... and its buffers go when the holder lets go, not before
    hold.release()
    assert freed == ["a"]
    other.release()
    assert freed == ["a"] and list(residency._backends.data) == ["b"]


# -------------------------------------- (e) /metrics and the job's trace
def test_the_lookup_spans_say_what_the_residency_held(six):
    def span(job, name):
        s, = [r for r in job["trace"]
              if r["kind"] == "span" and r["name"] == name]
        return s["attrs"]

    first, evicting = six["jobs"][0], six["jobs"][5]
    assert not evicting["ref_hit"] and len(evicting["ref_evicted"]) == 1
    build = span(first, "backend_build")
    assert (build["residency_entries"], build["residency_bytes"],
            build["residency_evicted"]) == (1, six["weigh"], 0)
    assert build["resident_bytes"] == six["weigh"]    # the backend's own
    build = span(evicting, "backend_build")
    assert (build["residency_entries"], build["residency_bytes"],
            build["residency_evicted"]) == (FIT, FIT * six["weigh"], 1)
    prep = span(evicting, "prepare_resident")
    assert prep["residency_entries"] == 5 and prep["residency_evicted"] == 0
    assert prep["residency_bytes"] == evicting["after"]["dataset_bytes"]
    from scripts import trace_report

    text = trace_report.render(trace_report.summarize(evicting["trace"]))
    line = next(ln for ln in text.splitlines()
                if ln.lstrip().startswith("backend_build"))
    assert "residency_evicted=1" in line and "cache_hit=False" in line
    assert f"residency_entries={FIT}" in line
    line = next(ln for ln in text.splitlines()
                if ln.lstrip().startswith("prepare_resident"))
    assert "residency_entries=5" in line


def test_metrics_expose_the_bytes_the_budgets_and_the_evictions(six):
    registry = MetricsRegistry()
    service = types.SimpleNamespace(residency=six["residency"])
    AnnotationService._collect_residency(service, registry)
    text = registry.expose()
    stats = six["residency"].stats
    for cache in ("dataset", "backend", "ion_table"):
        assert f'sm_residency_bytes{{cache="{cache}"}} ' \
            f'{stats[f"{cache}_bytes"]}' in text
    assert 'sm_residency_bytes{cache="backend_index"} ' \
        f'{stats["backend_host_bytes"]}' in text
    assert 'sm_residency_budget_bytes{tier="device"} ' \
        f'{six["limit"] - six["reserve"]}' in text
    assert 'sm_residency_budget_bytes{tier="host"}' not in text
    assert 'sm_residency_evictions_total{cache="backend",cause="bytes"} 8' \
        in text
    assert 'sm_residency_evictions_total{cache="dataset",cause="count"} 0' \
        in text
    # a second scrape moves a counter by what happened since, not again
    AnnotationService._collect_residency(service, registry)
    assert 'cause="bytes"} 8' in registry.expose()
    # the benchmark's two readers read them; a program without them: None
    run = {"metrics_before": "", "metrics_after": text
           + 'sm_device_hbm_limit_bytes{device="0:TPU v5 lite"} 1000000\n',
           "cell": {"chips": 1}}
    assert _reader("resident_hbm_pct").read(run) == pytest.approx(
        100.0 * stats["backend_bytes"] / 1e6)
    assert _reader("residency_evictions_in_window").read(run) == 8
    parent = {"metrics_before": "", "cell": {"chips": 1}, "metrics_after":
              'sm_device_hbm_limit_bytes{device="0:TPU v5 lite"} 1000000\n'}
    assert _reader("resident_hbm_pct").read(parent) is None
    assert _reader("residency_evictions_in_window").read(parent) is None


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", REPO / "benchmarks" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------ (f) the deployment's files
def test_the_file_is_the_typical_section_but_for_the_one_key():
    texts = {"name", "source", "deployment", "assumed", "guarantees"}
    assert set(WORKINGSET) == set(SECTION)
    for key in set(SECTION) - texts - {"sm_config"}:
        assert WORKINGSET[key] == SECTION[key], key
    sm, parallel = dict(WORKINGSET["sm_config"]), \
        dict(WORKINGSET["sm_config"]["parallel"])
    assert parallel.pop("resident_datasets") == "auto"
    assert SECTION["sm_config"]["parallel"]["resident_datasets"] == 2
    sibling = dict(SECTION["sm_config"]["parallel"])
    del sibling["resident_datasets"]
    assert parallel == sibling
    assert {**sm, "parallel": None} == {**SECTION["sm_config"],
                                        "parallel": None}
    assert SMConfig.from_dict(
        WORKINGSET["sm_config"]).parallel.resident_datasets == "auto"
    # the guarantees are the sibling's word for word, plus the one line
    mine, theirs = dict(WORKINGSET["guarantees"]), dict(SECTION["guarantees"])
    assert mine.pop("text") == theirs.pop("text") + [
        "a resubmitted ds_id whose bytes fit the budget is served from "
        "residency"]
    assert mine == theirs
    assert WORKINGSET["reduced"] == SECTION["reduced"] == \
        ["formulas", "target_adducts"]
    assert set(WORKINGSET["assumed"]) == set(SECTION["assumed"]) | {
        "working_set"}
    assert WORKINGSET["assumed"]["spectra"] == SECTION["assumed"]["spectra"]
    for said in ("270", "340", "16", "12", "8"):     # the rule and its runs
        assert said in WORKINGSET["assumed"]["working_set"], said
    for said in ("sm_residency_bytes", "bytes_limit"):
        assert said in WORKINGSET["assumed"]["device_memory"], said
    assert len(WORKINGSET["source"]) <= 200
    assert WORKINGSET["source"] != SECTION["source"]
    traffic = json.loads(
        (REPO / "benchmarks" / "traffic" / "workingset.json").read_text())
    word = {16: "sixteen", 12: "twelve", 8: "eight"}[traffic["catalogue"]]
    assert word in WORKINGSET["deployment"].lower()


def test_the_manifest_names_the_deployment_the_cell_and_its_two_readers():
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == WORKINGSET["name"]]
    assert entry["source"] == WORKINGSET["source"]
    assert entry["file"] == \
        "benchmarks/configs/maldi-section-128-workingset.json"
    assert entry["reduced"] == WORKINGSET["reduced"]
    assert entry["source"] not in {c["source"] for c in MANIFEST["configs"]
                                   if c is not entry}
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        WORKINGSET["name"], "workingset", 1)
    traffic = json.loads(
        (REPO / "benchmarks" / "traffic" / "workingset.json").read_text())
    assert {k: traffic[k] for k in traffic if k != "why"} == {
        "loop": "closed", "clients": 2, "catalogue": traffic["catalogue"],
        "ds_id": "same", "poll_ms": 50, "profile_seconds": 30,
        "profile_at_s": 3, "expect_residency_hit_pct": 100}
    assert traffic["catalogue"] in (16, 12, 8)      # the sizing rule's
    assert str(traffic["catalogue"]) in cell["why"]

    def listed(name):
        return {m["name"] for m in MANIFEST["per_layer"]
                if name in m.get("workloads", [])}

    # every list the sibling is on but the export's, the lookup's cost
    # before the lease, and its own two
    assert listed(CELL) == (listed(SIBLING) - {"export_streamed_pct"}) | {
        "pre_lease_s", "resident_hbm_pct", "residency_evictions_in_window"}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["resident_hbm_pct"] == {
        "name": "resident_hbm_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "residency",
        "moves": "report_s", "workloads": [CELL]}
    assert by_name["residency_evictions_in_window"] == {
        "name": "residency_evictions_in_window", "unit": "evictions",
        "better": "lower", "source": "program_counter",
        "layer": "residency", "moves": "report_p95_s", "workloads": [CELL]}
    assert by_name["residency_hit_pct"]["layer"] == "residency"
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"report_s", "report_p95_s", "ions_per_s", "setup_s"}
