"""Schema pin for bench.py's JSON report (the driver parses the one JSON
line, so silently dropping a field is a protocol break, not a
refactor)."""

from bench import report


def _fake_inputs():
    class Obj:
        pass

    table = Obj()
    table.n_ions = 100
    ds = Obj()
    ds.n_pixels = 4096
    prep = {"table": table, "ds": ds, "isocalc_dt": 0.5}
    floor = dict(np_rate=50.0, mp_rate=50.0, n_procs=1, floor_n_ions=100,
                 floor_spread=0.1, floor_spread_mid5=0.05)
    jaxr = dict(jax_rate=5000.0, compile_dt=12.0, jax_spread=0.02,
                cache_entries=7)
    return prep, floor, jaxr


def test_report_schema_and_values():
    out = report(*_fake_inputs())
    assert set(out) == {
        "value", "jax_spread", "vs_baseline", "numpy_floor_ions_per_s",
        "numpy_floor_spread", "numpy_floor_spread_mid5",
        "numpy_floor_n_ions", "floor_procs",
        "numpy_floor_multiproc_ions_per_s", "vs_baseline_multiproc",
        "compile_s", "warmup_skipped",
        "cold_compile_s", "first_annotation_cold_s",
        "hbm_peak_bytes", "device_kind",
        "xla_cache_entries_before",
        "n_ions", "n_pixels", "pixels_per_s", "isocalc_s",
        "isocalc_cold_s", "isocalc_workers", "patterns_per_s",
        "phases",
        # ISSUE 18: roofline + resident-cube-compaction pins
        "roofline_frac", "roofline_floor_s", "roofline_bound",
        "cube_dtype", "resident_cube_bytes",
        "resident_cube_bytes_f32",
        # ISSUE 20: profiler-measured roofline (device time attributed to
        # the scoring kernels by HLO module name, not wall-clock)
        "measured_roofline_frac", "kernel_time_frac", "device_kernel_s",
    }
    # per-phase wall (ISSUE 5 satellite): the trajectory explains WHERE
    # time moved; stream_s appears only when the case config is passed
    assert out["phases"] == {"isocalc_s": 0.5, "floor_rep_s": 2.0,
                             "compile_s": 12.0}
    assert out["value"] == 5000.0
    assert out["vs_baseline"] == 100.0
    assert out["jax_spread"] == 0.02
    assert out["compile_s"] == 12.0
    assert out["warmup_skipped"] is False
    assert out["xla_cache_entries_before"] == 7
    assert out["numpy_floor_ions_per_s"] == 50.0
    assert out["numpy_floor_spread_mid5"] == 0.05
    assert out["floor_procs"] == 1
    assert out["vs_baseline_multiproc"] == 100.0
    assert out["n_ions"] == 100 and out["n_pixels"] == 4096
    assert out["pixels_per_s"] == 5000.0 * 4096
    assert out["isocalc_s"] == 0.5
    # cold-path fields are None on cases that skip the regeneration
    assert out["isocalc_cold_s"] is None
    assert out["isocalc_workers"] is None
    assert out["patterns_per_s"] is None
    # cleared-cache cold-start pins (ISSUE 13): None under --skip-cold,
    # rounded pass-throughs when measured
    assert out["cold_compile_s"] is None
    assert out["first_annotation_cold_s"] is None
    prep, floor, jaxr = _fake_inputs()
    out2 = report(prep, floor, jaxr,
                  cold={"cold_compile_s": 31.456,
                        "first_annotation_cold_s": 4.321})
    assert out2["cold_compile_s"] == 31.46
    assert out2["first_annotation_cold_s"] == 4.32
    # HBM pinning (ISSUE 6 satellite): null when the platform exposes no
    # memory stats, passed through when measure_jax captured them
    assert out["hbm_peak_bytes"] is None
    assert out["device_kind"] is None
    # roofline/compaction pins (ISSUE 18): null when measure_roofline did
    # not run, passed through when measured
    assert out["roofline_frac"] is None
    assert out["resident_cube_bytes"] is None


def test_report_roofline_fields_pass_through():
    prep, floor, jaxr = _fake_inputs()
    jaxr.update(roofline_frac=0.62, roofline_floor_s=0.484,
                roofline_bound="bandwidth", cube_dtype="bf16",
                resident_cube_bytes=462_000_000,
                resident_cube_bytes_f32=924_000_000)
    out = report(prep, floor, jaxr)
    assert out["roofline_frac"] == 0.62
    assert out["roofline_bound"] == "bandwidth"
    assert out["cube_dtype"] == "bf16"
    # the compaction acceptance pin: compacted bytes at most half of f32
    assert out["resident_cube_bytes"] * 2 <= out["resident_cube_bytes_f32"]


def test_report_compile_split_phases():
    prep, floor, jaxr = _fake_inputs()
    jaxr["compile_split"] = {"trace_s": 0.4, "lower_s": 0.1,
                             "cache_load_s": 0.0, "backend_compile_s": 1.5,
                             "warmup_exec_s": 10.0}
    out = report(prep, floor, jaxr)
    assert out["phases"]["compile_trace_s"] == 0.4
    assert out["phases"]["compile_lower_s"] == 0.1
    assert out["phases"]["compile_cache_load_s"] == 0.0
    assert out["phases"]["compile_backend_s"] == 1.5
    assert out["phases"]["warmup_exec_s"] == 10.0


def test_report_hbm_fields_pass_through():
    prep, floor, jaxr = _fake_inputs()
    jaxr["hbm_peak_bytes"] = 1_940_000_000
    jaxr["device_kind"] = "TPU v5 lite"
    out = report(prep, floor, jaxr)
    assert out["hbm_peak_bytes"] == 1_940_000_000
    assert out["device_kind"] == "TPU v5 lite"


def test_report_flags_skipped_warmup():
    prep, floor, jaxr = _fake_inputs()
    jaxr["warmup_skipped"] = True
    out = report(prep, floor, jaxr)
    assert out["warmup_skipped"] is True


def test_report_isocalc_cold_fields():
    prep, floor, jaxr = _fake_inputs()
    iso = dict(isocalc_cold_s=12.345, isocalc_workers=4,
               patterns_per_s=812.5)
    out = report(prep, floor, jaxr, iso)
    assert out["isocalc_cold_s"] == 12.35
    assert out["isocalc_workers"] == 4
    assert out["patterns_per_s"] == 812.5


