"""Bring-up invariants (PR 21): where the compile cache lives, who may own
a chip, and that every Pallas kernel COMPILES for a v5e — checked from the
CPU through libtpu's compile-only topology, so a kernel that Mosaic refuses
fails here and not on the first chip run."""

from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from sm_distributed_tpu.parallel import distributed
from sm_distributed_tpu.utils.config import SMConfig

REPO_ROOT = Path(__file__).resolve().parent.parent


# ------------------------------------------------------- cache placement
@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_cache_placed_by_environment_sets_no_directory(
        monkeypatch, tmp_path, config_updates):
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    sm = SMConfig(work_dir=str(tmp_path / "work"))
    assert distributed.compile_cache_path(sm) == placed
    distributed.enable_compile_cache(sm)
    assert [k for k, _v in config_updates] == [
        "jax_persistent_cache_min_compile_time_secs"]


def test_cache_defaults_to_the_checkout_not_the_work_dir(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = REPO_ROOT / ".cache" / "xla_cache"
    for work in ("/tmp/sm_tpu_work", str(tmp_path / "elsewhere")):
        sm = SMConfig(work_dir=work)
        assert distributed.compile_cache_path(sm) == want
        distributed.enable_compile_cache(sm)
    assert config_updates.count(("jax_compilation_cache_dir", str(want))) == 2
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) \
        in config_updates


def test_compile_cache_dir_knob_no_longer_names_a_directory(config_updates):
    with pytest.raises(ValueError, match="compile_cache_dir"):
        SMConfig.from_dict({"parallel": {"compile_cache_dir": "/some/dir"}})
    off = SMConfig.from_dict({"parallel": {"compile_cache_dir": "off"}})
    assert distributed.compile_cache_path(off) is None
    distributed.enable_compile_cache(off)
    assert config_updates == []


def test_clear_compile_cache_empties_in_place(monkeypatch, tmp_path):
    cache = tmp_path / "xla"
    cache.mkdir()
    for name in ("jit_f-0123-cache", "warmup_manifest.json"):
        (cache / name).write_text("x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    assert distributed.clear_compile_cache(SMConfig()) == cache
    assert cache.is_dir() and not list(cache.iterdir())


# ----------------------------------------------------- one process per chip
def test_fleet_spawn_refuses_when_this_process_holds_the_chips(
        monkeypatch, tmp_path):
    from sm_distributed_tpu.service import fleet

    assert fleet.child_chip_conflict("jax_tpu") is None      # CPU platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    assert fleet.child_chip_conflict("numpy_ref") is None
    spawn = fleet.serve_spawn(tmp_path / "q", tmp_path / "sm.json",
                              backend="jax_tpu")
    with pytest.raises(fleet.ChipsBusyError, match="all 4 local TPU chip"):
        spawn("fr1")


def test_isocalc_pool_workers_never_import_jax():
    """Spawn workers unpickle ``ops.isocalc._compute_chunk`` by import
    path; on a TPU host a worker that imported jax could open the chip its
    parent holds."""
    code = ("import sys; import sm_distributed_tpu.ops.isocalc as m; "
            "m._pool_init(None); sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code],
                          cwd=str(REPO_ROOT)).returncode == 0


# ------------------------------------------------------ numerics repairs
def test_refine_quotient_recovers_the_rounded_division():
    """A device divide that lands 1-2 ulp off (the TPU's) is corrected to
    numpy's correctly rounded quotient; an exact one is left alone."""
    import jax.numpy as jnp
    import numpy as np

    from sm_distributed_tpu.ops.metrics_jax import refine_quotient

    rng = np.random.default_rng(0)
    b = (30 * rng.integers(1, 2 ** 18, 20000)).astype(np.float32)
    a = np.floor(rng.random(20000) * b).astype(np.float32)
    want = a / b
    up = np.nextafter(want, np.float32(2))
    for q in (want, up, np.nextafter(want, np.float32(-1)),
              np.nextafter(up, np.float32(2))):
        got = jax.jit(refine_quotient)(jnp.asarray(q), a, b)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_chaos_level_fractions_are_host_divided():
    """The kernels' threshold grid is the oracle's f32(i)/f32(nlevels),
    divided on the host — not a run-time divide of the level index."""
    import numpy as np

    from sm_distributed_tpu.ops.chaos_pallas import _level_fracs

    want = [np.float32(i) / np.float32(30) for i in range(30)]
    assert _level_fracs(30).dtype == np.float32
    assert _level_fracs(30).tolist() == [float(w) for w in want]


def test_component_report_allows_tiny_values_their_absolute_floor():
    import numpy as np

    from sm_distributed_tpu.analysis.numerics import component_report

    want = np.array([[0.5, 1e-4, 0.7, 0.2], [0.25, 0.9, 0.1, 0.3]])
    got = want.copy()
    got[0, 1] = 1e-4 * (1 + 1e-5)      # ~170 ulps, 1e-9 absolute: fine
    rep = component_report(got, want)
    assert rep["spatial"]["max_ulp"] > 16 and rep["spatial"]["outside"] == 0
    got[1, 1] = 0.9 + 5e-6             # past the ceiling both ways
    got[1, 0] = np.nextafter(np.float32(0.25), np.float32(1))   # chaos: exact
    rep = component_report(got, want)
    assert rep["spatial"]["outside"] == 1 and rep["chaos"]["outside"] == 1


# ------------------------------------------- kernels compile for a v5e
_AOT = textwrap.dedent('''
    import os, sys
    os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
    os.environ["TPU_WORKER_HOSTNAMES"] = "localhost"
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        dev = topologies.get_topology_desc(
            topology_name="v5e:1x1", platform="tpu",
            chips_per_host_bounds=(1, 1, 1)).devices[0]
    except Exception as exc:
        print("NO-TOPOLOGY", exc); sys.exit(77)
    from sm_distributed_tpu.ops import chaos_pallas as cp
    from sm_distributed_tpu.ops import moments_pallas as mp

    def compile_(fn, *avals):
        sh = SingleDeviceSharding(dev)
        avals = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in avals]
        jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",)).compile()

    f32, i32, k = jnp.float32, jnp.int32, 4
    for p in (4096, 65536, 262144):
        assert mp.moments_fit(k, p)
        compile_(lambda x: mp.batch_moments_pallas.__wrapped__(x),
                 ((8, k, p), f32))
        compile_(lambda x, n: mp.batch_moments_pallas_masked.__wrapped__(
            x, n), ((8, k, p), f32), ((), i32))
    for side in (64, 128, 256, 512):
        compile_(lambda x, s=side: cp.chaos_count_sums.__wrapped__(
            x, nrows=s, ncols=s), ((16, side * side), f32))
    compile_(lambda x: cp.chaos_count_sums_strips.__wrapped__(
        x, nrows=1024, ncols=1024), ((2, 1024 * 1024), f32))
    # the budget refuses what the compiler would refuse
    assert not mp.moments_fit(8, 524288)
    print("AOT-OK")
''')


def test_every_pallas_kernel_compiles_for_v5e():
    """Mosaic + XLA:TPU, from the CPU, at the headline / scale / desi
    shapes (strips at 1024x1024).  Compile only: numerics and time are
    chip_smoke.py's business."""
    proc = subprocess.run([sys.executable, "-c", _AOT], cwd=str(REPO_ROOT),
                          capture_output=True, text=True, timeout=900)
    if proc.returncode == 77:
        pytest.skip("libtpu offers no compile-only topology here: "
                    + proc.stdout.strip()[-200:])
    assert proc.returncode == 0 and "AOT-OK" in proc.stdout, \
        (proc.stdout + proc.stderr)[-3000:]


# --------------------------- the membership product's passes on a v5e
_MEMBERSHIP_AOT = textwrap.dedent('''
    import os, re, sys
    os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
    os.environ["TPU_WORKER_HOSTNAMES"] = "localhost"
    from functools import partial
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        dev = topologies.get_topology_desc(
            topology_name="v5e:1x1", platform="tpu",
            chips_per_host_bounds=(1, 1, 1)).devices[0]
    except Exception as exc:
        print("NO-TOPOLOGY", exc); sys.exit(77)
    from sm_distributed_tpu.ops.imager_jax import extract_images_flat_banded

    sh = SingleDeviceSharding(dev)
    i32, f32, gc, p, w = jnp.int32, jnp.float32, 1536, 4096, 8192
    avals = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in (
        ((262144,), i32), ((262144,), f32), ((2 * w,), i32), ((16,), i32),
        ((16, 512), i32), ((16, 512), i32), ((w,), i32))]
    text = jax.jit(partial(
        extract_images_flat_banded, gc_width=gc, n_pixels=p)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile().as_text()
    convs = [ln for ln in text.splitlines() if " convolution(" in ln]
    assert len(convs) == 1, convs
    lhs = re.search(r"convolution\\(%([\\w.]+),", convs[0]).group(1)
    made = next(ln for ln in text.splitlines()
                if ln.lstrip().startswith("%" + lhs + " = "))
    assert re.search(r"= pred\\[", made), made
    print("AOT-OK")
''')


def test_membership_product_meets_a_pred_operand_on_v5e():
    """What makes the f32 dot at ``Precision.HIGHEST`` of
    ``extract_images_flat_banded`` THREE bf16 MXU passes and not six
    (PERF.md section 6, PR 42): XLA:TPU folds the cast of the two compares
    away and hands the convolution the 0/1 side as a pred operand, one
    exact piece.  A compiler that stops doing so doubles the largest op of
    extraction; then explicit bf16 pieces are worth timing again."""
    proc = subprocess.run(
        [sys.executable, "-c", _MEMBERSHIP_AOT], cwd=str(REPO_ROOT),
        capture_output=True, text=True, timeout=600)
    if proc.returncode == 77:
        pytest.skip("libtpu offers no compile-only topology here: "
                    + proc.stdout.strip()[-200:])
    assert proc.returncode == 0 and "AOT-OK" in proc.stdout, \
        (proc.stdout + proc.stderr)[-3000:]


# ------------- the residency's scoring reserve covers the compiled program
_RESERVE_AOT = textwrap.dedent('''
    import os, sys, types
    os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
    os.environ["TPU_WORKER_HOSTNAMES"] = "localhost"
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        dev = topologies.get_topology_desc(
            topology_name="v5e:1x1", platform="tpu",
            chips_per_host_bounds=(1, 1, 1)).devices[0]
    except Exception as exc:
        print("NO-TOPOLOGY", exc); sys.exit(77)
    jax.default_backend = lambda: "tpu"     # the chaos route asks it
    from sm_distributed_tpu.models.msm_jax import JaxBackend
    from sm_distributed_tpu.service.primer import _flat_lower_call
    from sm_distributed_tpu.utils.config import DSConfig

    side, b, k, n_res = 64, 2048, 4, 3670016    # section64's own shapes
    spec = {"kind": "flat", "variant": "band", "nrows": side, "ncols": side,
            "nlevels": 30, "do_preprocessing": True, "q": 99.0,
            "n_resident": n_res, "b": b, "k": k, "gc_width": 1536,
            "n_keep": 0, "r_pad": 0, "w_cap": 2097152, "g": 2 * b * k,
            "c": 16, "wc": 512, "w": b * k, "devices": 1}
    fn, args, statics = _flat_lower_call(spec)
    sh = SingleDeviceSharding(dev)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
             for a in args]
    mem = fn.trace(*avals, **statics).lower(
        lowering_platforms=("tpu",)).compile().memory_analysis()
    scratch = 4 * (side * side + 1) * (2 * b * k + 1)
    backend = types.SimpleNamespace(
        ds_config=DSConfig(), batch=b, _n_pix_b=side * side,
        build_attrs={"hist_scratch_bytes": scratch})
    reserve = JaxBackend.scoring_reserve_bytes.fget(backend)
    print("TEMP", mem.temp_size_in_bytes, "SCRATCH", scratch,
          "RESERVE", reserve)
    assert scratch < mem.temp_size_in_bytes <= reserve
    print("AOT-OK")
''')


def test_the_scoring_reserve_covers_what_the_compiled_program_holds():
    """``JaxBackend.scoring_reserve_bytes`` (histogram scratch + three image
    blocks, from shapes) is what a byte-budgeted residency keeps free of
    resident arrays (``engine/residency.py``); XLA:TPU's own plan for the
    band program at ``maldi-section-64``'s shapes, the clip on, must fit
    inside it (0.54 GB of 0.67 here; 2.16 of 2.68 at 128x128 and 8.6 of
    10.7 at 256x256, by hand, PR 51).  Compile only."""
    proc = subprocess.run(
        [sys.executable, "-c", _RESERVE_AOT], cwd=str(REPO_ROOT),
        capture_output=True, text=True, timeout=600)
    if proc.returncode == 77:
        pytest.skip("libtpu offers no compile-only topology here: "
                    + proc.stdout.strip()[-200:])
    assert proc.returncode == 0 and "AOT-OK" in proc.stdout, \
        (proc.stdout + proc.stderr)[-3000:]
