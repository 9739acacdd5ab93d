"""Parity tests for the Pallas measure_of_chaos kernel (ops/chaos_pallas.py).

On the CPU test mesh the kernel runs in Pallas interpret mode — same kernel
code, bit-exact semantics, no TPU required (the reference's local[*] trick,
SURVEY.md §4).  The oracle is scipy.ndimage.label via ops/metrics_np.
"""

from __future__ import annotations

import numpy as np
import pytest
from scipy import ndimage

from sm_distributed_tpu.ops.chaos_pallas import chaos_count_sums
from sm_distributed_tpu.ops.metrics_np import measure_of_chaos

_S4 = [[0, 1, 0], [1, 1, 1], [0, 1, 0]]


def _sums(*args, **kwargs):
    """The kernel's per-image count sums alone; which path each program
    took (its second output) is held by ``test_cell_blocks_both_paths``."""
    return chaos_count_sums(*args, **kwargs)[0]


def _oracle_count_sum(img2d: np.ndarray, nlevels: int) -> int:
    """Sum over levels of 4-connectivity component counts, with the kernel's
    exact threshold grid (f32 vmax * i/nlevels)."""
    img = np.maximum(img2d.astype(np.float32), 0.0)
    vmax = img.max()
    total = 0
    for li in range(nlevels):
        thr = vmax * (np.float32(li) / np.float32(nlevels))
        _, n = ndimage.label(img > thr, structure=_S4)
        total += n
    return total


@pytest.mark.parametrize("shape", [(8, 8), (12, 10), (16, 33)])
def test_random_masks_match_scipy(rng, shape):
    r, c = shape
    n = 6
    imgs = np.where(rng.random((n, r * c)) < 0.45,
                    rng.random((n, r * c)), 0).astype(np.float32)
    got = np.asarray(_sums(imgs, nrows=r, ncols=c, nlevels=6,
                                      interpret=True))
    for i in range(n):
        assert got[i] == _oracle_count_sum(imgs[i].reshape(r, c), 6)


def test_serpentine_single_component():
    r = c = 16
    img = np.zeros((r, c), np.float32)
    for row in range(0, r, 2):
        img[row, :] = 1.0
        if row + 1 < r:
            img[row + 1, c - 1 if (row // 2) % 2 == 0 else 0] = 1.0
    got = np.asarray(_sums(img.reshape(1, -1), nrows=r, ncols=c,
                                      nlevels=1, interpret=True))
    assert got[0] == 1


def test_empty_and_full_images():
    r = c = 8
    empty = np.zeros((1, r * c), np.float32)
    full = np.ones((1, r * c), np.float32)
    assert np.asarray(_sums(empty, nrows=r, ncols=c, nlevels=4,
                                       interpret=True))[0] == 0
    # full image: every level threshold vmax*i/4 keeps i=0..3 -> mask full
    # except the last level... thresholds < vmax keep all pixels: 1 comp each
    assert np.asarray(_sums(full, nrows=r, ncols=c, nlevels=4,
                                       interpret=True))[0] == 4


def test_matches_full_chaos_oracle(rng):
    """End metric parity: chaos from kernel counts == metrics_np formula."""
    r, c, n, nlevels = 10, 14, 5, 8
    imgs = np.where(rng.random((n, r * c)) < 0.3,
                    rng.random((n, r * c)), 0).astype(np.float32)
    sums = np.asarray(_sums(imgs, nrows=r, ncols=c,
                                       nlevels=nlevels, interpret=True))
    for i in range(n):
        n_notnull = (imgs[i] > 0).sum()
        if n_notnull == 0:
            continue
        got = 1.0 - (sums[i] / nlevels) / n_notnull
        want = measure_of_chaos(imgs[i].reshape(r, c).astype(np.float64), nlevels)
        assert got == pytest.approx(want, abs=2e-6)


def test_image_isolation_across_lane_packing(rng):
    """Images packed side by side in lanes must not leak labels: a batch of
    identical images must all get identical counts, and differ-by-one images
    must stay independent."""
    r = c = 8
    base = np.where(rng.random(r * c) < 0.5, rng.random(r * c), 0).astype(np.float32)
    batch = np.stack([base] * 7 + [np.zeros(r * c, np.float32)])
    got = np.asarray(_sums(batch, nrows=r, ncols=c, nlevels=3,
                                      interpret=True))
    assert (got[:7] == got[0]).all()
    assert got[7] == 0


def test_wide_image_lean_kernel_matches_scipy(rng):
    """512x512 exceeds the packed kernel's VMEM budget; the LEAN variant
    (flags rematerialized per sweep) must cover it in-kernel with exact
    scipy parity (VERDICT r2 item 3).  Interpret mode; a smaller lean-path
    case keeps runtime sane while the geometry checks pin the real sizes."""
    from sm_distributed_tpu.ops.chaos_pallas import (
        _MAX_CELLS, _MAX_CELLS_LEAN, _pack_geometry, fits_vmem,
    )

    # geometry: 512x512 overflows the packed budget but fits the lean one
    rp, cp, ib = _pack_geometry(512, 512, 512)
    assert rp * cp * ib > _MAX_CELLS
    rp, cp, ib = _pack_geometry(512, 512, 512, _MAX_CELLS_LEAN)
    assert rp * cp * ib <= _MAX_CELLS_LEAN
    assert fits_vmem(512, 512)
    assert not fits_vmem(1024, 1024)       # beyond lean -> strip kernel

    # exact parity through the lean code path (forced by a shape past the
    # packed budget; small enough for interpret mode)
    r, c = 8, 16 * 1024  # rp*cp = 8*16384 = 131072 > _MAX_CELLS, <= lean
    rp2, cp2, ib2 = _pack_geometry(r, c, 512)
    assert rp2 * cp2 * ib2 > _MAX_CELLS
    img = np.where(rng.random((2, r * c)) < 0.4,
                   rng.random((2, r * c)), 0).astype(np.float32)
    got = np.asarray(_sums(img, nrows=r, ncols=c, nlevels=3,
                                      interpret=True))
    for i in range(2):
        assert got[i] == _oracle_count_sum(img[i].reshape(r, c), 3)


def test_strip_kernel_matches_scipy(rng):
    """Strip-processed kernel (images beyond the lean whole-image budget,
    VERDICT r3 item 4b): HBM-resident labels, row strips with halos through
    VMEM, down/up passes to a global no-change certificate.  strip_rows
    forces multi-strip flows on small interpret-mode images; parity must be
    exact, including components that snake across strip boundaries."""
    from sm_distributed_tpu.ops.chaos_pallas import chaos_count_sums_strips

    nr, nc = 48, 64
    imgs = [np.where(rng.random((nr, nc)) < 0.45,
                     rng.random((nr, nc)), 0).astype(np.float32)
            for _ in range(3)]
    # vertical serpentine: ONE component spanning every strip, flowing both
    # down and up across boundaries (exercises the pass alternation)
    snake = np.zeros((nr, nc), np.float32)
    snake[:, 2] = 1.0
    snake[0, 2:60] = 1.0
    snake[:, 60] = 1.0
    snake[nr - 1, 10:60] = 1.0
    imgs += [snake, np.zeros((nr, nc), np.float32)]
    batch = np.stack([i.reshape(-1) for i in imgs])
    got = np.asarray(chaos_count_sums_strips(
        batch, nrows=nr, ncols=nc, nlevels=6, interpret=True, strip_rows=16))
    for i, img in enumerate(imgs):
        assert got[i] == _oracle_count_sum(img, 6), f"image {i}"


@pytest.mark.parametrize("nr,nc,sr", [(50, 70, 16), (33, 129, 8)])
def test_strip_kernel_ragged_shapes(rng, nr, nc, sr):
    """Rows not divisible by strip height + cols needing lane padding: the
    -1 pad fill must never enter a component and counts stay exact."""
    from sm_distributed_tpu.ops.chaos_pallas import chaos_count_sums_strips

    imgs = np.where(rng.random((4, nr * nc)) < 0.5,
                    rng.random((4, nr * nc)), 0).astype(np.float32)
    got = np.asarray(chaos_count_sums_strips(
        imgs, nrows=nr, ncols=nc, nlevels=5, interpret=True, strip_rows=sr))
    for i in range(4):
        assert got[i] == _oracle_count_sum(imgs[i].reshape(nr, nc), 5)


def test_chaos_route_geometry():
    """Dispatch: packed for in-budget images, strips past the lean budget,
    scan only when even strips can't fit (absurd widths)."""
    from sm_distributed_tpu.ops.chaos_pallas import (
        _HALO, _MAX_CELLS_STRIP, _strip_geometry, chaos_geometry,
    )

    def chaos_route(nrows, ncols):
        return chaos_geometry(nrows, ncols).route

    assert chaos_route(64, 64) == "packed"
    assert chaos_route(512, 512) == "packed"      # lean kernel
    assert chaos_route(1024, 1024) == "strips"    # whole-slide DESI
    assert chaos_route(2048, 2048) == "strips"
    assert chaos_route(8, 1024 * 1024) == "scan"  # 1M-col monster
    # a platform without Mosaic scans whatever the shape
    assert chaos_geometry(64, 64, pallas=False) == (
        "scan", 64, 64, 0, False, 100.0)

    rp, cp, strip = _strip_geometry(1024, 1024)
    assert rp >= 1024 and rp % strip == 0 and cp == 1024 and strip % 8 == 0
    assert (strip + 2 * _HALO) * cp <= _MAX_CELLS_STRIP


def test_slide256_block_is_one_padded_image_and_matches_scipy(rng):
    """256x256 (the whole-slide cell, PERF.md section 4): at 256 rows the
    lane budget is 98304 // 256 = 384, so 256 columns pad to 384 and ONE
    image fills a program's block — exactly ``_MAX_CELLS``, one cell short
    of the lean variant, a third of it padding.  The padding columns sit
    INSIDE the image's own lane span here (cp > ncols with ib == 1), so the
    boundary guards and the per-lane count reduction see a geometry no
    smaller case has; two images make the grid step across programs."""
    from sm_distributed_tpu.ops.chaos_pallas import (
        _MAX_CELLS, _pack_geometry, chaos_geometry,
    )

    assert _pack_geometry(256, 256, 512) == (256, 384, 1)
    assert 256 * 384 * 1 == _MAX_CELLS
    geo = chaos_geometry(256, 256)
    assert geo == ("packed", 256, 384, 1, False, 66.7)

    r = c = 256
    img = np.where(rng.random((2, r * c)) < 0.4,
                   rng.random((2, r * c)), 0).astype(np.float32)
    got = np.asarray(_sums(img, nrows=r, ncols=c, nlevels=3,
                                      interpret=True))
    for i in range(2):
        assert got[i] == _oracle_count_sum(img[i].reshape(r, c), 3)


# The three blocks the benchmark's cells run: (side, images a program,
# images in the case: two programs each, levels).  The dense case floods
# labels in interpret mode, so the 256x256 block runs it at fewer levels.
_CELL_BLOCKS = {64: (8, 16), 128: (4, 8), 256: (1, 2)}


def _isolated(rng, side, n, hi=31):
    """``n`` images of a few pixels each with no two 4-adjacent or
    diagonal (every pixel on an even row and an even column), integer
    intensities."""
    imgs = np.zeros((n, side, side), np.float32)
    for img in imgs:
        k = int(rng.integers(1, 13))
        rows = 2 * rng.integers(0, side // 2, k)
        cols = 2 * rng.integers(0, side // 2, k)
        img[rows, cols] = rng.integers(1, hi, k)
    return imgs


def _case(kind, rng, side, n):
    """(images, flood flag wanted of each of the two programs)."""
    ib = _CELL_BLOCKS[side][0]
    imgs = _isolated(rng, side, n)
    if kind == "isolated":
        return imgs, [0, 0]
    if kind == "diagonal":
        # diagonal neighbours only: 4-connectivity keeps them apart, so the
        # block is still sparse and every pixel is a component
        for img in imgs:
            img[:] = 0
            i = int(rng.integers(0, side - 6))
            img[i, i], img[i + 1, i + 1], img[i + 2, i] = 3, 7, 5
        return imgs, [0, 0]
    if kind == "image_boundary":
        # last column of image i, first column of image i+1, same row: lane
        # neighbours in the block where a program packs several images, and
        # no pair whichever path the program takes
        imgs[0, 5, side - 1] = 9
        imgs[1, 5, 0] = 4
        return imgs, [0, 0]
    if kind == "one_dense":
        # a blob image in the SECOND program: that program floods, the
        # first stays sparse, every image comes out right
        dense = np.where(rng.random((side, side)) < 0.45,
                         rng.integers(1, 31, (side, side)), 0)
        imgs[ib] = dense
        return imgs, [0, 1]
    if kind == "all_zero":
        imgs[:] = 0
        return imgs, [0, 0]
    if kind == "on_thresholds":
        # vmax 30 over 30 levels: every intensity 1..29 sits ON a threshold
        # (``_level_fracs``' case), in the sparse program and, beside one
        # adjacent pair, in the flood one
        for img in imgs:
            img[0, 0] = 30
        imgs[ib, 10, 10], imgs[ib, 10, 11] = 15, 16
        return imgs, [0, 1]
    raise AssertionError(kind)


@pytest.mark.parametrize("side", sorted(_CELL_BLOCKS))
@pytest.mark.parametrize("kind", [
    "isolated", "diagonal", "image_boundary", "one_dense", "all_zero",
    "on_thresholds"])
def test_cell_blocks_both_paths(rng, side, kind):
    """The sparse and the flood path of ``_chaos_kernel`` on the blocks the
    cells run ([64, 512] x 8 images, [128, 512] x 4, [256, 384] x 1): sums
    bit-equal to ``scipy.ndimage.label`` and chaos to ``metrics_np.
    measure_of_chaos``, and the kernel's flag says which path each program
    took."""
    from sm_distributed_tpu.ops.chaos_pallas import chaos_geometry

    ib, n = _CELL_BLOCKS[side]
    assert chaos_geometry(side, side).images_per_program == ib
    nlevels = 4 if (side, kind) == (256, "one_dense") else 30
    imgs, want_flood = _case(kind, rng, side, n)
    sums, flood = chaos_count_sums(
        imgs.reshape(n, -1), nrows=side, ncols=side, nlevels=nlevels,
        interpret=True)
    sums = np.asarray(sums)
    assert np.asarray(flood).tolist() == want_flood
    for i in range(n):
        assert sums[i] == _oracle_count_sum(imgs[i], nlevels), (kind, i)
        n_notnull = int((imgs[i] > 0).sum())
        if n_notnull:
            chaos = np.float32(1.0) - np.float32(sums[i]) / np.float32(
                nlevels * n_notnull)
            assert float(np.clip(chaos, 0, 1)) == measure_of_chaos(
                imgs[i], nlevels), (kind, i)
        else:
            assert sums[i] == 0 and measure_of_chaos(imgs[i], nlevels) == 0.0
    if kind in ("isolated", "diagonal", "image_boundary"):
        # no labels needed: a level's count is its pixels above threshold
        want = [sum(int((img > img.max() * (np.float32(li) / np.float32(
            nlevels))).sum()) for li in range(nlevels)) for img in imgs]
        assert sums.tolist() == want


def _pallas_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"]
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_kernels(inner)


@pytest.mark.parametrize("side,want", [
    (64, ("packed", 64, 64, 8, False, 100.0)),
    (128, ("packed", 128, 128, 4, False, 100.0)),
    (256, ("packed", 256, 384, 1, False, 66.7)),
    (512, ("packed", 512, 512, 1, True, 100.0)),
    (1024, ("strips", 192, 1024, 1, False, 97.0)),
])
def test_chaos_geometry_is_what_the_kernels_use(side, want):
    """``chaos_geometry`` (what a backend's ``backend_build`` span reports)
    against the kernel itself: the first VMEM block of the traced
    ``pallas_call`` of the route it names is the block it names."""
    import functools

    import jax
    import jax.numpy as jnp

    from sm_distributed_tpu.ops import chaos_pallas as cp

    geo = cp.chaos_geometry(side, side)
    assert geo == want
    fn = {"packed": cp.chaos_count_sums,
          "strips": cp.chaos_count_sums_strips}[geo.route]
    traced = jax.make_jaxpr(functools.partial(
        fn, nrows=side, ncols=side, nlevels=3, interpret=True))(
        jax.ShapeDtypeStruct((2, side * side), jnp.float32))
    kernel, = _pallas_kernels(traced.jaxpr)
    vmem = [v.aval for v in kernel.invars if "vmem" in str(v.aval)]
    assert vmem[0].shape == (geo.rows_pad,
                             geo.cols_pad * geo.images_per_program)
    assert geo.lean == (cp._packed_block(side, side, 512)[3]
                        and geo.route == "packed")


def test_strip_kernel_full_metric_parity(rng):
    """chaos computed from strip-kernel count sums must agree with the
    numpy oracle metric end to end (the same formula
    measure_of_chaos_batch applies to the 'strips' route on TPU)."""
    from sm_distributed_tpu.ops.chaos_pallas import chaos_count_sums_strips

    nr, nc = 40, 48
    imgs = np.where(rng.random((3, nr * nc)) < 0.35,
                    rng.random((3, nr * nc)), 0).astype(np.float32)
    sums = np.asarray(chaos_count_sums_strips(
        imgs, nrows=nr, ncols=nc, nlevels=8, interpret=True, strip_rows=8))
    for i in range(3):
        n_notnull = (imgs[i] > 0).sum()
        got = 1.0 - (sums[i] / 8) / n_notnull
        want = measure_of_chaos(imgs[i].reshape(nr, nc).astype(np.float64), 8)
        assert got == pytest.approx(want, abs=2e-6)


def test_strip_work_span_result_invariant(rng):
    """Work-sweep spans only accelerate the flood — the global no-change
    certificate carries exactness at any span, strips included."""
    from sm_distributed_tpu.ops.chaos_pallas import chaos_count_sums_strips

    nr, nc = 32, 40
    imgs = np.where(rng.random((3, nr * nc)) < 0.55,
                    rng.random((3, nr * nc)), 0).astype(np.float32)
    base = np.asarray(chaos_count_sums_strips(
        imgs, nrows=nr, ncols=nc, nlevels=4, interpret=True,
        strip_rows=8, work_span=0))
    for span in (2, 16):
        got = np.asarray(chaos_count_sums_strips(
            imgs, nrows=nr, ncols=nc, nlevels=4, interpret=True,
            strip_rows=8, work_span=span))
        np.testing.assert_array_equal(got, base, err_msg=f"span={span}")
    for i in range(3):
        assert base[i] == _oracle_count_sum(imgs[i].reshape(nr, nc), 4)


def test_work_span_result_invariant(rng):
    """The span-2 certificate carries exactness: any work-sweep span must
    give identical counts (spans only change how fast the flood converges,
    never where it converges)."""
    r, c = 16, 33
    imgs = np.where(rng.random((4, r * c)) < 0.5,
                    rng.random((4, r * c)), 0).astype(np.float32)
    base = np.asarray(_sums(imgs, nrows=r, ncols=c, nlevels=5,
                                       interpret=True, work_span=0))
    for span in (2, 3, 8, 64):
        got = np.asarray(_sums(imgs, nrows=r, ncols=c, nlevels=5,
                                          interpret=True, work_span=span))
        np.testing.assert_array_equal(got, base, err_msg=f"span={span}")
    for i in range(4):
        assert base[i] == _oracle_count_sum(imgs[i].reshape(r, c), 5)
