"""The candidate scorer of the stitching refinement: exact integer sums on
the device for whole uint16 crops (ops/phasecorr.pearson_sums), the host's
float64 tables for anything else, one search over both."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigstitcher_spark_tpu.io.dataset_io import ViewLoader
from bigstitcher_spark_tpu.io.spimdata import SpimData
from bigstitcher_spark_tpu.models import stitching as st
from bigstitcher_spark_tpu.ops import phasecorr as pc


def _padded(crop, shape):
    out = np.zeros((1,) + tuple(shape), np.uint16)
    out[(0,) + tuple(slice(0, n) for n in crop.shape)] = crop
    return out


def _python_sums(ca, cb, s):
    """The five sums over the overlap in Python integers."""
    ea, eb = np.array(ca.shape), np.array(cb.shape)
    lo, hi = np.maximum(0, -s), np.minimum(ea, eb - s)
    av = ca[tuple(slice(lo[d], hi[d]) for d in range(3))].astype(object)
    bv = cb[tuple(slice(lo[d] + s[d], hi[d] + s[d]) for d in range(3))
            ].astype(object)
    return tuple(int(v) for v in (av.sum(), (av * av).sum(), bv.sum(),
                                  (bv * bv).sum(), (av * bv).sum()))


def _scorer(ca, cb, shape):
    ext = lambda c: np.array([c.shape], np.int32)
    return pc.device_sums(jnp.asarray(_padded(ca, shape)),
                          jnp.asarray(_padded(cb, shape)),
                          ext(ca), ext(cb), 0)


def test_sums_are_exact_where_every_voxel_is_65535():
    """2^17 voxels of 65535: a float32 sum is off after 2^8 of them, a
    uint32 one wraps after 2^16; the limbs cannot."""
    shape = (8, 128, 128)
    crop = np.full(shape, 65535, np.uint16)
    whole, part = _scorer(crop, crop, shape)(
        np.array([[0, 0, 0], [-1, 2, -3]], np.int64))
    for got, n in ((whole, 8 * 128 * 128), (part, 7 * 126 * 125)):
        assert n >= 1 << 16
        assert got == (n * 65535, n * 65535 ** 2) * 2 + (n * 65535 ** 2,)


@pytest.mark.parametrize("seed,ext_a,ext_b", [
    (0, (13, 29, 51), (15, 27, 63)),
    (1, (7, 31, 33), (7, 31, 33)),
    (2, (16, 5, 64), (11, 9, 57)),
])
def test_sums_are_exact_on_random_boxes(seed, ext_a, ext_b):
    rng = np.random.default_rng(seed)
    ca = rng.integers(0, 65536, ext_a, dtype=np.uint16)
    cb = rng.integers(0, 65536, ext_b, dtype=np.uint16)
    shifts = np.concatenate([
        np.array([[0, 0, 0], [-3, 2, -7], [4, -4, 10]]),
        -(np.array(ext_a) - 1)[None], (np.array(ext_b) - 1)[None],
        rng.integers(-5, 6, (4, 3))]).astype(np.int64)
    got = _scorer(ca, cb, (16, 32, 64))(shifts)
    assert got == [_python_sums(ca, cb, s) for s in shifts]


def test_a_long_candidate_list_goes_in_turns():
    rng = np.random.default_rng(3)
    ca = rng.integers(0, 65536, (9, 9, 9), dtype=np.uint16)
    shifts = rng.integers(-4, 5, (pc._MAX_CANDIDATES + 3, 3))
    got = _scorer(ca, ca, (16, 16, 16))(shifts.astype(np.int64))
    assert got == [_python_sums(ca, ca, s) for s in shifts]


def _smooth_uint16(shape, seed, sigma=2.0):
    from scipy.ndimage import gaussian_filter

    rng = np.random.default_rng(seed)
    return np.round(gaussian_filter(rng.normal(2000, 600, shape), sigma)
                    ).astype(np.uint16)


def _crops(case):
    """test_stitching.py's three kernel cases, as stored uint16 voxels."""
    if case == "noise":
        return (_smooth_uint16((48, 48, 24), 1),
                _smooth_uint16((48, 48, 24), 2))
    base = _smooth_uint16((80, 80, 40), 0)
    a = base[10:58, 10:58, 8:32]
    if case == "integer":
        d = (5, -3, 2)
        return a, base[10 - d[0]:58 - d[0], 10 - d[1]:58 - d[1],
                       8 - d[2]:32 - d[2]]
    from scipy.ndimage import shift as ndshift

    moved = ndshift(base.astype(np.float64), (2.3, -1.7, 0.5), order=3)
    return a, np.round(np.clip(moved, 0, 65535)).astype(np.uint16)[
        10:58, 10:58, 8:32]


@pytest.mark.parametrize("case", ["integer", "subpixel", "noise"])
def test_device_scorer_gives_the_host_scorer_s_bits(case):
    a, b = _crops(case)
    shape = (64, 64, 32)
    peaks = np.asarray(pc.pcm_peaks(
        jnp.asarray(_padded(a, shape)[0]), jnp.asarray(_padded(b, shape)[0]),
        jnp.array(a.shape, jnp.int32), jnp.array(b.shape, jnp.int32)))
    calls = []
    score = _scorer(a, b, shape)

    def sums(cands):
        calls.append(len(cands))
        return score(cands)

    host = pc.refine_peaks(a, b, peaks, shape)
    dev = pc.refine_peaks(a, b, peaks, shape, sums=sums)
    assert dev[1] == host[1] and np.array_equal(dev[0], host[0])
    # the wraps, at most three rounds, the parabola: each one call
    assert 1 <= len(calls) <= 5 and max(calls[1:], default=0) <= 6
    # and the single-pair entry takes the device scorer for such crops
    one = pc.stitch_crops(_padded(a, shape)[0], _padded(b, shape)[0],
                          np.array(a.shape), np.array(b.shape))
    assert one[1] == host[1] and np.array_equal(one[0], host[0])
    if case == "integer":
        assert np.allclose(host[0], (5, -3, 2), atol=0.3) and host[1] > 0.95


@pytest.fixture(scope="module")
def project(tmp_path_factory):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    proj = make_synthetic_project(
        str(tmp_path_factory.mktemp("pearson") / "proj"),
        n_tiles=(2, 2, 1), tile_size=(64, 64, 32), overlap=20,
        jitter=2.0, seed=4, n_beads_per_tile=40)
    sd = SpimData.load(proj.xml_path)
    return sd, ViewLoader(sd)


def _counts():
    return {"device": st._REFINE_PAIRS["device"].value,
            "host": st._REFINE_PAIRS["host"].value,
            "candidates": st._REFINE_CANDIDATES.value}


def _stitch(project, **kw):
    sd, loader = project
    base = _counts()
    res = st.stitch_all_pairs(sd, loader, sd.view_ids(),
                              st.StitchingParams(**kw), progress=False,
                              devices=1)
    return (sorted(res, key=lambda r: r.pair_key),
            {k: v - base[k] for k, v in _counts().items()})


def test_the_data_picks_the_scorer_and_the_counters_say_which(
        project, monkeypatch):
    """Stored uint16 voxels take the device scorer; the same crops as
    floats that are not whole numbers take the host's, as does a bucket
    that crosses as float32 — with the same bits out."""
    dev, n_dev = _stitch(project, downsampling=(1, 1, 1))
    assert n_dev["device"] == len(dev) >= 4 and n_dev["host"] == 0
    assert n_dev["candidates"] >= 8 * len(dev)

    averaged, n_avg = _stitch(project, downsampling=(2, 2, 1))
    assert n_avg["host"] == len(averaged) >= 4
    assert n_avg["device"] == 0 and n_avg["candidates"] == 0

    # where a bucket's stacks, and with them its scorer, are decided: the
    # same crops in float32, as a bucket the lossless cast turns down
    monkeypatch.setattr(st, "_pack_stacks", lambda jobs, shp: (
        np.stack([pc.pad_to(j.crop_a, shp) for j in jobs]),
        np.stack([pc.pad_to(j.crop_b, shp) for j in jobs]), "float"))
    host, n_host = _stitch(project, downsampling=(1, 1, 1))
    assert n_host == {"device": 0, "host": len(dev), "candidates": 0}
    for d, h in zip(dev, host):
        assert d.pair_key == h.pair_key
        np.testing.assert_array_equal(d.transform, h.transform)
        assert d.correlation == h.correlation


def test_a_second_pass_compiles_nothing(project):
    pc.pearson_sums.clear_cache()
    pc.pcm_peaks_batch.clear_cache()
    _stitch(project, downsampling=(1, 1, 1))
    first = (pc.pearson_sums._cache_size(), pc.pcm_peaks_batch._cache_size())
    # a scorer program a shape bucket, whatever the candidates
    assert first[0] == first[1] >= 2
    _, n = _stitch(project, downsampling=(1, 1, 1))
    assert n["device"] >= 4
    assert (pc.pearson_sums._cache_size(),
            pc.pcm_peaks_batch._cache_size()) == first


def test_the_scorer_runs_on_the_device_that_holds_the_bucket(
        project, monkeypatch):
    if len(jax.local_devices()) < 2:
        pytest.skip("one device")
    seen = []
    real = pc.pearson_sums

    def recording(a, b, *rest):
        out = real(a, b, *rest)
        seen.append((a.devices(), b.devices(), out.devices()))
        return out

    monkeypatch.setattr(pc, "pearson_sums", recording)
    sd, loader = project
    params = st.StitchingParams(downsampling=(1, 1, 1), batch_size=1)
    multi = st.stitch_all_pairs(sd, loader, sd.view_ids(), params,
                                progress=False,
                                devices=len(jax.local_devices()))
    assert seen and all(a == b == o and len(a) == 1 for a, b, o in seen)
    assert len({next(iter(a)) for a, _b, _o in seen}) > 1
    monkeypatch.setattr(pc, "pearson_sums", real)
    single = st.stitch_all_pairs(sd, loader, sd.view_ids(), params,
                                 progress=False, devices=1)
    key = lambda r: r.pair_key
    for m, s in zip(sorted(multi, key=key), sorted(single, key=key)):
        assert key(m) == key(s) and m.correlation == s.correlation
        np.testing.assert_array_equal(m.transform, s.transform)
