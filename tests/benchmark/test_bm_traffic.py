"""The traffic is a function of the seed: same sizes and arrivals, another order."""

import hashlib
import io
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmark import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted((ROOT / "benchmark" / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_mix_loads_and_none_takes_the_decoders_downscale_path(path):
    import json
    mix = traffic.Mix.load(path)
    table = json.loads(path.read_text())["images"]["long_side_px"]
    long_sides = Counter(max(hw) for hw in mix.shapes)
    n, weight = len(mix.shapes), sum(w for _, w in table)
    assert {k: v / n for k, v in long_sides.items()} == pytest.approx({k: w / weight for k, w in table})
    landscape = sum(1 for h, w in mix.shapes if w > h) / n
    assert landscape == pytest.approx(0.7)
    # phone originals: 3, 8 and 12 megapixels, none over the largest canvas a configuration serves
    assert max(max(hw) for hw in mix.shapes) <= 4096, "a larger image would take the decoder's downscale path"
    assert 3e6 <= min(h * w for h, w in mix.shapes) and max(h * w for h, w in mix.shapes) <= 12.2e6


def test_schedule_same_seed_same_times_other_seed_same_gaps():
    a, b, c = (traffic.schedule(120.0, 10.0, s) for s in (7, 7, BIG_SEED))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == len(c)
    gaps_of = lambda due: np.sort(np.diff(np.concatenate([[0.0], due])))
    n = min(len(a), len(c)) - 50   # all but the window's last arrivals: the same gaps, reordered
    assert abs(gaps_of(a)[:n].sum() - gaps_of(c)[:n].sum()) < 0.05 * a[-1]
    gaps = -np.log1p(-(np.arange(1200) + 0.5) / 1200) / 120.0
    assert gaps.mean() == pytest.approx(1 / 120.0, rel=0.01)   # a Poisson process at the stated rate
    assert np.std(gaps) == pytest.approx(1 / 120.0, rel=0.05)


@pytest.fixture(scope="module")
def small_mix(tmp_path_factory):
    import json
    p = tmp_path_factory.mktemp("mix") / "small.json"
    p.write_text(json.dumps({"loop": "closed", "clients": 2, "files_per_request": 3,
                             "images": {"quality": 88, "long_side_px": [[64, 1], [96, 3]],
                                        "aspect_h_w": [[[3, 4], 1], [[4, 3], 1]], "bases_per_shape": 2}}))
    return traffic.Mix.load(p)


def test_decks_hold_the_same_shapes_in_another_order(small_mix):
    def shapes(seed):
        src = traffic.Source(traffic.Corpus(small_mix, seed, threads=2), seed)
        return [b.hw for _ in range(16) for b, _ in src.take().images]
    a, b, c = shapes(3), shapes(3), shapes(BIG_SEED)
    assert a == b and a != c
    assert Counter(a) == Counter(c) == Counter(small_mix.shapes * 6)


def test_no_two_images_of_a_run_decode_to_the_same_pixels(small_mix):
    from PIL import Image
    corpus = traffic.Corpus(small_mix, BIG_SEED, threads=2)
    src = traffic.Source(corpus, BIG_SEED)
    digests, bodies = set(), 0
    for _ in range(40):
        req = src.take()
        for base, k in req.images:
            px = np.asarray(Image.open(io.BytesIO(traffic.variant(base, k))).convert("RGB"))
            assert px.shape[:2] == base.hw
            digests.add(hashlib.sha1(px.tobytes()).hexdigest())
            bodies += 1
    assert len(digests) == bodies == 120
    body, ctype = req.body()
    assert ctype.startswith("multipart/form-data") and body.count(b"\xff\xd8\xff") == 3


def test_variants_patch_only_the_luminance_table(small_mix):
    base = traffic.Corpus(small_mix, 1, threads=2).bases[small_mix.shapes[0]][0]
    assert traffic.variant(base, 0) == base.jpeg
    v = traffic.variant(base, traffic.VARIANTS_PER_BASE - 1)
    diff = [i for i, (x, y) in enumerate(zip(base.jpeg, v)) if x != y]
    assert diff == [base.table + p for p in traffic.PATCH_POSITIONS]
    with pytest.raises(ValueError):
        traffic.variant(base, traffic.VARIANTS_PER_BASE)


def test_a_corpus_that_runs_out_says_so(small_mix, monkeypatch):
    monkeypatch.setattr(traffic, "VARIANTS_PER_BASE", 2)
    corpus = traffic.Corpus(small_mix, 1, threads=2)
    hw = small_mix.shapes[0]
    for _ in range(4):
        corpus.deal(hw)
    with pytest.raises(RuntimeError, match="unique variants"):
        corpus.deal(hw)


def test_the_copy_of_seeded_jpeg_idea_makes_photo_like_bytes(small_mix):
    rs = np.random.Generator(np.random.PCG64(5))
    jpeg = traffic.encode_jpeg(traffic.synth_image(rs, 480, 640), 88)
    assert 0.1 < len(jpeg) / (480 * 640) < 0.5   # bytes per pixel of a photograph at q88
