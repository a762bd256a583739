"""The generators are deterministic by seed, and every seed gets the
same mix of work in another order."""

import numpy as np

from bench.tests import tiny
from bench.traffic import serve_closed_decode, serve_open_loop
from bench.traffic.train_fixed_batch import StructuredLM

BIG = 3_000_000_017          # past 2**31: seeds may be that large


def test_train_rows_deterministic_and_all_different():
    a = StructuredLM(512, 8, 64, BIG, 128, 8)
    b = StructuredLM(512, 8, 64, BIG, 128, 8)
    for _ in range(3):
        x, y = a.next(), b.next()
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"][:, :-1],
                                      x["tokens"][:, 1:])
        assert len({r.tobytes() for r in x["tokens"]}) == 8
    c = StructuredLM(512, 8, 64, BIG + 1, 128, 8).next()
    assert not np.array_equal(c["tokens"], x["tokens"])


def test_train_row_halves_differ():
    """A row repeats no pattern, so leaving out half of its tokens leaves
    out tokens that the other half does not hold (the half-batch fault of
    a one-row batch changes the gradient)."""
    data = StructuredLM(100_000, 1, 4096, BIG, 4096, 16)
    row = data.next()["tokens"][0]
    first, second = set(row[:2048].tolist()), set(row[2048:].tolist())
    assert len(first & second) < 0.05 * len(first)
    with np.testing.assert_raises(ValueError):
        StructuredLM(512, 4, 64, BIG, 30, 8)


def test_open_loop_deterministic_and_stratified():
    mix = dict(tiny.MIXES["tiny-chat"], rate_per_s=8.0,
               prompt={"median": 1024, "sigma": 0.8, "min": 64, "max": 3072},
               output={"median": 128, "sigma": 0.8, "min": 8, "max": 1024})
    a = serve_open_loop.arrivals(mix, 1000, BIG, 30.0)
    b = serve_open_loop.arrivals(mix, 1000, BIG, 30.0)
    assert [d for d, _, _ in a] == [d for d, _, _ in b]
    assert all(np.array_equal(p, q) for (_, p, _), (_, q, _) in zip(a, b))
    totals = []
    n_ramp, n_window = round(8.0 * mix["ramp_s"]), 240
    for seed in (1, 2, BIG):
        reqs = serve_open_loop.arrivals(mix, 1000, seed, 30.0)
        window = reqs[n_ramp:n_ramp + n_window]
        assert mix["ramp_s"] <= window[0][0] and window[-1][0] < 45.0
        assert all(64 <= len(p) <= 3072 and 8 <= n <= 1024
                   for _, p, n in reqs)
        dues = [d for d, _, _ in reqs]
        assert dues == sorted(dues)
        totals.append(sum(len(p) for _, p, _ in window))
    # every seed offers the same work: the window's prompt tokens do not
    # move between seeds
    assert max(totals) == min(totals)
    lens = [len(p) for d, p, _ in a if d < 30.0]
    assert 900 < np.median(lens) < 1150


def test_closed_decode_prompts():
    mix = dict(slots=16, prompt_min=6144, prompt_max=10240)
    a = serve_closed_decode.prompts(mix, 1000, BIG)
    b = serve_closed_decode.prompts(mix, 1000, BIG)
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    lens = sorted(len(p) for p in a)
    assert 6144 <= lens[0] and lens[-1] <= 10240
    # one prompt from each sixteenth of the range
    edges = 6144 + np.arange(17) * (10240 - 6144 + 1) / 16
    assert all(lo <= n < hi for n, lo, hi in zip(lens, edges, edges[1:]))


def test_stratified_same_set_in_blocks():
    """Every seed draws the same quantiles, reordered only within blocks
    of consecutive draws, each block spanning the distribution."""
    rng1, rng2 = np.random.default_rng(1), np.random.default_rng(BIG)
    a = serve_open_loop.stratified(rng1, 82, 8)
    b = serve_open_loop.stratified(rng2, 82, 8)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), (np.arange(82) + 0.5) / 82)
    nb = 11                      # blocks of 8 or 7 draws
    sizes = [sum(1 for g in range(0, 82, nb) if k < min(nb, 82 - g))
             for k in range(nb)]
    assert sum(sizes) == 82 and set(sizes) == {7, 8}
    start = 0
    for size in sizes:
        blk_a, blk_b = a[start:start + size], b[start:start + size]
        np.testing.assert_array_equal(np.sort(blk_a), np.sort(blk_b))
        assert blk_a.min() < 0.2 and blk_a.max() > 0.8
        start += size
