"""The benchmark's copied generators give the repository's arrays."""
import jax
import numpy as np
import pytest

from hbench import gen


@pytest.mark.parametrize("seed", [0, 7])
def test_zipfian_matches_the_repository(seed):
    from repro.trace import TraceSpec, generate

    want = generate(TraceSpec(n_requests=4096, footprint_pages=3000,
                              write_frac=0.5, pattern="zipfian",
                              zipf_alpha=0.9, seed=seed))
    got = gen.zipfian(jax.random.PRNGKey(seed), 4096, 3000, 0.9, 0.5)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_sequential_matches_the_repository():
    from repro.trace import TraceSpec, generate

    want = generate(TraceSpec(n_requests=5000, footprint_pages=30,
                              write_frac=0.3, pattern="sequential", seed=3))
    got = gen.sequential(jax.random.PRNGKey(3), 5000, 30, 0.3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_segments_follow_the_seed():
    stream = {"pattern": "zipfian", "requests": 512,
              "footprint_bytes": 100 * 4096, "zipf_alpha": 0.9,
              "write_frac": 0.5}
    big = 2 ** 31 + 12345
    a = gen.segments(big, stream, 3)
    b = gen.segments(big, stream, 3)
    c = gen.segments(big + 1, stream, 3)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert not np.array_equal(np.asarray(a[0][0]), np.asarray(a[0][1]))
