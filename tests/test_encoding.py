"""Unit + property tests for spike encodings (paper Sec. 2.1.2 / 5.2)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, st

from repro.core import encoding


def test_paper_eq6_mnist_geometry():
    # W=28, K=3: ceil(log2(28/3)) = 4 bits per coordinate (paper Eq. 6)
    fmt = encoding.make_format(28, 3)
    assert fmt.bits_coord == 4
    assert fmt.compressed
    # paper: "There exist 6 unused bit-patterns"
    assert encoding.spare_patterns(28, 3) == 6
    # compressed word: 2*4 = 8 bits -> fits the 4096-word BRAM geometry
    assert fmt.word_bits == 8
    assert encoding.word_nbytes(fmt) == 1


def test_paper_eq7_fallback():
    # W/K just below a power of two -> no spare patterns -> fallback (Eq. 7)
    # n_win = 16 = 2^4 exactly -> spare = 0 -> original encoding
    fmt = encoding.make_format(48, 3)  # ceil(48/3) = 16
    assert not fmt.compressed
    assert fmt.word_bits == 2 * 4 + 2  # explicit status bits return


def test_original_encoding_word_width():
    # paper Table 3: w_AE = 10 bits for the 28x28 uncompressed AEQ
    fmt = encoding.make_format(28, 3, compressed=False)
    assert fmt.word_bits == 10


@given(
    width=st.integers(1, 64),  # a map at most the kernel is one window
    kernel=st.sampled_from([3, 5]),
    seed=st.integers(0, 2**16),
)
def test_pack_unpack_roundtrip(width, kernel, seed):
    fmt = encoding.make_format(width, kernel)
    rng = np.random.default_rng(seed)
    n = 32
    i = rng.integers(0, fmt.n_win, n)
    j = rng.integers(0, fmt.n_win, n)
    valid = rng.random(n) < 0.7
    words = encoding.pack_events(fmt, jnp.asarray(i), jnp.asarray(j),
                                 jnp.asarray(valid))
    i2, j2, v2 = encoding.unpack_events(fmt, words)
    np.testing.assert_array_equal(np.asarray(v2), valid)
    np.testing.assert_array_equal(np.asarray(i2)[valid], i[valid])
    np.testing.assert_array_equal(np.asarray(j2)[valid], j[valid])


@pytest.mark.parametrize("width, kernel, n_win", [(1, 3, 1), (2, 3, 1),
                                                  (3, 5, 1), (4, 3, 2)])
def test_maps_at_most_the_kernel_are_one_window(width, kernel, n_win):
    """VGG-16's 2x2 maps under 3x3 kernels: one window, whose word packs."""
    fmt = encoding.make_format(width, kernel)
    assert fmt.n_win == n_win
    i, j, valid = encoding.unpack_events(fmt, encoding.pack_events(
        fmt, jnp.arange(n_win), jnp.zeros(n_win, jnp.int32),
        jnp.ones(n_win, bool)))
    assert list(np.asarray(i)) == list(range(n_win)) and bool(valid.all())


@given(width=st.integers(4, 96), kernel=st.sampled_from([2, 3, 5, 7]))
def test_invalid_word_never_collides(width, kernel):
    """The in-band status sentinel can never decode as a valid event."""
    fmt = encoding.make_format(width, kernel)
    _, _, valid = encoding.unpack_events(
        fmt, jnp.asarray([fmt.invalid_word]))
    assert not bool(valid[0])


def test_ttfs_input_encoding():
    img = jnp.asarray([[0.0, 0.2, 0.5, 1.0]])
    raster = encoding.encode_ttfs(img, T=4)
    assert raster.shape == (4, 1, 4)
    # each above-threshold pixel spikes exactly once; brighter spikes earlier
    sums = np.asarray(raster.sum(0))[0]
    np.testing.assert_array_equal(sums, [0, 1, 1, 1])
    t_of = lambda px: int(np.argmax(np.asarray(raster[:, 0, px])))
    assert t_of(3) <= t_of(2) <= t_of(1)


def test_rate_encoding_statistics():
    import jax

    img = jnp.full((8, 8), 0.5)
    raster = encoding.encode_rate(img, 64, jax.random.PRNGKey(0))
    assert abs(float(raster.mean()) - 0.5) < 0.05
