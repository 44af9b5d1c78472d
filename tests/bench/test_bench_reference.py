"""The RST reference's buffer values: the program builds its working buffer
as a float32 iota mod 251, whose values above 2^24 follow the index
rounded to float32, not the index itself."""
import numpy as np
import pytest

from bench_cells import ROOT  # noqa: F401  (puts the benchmark on the path)

from bench.references import rst_checksum as reference


def _program_rule(first: int, elems: int = 1024) -> np.ndarray:
    """The program's buffer rule for one burst, in plain float32."""
    flat = np.arange(first, first + elems, dtype=np.int64).astype(np.float32)
    return np.remainder(flat, np.float32(251))


@pytest.mark.parametrize("first", [0, 1024, (1 << 24) - 1024, 1 << 24,
                                   (1 << 24) + 1024, (1 << 25) + 7 * 1024,
                                   (1 << 26) + 3 * 1024, (1 << 28) - 1024])
def test_one_burst_follows_float32_rounding(first):
    got = reference.checksum(np.array([first * 4]), 4096, 251)
    np.testing.assert_array_equal(got, _program_rule(first))


def test_bursts_add_in_order_in_float32():
    """Enough bursts that the running sums pass 2^24, where the order of
    the float32 additions shows."""
    rng = np.random.default_rng(5)
    pool = rng.integers(0, 1 << 18, 64) * 1024
    rows = np.stack([_program_rule(int(f)) for f in pool])
    order = rng.integers(0, len(pool), 140_000)
    acc = np.zeros(1024, np.float32)
    for i in order:
        acc += rows[i]
    assert acc.max() > 2 ** 24
    np.testing.assert_array_equal(
        reference.checksum(pool[order] * 4, 4096, 251), acc)


def test_the_program_buffer_matches_below_and_above_2_24():
    """The program's own buffer, built as it builds it, on the CPU."""
    from repro.core import RSTParams
    from repro.kernels import ops
    p = RSTParams(n=1, b=4096, s=4096, w=(1 << 24) * 4 + 8 * 4096)
    buf = np.asarray(ops.make_working_buffer(p, np.float32)).reshape(-1, 1024)
    for row in (0, 16383, 16384, 16385, 16391):
        got = reference.checksum(np.array([row * 4096]), 4096, 251)
        np.testing.assert_array_equal(got, buf[row])
