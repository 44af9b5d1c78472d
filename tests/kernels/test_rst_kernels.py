"""Pallas RST engines vs pure-numpy oracles: shape/dtype sweep (interpret)."""
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro import spans
from repro.core import RSTParams
from repro.kernels import ops
from repro.kernels.ref import rst_read_checksum_ref, rst_write_ref
from repro.kernels.rst_read import LANE, rst_read
from repro.kernels.rst_write import rst_write

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8]


def _mk(rows, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if jnp.dtype(dtype) == jnp.int8:
        x = rng.integers(-4, 5, size=(rows, LANE), dtype=np.int8)
    else:
        x = rng.standard_normal((rows, LANE)).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("burst_rows,stride,wset,n", [
    (8, 1, 8, 8),      # pure sequential, one pass
    (8, 1, 8, 20),     # wraps the working set
    (8, 2, 16, 16),    # strided
    (8, 4, 8, 9),      # stride wraps within W
    (16, 1, 4, 7),     # bigger burst
    (8, 8, 8, 5),      # stride == W: hammer one tile
])
def test_read_checksum_vs_ref(dtype, burst_rows, stride, wset, n):
    rows = wset * burst_rows
    buf = _mk(rows, dtype)
    params = jnp.array([stride, wset, 0, n], jnp.int32)
    out = rst_read(params, buf, grid_txns=max(n, 4), burst_rows=burst_rows)
    ref = rst_read_checksum_ref(np.asarray(buf), stride, wset, 0, n,
                                burst_rows)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out), ref, rtol=rtol, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("burst_rows,stride,wset,n,base", [
    (8, 1, 8, 8, 0),
    (8, 3, 8, 12, 0),    # revisits: last write wins
    (8, 2, 8, 3, 2),     # nonzero base, partial coverage
    (16, 1, 6, 4, 1),
])
def test_write_vs_ref(dtype, burst_rows, stride, wset, n, base):
    rows = (base + wset) * burst_rows
    buf = _mk(rows, dtype, seed=1)
    buf_np = np.asarray(buf).copy()
    params = jnp.array([stride, wset, base, n], jnp.int32)
    out = rst_write(params, buf, grid_txns=max(n, 4), burst_rows=burst_rows)
    ref = rst_write_ref(buf_np, stride, wset, base, n, burst_rows)
    np.testing.assert_allclose(np.asarray(out).astype(np.float32),
                               ref.astype(np.float32), rtol=1e-6)


@given(stride=st.integers(1, 8).map(lambda e: 1 << (e % 4)),
       wset_log=st.integers(1, 4), n=st.integers(1, 40))
@settings(max_examples=40, deadline=None)
def test_read_property(stride, wset_log, n):
    wset = 1 << wset_log
    stride = min(stride, wset)
    buf = _mk(wset * 8, jnp.float32, seed=42)
    params = jnp.array([stride, wset, 0, n], jnp.int32)
    out = rst_read(params, buf, grid_txns=64, burst_rows=8)
    ref = rst_read_checksum_ref(np.asarray(buf), stride, wset, 0, n, 8)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-4)


def test_runtime_reparameterization_no_retrace():
    """Paper challenge C2: one compiled engine serves many (N,S,W,A).

    Same grid + shapes => the jitted pallas_call must not retrace when only
    the scalar operand changes.
    """
    buf = _mk(8 * 16, jnp.float32)
    # Count traces via cache: call twice with different params.
    r1 = rst_read(jnp.array([1, 16, 0, 16], jnp.int32), buf, grid_txns=32)
    misses0 = rst_read._cache_size()
    r2 = rst_read(jnp.array([4, 8, 2, 9], jnp.int32), buf, grid_txns=32)
    assert rst_read._cache_size() == misses0   # no recompilation
    # And results still match their own oracles.
    np.testing.assert_allclose(
        np.asarray(r1), rst_read_checksum_ref(np.asarray(buf), 1, 16, 0, 16, 8),
        rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(r2), rst_read_checksum_ref(np.asarray(buf), 4, 8, 2, 9, 8),
        rtol=1e-4)


def test_n_beyond_grid_is_clamped():
    buf = _mk(8 * 8, jnp.float32)
    out = rst_read(jnp.array([1, 8, 0, 99], jnp.int32), buf, grid_txns=16)
    ref = rst_read_checksum_ref(np.asarray(buf), 1, 8, 0, 16, 8)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5)


class TestGridBucketing:
    def test_bucket_values(self):
        assert ops.grid_bucket(1) == 16      # floor
        assert ops.grid_bucket(16) == 16
        assert ops.grid_bucket(17) == 32
        assert ops.grid_bucket(1024) == 1024
        assert ops.grid_bucket(1025) == 2048

    def test_bucket_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            ops.grid_bucket(0)

    def test_variants_share_one_compiled_kernel(self):
        """RST variants with different N (same bucket + buffer shape) must
        reuse the jitted kernel — the grid is static, so without bucketing
        every N cost a fresh ~0.5 s trace/compile."""
        p1 = RSTParams(n=17, b=4096, s=4096, w=16 * 4096)
        s1 = ops.measure_read_bandwidth(p1)
        size = rst_read._cache_size()
        p2 = RSTParams(n=25, b=4096, s=8192, w=16 * 4096)
        s2 = ops.measure_read_bandwidth(p2)
        assert rst_read._cache_size() == size   # no recompilation
        # Bucketed grids still move exactly N transactions.
        assert s1.bytes_moved == 17 * 4096
        assert s2.bytes_moved == 25 * 4096

    def test_compiled_mode_defaults_to_exact_grid(self, monkeypatch):
        """Off interpret mode the gbps number is a real measurement, and a
        bucketed grid would bias it low (excess steps are timed but not
        counted) — the default must stay the exact grid."""
        p = RSTParams(n=17, b=4096, s=4096, w=16 * 4096)
        operand_exact = ops.params_operand(p, jnp.float32, 8, 17)
        assert int(operand_exact[3]) == 17
        # The wrappers' grid choice: interpret buckets, compiled does not.
        assert ops.default_grid(p.n) == 32
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ops.default_grid(p.n) == 17

    @pytest.mark.parametrize("platform,interpret", [
        ("tpu", False), ("cpu", True), ("gpu", True)])
    def test_interpret_mode_follows_platform(self, monkeypatch, platform,
                                             interpret):
        # The platform alone decides: a TPU never runs the interpreter.
        from repro.kernels.rst_read import interpret_mode
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert interpret_mode() is interpret

    def test_bucketed_checksum_matches_ref(self):
        p = RSTParams(n=13, b=4096, s=8192, w=16 * 4096)   # grid bucket 16
        s = ops.measure_read_bandwidth(p, dtype=jnp.float32)
        ref = rst_read_checksum_ref(
            np.asarray(ops.make_working_buffer(p, jnp.float32)), 2, 16, 0,
            13, 8)
        np.testing.assert_allclose(s.checksum, ref, rtol=1e-5)


class TestOpsWrappers:
    def test_measure_read_bandwidth(self):
        p = RSTParams(n=16, b=4096, s=4096, w=16 * 4096)
        s = ops.measure_read_bandwidth(p, dtype=jnp.float32)
        assert s.bytes_moved == 16 * 4096
        assert s.gbps > 0
        ref = rst_read_checksum_ref(
            np.asarray(ops.make_working_buffer(p, jnp.float32)), 1, 16, 0,
            16, 8)
        np.testing.assert_allclose(s.checksum, ref, rtol=1e-5)

    def test_measure_write_bandwidth(self):
        p = RSTParams(n=8, b=4096, s=8192, w=16 * 4096)
        s = ops.measure_write_bandwidth(p, dtype=jnp.float32)
        assert s.bytes_moved == 8 * 4096

    def test_measure_duplex_bandwidth(self):
        # Both directions over one buffer: bytes count read + write, and
        # the checksum is the read engine's (taken before the write
        # mutates the buffer).
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        s = ops.measure_duplex_bandwidth(p, dtype=jnp.float32)
        assert s.bytes_moved == 2 * 8 * 4096
        ref = rst_read_checksum_ref(
            np.asarray(ops.make_working_buffer(p, jnp.float32)), 1, 16, 0,
            8, 8)
        np.testing.assert_allclose(s.checksum, ref, rtol=1e-5)

    def test_duplex_wired_into_pallas_backend(self):
        from repro.core import HBM, get_backend, get_mapping
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        res = get_backend("pallas").throughput(HBM, p, get_mapping(HBM),
                                               op="duplex")
        assert res.bound == "measured"
        assert res.detail["bytes"] == 2 * 8 * 4096
        with pytest.raises(ValueError, match="unknown op"):
            get_backend("pallas").throughput(HBM, p, get_mapping(HBM),
                                             op="erase")

    def test_burst_must_match_tile(self):
        p = RSTParams(n=8, b=64, s=4096, w=16 * 4096)
        with pytest.raises(ValueError, match="tile"):
            ops.params_operand(p, jnp.float32)

    def test_tile_bytes(self):
        assert ops.tile_bytes(jnp.float32) == 4096
        assert ops.tile_bytes(jnp.bfloat16) == 2048
        assert ops.tile_bytes(jnp.int8, burst_rows=16) == 2048


class TestContendedKernel:
    """Concurrent-access engines (rst_contend.py) vs a numpy replay."""

    def _oracle(self, buf, stride, wset, n, num_engines, burst_rows=8):
        expect = np.zeros((burst_rows, LANE), dtype=np.float64)
        b = np.asarray(buf, dtype=np.float64)
        for k in range(num_engines):
            for t in range(n):
                blk = k * wset + (t * stride) % wset
                expect += b[blk * burst_rows:(blk + 1) * burst_rows, :]
        return expect.astype(np.float32)

    @pytest.mark.parametrize("num_engines", [1, 2, 3, 4])
    def test_checksum_vs_oracle(self, num_engines):
        stride, wset, n = 2, 8, 12
        buf = _mk(num_engines * wset * 8, jnp.float32, seed=3)
        p = RSTParams(n=n, b=4096, s=stride * 4096, w=wset * 4096)
        s = ops.measure_contended_bandwidth(p, num_engines=num_engines,
                                            grid_txns=16)
        np.testing.assert_allclose(
            s.checksum,
            self._oracle(ops.make_working_buffer(
                p, jnp.float32, num_engines=num_engines),
                stride, wset, n, num_engines),
            rtol=1e-5)
        assert s.bytes_moved == num_engines * n * 4096

    def test_single_engine_matches_read_kernel(self):
        # N=1 must degenerate to the plain read engine's checksum.
        p = RSTParams(n=9, b=4096, s=8192, w=16 * 4096)
        cont = ops.measure_contended_bandwidth(p, num_engines=1)
        read = ops.measure_read_bandwidth(p)
        np.testing.assert_allclose(cont.checksum, read.checksum, rtol=1e-6)
        assert cont.bytes_moved == read.bytes_moved

    def test_wired_into_pallas_backend(self):
        from repro.core import HBM, get_backend, get_mapping
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        res = get_backend("pallas").contended_throughput(
            HBM, p, get_mapping(HBM), num_engines=2)
        assert res.num_engines == 2
        assert res.bound == "measured"
        assert res.detail["bytes"] == 2 * 8 * 4096
        assert np.isnan(res.queueing_delay_cycles)
        with pytest.raises(ValueError, match="read"):
            get_backend("pallas").contended_throughput(
                HBM, p, get_mapping(HBM), num_engines=2, op="write")

    def test_rejects_bad_engine_count(self):
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        with pytest.raises(ValueError, match="num_engines"):
            ops.measure_contended_bandwidth(p, num_engines=0)

    # -- burst-grant arbitration variant (DESIGN.md §9) ----------------------

    @pytest.mark.parametrize("burst_beats", [2, 4, 8])
    @pytest.mark.parametrize("num_engines", [2, 3])
    def test_burst_grant_checksum_vs_oracle(self, num_engines, burst_beats):
        # The checksum is the sum of every tile each engine reads — the
        # same multiset regardless of grant interleave — so the round-
        # robin oracle pins every grant size, including n % bb != 0.
        stride, wset, n = 2, 8, 11
        p = RSTParams(n=n, b=4096, s=stride * 4096, w=wset * 4096)
        s = ops.measure_contended_bandwidth(
            p, num_engines=num_engines, arbitration="burst",
            burst_beats=burst_beats, grid_txns=16)
        np.testing.assert_allclose(
            s.checksum,
            self._oracle(ops.make_working_buffer(
                p, jnp.float32, num_engines=num_engines),
                stride, wset, n, num_engines),
            rtol=1e-5)
        assert s.bytes_moved == num_engines * n * 4096

    def test_exclusive_matches_round_robin_checksum(self):
        p = RSTParams(n=9, b=4096, s=8192, w=8 * 4096)
        rr = ops.measure_contended_bandwidth(p, num_engines=2, grid_txns=16)
        ex = ops.measure_contended_bandwidth(p, num_engines=2,
                                             arbitration="exclusive",
                                             grid_txns=16)
        np.testing.assert_allclose(ex.checksum, rr.checksum, rtol=1e-5)

    def test_backend_threads_arbitration(self):
        from repro.core import HBM, get_backend, get_mapping
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        res = get_backend("pallas").contended_throughput(
            HBM, p, get_mapping(HBM), num_engines=2,
            arbitration="burst", burst_beats=4)
        assert (res.arbitration, res.burst_beats) == ("burst", 4)
        assert res.bound == "measured"

    def test_rejects_bad_arbitration(self):
        p = RSTParams(n=8, b=4096, s=4096, w=16 * 4096)
        with pytest.raises(ValueError, match="arbitration"):
            ops.measure_contended_bandwidth(p, num_engines=2,
                                            arbitration="lottery")
        with pytest.raises(ValueError, match="burst_beats"):
            ops.measure_contended_bandwidth(p, num_engines=2,
                                            arbitration="round_robin",
                                            burst_beats=4)

    def test_grant_beats_clamped_to_grid(self):
        # Regression: an oversized grant must not pad the grid with gated
        # dummy steps (they occupy the pipeline and bias gbps low) — a
        # grant covering the stream IS the exclusive whole-stream grant.
        assert ops._resolve_grant_beats("burst", 10**9, 16) == 16
        assert ops._resolve_grant_beats("burst", 6, 16) == 6
        assert ops._resolve_grant_beats("exclusive", 1, 16) == 16
        assert ops._resolve_grant_beats("round_robin", 1, 16) == 1
        p = RSTParams(n=11, b=4096, s=2 * 4096, w=8 * 4096)
        huge = ops.measure_contended_bandwidth(
            p, num_engines=2, arbitration="burst", burst_beats=10**9,
            grid_txns=16)
        ex = ops.measure_contended_bandwidth(
            p, num_engines=2, arbitration="exclusive", grid_txns=16)
        np.testing.assert_allclose(huge.checksum, ex.checksum, rtol=1e-5)
        assert huge.bytes_moved == ex.bytes_moved


class TestMixKernel:
    """Heterogeneous engine mixes (rst_contend_mix_read, DESIGN.md §13):
    per-engine scalar-prefetch operand table vs a numpy replay."""

    def _mix(self, entries):
        from repro.core.engine_mix import EngineMix
        return EngineMix(tuple(entries))

    def _oracle(self, buf, rows, grid, burst_rows=8):
        # Sum of every tile each engine reads along its own (stride,
        # wset, base) walk — grant-interleave invariant, like the
        # homogeneous oracle above.
        expect = np.zeros((burst_rows, LANE), dtype=np.float64)
        b = np.asarray(buf, dtype=np.float64)
        for stride, wset, base, n in rows:
            for t in range(min(n, grid)):
                blk = base + (t * stride) % wset
                expect += b[blk * burst_rows:(blk + 1) * burst_rows, :]
        return expect.astype(np.float32)

    @pytest.mark.parametrize("arbitration,burst_beats",
                             [("round_robin", 1), ("burst", 4),
                              ("exclusive", 1)])
    def test_checksum_vs_oracle(self, arbitration, burst_beats):
        # Three readers with different strides, window sets and stream
        # lengths — genuinely heterogeneous, ragged counts included.
        mix = self._mix([
            (RSTParams(n=12, b=4096, s=2 * 4096, w=8 * 4096), "read"),
            (RSTParams(n=9, b=4096, s=4096, w=4 * 4096), "read"),
            (RSTParams(n=16, b=4096, s=8 * 4096, w=16 * 4096), "read"),
        ])
        grid = 16
        s = ops.measure_contended_mix_bandwidth(
            mix, arbitration=arbitration, burst_beats=burst_beats,
            grid_txns=grid)
        rows, _ = ops._mix_block_rows(mix, jnp.float32, 8, grid)
        buf = ops.make_mix_working_buffer(mix, jnp.float32, grid_txns=grid)
        np.testing.assert_allclose(
            s.checksum, self._oracle(buf, rows, grid), rtol=1e-5)
        assert s.bytes_moved == sum(min(p.n, grid) * p.b
                                    for p in mix.params)

    def test_uniform_mix_delegates_bit_identically(self):
        # The tentpole reduction at the kernel layer: an all-identical
        # mix IS measure_contended_bandwidth — same kernel, same floats.
        p = RSTParams(n=12, b=4096, s=2 * 4096, w=8 * 4096)
        mix = self._mix([(p, "read")] * 3)
        via_mix = ops.measure_contended_mix_bandwidth(mix, grid_txns=16)
        homo = ops.measure_contended_bandwidth(p, num_engines=3,
                                               grid_txns=16)
        assert np.array_equal(via_mix.checksum, homo.checksum)
        assert via_mix.bytes_moved == homo.bytes_moved

    def test_operand_table_layout(self):
        # int32[N+1, 4]: header row (engines, grant beats, 0, 0) then one
        # (stride_blocks, wset_blocks, base_block, n_txns) row per engine
        # with consecutive window offsets folded into the bases.
        mix = self._mix([
            (RSTParams(n=8, b=4096, s=2 * 4096, w=8 * 4096), "read"),
            (RSTParams(n=6, b=4096, s=4096, w=4 * 4096), "read"),
        ])
        table = ops.mix_params_operand(mix, jnp.float32, grid_txns=16,
                                       burst_beats=4)
        assert table.shape == (3, 4)
        assert table.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(table),
                                      [[2, 4, 0, 0],
                                       [2, 8, 0, 8],
                                       [1, 4, 8, 6]])

    def test_non_read_entries_are_routed_away(self):
        p = RSTParams(n=8, b=4096, s=4096, w=4 * 4096)
        mix = self._mix([(p, "read"), (p, "write")])
        with pytest.raises(ValueError, match="DESIGN.md"):
            ops.measure_contended_mix_bandwidth(mix)
        with pytest.raises(ValueError, match="DESIGN.md"):
            ops.mix_params_operand(mix, jnp.float32)

    def test_mismatched_burst_names_the_entry(self):
        mix = self._mix([
            (RSTParams(n=8, b=4096, s=4096, w=4 * 4096), "read"),
            (RSTParams(n=8, b=8192, s=8192, w=8 * 8192), "read"),
        ])
        with pytest.raises(ValueError, match="entry 1"):
            ops.mix_params_operand(mix, jnp.float32)

    def test_wired_into_pallas_backend(self):
        from repro.core import HBM, get_backend, get_mapping
        mix = self._mix([
            (RSTParams(n=8, b=4096, s=4096, w=4 * 4096), "read"),
            (RSTParams(n=8, b=4096, s=2 * 4096, w=8 * 4096), "read"),
        ])
        res = get_backend("pallas").contended_throughput(
            HBM, mix.entries[0][0], get_mapping(HBM),
            num_engines=len(mix), mix=mix)
        assert res.bound == "measured"
        assert res.mix == mix
        assert res.num_engines == 2


def _read_ref(p, num_engines=1):
    """The reference checksum of `p`'s stream on the working buffer, for
    each of `num_engines` engines in its own window."""
    buf = np.asarray(ops.make_working_buffer(p, jnp.float32,
                                             num_engines=num_engines))
    s, w = p.s // p.b, p.w // p.b
    return sum(rst_read_checksum_ref(buf, s, w, k * w, p.n, 8)
               for k in range(num_engines))


def _write_ref(p):
    """The first 8 rows of the working buffer after `p`'s write stream."""
    buf = np.asarray(ops.make_working_buffer(p, jnp.float32))
    return rst_write_ref(buf, p.s // p.b, p.w // p.b, 0, p.n, 8)[:8]


class TestWarmOnce:
    """A compiled RST program is warmed once per process: the first call
    with a signature (kernel, static arguments, operand shapes) makes one
    untimed call before the timed one, every later call only the timed
    one, and no timed call compiles."""

    P = RSTParams(n=12, b=4096, s=2 * 4096, w=8 * 4096)
    CASES = {
        # kernel, measure call, its reference, what makes a new signature
        "read": ("rst_read", ops.measure_read_bandwidth, _read_ref,
                 {"grid_txns": 32}),
        "contend": ("rst_contend_read",
                    functools.partial(ops.measure_contended_bandwidth,
                                      num_engines=2),
                    functools.partial(_read_ref, num_engines=2),
                    {"arbitration": "burst", "burst_beats": 4}),
        "write": ("rst_write", ops.measure_write_bandwidth, _write_ref,
                  {"grid_txns": 32}),
    }

    @pytest.fixture
    def fresh(self, monkeypatch):
        """A process that has warmed no signature yet."""
        monkeypatch.setattr(ops, "_WARMED", set())

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_a_repeated_signature_runs_one_kernel_call(self, monkeypatch,
                                                       fresh, case):
        name, measure, reference, change = self.CASES[case]
        kernel = getattr(ops, name)
        calls = []

        def counted(*args, **kw):
            calls.append(kw)
            return kernel(*args, **kw)
        monkeypatch.setattr(ops, name, counted)
        first = measure(self.P, grid_txns=16)
        assert len(calls) == 2                  # warm-up, then timed
        second = measure(self.P, grid_txns=16)
        assert len(calls) == 3                  # timed only
        measure(self.P, **{"grid_txns": 16, **change})
        assert len(calls) == 5                  # a new signature: both
        assert np.array_equal(second.checksum, first.checksum)
        np.testing.assert_allclose(second.checksum, reference(self.P),
                                   rtol=1e-5)
        assert second.bytes_moved == first.bytes_moved

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_no_compile_lands_in_a_timed_call(self, monkeypatch, fresh,
                                              case):
        _, measure, _, _ = self.CASES[case]
        compiles, timed = [], []
        span = spans.span

        @contextlib.contextmanager
        def recorded(name, **stats):
            start = time.time()       # the clock of JAX's compile spans
            with span(name, **stats):
                yield
            if name == "repro.ops.timed":
                timed.append((start, time.time()))

        def on_span(event, start, end, **_):
            if event.startswith("/jax/core/compile/"):
                compiles.append((start, end))
        monkeypatch.setattr(spans, "span", recorded)
        jax.clear_caches()
        jax.monitoring.register_event_time_span_listener(on_span)
        try:
            counts = []
            # first-seen signature, the same again, a new window size
            for w in (8, 8, 16):
                before = len(compiles)
                measure(RSTParams(n=12, b=4096, s=4096, w=w * 4096),
                        grid_txns=16)
                counts.append(len(compiles) - before)
        finally:
            jax.monitoring.unregister_event_time_span_listener(on_span)
        assert counts[0] > 0 and counts[2] > 0  # the listener hears them
        assert counts[1] == 0
        assert len(timed) == 3
        assert not [(c, t) for c in compiles for t in timed
                    if c[0] < t[1] and t[0] < c[1]]
