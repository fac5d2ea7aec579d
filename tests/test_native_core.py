"""Native C++ core tests: the ctypes-loaded partitioner/reducer
must agree with the pure-Python implementations (the reference's analogous
split is C++ core + numpy test replications, SURVEY.md §4)."""

import numpy as np
import pytest

from byteps_tpu import native
from byteps_tpu.common.partitioner import chunk_bounds as py_bounds

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def test_key_encoding_matches_python():
    lib = native.load()
    for declared, part in [(0, 0), (1, 2), (77, 65535), (65535, 1)]:
        assert native.make_key(declared, part) == (declared << 16) | part
        assert lib.bps_key_declared(native.make_key(declared, part)) == \
            declared
        assert lib.bps_key_part(native.make_key(declared, part)) == part


@pytest.mark.parametrize("num_elems,itemsize,pbytes", [
    (0, 4, 4096), (1, 4, 4096), (1024, 4, 4096), (1025, 4, 4096),
    (10_000_000, 4, 4096000), (123_457, 2, 1000), (512, 8, 512),
])
def test_chunk_bounds_matches_python(num_elems, itemsize, pbytes):
    assert native.chunk_bounds(num_elems, itemsize, pbytes) == \
        py_bounds(num_elems, itemsize, pbytes)


def test_library_has_no_scheduler_and_reports_its_abi():
    """The chunk queue is Python (common/scheduler.py): the library
    exports no bps_sched_* symbol, and says so through its ABI version."""
    lib = native.load()
    assert lib.bps_native_abi_version() == 5
    for sym in ("bps_sched_create", "bps_sched_add", "bps_sched_get",
                "bps_sched_drain"):
        assert not hasattr(lib, sym), sym


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                   np.int64])
def test_inplace_add_matches_numpy(dtype):
    rng = np.random.default_rng(0)
    if np.issubdtype(dtype, np.floating):
        a = rng.standard_normal(1 << 20).astype(dtype)
        b = rng.standard_normal(1 << 20).astype(dtype)
    else:
        a = rng.integers(-1000, 1000, 1 << 20).astype(dtype)
        b = rng.integers(-1000, 1000, 1 << 20).astype(dtype)
    expect = a + b
    out = native.inplace_add(a.copy(), b)
    np.testing.assert_array_equal(out, expect)


def test_inplace_scaled_add():
    rng = np.random.default_rng(1)
    a = rng.standard_normal(100_003).astype(np.float32)
    b = rng.standard_normal(100_003).astype(np.float32)
    expect = a + 0.25 * b
    out = native.inplace_scaled_add(a.copy(), b, 0.25)
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_bf16_reduce_round_to_nearest_even():
    lib = native.load()
    import ctypes
    try:
        import ml_dtypes
    except ImportError:
        pytest.skip("ml_dtypes unavailable")
    rng = np.random.default_rng(2)
    a32 = rng.standard_normal(4096).astype(np.float32)
    b32 = rng.standard_normal(4096).astype(np.float32)
    a = a32.astype(ml_dtypes.bfloat16)
    b = b32.astype(ml_dtypes.bfloat16)
    expect = (a.astype(np.float32) + b.astype(np.float32)) \
        .astype(ml_dtypes.bfloat16)
    dst = a.view(np.uint16).copy()
    src = b.view(np.uint16).copy()
    lib.bps_reduce_sum_bf16(
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        dst.size, 2)
    np.testing.assert_array_equal(dst.view(ml_dtypes.bfloat16), expect)


def test_parallel_builders_each_write_a_file_of_their_own(tmp_path,
                                                          monkeypatch):
    """Several builders at once (a fresh checkout under six test
    workers): every one ends with the library in place and none fails —
    on one shared temporary file the first ``os.replace`` took it from
    under the others (ISSUE 33: the eighteen tests that wandered)."""
    import threading
    out = str(tmp_path / "_lib.so")
    n = 4
    mid_write = threading.Barrier(n)

    def fake_gxx(cmd, **kw):
        target = cmd[cmd.index("-o") + 1]
        body = target.encode() * 64
        with open(target, "wb") as f:
            f.write(body[:len(body) // 2])
            f.flush()
            mid_write.wait(timeout=30)        # all builders are mid-file
            f.write(body[len(body) // 2:])

    monkeypatch.setattr(native.subprocess, "run", fake_gxx)
    errors = []

    def build():
        try:
            native._compile(out)
        except Exception as e:  # noqa: BLE001 — the assertion names it
            errors.append(e)

    threads = [threading.Thread(target=build) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert [p.name for p in tmp_path.iterdir()] == ["_lib.so"]
    body = (tmp_path / "_lib.so").read_bytes()
    assert body == body[:len(body) // 64] * 64     # one builder's, whole
