"""Native C++ host-runtime tests (nnstreamer_tpu/native/csrc/nns_core.cc).

Reference analogs: tensor_allocator tests + datareposrc unit tests
(tests/unittest_datareposrc.cc in the reference tree). Tests skip when no
C++ toolchain is available (mirrors the reference's hardware-gated dirs).
"""
import json
import os
import threading

import numpy as np
import pytest

from nnstreamer_tpu import native


pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime not buildable here"
)


def test_pool_acquire_release_reuse():
    pool = native.BufferPool(4096, alignment=64)
    a = pool.acquire()
    b = pool.acquire()
    assert a and b and a != b
    assert a % 64 == 0 and b % 64 == 0
    pool.release(a)
    c = pool.acquire()
    assert c == a  # LIFO reuse
    stats = pool.stats()
    assert stats["acquires"] == 3 and stats["reuses"] == 1
    pool.close()


def test_pool_max_blocks_bound():
    pool = native.BufferPool(128, max_blocks=2)
    a, b = pool.acquire(), pool.acquire()
    assert a and b
    assert pool.acquire() is None  # bounded
    pool.release(a)
    assert pool.acquire() == a
    pool.close()


def test_ring_push_pop_order_and_close():
    ring = native.Ring(capacity=4)
    for i in range(4):
        assert ring.push(0x1000 + i, 10 * i, tag=i)
    got = [ring.pop() for _ in range(4)]
    assert [g[2] for g in got] == [0, 1, 2, 3]
    assert got[3] == (0x1003, 30, 3)
    assert ring.pop(timeout_ms=10) is None  # empty -> timeout
    ring.close_ring()
    with pytest.raises(EOFError):
        ring.pop()
    ring.destroy()


def test_ring_backpressure_blocks_producer():
    ring = native.Ring(capacity=2)
    assert ring.push(1, 0) and ring.push(2, 0)
    assert not ring.push(3, 0, timeout_ms=20)  # full -> timeout

    popped = []

    def consumer():
        popped.append(ring.pop())

    t = threading.Thread(target=consumer)
    t.start()
    assert ring.push(3, 0, timeout_ms=2000)  # unblocked by the pop
    t.join()
    assert popped[0][0] == 1
    ring.destroy()


def test_gather_scatter_roundtrip():
    parts = [
        np.arange(10, dtype=np.float32),
        np.arange(7, dtype=np.uint8),
        np.arange(4, dtype=np.int64).reshape(2, 2),
    ]
    flat = native.gather([p.view(np.uint8).reshape(-1) if p.dtype == np.uint8
                          else np.frombuffer(p.tobytes(), np.uint8)
                          for p in parts])
    outs = [np.empty_like(p) for p in parts]
    native.scatter(flat, outs)
    for p, o in zip(parts, outs):
        np.testing.assert_array_equal(p, o)


def test_repo_reader_orders_and_eof(tmp_path):
    sample = 32
    n = 10
    data = np.arange(n * sample, dtype=np.uint8)
    path = tmp_path / "samples.dat"
    path.write_bytes(data.tobytes())

    order = [3, 1, 4, 1, 5, 9, 2, 6]
    reader = native.RepoReader(str(path), sample, order, prefetch_depth=3)
    seen = []
    while True:
        try:
            view, idx, block = reader.next()
        except StopIteration:
            break
        np.testing.assert_array_equal(
            view, data[idx * sample:(idx + 1) * sample])
        seen.append(idx)
        reader.release(block)
    assert seen == order
    reader.close()


def test_repo_reader_read_error(tmp_path):
    path = tmp_path / "short.dat"
    path.write_bytes(b"\x00" * 16)  # one half-sample
    reader = native.RepoReader(str(path), 32, [0], prefetch_depth=2)
    with pytest.raises(OSError):
        while True:
            _, _, block = reader.next()
            reader.release(block)
    reader.close()


def _write_repo(tmp_path, n_samples=12):
    """Write a tiny datarepo (location + json meta) like datareposink does."""
    from nnstreamer_tpu.core import (
        TensorsInfo, caps_from_tensors_info,
    )
    from nnstreamer_tpu.core.tensors import DataType, TensorSpec

    info = TensorsInfo.of(TensorSpec((2, 3), DataType.FLOAT32))
    rng = np.random.default_rng(7)
    samples = rng.standard_normal((n_samples, 2, 3)).astype(np.float32)
    loc = tmp_path / "d.dat"
    loc.write_bytes(samples.tobytes())
    meta = {
        "gst_caps": str(caps_from_tensors_info(info)),
        "total_samples": n_samples,
        "sample_size": info.nbytes,
    }
    jpath = tmp_path / "d.json"
    jpath.write_text(json.dumps(meta))
    return loc, jpath, samples


@pytest.mark.parametrize("shuffle", [False, True])
def test_datareposrc_native_matches_python(tmp_path, shuffle):
    """The native prefetch path must emit byte-identical streams in the
    identical (seeded) order as the pure python path."""
    from nnstreamer_tpu.runtime.parse import parse_launch

    loc, jpath, _ = _write_repo(tmp_path)

    def run(use_native: bool):
        got = []
        pipe = parse_launch(
            f"datareposrc location={loc} json={jpath} epochs=2 "
            f"is-shuffle={str(shuffle).lower()} seed=5 "
            f"use-native={str(use_native).lower()} "
            "! tensor_sink name=out"
        )
        pipe.get("out").connect(lambda b: got.append(
            (b.offset, b.as_numpy().tensors[0].copy())))
        pipe.run(timeout=30.0)
        return got

    py = run(False)
    nat = run(True)
    assert [o for o, _ in py] == [o for o, _ in nat]
    for (_, a), (_, b) in zip(py, nat):
        np.testing.assert_array_equal(a, b)
    assert len(py) == 24  # 12 samples x 2 epochs


@pytest.mark.parametrize("use_native", [False, True])
def test_datareposrc_replay_is_deterministic(tmp_path, use_native):
    """Replaying a shuffled pipeline (second play() after EOS) must repeat
    the exact same sample order in both the python and native paths."""
    from nnstreamer_tpu.runtime.parse import parse_launch

    loc, jpath, _ = _write_repo(tmp_path, n_samples=8)
    got = []
    pipe = parse_launch(
        f"datareposrc location={loc} json={jpath} epochs=2 is-shuffle=true "
        f"seed=11 use-native={str(use_native).lower()} ! tensor_sink name=out"
    )
    pipe.get("out").connect(lambda b: got.append(b.offset))
    pipe.run(timeout=30.0)
    first = list(got)
    got.clear()
    pipe.run(timeout=30.0)  # replay
    assert got == first and len(first) == 16


def test_loader_ignores_a_library_of_other_source(tmp_path):
    """Staleness is decided by content: the library's name carries a hash
    of its source and compile command, so a binary built from anything
    else — here garbage under another hash — is never opened."""
    from nnstreamer_tpu.native import _build

    src = os.path.join(os.path.dirname(_build.__file__), "csrc",
                       "nns_core.cc")
    want = _build.lib_path(src, "nns_core", ("-lpthread",))
    assert os.path.basename(want).startswith("libnns_core-")
    # the same source, compiled another way or edited: another name
    assert _build.lib_path(src, "nns_core", ()) != want
    edited = tmp_path / "nns_core.cc"
    with open(src, "rb") as fh:
        edited.write_bytes(fh.read() + b"\n// edited\n")
    assert (os.path.basename(_build.lib_path(str(edited), "nns_core",
                                             ("-lpthread",)))
            != os.path.basename(want))
    stale = os.path.join(os.path.dirname(want), "libnns_core-0000stale.so")
    with open(stale, "wb") as fh:
        fh.write(b"not an ELF file")
    try:
        lib = _build.load_once(src, "nns_core", native.ABI_VERSION,
                               "nns_abi_version", lambda lib: None,
                               extra_args=("-lpthread",))
        assert lib is not None and lib._name == want
    finally:
        if os.path.exists(stale):
            os.remove(stale)
