"""The scidata write path: a one-pass container build and a store that
keeps the built buffer.

- ``serialize_scidata`` gives exactly the bytes of the copying
  implementation it replaced (kept below as the reference) for strided
  views, 0-d, empty and mixed-dtype arrays and every attribute type, its
  copies made by one thread or by several, and each dataset reads back
  from a memory and a POSIX store;
- ``MemoryBackend.adopt`` keeps the caller's buffer as the file, and every
  observable effect after it is that of a full ``write`` at offset 0;
- ``NativeSession.write_scidata`` tags ``store.write`` with the bytes the
  store copied: none on a memory store, the whole blob on a POSIX one.
"""

import json
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import ml_dtypes
import numpy as np
import pytest

from repro.core import Collaboration, NativeSession
from repro.core import scidata
from repro.core.backends import SYNC_XATTR, MemoryBackend, PosixBackend
from repro.core.scidata import (
    MAGIC,
    read_dataset,
    read_dataset_via,
    read_header,
    serialize_scidata,
    write_scidata,
)
from repro.core.telemetry import SpanBuffer, Tracer


def _serialize_reference(arrays, attrs) -> bytes:
    """The container as the copying implementation built it."""
    directory = []
    offset = 0
    payloads = []
    for name, arr in arrays.items():
        arr = np.ascontiguousarray(arr)
        raw = arr.tobytes()
        directory.append(
            {
                "name": name,
                "shape": list(arr.shape),
                "dtype": arr.dtype.str,
                "offset": offset,
                "nbytes": len(raw),
            }
        )
        payloads.append(raw)
        offset += len(raw)
    header = json.dumps({"attrs": attrs, "datasets": directory}).encode("utf-8")
    return MAGIC + struct.pack("<I", len(header)) + header + b"".join(payloads)


def _cases():
    rng = np.random.default_rng(7)
    full = rng.standard_normal((6, 8, 16)).astype(np.float32)
    later = dict(zip("abcd", np.split(full, 4, axis=2)))
    middle = dict(zip("ef", np.split(full, 2, axis=1)))
    scalars = {"step": np.array(7, np.int32), "lr": np.float32(3.5), "py": 2.0}
    dtypes = {
        "bf16": rng.standard_normal((3, 5)).astype(ml_dtypes.bfloat16),
        "i32": rng.integers(-9, 9, (4, 2), dtype=np.int32),
        "f32": rng.standard_normal(9).astype(np.float32),
        "bf16_view": rng.standard_normal((4, 6)).astype(ml_dtypes.bfloat16)[:, ::2],
    }
    empty = {"none": np.zeros((0, 4), np.float32), "after": np.arange(3, dtype=np.int32)}
    attrs = {"run": "tiny", "step": 12, "loss": 2.5, "split_axes": json.dumps({"w": 2})}
    return {
        "later_axis_views": (later, {"k": 1}),
        "middle_axis_views": (middle, {}),
        "zero_d": (scalars, {"kind": "checkpoint"}),
        "dtypes": (dtypes, {"x": 0.125}),
        "empty": (empty, {"n": 0}),
        "attrs": ({"w": full[:, 1, :]}, attrs),
        "mix": ({**later, **scalars, **dtypes, **empty}, attrs),
    }


CASES = _cases()


@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_one_pass_gives_the_reference_bytes(monkeypatch, case, threads):
    """Copied in by one thread or by several."""
    with ThreadPoolExecutor(threads) as pool:
        monkeypatch.setattr(scidata, "_POOL", pool)
        arrays, attrs = CASES[case]
        blob = serialize_scidata(arrays, attrs)
    assert isinstance(blob, bytearray)
    assert bytes(blob) == _serialize_reference(arrays, attrs)
    # no view of the buffer is left: it can still be resized
    blob.extend(b"\0")
    del blob[-1:]


def _stores(tmp_path):
    return {"memory": MemoryBackend("dc0"), "posix": PosixBackend("dc0", str(tmp_path / "pfs"))}


@pytest.mark.parametrize("store", ["memory", "posix"])
def test_every_dataset_reads_back(tmp_path, store):
    arrays, attrs = CASES["mix"]
    backend = _stores(tmp_path)[store]
    n = write_scidata(backend, "/d/mix.sci", arrays, attrs)
    assert n == backend.stat("/d/mix.sci").size
    assert read_header(backend, "/d/mix.sci").attrs == attrs
    reader = lambda off, ln: backend.read("/d/mix.sci", offset=off, length=ln)
    for name, arr in arrays.items():
        want = np.ascontiguousarray(arr)
        got = read_dataset_via(reader, name)
        # bfloat16 is stored as its ``dtype.str`` ('<V2') and reads back so
        assert got.shape == want.shape and got.dtype == np.dtype(want.dtype.str)
        assert got.tobytes() == want.tobytes()
    np.testing.assert_array_equal(read_dataset(backend, "/d/mix.sci", "a"), arrays["a"])


# -- the store's hand-over ---------------------------------------------------


def test_memory_store_keeps_the_callers_buffer():
    b = MemoryBackend("dc0")
    blob = bytearray(b"0123456789")
    assert b.adopt("/f.bin", blob) == 0
    blob[0:1] = b"X"  # the file is the buffer itself
    assert b.read("/f.bin") == b"X123456789"
    # anything but a bytearray is copied in, and says so
    assert b.adopt("/g.bin", b"abc") == 3
    assert b.read("/g.bin") == b"abc"


def test_posix_store_copies_the_buffer(tmp_path):
    b = PosixBackend("dc0", str(tmp_path / "pfs"))
    blob = bytearray(b"0123456789")
    assert b.adopt("/f.bin", blob) == 10
    blob[0:1] = b"X"
    assert b.read("/f.bin") == b"0123456789"


def _record_sleeps(monkeypatch):
    """The backends module's sleeps, recorded instead of slept."""
    import repro.core.backends as backends

    slept = []
    monkeypatch.setattr(backends, "time", SimpleNamespace(time=time.time, sleep=slept.append))
    return slept


def _observe(b, paths):
    """Everything a caller can see of ``paths`` and their parents."""
    seen = {}
    for p in paths:
        st = b.stat(p)
        seen[p] = (st.size, st.is_dir, st.owner, b.get_xattr(p, SYNC_XATTR))
        if not st.is_dir:
            seen[p] += (b.read(p),)
    return seen, b.data_bytes_written()


@pytest.mark.parametrize("gbps", [0.0, 8.0])
def test_adopt_behaves_as_a_full_write(monkeypatch, gbps):
    """The same history through ``write`` and through ``adopt`` leaves the
    same files, owners, sizes, sync flags, byte counts and modelled delays;
    later writes over an adopted buffer raise no ``BufferError``."""
    slept = _record_sleeps(monkeypatch)
    blob = serialize_scidata(CASES["zero_d"][0], {"v": 1})
    paths = ["/", "/a", "/a/b", "/a/b/f.sci"]
    seen, delays = [], []
    for hand_over in (False, True):
        b = MemoryBackend("dc0", store_gbps=gbps, store_lat_s=1e-3 if gbps else 0.0)
        b.mkdir("/a", owner="alice")
        for p in ("/", "/a"):
            b.set_xattr(p, SYNC_XATTR, "true")
        del slept[:]
        if hand_over:
            b.adopt("/a/b/f.sci", bytearray(blob), owner="bob")
        else:
            b.write("/a/b/f.sci", blob, owner="bob")
        first = _observe(b, paths)
        created = b.stat("/a/b/f.sci")
        b.set_xattr("/a", SYNC_XATTR, "true")
        b.write("/a/b/f.sci", b"XY", offset=3, owner="carol")
        b.write("/a/b/f.sci", b"tail", offset=len(blob) + 2)
        b.write("/a/b/f.sci", b"short", owner="carol")
        again = bytearray(blob)
        if hand_over:
            b.adopt("/a/b/f.sci", again, owner="dave")
        else:
            b.write("/a/b/f.sci", again, owner="dave")
        b.write("/a/b/f.sci", b"!", offset=1)
        last = b.stat("/a/b/f.sci")
        assert last.ctime == created.ctime and last.mtime >= created.mtime
        seen.append((first, _observe(b, paths)))
        delays.append(list(slept))
    assert seen[0] == seen[1]
    assert seen[1][0][0]["/a/b/f.sci"][2] == "bob"  # the first writer owns it
    assert seen[1][0][0]["/a"][3] is None  # the sync flags up the tree are cleared
    assert delays[0] == delays[1]
    assert bool(delays[1]) == bool(gbps)
    if gbps:
        assert delays[1][0] == pytest.approx(b.store_delay_for(len(blob)))


@pytest.mark.parametrize("store", ["memory", "posix"])
def test_native_write_tags_what_the_store_copied(tmp_path, store):
    collab = Collaboration()
    root = str(tmp_path / "dc0") if store == "posix" else None
    collab.add_datacenter("dc0", root=root, n_dtns=1)
    buf = SpanBuffer()
    native = NativeSession(collab.dc("dc0"), "alice", tracer=Tracer("t", buf))
    arrays, attrs = CASES["later_axis_views"]
    n = native.write_scidata("/ckpt/s0.sci", arrays, attrs)
    (write,) = [s for s in buf.spans() if s.name == "store.write"]
    assert write.tags == {"bytes": n, "copied": 0 if store == "memory" else n}
    assert collab.dc("dc0").backend.read("/ckpt/s0.sci") == _serialize_reference(arrays, attrs)
    # the tracer off: the same bytes, nothing recorded
    quiet = NativeSession(collab.dc("dc0"), "alice")
    assert quiet.write_scidata("/ckpt/s1.sci", arrays, attrs) == n
    assert len(buf.spans()) == 2
    collab.close()
