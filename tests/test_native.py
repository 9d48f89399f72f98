"""Native C++ host library tests: build, parse parity with the Python
parser, and the reader integration."""

import numpy as np
import pytest

from sptag_tpu import native
from sptag_tpu.core.types import VectorValueType
from sptag_tpu.io.reader import ReaderOptions, VectorSetReader


@pytest.fixture(scope="module")
def lib():
    lib = native.load()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def test_native_count_lines(lib):
    blob = b"a\t1|2\nb\t3|4\n\nc\t5|6"
    assert lib.sptag_count_lines(blob, len(blob)) == 3


def test_native_parse_matches_python(lib, tmp_path):
    rng = np.random.default_rng(7)
    data = rng.standard_normal((400, 16)).astype(np.float32)
    metas = [f"meta-{i}".encode() for i in range(400)]
    lines = []
    for row, meta in zip(data, metas):
        lines.append(meta + b"\t"
                     + "|".join(repr(float(x)) for x in row).encode())
    blob = b"\n".join(lines) + b"\n"

    parsed = native.parse_tsv(blob, "|", 16, 4)
    assert parsed is not None
    vec, got_metas = parsed
    np.testing.assert_allclose(vec, data, rtol=1e-6)
    assert got_metas == metas


def test_native_rejects_ragged(lib):
    blob = b"a\t1|2|3\nb\t4|5\n"
    assert native.parse_tsv(blob, "|", 3, 2) is None


def test_reader_uses_native_and_matches(tmp_path):
    rng = np.random.default_rng(3)
    data = rng.standard_normal((200, 8)).astype(np.float32)
    path = str(tmp_path / "x.tsv")
    with open(path, "wb") as f:
        for i, row in enumerate(data):
            f.write(f"m{i}\t".encode()
                    + "|".join(repr(float(x)) for x in row).encode() + b"\n")
    reader = VectorSetReader(ReaderOptions(
        value_type=VectorValueType.Float, dimension=8, thread_num=4))
    assert reader.load_file(path)
    np.testing.assert_allclose(reader.vectors, data, rtol=1e-6)
    assert reader.metadata[13] == b"m13"


def test_native_header_codec_cross_validates_python(lib):
    """The C++ packet-header codec and serve/wire.py are two INDEPENDENT
    implementations of inc/Socket/Packet.h:52-76; byte-for-byte agreement
    in both directions pins the 16-byte layout from both sides (the same
    role the reference-built index fixture plays for the file formats)."""
    import ctypes

    from sptag_tpu.serve import wire

    lib.sptag_pack_header.restype = None
    lib.sptag_pack_header.argtypes = [
        ctypes.c_uint8, ctypes.c_uint8, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint8)]
    lib.sptag_unpack_header.restype = None
    lib.sptag_unpack_header.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32)]

    cases = [
        (wire.PacketType.SearchRequest, wire.PacketProcessStatus.Ok,
         123456, 7, 99),
        (wire.PacketType.HeartbeatResponse, wire.PacketProcessStatus.Dropped,
         0, 0xFFFFFFFF, 0),
        (wire.PacketType.RegisterRequest, wire.PacketProcessStatus.Failed,
         1, 2, 3),
    ]
    for ptype, status, blen, cid, rid in cases:
        # native pack == python pack
        out = (ctypes.c_uint8 * 16)()
        lib.sptag_pack_header(int(ptype), int(status), blen, cid, rid, out)
        py = wire.PacketHeader(ptype, status, blen, cid, rid).pack()
        native = bytes(out)
        assert native == py, (native.hex(), py.hex())
        # native unpack(python pack) == original fields
        t = ctypes.c_uint8()
        s = ctypes.c_uint8()
        b = ctypes.c_uint32()
        c = ctypes.c_uint32()
        r = ctypes.c_uint32()
        buf = (ctypes.c_uint8 * 16).from_buffer_copy(native)
        lib.sptag_unpack_header(buf, t, s, b, c, r)
        assert (t.value, s.value, b.value, c.value, r.value) == (
            int(ptype), int(status), blen, cid, rid)


# ---- the stamp rule: a binary not built here from this source is rebuilt,
# ---- never loaded (a chip run copies the checkout from another machine)

@pytest.fixture
def planted(lib, tmp_path, monkeypatch):
    """A dummy `.so` in a private directory, the loader pointed at it and
    reset; records the first bytes of whatever `ctypes.CDLL` is given."""
    import ctypes

    so = tmp_path / "libsptag_host.so"
    so.write_bytes(b"built on another machine")
    monkeypatch.setattr(native, "_LIB", str(so))
    monkeypatch.setattr(native, "_STAMP", str(so) + ".stamp")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    loaded = []
    real_cdll = ctypes.CDLL

    def cdll(path, *args, **kwargs):
        with open(path, "rb") as f:
            loaded.append(f.read(4))
        return real_cdll(path, *args, **kwargs)

    monkeypatch.setattr(native.ctypes, "CDLL", cdll)
    return so, loaded


@pytest.mark.parametrize("stamp", ["0" * 64, None],
                         ids=["wrong-stamp", "no-stamp"])
def test_stamp_mismatch_rebuilds_and_never_loads_the_stale_binary(
        planted, stamp):
    so, loaded = planted
    if stamp is not None:
        (so.parent / (so.name + ".stamp")).write_text(stamp + "\n")
    got = native.load()
    assert got is not None
    assert loaded == [b"\x7fELF"]          # the rebuilt file, loaded once
    assert got.sptag_count_lines(b"a\t1\n", 4) == 1
    assert (so.parent / (so.name + ".stamp")).read_text().strip() \
        == native._expected_stamp()


def test_stamp_mismatch_with_failing_build_loads_nothing(planted,
                                                         monkeypatch):
    so, loaded = planted
    (so.parent / (so.name + ".stamp")).write_text("0" * 64 + "\n")
    monkeypatch.setattr(native, "_FLAGS", ["--no-such-flag"])
    assert native.load() is None
    assert loaded == []
    assert so.read_bytes() == b"built on another machine"


def test_matching_stamp_loads_without_rebuild(planted, monkeypatch):
    so, loaded = planted
    assert native.load() is not None       # builds + stamps
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_build", lambda stamp: pytest.fail(
        "rebuilt a binary whose stamp matches"))
    assert native.load() is not None
    assert loaded == [b"\x7fELF", b"\x7fELF"]
