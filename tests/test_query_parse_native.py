"""A group's query vectors are parsed by one native call (ISSUE 39).

`SearchExecutor._parse_vectors` hands the text vectors of a batch group
to `native.parse_query_vectors` (native/sptag_host.cpp::
sptag_parse_query_vectors: ONE call, the interpreter lock free) and every
row that call did not accept to `ParsedQuery.extract_vector`, which
stays the definition of what a vector is.  Held here: the arrays, the
order of `ok`, the FailedExecute positions and the served reply bytes are
the all-Python loop's for every input; counters `service.parse_native` /
`service.parse_python` say which route decided a query.
"""

import base64
import os
import socket
import types
import warnings

import numpy as np
import pytest

from conftest import ServerThread
from sptag_tpu import native
from sptag_tpu.core.types import VectorValueType, dtype_of
from sptag_tpu.serve import wire
from sptag_tpu.serve.protocol import ParsedQuery, parse_query
from sptag_tpu.serve.server import SearchServer
from sptag_tpu.serve.service import (SearchExecutor, ServiceContext,
                                     ServiceSettings)
from sptag_tpu.utils import metrics
from test_batched_responses import _flat_context, _read_packets, _request

VT = VectorValueType


@pytest.fixture(scope="module", autouse=True)
def lib():
    lib = native.load()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def _executor(sep="|"):
    return SearchExecutor(ServiceContext(ServiceSettings(
        vector_separator=sep)))


def _index(value_type, dim):
    return types.SimpleNamespace(value_type=value_type, feature_dim=dim)


def _all_python(parsed, index, idxs, sep):
    """`_parse_vectors` as it was before the native call: the semantics."""
    failed, vecs, ok = [], [], []
    for i in idxs:
        v = parsed[i].extract_vector(
            parsed[i].data_type or index.value_type, sep)
        if v is None or v.shape[-1] != index.feature_dim:
            failed.append(i)
        else:
            vecs.append(v)
            ok.append(i)
    return (np.stack(vecs) if ok else None), ok, failed


def _both(parsed, value_type, dim, sep="|"):
    """-> ((queries, ok, failed) of the executor, the same of the
    all-Python loop); numpy's warnings about a cast of inf or an
    out-of-range value are both routes' alike."""
    index = _index(value_type, dim)
    idxs = list(range(len(parsed)))
    results = [None] * len(parsed)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        queries, ok = _executor(sep)._parse_vectors(parsed, results, index,
                                                    idxs)
        want = _all_python(parsed, index, idxs, sep)
    failed = [i for i, r in enumerate(results) if r is not None]
    assert all(results[i].status == wire.ResultStatus.FailedExecute
               and results[i].results == [] for i in failed)
    return (queries, ok, failed), want


def _same(got, want):
    (queries, ok, failed), (w_queries, w_ok, w_failed) = got, want
    assert ok == w_ok and failed == w_failed
    if w_queries is None:
        assert queries is None
        return
    assert queries.dtype == w_queries.dtype
    assert queries.shape == w_queries.shape
    assert np.array_equal(queries, w_queries, equal_nan=True)
    assert queries.tobytes() == w_queries.tobytes()      # -0.0 too


# ---- (a) parity on everything the native call accepts ----------------------

def _forms(value_type):
    """Writers value -> text: what a client may send for one element."""
    if value_type == VT.Float:
        return [lambda v: repr(float(v)),
                lambda v: "%.9e" % v,
                lambda v: "%.7E" % v,
                lambda v: str(int(v)),
                lambda v: str(int(v)) + ".",
                lambda v: ("-" if v < 0 else "") + ".5",
                lambda v: "-0.0",
                lambda v: "%de-2" % int(v * 100),
                lambda v: "1e-50" if v > 0 else "-3.25E+4",
                lambda v: "000" + str(abs(int(v))) + ".250"]
    return [lambda v: repr(float(v)),
            lambda v: str(int(v)),
            lambda v: str(int(v)) + ".",
            lambda v: "%de-1" % (int(v) * 10),
            lambda v: "%.4E" % v,
            lambda v: ".5" if v >= 0 else "-.5",
            lambda v: "-0.0",
            lambda v: "%d.9" % v,
            lambda v: ("-00" if v < 0 else "00") + str(abs(int(v))) + ".0"]


def _values(rng, value_type, shape):
    if value_type == VT.Float:
        return rng.standard_normal(shape) * 50.0
    info = np.iinfo(dtype_of(value_type))
    # the extremes too: -128 / 127, 0 / 255, -32768 / 32767
    v = rng.integers(info.min, info.max + 1, shape).astype(np.float64)
    v.flat[0], v.flat[-1] = info.min, info.max
    return v


def _group_texts(rng, value_type, dim, rows, sep):
    forms = _forms(value_type)
    vals = _values(rng, value_type, (rows, dim))
    texts = []
    for r in range(rows):
        parts = [forms[(r + c) % len(forms)](vals[r, c])
                 for c in range(dim)]
        text = sep.join(parts)
        if r % 3 == 1:
            text = sep + text + sep               # leading and trailing
        if r % 4 == 2 and dim > 1:
            text = text.replace(sep, sep + sep, 2)          # doubled
        texts.append(text)
    return texts


@pytest.mark.parametrize("rows", [1, 64, 128])
@pytest.mark.parametrize("dim", [1, 96, 128, 384])
@pytest.mark.parametrize("value_type",
                         [VT.Float, VT.Int8, VT.UInt8, VT.Int16],
                         ids=lambda v: v.name)
def test_native_parse_is_the_python_parse(value_type, dim, rows):
    sep = {1: "|", 64: ",", 128: ";"}[rows]
    rng = np.random.default_rng(1000 * int(value_type) + 10 * dim + rows)
    parsed = [ParsedQuery({}, t)
              for t in _group_texts(rng, value_type, dim, rows, sep)]
    got, want = _both(parsed, value_type, dim, sep)
    assert want[1] == list(range(rows))          # every row is a vector
    _same(got, want)
    assert got[0][0].dtype == dtype_of(value_type)
    # ... and every one of these forms was decided by the native call
    assert metrics.counter_value("service.parse_native") == rows
    assert metrics.counter_value("service.parse_python") == 0


def test_native_parse_of_the_benchmark_text_form():
    """`repr(float(x))` joined by `|`: what every cell sends."""
    rng = np.random.default_rng(7)
    rows = rng.integers(-127, 128, (64, 384)).astype(np.int8)
    texts = ["|".join(repr(float(x)) for x in row) for row in rows]
    out, ok = native.parse_query_vectors(texts, "|", 384, VT.Int8)
    assert ok.all() and out.dtype == np.int8 and np.array_equal(out, rows)
    data = rng.standard_normal((50, 128)).astype(np.float32)
    texts = ["|".join(repr(float(x)) for x in row) for row in data]
    out, ok = native.parse_query_vectors(texts, "|", 128, VT.Float)
    assert ok.all() and out.tobytes() == data.tobytes()


# ---- (b) what it does not accept is Python's to decide ---------------------

GOOD = "1|2|3|4"

REJECTED = {
    "plus_sign": "+5|2|3|4",                 # Python: 5.0
    "inner_space": " 5|2|3|4",               # Python strips: 5.0
    "underscore": "1_0|2|3|4",               # Python: 10.0
    "nan": "nan|2|3|4",
    "inf": "1|inf|3|4",
    "minus_infinity": "1|2|-Infinity|4",
    "overflow": "1e999|2|3|4",               # Python: inf
    "underflow": "1e-999|2|3|4",             # Python: 0.0
    "hex": "0x10|2|3|4",                     # neither takes it
    "fullwidth_digits": "１２|2|3|4",   # Python: 12.0
    "letters": "abc|2|3|4",
    "half_exponent": "1e|2|3|4",
    "two_points": "1.2.3|2|3|4",
    "lone_sign": "-|2|3|4",
    "nul_byte": "1\x00|2|3|4",
    "short_row": "1|2|3",
    "long_row": "1|2|3|4|5",
    "empty": "",
    "separators_only": "|||",
    "out_of_int8": "300|2|3|4",
    "below_int8": "-129|2|3|4",
    "past_float32": "1e39|2|3|4",            # a double, inf as float32
}


@pytest.mark.parametrize("value_type", [VT.Float, VT.Int8],
                         ids=lambda v: v.name)
@pytest.mark.parametrize("form", sorted(REJECTED))
def test_a_rejected_row_falls_to_python(form, value_type):
    texts = [GOOD, REJECTED[form], "5|6|7|8", REJECTED[form], GOOD]
    parsed = [ParsedQuery({}, t) for t in texts]
    got, want = _both(parsed, value_type, 4)
    _same(got, want)
    accepted = native.parse_query_vectors(texts, "|", 4, value_type)[1]
    if value_type == VT.Float and form in ("out_of_int8", "below_int8"):
        assert accepted.all()            # 300 is a float32
        assert metrics.counter_value("service.parse_python") == 0
    else:
        assert accepted.tolist() == [True, False, True, False, True]
        assert metrics.counter_value("service.parse_native") == 3
        assert metrics.counter_value("service.parse_python") == 2


def _b64(row, dtype):
    return base64.b64encode(np.asarray(row, dtype).tobytes()).decode()


@pytest.mark.parametrize("value_type", [VT.Float, VT.Int8, VT.UInt8,
                                        VT.Int16], ids=lambda v: v.name)
def test_mixed_group_equals_the_all_python_run(value_type):
    """Good rows, a short row, a nan, a `#base64` row, rows with
    `$datatype` (the index's, another, one that does not parse), a row
    with both vector forms, a row with none: one group."""
    dt = dtype_of(value_type)
    other = VT.Int16 if value_type != VT.Int16 else VT.UInt8
    lines = [
        GOOD,
        "1|2|3",
        "9|8|7|6",
        "nan|2|3|4",
        "#" + _b64([4, 3, 2, 1], dt),
        f"$datatype:{value_type.name} 2|4|6|8",
        f"$datatype:{other.name} 1|1|2|3",
        "$datatype:Int8 5|5|5|5",          # Int8 is 0: `or` skips it
        "$datatype:nonsense 7|7|7|7",
        "#" + _b64([1, 2, 3, 4], dt) + " 4|4|4|4",    # base64 wins
        "$resultnum:3",
        "#" + _b64([1, 2, 3], dt),                    # short base64
        "#not-base64!",
        "+1|2|3|4",
        "6|5|4|3|",
        "300|2|3|4",
        "0.5|1.5|2.5|-0.0",
    ]
    parsed = [parse_query(t) for t in lines]
    got, want = _both(parsed, value_type, 4)
    assert want[2]                       # some rows do fail
    assert len(want[1]) > 8              # and most are vectors
    _same(got, want)
    assert (metrics.counter_value("service.parse_native")
            + metrics.counter_value("service.parse_python")) == len(lines)
    assert metrics.counter_value("service.parse_native") >= 5
    assert metrics.counter_value("service.parse_python") >= 8


@pytest.mark.parametrize("sep", ["||", "¦", "e", "-", "."])
def test_a_separator_the_native_call_may_misread(sep):
    """More than one byte, or a byte a number is written with, goes to
    Python whole: `str.split` cuts first, whatever the separator."""
    texts = [sep.join(["1", "25", "3", "4"]), sep.join(["7", "8"]),
             "1|2|3|4"]
    parsed = [ParsedQuery({}, t) for t in texts]
    got, want = _both(parsed, VT.Float, 4, sep)
    _same(got, want)


def test_empty_group_and_all_failed_group():
    ex = _executor()
    index = _index(VT.Float, 4)
    assert ex._parse_vectors([], [], index, []) == (None, [])
    parsed = [ParsedQuery({}, "1|2"), ParsedQuery({}, None)]
    results = [None, None]
    queries, ok = ex._parse_vectors(parsed, results, index, [0, 1])
    assert queries is None and ok == []
    assert all(r.status == wire.ResultStatus.FailedExecute
               for r in results)


def test_a_subset_of_the_batch_keeps_its_positions():
    """`idxs` are batch positions, not 0..Q-1."""
    parsed = [ParsedQuery({}, t) for t in
              ["9|9|9|9", GOOD, "bad", "5|6|7|8", "1|2", "4|3|2|1"]]
    ex = _executor()
    results = [None] * len(parsed)
    queries, ok = ex._parse_vectors(parsed, results, _index(VT.Float, 4),
                                    [5, 1, 2, 3])
    assert ok == [5, 1, 3]
    assert queries.tolist() == [[4, 3, 2, 1], [1, 2, 3, 4], [5, 6, 7, 8]]
    assert [r is not None for r in results] == [False, False, True, False,
                                                False, False]


def test_binding_refuses_what_the_library_does_not_take():
    assert native.parse_query_vectors(["1|2"], "||", 2, VT.Float) is None
    assert native.parse_query_vectors(["1|2"], "¦", 2, VT.Float) is None
    assert native.parse_query_vectors(["1|2"], "|", 0, VT.Float) is None
    for sep in "0e.E+-":         # a number is written with these
        assert native.parse_query_vectors(["1" + sep + "2"], sep, 2,
                                          VT.Float) is None
    assert native.parse_query_vectors(["1|2"], "|", 2, VT.Undefined) is None
    out, ok = native.parse_query_vectors([], "|", 2, VT.Float)
    assert out.shape == (0, 2) and ok.shape == (0,)


# ---- (c) the library absent ------------------------------------------------

def _queries(data, rows):
    return ["|".join(repr(float(x)) for x in data[r]) for r in rows]


def test_without_the_library_the_answers_are_unchanged(monkeypatch):
    ctx, data = _flat_context()
    texts = _queries(data, range(24)) + ["1|2|3", "$indexname:nope 1|2",
                                         "nan|" * 8]
    ex = SearchExecutor(ctx)
    with_lib = [r.pack() for r in ex.execute_batch(texts)]
    assert metrics.counter_value("service.parse_native") == 24
    assert metrics.counter_value("service.parse_python") == 2
    metrics.reset()
    monkeypatch.setattr(native, "load", lambda: None)
    assert native.parse_query_vectors(["1"], "|", 1, VT.Float) is None
    without = [r.pack() for r in SearchExecutor(ctx).execute_batch(texts)]
    assert without == with_lib
    assert metrics.counter_value("service.parse_python") == 26
    assert metrics.counter_value("service.parse_native") == 0


def test_executor_loads_the_library_when_constructed(monkeypatch):
    calls = []
    monkeypatch.setattr(native, "load", lambda: calls.append(1))
    ctx = ServiceContext(ServiceSettings())
    SearchExecutor(ctx)
    assert calls == [1]
    SearchServer(ctx)
    assert calls == [1, 1]


# ---- (d) through a served socket -------------------------------------------

def _serve(n):
    """`n` requests on one raw connection of a fresh server -> the raw
    reply packets, sorted by resource id."""
    ctx, data = _flat_context()
    server = SearchServer(ctx, batch_window_ms=20.0, max_batch=n)
    thread = ServerThread(server)
    thread.start()
    try:
        with socket.create_connection(thread.wait_ready(),
                                      timeout=20) as sock:
            sock.settimeout(20)
            sock.sendall(b"".join(_request(data, row, row, f"rid-{row}")
                                  for row in range(n)))
            packets, _ = _read_packets(sock, n)
    finally:
        thread.stop()
    return sorted(packets, key=lambda p: wire.PacketHeader.unpack(
        p[:wire.HEADER_SIZE]).resource_id)


def test_served_replies_are_the_python_routes_byte_for_byte(monkeypatch):
    n = 48
    native_replies = _serve(n)
    assert metrics.counter_value("service.parse_native") == n
    assert metrics.counter_value("service.parse_python") == 0
    for p in native_replies:
        body = wire.RemoteSearchResult.unpack(p[wire.HEADER_SIZE:])
        assert body.status == wire.ResultStatus.Success
    metrics.reset()
    monkeypatch.setattr(native, "load", lambda: None)
    python_replies = _serve(n)
    assert metrics.counter_value("service.parse_python") == n
    assert metrics.counter_value("service.parse_native") == 0
    assert python_replies == native_replies


@pytest.mark.parametrize("name", ["service.parse_native",
                                  "service.parse_python"])
def test_docs_list_the_route_counters(name):
    docs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "TELEMETRY.md")
    with open(docs) as f:
        assert f"`{name}`" in f.read()
