"""The reductions of the program's own marks (``program_trace``).

* A synthetic plain form with nested ops, ``;``-joined scope paths and
  nested program spans, whose split and gap names are worked out by hand.
* ``fixtures/v5e_async_1x512_spans.json.gz``, cut from the plain form
  (``program_trace.load``) of a real trace of ``stablelm-async-1x512`` on a
  TPU v5 lite, with the program's spans and scopes: four ticks around the
  first refresh of a traced window.  Its expected numbers are those the
  reductions gave when it was cut.
* ``fixtures/v5e_async_1x512.json.gz``, traced before the program had
  marks: there every new reader returns None.
"""

from __future__ import annotations

import gzip
import json
import types
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repo and src on the path)

FIXTURES = Path(__file__).parent / "fixtures"
NEW_READERS = ("forward_ms", "backward_ms", "param_view_ms", "dispatch_ms", "refit_ms")


def _op(name, start, dur, path):
    return [name, float(start), float(dur)], path


SYNTHETIC_OPS = [
    _op("%fusion.1", 0, 100, "jit(counting)/jvp(param_view)/slice"),
    _op("%while.5", 100, 300, "jit(counting)/jvp(forward)/while"),
    _op("%fusion.2", 120, 80, "jit(counting)/jvp(forward)/while/body/dot_general"),
    _op("%fusion.3", 220, 50, "jit(counting)/jvp(forward)/while/body/add"),
    _op("%convert_add_fusion", 400, 100,
        "jit(counting)/transpose(jvp(forward))/dot_general;jit(counting)/jvp(forward)/mul"),
    _op("%reshape.9", 500, 50, "jit(counting)/transpose(jvp(param_view))/reshape"),
    _op("%fusion.4", 550, 30, "jit(counting)/staleness/jit(searchsorted)/while"),
    _op("%fused_tick_call.1", 600, 200, "jit(counting)/update/jit(fused_tick_call)/pallas_call"),
    _op("%fusion.6", 800, 20, "jit(counting)/update/mul"),
    _op("%copy.3", 820, 30, ""),
    _op("%fusion.7", 900, 50, "jit(counting)/jit(forward)/add"),  # a function, not the scope
]
# self time within the class "other", by part (ns)
SYNTHETIC_SPLIT = {"param_view": 150, "forward": 300, "backward": 100, "staleness": 30,
                   "update": 20, "unscoped": 80}


@pytest.fixture()
def synthetic():
    return {
        "window_ns": [0.0, 1000.0],
        "devices": {"0": [op for op, _ in SYNTHETIC_OPS]},
        "device_scopes": {"0": [path for _, path in SYNTHETIC_OPS]},
        "host": [["window", 0.0, 1000.0], ["tick", 570.0, 29.0], ["refresh", 840.0, 121.0]],
        "program": [
            ["engine.tick", 575.0, 23.0],
            ["engine.refresh", 840.0, 120.0],
            ["refresh.drain", 845.0, 25.0],
            ["refresh.refit", 870.0, 70.0],
            ["refresh.swap", 940.0, 15.0],
            ["run.hooks", 962.0, 37.0],
            ["engine.tick", 2000.0, 10.0],  # after the window
        ],
    }


@pytest.fixture(scope="module")
def spans_plain():
    with gzip.open(FIXTURES / "v5e_async_1x512_spans.json.gz", "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def old_plain():
    with gzip.open(FIXTURES / "v5e_async_1x512.json.gz", "rt") as f:
        return json.load(f)


def _rec(plain, ticks):
    from bench import trace as tr

    return types.SimpleNamespace(reduced=tr.Reduced(plain), ticks=ticks)


def _read(name, rec):
    from bench.cells import module

    return module("metrics", name, bench_tiny.ROOT).read(rec)


@pytest.mark.parametrize("path,part", [
    ("jit(counting)/jvp(param_view)/slice", "param_view"),
    ("jit(counting)/transpose(jvp(param_view))/reshape", "param_view"),
    ("jit(counting)/jvp(forward)/dot_general", "forward"),
    ("jit(counting)/transpose(jvp(forward))/jit(_var)/mul", "backward"),
    ("jit(counting)/transpose(jvp(forward))/add;jit(counting)/transpose(jvp(param_view))/x",
     "backward"),
    ("jit(counting)/staleness/jit(_threefry_split)/xor", "staleness"),
    ("jit(counting)/update/jit(remainder)/rem", "update"),
    ("jit(counting)/jit(forward)/add", "unscoped"),
    ("jit(counting)/closed_call/while", "unscoped"),
    ("", "unscoped"),
])
def test_scope_part(path, part):
    from bench.program_trace import scope_part

    assert scope_part(path) == part


def test_split_adds_up_to_the_other_class(synthetic):
    from bench import program_trace as pt

    scoped = pt.Scoped(synthetic)
    split = scoped.other_by_scope()
    assert split == pytest.approx({k: v * 1e-9 for k, v in SYNTHETIC_SPLIT.items()}, abs=1e-15)
    assert sum(split.values()) == pytest.approx(scoped.class_s("other"), rel=1e-12)


def test_gaps_are_named_by_the_innermost_program_span(synthetic):
    from bench import program_trace as pt
    from bench import trace as tr

    scoped = pt.Scoped(synthetic)
    assert scoped.idle_gaps_program(None) == [
        ["refresh.refit", pytest.approx(50e-9)],  # drain 20 ns, refit 30 ns of it
        ["run.hooks", pytest.approx(50e-9)],      # swap 5, engine.refresh 5, hooks 37
        ["engine.tick", pytest.approx(20e-9)],
    ]
    assert [n for n, _ in scoped.idle_gaps_program(1)] == ["refresh.refit"]
    # the harness's own naming is untouched
    assert [n for n, _ in tr.Reduced(synthetic).idle_gaps(3)] == ["refresh", "refresh", "tick"]


def test_readers_on_the_synthetic_trace(synthetic):
    rec = _rec(synthetic, ticks=1)
    assert _read("forward_ms", rec) == pytest.approx(300e-6)
    assert _read("backward_ms", rec) == pytest.approx(100e-6)
    assert _read("param_view_ms", rec) == pytest.approx(150e-6)
    assert _read("dispatch_ms", rec) == pytest.approx(23e-6)  # the span in the window
    assert _read("refit_ms", rec) == pytest.approx(70e-6)


def test_readers_return_none_without_the_programs_marks(synthetic, old_plain):
    # the fixture traced before the program had marks
    for name in NEW_READERS:
        assert _read(name, _rec(old_plain, ticks=3)) is None
    # a trace loaded with the marks, of a program without scopes or spans
    synthetic["device_scopes"]["0"] = ["jit(counting)/dot_general"] * len(SYNTHETIC_OPS)
    synthetic["program"] = []
    for name in NEW_READERS:
        assert _read(name, _rec(synthetic, ticks=1)) is None


def test_recorded_trace_gives_the_recorded_numbers(spans_plain):
    from bench import program_trace as pt

    expected = json.loads((FIXTURES / "v5e_async_1x512_spans.expected.json").read_text())
    scoped = pt.Scoped(spans_plain)
    assert scoped.window_s == pytest.approx(expected["window_s"], rel=1e-12)
    assert scoped.mean_busy_s() == pytest.approx(expected["busy_s"], rel=1e-12)
    assert scoped.class_s("other") == pytest.approx(expected["other_s"], rel=1e-12)
    assert scoped.class_s("update") == pytest.approx(expected["update_s"], rel=1e-12)
    assert scoped.other_by_scope() == pytest.approx(expected["other_by_scope"], rel=1e-9)
    assert scoped.span_s("engine.tick") == pytest.approx(expected["engine_tick_s"], rel=1e-12)
    assert scoped.span_s("refresh.refit") == pytest.approx(expected["refresh_refit_s"], rel=1e-12)
    assert [n for n, _ in scoped.idle_gaps_program(5)] == expected["idle_gaps_program"]
    # the reductions the harness already had read this plain form as before
    assert [n for n, _ in scoped.idle_gaps(5)] == expected["idle_gaps"]
    assert [n for n, _ in scoped.top_ops(3)] == expected["top_ops"]


def test_recorded_trace_split_and_gaps(spans_plain):
    from bench import program_trace as pt

    scoped = pt.Scoped(spans_plain)
    split = scoped.other_by_scope()
    assert sum(split.values()) == pytest.approx(scoped.class_s("other"), rel=1e-9)
    assert all(split[part] > 0 for part in pt.PARTS)
    named = [(n, s) for n, s in scoped.idle_gaps_program(None) if s >= 1e-4]
    assert named and all(n != "python" for n, _ in named), named


def test_readers_on_the_recorded_trace(spans_plain):
    expected = json.loads((FIXTURES / "v5e_async_1x512_spans.expected.json").read_text())
    rec = _rec(spans_plain, ticks=expected["waits"])
    split = expected["other_by_scope"]
    for name, part in (("forward_ms", "forward"), ("backward_ms", "backward"),
                       ("param_view_ms", "param_view")):
        assert _read(name, rec) == pytest.approx(1e3 * split[part] / expected["waits"], rel=1e-9)
    ticks = expected["engine_tick_s"]
    assert _read("dispatch_ms", rec) == pytest.approx(1e3 * sum(ticks) / len(ticks), rel=1e-12)
    assert _read("refit_ms", rec) == pytest.approx(1e3 * expected["refresh_refit_s"][0], rel=1e-12)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _vi(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _ld(field: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines, events_meta, stats_meta) -> bytes:
    """An XPlane message: ``events_meta`` {id: [stats]}, ``stats_meta`` {id: name}."""
    out = _vi(1, 7) + _ld(2, name)
    for line_name, meta_ids in lines:
        events = b"".join(_ld(4, _vi(1, m) + _vi(2, 1000 * i) + _vi(3, 500))
                          for i, m in enumerate(meta_ids))
        out += _ld(3, _vi(1, 1) + _ld(2, line_name) + events)
    for mid, stats in events_meta.items():
        out += _ld(4, _vi(1, mid) + _ld(2, _vi(1, mid) + _ld(2, f"%op.{mid} = f32[] op()")
                                       + b"".join(_ld(5, s) for s in stats)))
    for sid, sname in stats_meta.items():
        out += _ld(5, _vi(1, sid) + _ld(2, _vi(1, sid) + _ld(2, sname)))
    return out


def test_scope_paths_are_read_from_the_event_metadata(tmp_path):
    """The wire-format reader finds ``tf_op`` among each op's metadata stats,
    for the op line of the devices asked for, in event order."""
    import struct

    from bench import program_trace as pt

    stats_meta = {1: "flops", 2: "tf_op", 3: "hlo_category"}
    double = _varint(2 << 3 | 1) + struct.pack("<d", 1.5)  # XStat.double_value
    events_meta = {
        1: [_vi(1, 3) + _ld(5, "convolution"), _vi(1, 2) + _ld(5, "jit(f)/jvp(forward)/dot:")],
        2: [_vi(1, 1) + double],
        3: [_vi(1, 2) + _ld(5, "jit(f)/update/mul:")],
    }
    space = (
        _ld(1, _plane("/host:CPU", [("python", [1])], {}, {}))
        + _ld(1, _plane("/device:TPU:0", [("Steps", [3]), ("XLA Ops", [1, 2, 1, 3])],
                        events_meta, stats_meta))
        + _ld(1, _plane("/device:TPU:1", [("XLA Ops", [3])], events_meta, stats_meta))
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert pt._device_scopes(str(path), {"0"}) == {"0": [
        "jit(f)/jvp(forward)/dot", "", "jit(f)/jvp(forward)/dot", "jit(f)/update/mul",
    ]}
