"""The readers of the program's spans and of the device's idle gaps put
down to them, on hand-built spans and traces."""
import random

import pytest

import run_cell
from ref import devtrace, spanidle


def _reader(name):
    return run_cell.load_module(run_cell.reader_path(name),
                                "test_metric_" + name.replace(".", "_"))


def _span(name, t0_s, t1_s):
    return {"name": name, "ts": t0_s * 1e6, "dur": (t1_s - t0_s) * 1e6}


def _ctx(spans, **counts):
    return run_cell.Context(spans=spans, trace=None, counts=counts,
                            peaks=None, e2e={})


def _trace():
    # window [0, 10]; one device busy on [1, 2], [4, 5] and [8, 9]: gaps
    # [0, 1] (mid 0.5), [2, 4] (mid 3), [5, 8] (mid 6.5), [9, 10] (9.5)
    ops = [("level_hist.1", 1.0, 2.0), ("copy.1", 4.0, 5.0),
           ("level_hist.1", 8.0, 9.0)]
    host = [(devtrace.WINDOW, 0.0, 10.0),
            ("submit", 0.2, 3.5), ("stage.upload", 2.5, 3.4),  # producer
            ("reduce", 5.2, 7.9), ("device.pull", 6.0, 7.0),   # lane
            ("write", 9.1, 9.4)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_sweep_labels_as_devtrace_does():
    t = _trace()
    want = {"submit": 1.0, "stage.upload": 2.0, "device.pull": 3.0,
            "none": 1.0}
    got = spanidle.idle_by_span(t)
    assert got == pytest.approx(want)
    assert got == pytest.approx(devtrace.reduce(t)["idle"])


def test_sweep_matches_devtrace_on_random_traces():
    rng = random.Random(5)
    for _ in range(20):
        ops = []
        for _ in range(rng.randint(1, 30)):
            a = rng.uniform(-1, 11)
            ops.append(("op", a, a + rng.uniform(0.01, 1.0)))
        host = [(devtrace.WINDOW, 0.0, 10.0)]
        for _ in range(rng.randint(0, 40)):
            a = rng.uniform(-1, 11)
            host.append((rng.choice(spanidle.PROGRAM_SPANS), a,
                         a + rng.uniform(0.01, 4.0)))
        t = {"devices": {"/device:TPU:0": ops, "/device:TPU:1": ops[::2]},
             "host": host}
        want = devtrace.reduce(t)
        got = spanidle.idle_by_span(t)
        if want is None:
            assert got is None
        else:
            assert got == pytest.approx(want["idle"])


def test_no_window_or_no_op():
    t = _trace()
    t["host"] = t["host"][1:]
    assert spanidle.idle_by_span(t) is None
    t = _trace()
    t["devices"] = {"/device:TPU:0": [("x", 20.0, 21.0)]}
    assert spanidle.idle_by_span(t) is None


@pytest.fixture()
def fake_trace(tmp_path, monkeypatch):
    """Point spanidle at a file whose parse is a hand-built trace."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"x")
    box = {"trace": _trace(), "loads": 0}

    def load(p, labels=()):
        assert p == str(path) and set(labels) == set(spanidle.PROGRAM_SPANS)
        box["loads"] += 1
        keep = {devtrace.WINDOW, *labels}
        return {"devices": box["trace"]["devices"],
                "host": [h for h in box["trace"]["host"] if h[0] in keep]}
    monkeypatch.setattr(spanidle, "trace_path", lambda: str(path))
    monkeypatch.setattr(devtrace, "load", load)
    spanidle._cache.clear()
    yield box
    spanidle._cache.clear()


def test_idle_readers(fake_trace):
    ctx = _ctx([], snapshots=2)
    # lane spans: device.pull's 3 s over 2 snapshots
    assert _reader("hdep.idle_lane_s").read(ctx) == pytest.approx(1.5)
    # no gather span in the trace: nothing to read
    assert _reader("hprot.idle_gather_s").read(ctx) is None
    assert fake_trace["loads"] == 1             # parsed once for both
    fake_trace["trace"]["host"].append(("ckpt.crc", 5.5, 7.5))
    spanidle._cache.clear()
    # ckpt.crc (2 s) is inner to reduce at 6.5 but not to device.pull
    assert _reader("hprot.idle_gather_s").read(ctx) == pytest.approx(0.0)
    fake_trace["trace"]["host"].append(("ckpt.pull", 6.4, 6.6))
    spanidle._cache.clear()
    assert _reader("hprot.idle_gather_s").read(ctx) == pytest.approx(3.0)


def test_idle_readers_without_program_spans(fake_trace):
    # the trace holds only the drivers' annotations: the program did not
    # annotate its spans, so neither reader has anything to read
    fake_trace["trace"]["host"] = [
        h for h in fake_trace["trace"]["host"]
        if h[0] in (devtrace.WINDOW, "submit", "train_step")]
    ctx = _ctx([], snapshots=2)
    assert _reader("hdep.idle_lane_s").read(ctx) is None
    assert _reader("hprot.idle_gather_s").read(ctx) is None


def test_idle_readers_without_a_trace(monkeypatch):
    monkeypatch.setattr(spanidle, "trace_path", lambda: None)
    ctx = _ctx([], snapshots=2)
    assert _reader("hdep.idle_lane_s").read(ctx) is None
    assert _reader("hprot.idle_gather_s").read(ctx) is None


def test_upload_reader():
    spans = [_span("stage.upload", 0.0, 0.03), _span("stage.wait", 0.03, 0.5),
             _span("stage.upload", 0.5, 0.54), _span("submit", 0.0, 0.6)]
    r = _reader("hdep.upload_s")
    assert r.read(_ctx(spans, snapshots=2)) == pytest.approx(0.035)
    assert r.read(_ctx(spans[1:2], snapshots=2)) is None
    assert run_cell.reader_path("hdep.upload_s.lod") == \
        run_cell.reader_path("hdep.upload_s")


@pytest.mark.parametrize("metric,child", [("hprot.pull_s", "ckpt.pull"),
                                          ("hprot.crc_s", "ckpt.crc")])
def test_gather_part_readers(metric, child):
    spans = [_span("ckpt.stage", 0.0, 1.0), _span(child, 0.0, 0.4),
             _span("ckpt.stage", 1.0, 2.0), _span(child, 1.1, 1.3),
             _span("ckpt.encode", 0.4, 0.9)]
    r = _reader(metric)
    assert r.read(_ctx(spans)) == pytest.approx(0.6)
    assert r.read(_ctx(spans[:1])) is None
