"""The trace reduction and the work arithmetic, on constructed input."""
import pytest

import run_cell
import work
from ref import devtrace

PEAK = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def _trace():
    # one device, window [1, 11]: ops cover [0.5, 2] (clipped to [1, 2]),
    # [2, 3] and [2.5, 4] (overlapping), and [9, 12] (clipped to [9, 11])
    ops = [("slice_raster.1", 0.5, 2.0), ("copy.3", 2.0, 3.0),
           ("projection_raster.1", 2.5, 4.0), ("slice_raster.1", 9.0, 12.0)]
    host = [(devtrace.WINDOW, 1.0, 11.0), ("submit", 3.5, 6.0),
            ("drain", 5.0, 10.0)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_busy_idle_and_ops():
    r = devtrace.reduce(_trace())
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(1.0 + 2.0 + 2.0)
    assert r["ops"]["slice_raster.1"] == (pytest.approx(3.0), 2)
    assert r["ops"]["copy.3"] == (pytest.approx(1.0), 1)
    # the one gap [4, 9]: its midpoint 6.5 lies in "drain" only
    assert r["idle"] == {"drain": pytest.approx(5.0)}
    assert devtrace.top(r["ops"], 2) == [["slice_raster.1", 3.0],
                                         ["projection_raster.1", 1.5]]


def test_innermost_label_and_no_window():
    t = _trace()
    t["host"].append(("submit", 6.0, 7.0))       # open at 6.5, shorter
    assert devtrace.reduce(t)["idle"] == {"submit": pytest.approx(5.0)}
    t["host"] = [h for h in t["host"] if h[0] != devtrace.WINDOW]
    assert devtrace.reduce(t) is None


def test_no_device_op_in_window():
    t = _trace()
    t["devices"] = {"/device:TPU:0": [("x", 20.0, 21.0)]}
    assert devtrace.reduce(t) is None


def test_op_names():
    text = "%slice_raster.1 = (f32[512,512]) custom-call(s32[1,8] %b)"
    assert devtrace.op_name(text) == "slice_raster.1"
    assert work.kernel_of("slice_raster.1") == "slice"
    assert work.kernel_of("projection_raster") == "projection"
    assert work.kernel_of("level_hist.12") == "hist"
    assert work.kernel_of("dynamic-slice.4") is None
    assert work.kernel_of("copy.1") is None


def _arrays():
    from ref import orion
    gen = {**run_cell.cell_spec("hdep_orion_r512")["config"]["generator"],
           "max_level": 6}
    return orion.generate(gen, 3)


def test_raster_work_and_roofline_share():
    arrays = _arrays()
    leaves = work.level_counts(arrays)
    assert leaves.sum() == (~arrays["refine"]).sum()
    spec = {"field": "density", "axis": 2, "position": 0.5,
            "resolution": 64, "bins": 32}
    ops, nbytes = work.raster_work("projection", arrays, spec)
    # every leaf covers (64 / 2**l)**2 pixels, at least one
    assert ops == sum(int(c) * max(1, (64 >> l) ** 2)
                      for l, c in enumerate(leaves))
    assert nbytes == leaves.sum() * 4 * 4 + 64 * 64 * 4
    s_ops, _ = work.raster_work("slice", arrays, spec)
    # a plane through the box is covered exactly once per pixel... by
    # the leaves it crosses: their footprints tile the 64 x 64 image
    assert s_ops == 64 * 64
    h_ops, _ = work.raster_work("hist", arrays, spec)
    assert h_ops == leaves.sum()
    least, bound = work.least_time(ops, nbytes, PEAK)
    assert bound == "memory" and least == nbytes / PEAK["hbm_bytes_per_s"]
    # a kernel can never beat the least time: the share stays <= 1
    for secs in (least, 2 * least, 1e3 * least):
        assert least / secs <= 1.0


def test_peaks_table():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_train_flops():
    cfg = {"hidden_size": 2048, "intermediate_size": 5632,
           "num_attention_heads": 32, "num_key_value_heads": 32,
           "num_hidden_layers": 4, "vocab_size": 25088}
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    attn = 2 * 2048 * 512.5 / 1      # two score products, 2048 wide
    fwd = 2 * (4 * (per_layer + attn)) + 2 * 2048 * 25088
    assert work.lm_train_flops_per_token(cfg, 1024) == pytest.approx(3 * fwd)
