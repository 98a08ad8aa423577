"""The reducer on recorded traces: three slices of one traced stretch of
``rn50_sketch_8x64`` on a v5e (PR 22, seed 1; cut with ``cut_trace.py`` from
the dump of ``run.py --trace 1 --dump-trace``), each small enough to read.

The expected numbers were worked out from the JSON files by hand and are
written here in nanoseconds; ``raster`` is a second, independent way to the
same busy time (paint every event onto a nanosecond timeline and count), so
that neither the constants nor the reducer can drift alone.

rn50_sketch_start.json   the first 3 ms of the stretch: the chip idles
    2,676,467 ns while the host fetches the first batch (17,550..54,710) and
    is inside ``FedRuntime.round`` (172,990 on); then 22 short operations
    (``pad.5`` 135,341 ns and 21 under 1 us) and idles to the slice's end.
    busy 143,575; idle 2,856,425 = 95.2142%. The long gap overlaps
    ``bench:fetch`` by 37,160 and ``bench:dispatch`` by 2,503,477, so it is
    the dispatch's; every other gap lies inside the dispatch span too.
rn50_sketch_decode.json  1 ms of the server tail around the decode kernel:
    ``circulant_sketch_decode.1`` 175,596..868,683 = 693,087 ns; 88 events,
    busy 855,605, the rest gaps of 1..5 us between operations and 131,314 at
    the slice's end.
rn50_sketch_encode.json  22.4 ms of the client scan around one call of the
    encode kernel: ``circulant_sketch_encode.8`` 116,266..22,246,632 =
    22,130,366 ns of a busy 22,282,884.
"""

import json
import os
import re

import numpy as np
import pytest

from perfbench.harness import readers, tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNELS = re.compile("circulant_sketch_(encode|decode)")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def raster(trace, chip=0):
    n = trace["cut"]["len_ns"]
    busy = np.zeros(n, bool)
    for p in trace["planes"]:
        if p["name"] == f"/device:TPU:{chip}":
            for _name, s, d, _m in p["lines"][0]["events"]:
                busy[s:s + d] = True
    return int(busy.sum())


@pytest.mark.parametrize("name,window,busy,kernel_ns,top", [
    ("rn50_sketch_start.json", 3_000_000, 143_575, 0, "pad.5"),
    ("rn50_sketch_decode.json", 1_000_000, 855_605, 693_087,
     "circulant_sketch_decode.1"),
    ("rn50_sketch_encode.json", 22_400_000, 22_282_884, 22_130_366,
     "circulant_sketch_encode.8"),
])
def test_busy_union_idle_share_and_kernel_sums(name, window, busy,
                                               kernel_ns, top):
    trace = load(name)
    r = tracered.reduce(trace)
    assert r["window_source"] == "annotation"
    assert round(r["window_s"] * 1e9) == window
    assert round(r["busy_s"] * 1e9) == busy == raster(trace)
    assert abs(r["idle_share"] - (1 - busy / window)) < 1e-12
    selfs = r["chips"][0]["selfs"]
    assert round(tracered.sum_matching(selfs, KERNELS) * 1e9) == kernel_ns
    assert r["device_ops"][0][0] == top
    # no event of these slices contains another: self times add up to busy
    # wherever operations do not overlap, and never to more than their sum
    assert sum(t[1] for t in selfs) >= busy


def test_the_start_gap_belongs_to_the_dispatch():
    r = tracered.reduce(load("rn50_sketch_start.json"))
    gaps = r["chips"][0]["gaps"]
    assert gaps[0] == (0, 2_676_467)
    assert abs(r["idle_share"] - 0.9521416666666667) < 1e-9
    assert [g[0] for g in r["idle_gaps"]] == ["bench:dispatch"]
    assert round(r["idle_gaps"][0][1] * 1e9) == 2_856_425
    assert round(r["longest_gap_s"] * 1e9) == 2_676_467


def test_declarative_reader_gives_ms_per_round():
    trace = tracered.reduce(load("rn50_sketch_decode.json"))
    metric = {"name": "sketch_kernel_ms", "reader": {
        "kind": "device_events", "match": "circulant_sketch_(encode|decode)",
        "zero_if_absent": True}}
    ctx = {"trace": trace, "traced_rounds": 2}
    assert abs(readers.read(metric, ctx) - 0.693087 / 2) < 1e-12
    # a cell without a sketch reads 0, a metric without the flag nothing
    none = tracered.reduce(load("rn50_sketch_start.json"))
    assert readers.read(metric, {"trace": none, "traced_rounds": 2}) == 0.0
    metric["reader"].pop("zero_if_absent")
    assert readers.read(metric, {"trace": none, "traced_rounds": 2}) is None
