"""The reducer's interval arithmetic on a trace small enough to draw.

Chip 0, times in ns (the window is the ``bench:stretch`` span, 0..1000):

    while.1        100 ........................... 500      (parent)
      fusion.2       100 .. 200
      all-reduce.3              250 ......... 400
      fusion.4                        350 .. 450            (overlaps it)
    circulant_sketch_encode.5                      600 .. 700
    all-gather.6                                            800 .. 900

busy  = [100,500] + [600,700] + [800,900]            = 600 ns
gaps  = [0,100] [500,600] [700,800] [900,1000]       = 400 ns, idle 40%
self  : while.1 = 400 - (100 + 150 + 100) = 50; the rest their durations
collective self time = all-reduce.3 150 + all-gather.6 100 = 250
exposed  = [250,350] (fusion.4 covers 350..400) + [800,900] = 200
           (while.1 is a parent, not a leaf, so it hides nothing)
host spans: bench:fetch 0..120, bench:dispatch 480..620, bench:sync 690..1000
gap [0,100] -> fetch 100; [500,600] -> dispatch 100;
gap [700,800] -> sync 100; [900,1000] -> sync 100    => sync 200, others 100
"""

import re

from perfbench.harness import tracered

TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["all-gather.6", 800, 100, "op=all-gather"],
            ["while.1", 100, 400, "op=while"],
            ["fusion.2", 100, 100, "op=fusion"],
            ["all-reduce.3", 250, 150, "op=all-reduce"],
            ["fusion.4", 350, 100, "op=fusion"],
            ["circulant_sketch_encode.5", 600, 100, "op=custom-call"]]},
        {"name": "XLA Modules", "events": [["jit_round", 100, 800, ""]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["bench:stretch", 0, 1000, ""], ["bench:fetch", 0, 120, ""],
            ["bench:dispatch", 480, 140, ""], ["bench:sync", 690, 310, ""],
            ["PjitFunction(round)", 480, 100, ""]]}]}]}
COLL = re.compile("(^|op=)(all-reduce|all-gather|reduce-scatter|all-to-all"
                  "|collective-permute)")


def test_busy_gaps_and_window():
    r = tracered.reduce(TRACE)
    assert r["window_source"] == "annotation"
    assert abs(r["window_s"] - 1000e-9) < 1e-15
    assert abs(r["busy_s"] - 600e-9) < 1e-15      # modules line not added
    assert abs(r["idle_share"] - 0.4) < 1e-12
    assert r["chips"][0]["gaps"] == [(0, 100), (500, 600), (700, 800),
                                     (900, 1000)]


def test_self_time_counts_nothing_twice():
    selfs = tracered.reduce(TRACE)["chips"][0]["selfs"]
    by = {t[0]: t[1] for t in selfs}
    assert by == {"while.1": 50, "fusion.2": 100, "all-reduce.3": 150,
                  "fusion.4": 100, "circulant_sketch_encode.5": 100,
                  "all-gather.6": 100}
    assert sum(by.values()) == 600                  # = busy, as it must
    leaf = {t[0]: t[2] for t in selfs}
    assert leaf["while.1"] is False and leaf["fusion.2"] is True


def test_collective_and_exposed_and_kernel_sums():
    selfs = tracered.reduce(TRACE)["chips"][0]["selfs"]
    assert abs(tracered.sum_matching(selfs, COLL) - 250e-9) < 1e-15
    assert abs(tracered.exposed(selfs, COLL) - 200e-9) < 1e-15
    kern = re.compile("circulant_sketch_(encode|decode)")
    assert abs(tracered.sum_matching(selfs, kern) - 100e-9) < 1e-15


def test_gaps_go_to_the_host_phase_that_covers_them():
    r = tracered.reduce(TRACE)
    gaps = {k: round(v * 1e9) for k, v in r["idle_gaps"]}
    assert gaps == {"bench:sync": 200, "bench:fetch": 100,
                    "bench:dispatch": 100}
    name, secs = r["device_ops"][0]
    assert name == "all-reduce.3" and abs(secs - 150e-9) < 1e-15


def test_without_the_annotation_the_window_is_the_device_extent():
    t = {"planes": [TRACE["planes"][0]]}
    r = tracered.reduce(t)
    assert r["window_source"] == "device_extent"
    assert abs(r["window_s"] - 800e-9) < 1e-15      # 100 .. 900
    assert abs(r["busy_s"] - 600e-9) < 1e-15


def test_a_trace_in_which_nothing_ran_on_a_chip_is_refused():
    import pytest
    with pytest.raises(ValueError):
        tracered.reduce({"planes": [TRACE["planes"][1]]})


def test_on_a_mesh_the_worst_chip_is_reported_and_busy_is_the_mean():
    """Chip 1 is chip 0 with its all-gather twice as long (800..1000):
    busy 700 against 600, so ``busy_s`` is 650; collective time 350 against
    250 and exposed 300 against 200, and a metric takes the larger."""
    import copy
    from perfbench.harness import readers
    chip1 = copy.deepcopy(TRACE["planes"][0])
    chip1["name"] = "/device:TPU:1"
    chip1["lines"][0]["events"][0] = ["all-gather.6", 800, 200,
                                      "op=all-gather"]
    trace = {"planes": [TRACE["planes"][0], chip1, TRACE["planes"][1]]}
    r = tracered.reduce(trace)
    assert sorted(r["chips"]) == [0, 1]
    assert abs(r["busy_s"] - 650e-9) < 1e-15
    ctx = {"trace": r, "traced_rounds": 1}
    match = COLL.pattern
    ms = readers.read({"name": "collective_ms", "reader": {
        "kind": "device_events", "match": match}}, ctx)
    exposed = readers.read({"name": "collective_exposed_ms", "reader": {
        "kind": "device_exposed", "match": match}}, ctx)
    assert abs(ms - 350e-6) < 1e-12 and abs(exposed - 300e-6) < 1e-12
