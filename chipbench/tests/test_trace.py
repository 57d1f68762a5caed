"""Trace reduction arithmetic, on a hand-made trace and on one request
recorded on the chip (``data/trace_small.json``)."""
import json
import pathlib

import pytest

from chipbench import kernels
from chipbench import trace as tr

#: 0-100 ns window; ops overlap at 10-30 and leave gaps 30-40, 60-100.
SMALL = {
    "window": [0, 100],
    "ops": [["ntt_kernel.1", 5, 15], ["fusion.2", 10, 20],
            ["fused_ip_kernel", 40, 20], ["copy.3", 95, 20],
            ["fusion.2", -10, 5]],
    "spans": [["request", 0, 100], ["dispatch", 0, 35],
              ["block", 55, 45]],
}


def test_union_merges_overlaps():
    assert tr.union([(5, 20), (10, 30), (40, 60)]) == [(5, 30), (40, 60)]
    assert tr.union([(1, 2), (2, 3)]) == [(1, 3)]


def test_busy_and_idle_share():
    # busy: 5-30, 40-60, 95-100 -> 50 ns of 100
    assert tr.busy_ns(SMALL) == 50
    assert tr.window_ns(SMALL) == 100
    assert tr.idle_share(SMALL) == pytest.approx(0.5)
    assert tr.idle_share(dict(SMALL, ops=[])) is None


def test_op_times_are_clipped_to_the_window():
    t = tr.op_times(SMALL)
    assert t == {"ntt_kernel.1": 15, "fusion.2": 20,
                 "fused_ip_kernel": 20, "copy.3": 5}
    assert tr.op_times(SMALL, lambda n: "kernel" in n) == {
        "ntt_kernel.1": 15, "fused_ip_kernel": 20}
    assert tr.op_calls(SMALL, lambda n: n.startswith("fusion")) == 1


def test_gaps_are_labelled_by_the_innermost_span():
    assert tr.gaps(SMALL) == [("block", 35), ("dispatch", 10),
                              ("dispatch", 5)]


def test_breakdown_is_in_seconds():
    b = tr.breakdown(SMALL, top=2)
    assert b["device_ops"] == [["fusion.2", 20e-9],
                               ["fused_ip_kernel", 20e-9]]
    assert b["idle_gaps"][0] == ["block", 35e-9]


def recorded():
    path = pathlib.Path(__file__).parent / "data" / "trace_small.json"
    return json.loads(path.read_text())


def test_recorded_request_splits_into_kernels_and_glue():
    """One request of ``helr-paper.serial`` on a v5e: every Pallas call
    belongs to exactly one kernel, and kernels plus glue are all the
    device time."""
    t = recorded()
    calls = {k: tr.op_calls(t, kernels.matcher(k)) for k in kernels.kernels()}
    assert calls == {"bconv": 72, "fused_ip": 18, "modup": 18, "ntt": 60}
    pallas = [n for n, _, _ in t["ops"] if n.endswith("tpu_custom_call")]
    assert sum(calls.values()) == len(pallas)
    glue = sum(tr.op_times(t, kernels.is_glue).values())
    kern = sum(sum(tr.op_times(t, kernels.matcher(k)).values())
               for k in kernels.kernels())
    assert glue + kern == pytest.approx(sum(tr.op_times(t).values()))
    assert glue == pytest.approx(82074524.0)
    assert tr.idle_share(t) == pytest.approx(0.056564969233859896)


def test_recorded_rooflines():
    """Least bytes over 819 GB/s over kernel time: the fused IP reads
    its key at about half the HBM bandwidth; the others are far from
    the memory bound."""
    t = recorded()
    roof = {k: kernels.roofline(t, k, "TPU v5 lite")
            for k in kernels.kernels()}
    assert roof == pytest.approx({"bconv": 12.360879483054934,
                                  "fused_ip": 57.01051221617455,
                                  "modup": 0.4547880703393615,
                                  "ntt": 1.0415524147892266})
    # fused IP by hand: digits (dnum=1) + key (2 polys) + two results of
    # e limbs of 2^16 4-byte words, plus e limbs of plaintext where the
    # call has one (a fifth operand)
    moved, ns = 0, 0.0
    for name, _, dur in t["ops"]:
        if kernels.matcher("fused_ip")(name):
            e = int(name.split("custom-call(u32[")[1].split("]")[0])
            moved += (5 + (name.count("u32[") == 7)) * e * 65536 * 4
            ns += dur
    assert roof["fused_ip"] == pytest.approx(100 * moved / 819e9 / ns / 1e-9)
    with pytest.raises(KeyError):
        kernels.roofline(t, "ntt", "TPU v4")


def test_op_label_drops_layouts_names_and_attributes():
    hlo = ('%vmap_jit_dispatch__.7 = (u32[19,512,128]{2,1,0:T(8,128)}, '
           'u32[19,512,128]{2,1,0:T(8,128)}) custom-call(u32[19]{0} %a.1, '
           'u32[1,2,19,512,128]{4,3,2,1,0} %b), custom_call_target='
           '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert tr.op_label(hlo) == (
        "vmap_jit_dispatch__.7 = (u32[19,512,128], u32[19,512,128]) "
        "custom-call(u32[19], u32[1,2,19,512,128]) tpu_custom_call")
