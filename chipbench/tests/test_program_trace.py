"""The program's own marks in a traced run (``chipbench/program_trace.py``)
and the readers built on them, on hand-made data and on a recorded chip
trace (``data/trace_scoped.json``); every reader of the benchmark on the
recorded request of ``data/trace_small.json``, which the program's marks
do not change."""
import json
import os
import pathlib
from collections import Counter

import pytest

from chipbench import kernels
from chipbench import program_trace as pt
from chipbench import run
from chipbench import trace as tr
from chipbench.tests.test_trace import SMALL, recorded
from repro.obs.setup import SetupCounters


def reader(name: str):
    return run.load_module(run.HERE / "metrics" / f"{name}.py")


#: SMALL's window with scoped ops, one of them cut by the window's end,
#: and spans of the program: a step over the 30-40 gap, a batch span
#: over the first 20 ns of the 60-100 gap.
PROGRAM = {
    "ops": [["ks_galois_L7/ks.modup", 5, 15], ["ks_galois_L7/ks.ip", 40, 20],
            ["ks_galois_L7/ks.modup", 95, 20],
            ["ks_galois_L7/ks.automorph", -10, 5]],
    "spans": [["exec.run", 0, 90, 0], ["exec.step.RelinStep", 25, 20, 0],
              ["exec.batch", 60, 20, 0]],
}


def test_op_scope_keeps_plan_and_phase():
    assert pt.op_scope("jit(ks_hoisted_L35)/vmap(ks.automorph)/gather") \
        == "ks_hoisted_L35/ks.automorph"
    assert pt.op_scope("jit(ks_modup_b_L7)/vmap(ks.modup)/jit(dispatch)/"
                       "modup") == "ks_modup_b_L7/ks.modup"
    assert pt.op_scope("jit(_where)/select_n") == ""
    assert pt.op_scope("") == ""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of ``(field, value)`` pairs: an int is a
    varint, ``str``/``bytes`` are length-delimited."""
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += _varint(field << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(field << 3 | 2) + _varint(len(data)) + data
    return out


def _xspace() -> bytes:
    """A host plane and two TPU planes (``xplane.proto``): the first TPU
    plane's ``XLA Ops`` line has three ops; the first op's ``tf_op`` is
    a string, the second's a reference to an interned string, the third
    has no metadata.  Metadata follows the lines, as in a real profile."""
    stat_names = [(1, "tf_op"), (2, "program_id"),
                  (3, "jit(ks_galois_L7)/vmap(ks.automorph)/gather:")]
    meta = [(11, "%fusion.2 = u32[4] fusion()", [(1, _msg((1, 1), (
                 5, "jit(ks_relin_b_L3)/vmap(ks.ip)/jit(dispatch)/"
                    "fused_ip/pallas_call:"))), (1, _msg((1, 2), (3, 9)))]),
            (12, "%gather.1 = u32[4] gather()", [(1, _msg((1, 1),
                                                          (7, 3)))])]
    ops_line = _msg((2, "XLA Ops"), (3, 1000),
                    (4, _msg((1, 11), (2, 2500), (3, 4000))),
                    (4, _msg((1, 12), (2, 10000), (3, 1999))),
                    (4, _msg((1, 13), (2, 20000), (3, 1000))))
    tpu = _msg((2, "/device:TPU:0"), (3, _msg((2, "XLA Modules"))),
               (3, ops_line),
               *[(4, _msg((1, i), (2, _msg((1, i), (2, name),
                                            *[(5, st) for _, st in stats]))))
                 for i, name, stats in meta],
               *[(5, _msg((1, i), (2, _msg((1, i), (2, name)))))
                 for i, name in stat_names])
    other = _msg((2, "/device:TPU:1"), (3, _msg((2, "XLA Ops"), (4, _msg(
        (1, 1), (2, 5), (3, 5))))))
    host = _msg((2, "/host:CPU"), (3, _msg((2, "main"))))
    return _msg((1, host), (1, tpu), (1, other))


def test_device_ops_read_the_name_path_from_event_metadata(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_xspace())
    assert pt.device_ops(str(path)) == [
        ["jit(ks_relin_b_L3)/vmap(ks.ip)/jit(dispatch)/fused_ip/"
         "pallas_call:", 1002, 4],
        ["jit(ks_galois_L7)/vmap(ks.automorph)/gather:", 1010, 1],
        ["", 1020, 1]]
    assert [pt.op_scope(p) for p, _, _ in pt.device_ops(str(path))] == [
        "ks_relin_b_L3/ks.ip", "ks_galois_L7/ks.automorph", ""]


def test_scope_time_is_clipped_to_the_window():
    assert pt.scope_ns(SMALL["window"], PROGRAM, "ks.modup") == 15 + 5
    assert pt.scope_ns(SMALL["window"], PROGRAM, "ks.ip") == 20
    assert pt.scope_ns(SMALL["window"], PROGRAM, "ks.moddown") == 0
    assert pt.scope_ns(SMALL["window"], PROGRAM, "ks.automorph") == 0
    assert pt.scope_ns(SMALL["window"], {"ops": [], "spans": []},
                       "ks.ip") is None


@pytest.mark.parametrize("phase,ms", [("modup", 10e-6), ("ip", 10e-6),
                                      ("moddown", 0.0),
                                      ("automorph", 0.0)])
def test_phase_readers_per_request(phase, ms):
    data = {"requests": 2, "trace": SMALL, "program": PROGRAM}
    assert reader(f"ks_ms_per_req.{phase}").read(data) == \
        pytest.approx(ms)
    bare = {"requests": 2, "trace": SMALL,
            "program": {"ops": [], "spans": []}}
    assert reader(f"ks_ms_per_req.{phase}").read(bare) is None


def test_idle_inside_program_spans():
    # idle: 0-5, 30-40 and 60-95; the step span covers 30-40, the
    # exec.* spans together 0-90
    assert pt.idle_in_spans_ns(SMALL, PROGRAM, "exec.step.") == 10
    assert pt.idle_in_spans_ns(SMALL, PROGRAM, "exec.") == 5 + 10 + 30
    assert pt.idle_in_spans_ns(SMALL, {"ops": [], "spans": []},
                               "exec.") is None
    assert pt.idle_in_spans_ns(dict(SMALL, ops=[]), PROGRAM,
                               "exec.") is None


def test_setup_counters_stop_where_the_profile_was_written(
        tmp_path, monkeypatch):
    """Set-up's seconds are those before the traced window's profile
    was written; a compile after it (the checks') is left out."""
    from repro import obs

    profile = tmp_path / "plugins" / "profile" / "run1"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    os.utime(profile / "host.xplane.pb", (100.0, 100.0))
    counters = SetupCounters(clock=lambda: 0.0)
    counters.intervals = [(10.0, 30.0, "keygen"), (12.0, 14.0, "compile"),
                          (20.0, 26.0, "trace_lower"),
                          (99.0, 103.0, "compile")]
    counters.misses = [13.0, 101.0]
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(obs, "SETUP", counters)
    data = {}
    got = {n: reader(n).read(data) for n in (
        "setup_s.trace_lower", "setup_s.compile", "setup_s.keygen",
        "setup_cache_misses")}
    assert got == {"setup_s.trace_lower": 6.0, "setup_s.compile": 3.0,
                   "setup_s.keygen": 12.0, "setup_cache_misses": 1}
    assert data["setup"]["cache_hits"] == 0


def test_setup_readers_find_nothing_in_a_program_without_counters(
        monkeypatch):
    from repro import obs

    monkeypatch.delattr(obs, "SETUP")
    for n in ("setup_s.trace_lower", "setup_s.compile", "setup_s.keygen",
              "setup_cache_misses"):
        assert reader(n).read({}) is None


def test_every_reader_reads_the_recorded_trace_as_before():
    """Every metric of the first benchmark reads the recorded request as
    it did before the program had scopes and spans; the new ones find
    nothing there to read."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    data = {"requests": 1, "trace": recorded(),
            "device_kind": "TPU v5 lite",
            "counters": {"modup": 27, "moddown": 27},
            "program": {"ops": [], "spans": []}, "setup": None}
    got = {m["name"]: reader(m["name"]).read(data)
           for m in bench["per_layer"]}
    assert {k: v for k, v in got.items() if v is not None} == {
        "modup_per_req": 27.0,
        "moddown_per_req": 27.0,
        "device_idle_share": 5.65649692338599,
        "xla_glue_ms_per_req": 82.074524,
        "kernel_ms_per_req.ntt": 42.162757,
        "kernel_ms_per_req.bconv": 3.842736,
        "kernel_ms_per_req.fused_ip": 0.878649,
        "kernel_ms_per_req.modup": 28.433369,
        "ntt_roofline": 1.0415524147892266,
        "bconv_roofline": 12.360879483054934,
        "fused_ip_roofline": 57.01051221617455,
        "modup_roofline": 0.4547880703393615,
    }


def scoped() -> dict:
    """Two cuts of one ``helr-paper.serial`` process on a v5e
    (``data/trace_scoped.json``): ``scoped``, an iteration of the
    harness's traced window with each op's program scope, and
    ``spans``, an iteration of an obs-enabled window with the program's
    spans."""
    path = pathlib.Path(__file__).parent / "data" / "trace_scoped.json"
    return json.loads(path.read_text())


def test_recorded_phase_times():
    t = scoped()["scoped"]
    data = {"requests": 1, "trace": t, "program": t["program"]}
    got = {p: reader(f"ks_ms_per_req.{p}").read(data)
           for p in ("modup", "ip", "moddown", "automorph")}
    assert got == pytest.approx({"modup": 51.2107, "ip": 4.414355,
                                 "moddown": 97.553049,
                                 "automorph": 26.190881})
    assert sum(got.values()) < tr.busy_ns(t) / 1e6
    assert got["modup"] >= reader("kernel_ms_per_req.modup").read(data)
    assert got["ip"] >= reader("kernel_ms_per_req.fused_ip").read(data)


def test_recorded_kernels_run_inside_their_phase():
    """Every fused ModUp call lies under ``ks.modup``, every fused IP
    under ``ks.ip``, every BConv under ``ks.moddown``; the NTTs outside
    a keyswitch are the executor's own."""
    t = scoped()["scoped"]
    phase = {(s, d): path.rpartition("/")[2]
             for path, s, d in t["program"]["ops"]}
    got = {k: Counter(phase.get((s, d), "") for n, s, d in t["ops"]
                      if kernels.matcher(k)(n))
           for k in kernels.kernels()}
    assert got == {"modup": {"ks.modup": 27}, "fused_ip": {"ks.ip": 27},
                   "bconv": {"ks.moddown": 108},
                   "ntt": {"ks.moddown": 54, "": 24}}


def test_recorded_program_spans_lie_inside_their_request():
    t = scoped()["spans"]
    (req,) = [s for s in t["spans"] if s[0] == "request"]
    spans = t["program"]["spans"]
    assert Counter(s[0] for s in spans)["exec.run"] == 1
    assert sum(s[0].startswith("exec.step.") for s in spans) == 43
    assert all(req[1] <= s[1] and s[1] + s[2] <= req[1] + req[2]
               for s in spans)
    assert len({s[3] for s in spans}) == 1      # one dispatch's batch_index


def test_recorded_idle_inside_steps_is_part_of_the_idle():
    t = scoped()["spans"]
    idle = pt.idle_in_spans_ns(t, t["program"], "exec.step.")
    assert idle == 10247945.0
    assert idle <= tr.idle_share(t) * tr.window_ns(t)
    assert idle < pt.idle_in_spans_ns(t, t["program"], "exec.")
