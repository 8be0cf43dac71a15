"""The reduction of what the program records about itself
(``bench/program_trace.py``): program spans in the idle-gap labels, the
op -> scope attribution, the service's stage numbers, and the reader of
``sic_power_device_share``."""
import types
from pathlib import Path

import pytest

from bench import program_trace, run, trace_reduce

TRACE = Path(__file__).with_name("data") / "mmtc_small.xplane.pb"
ROOT = Path(__file__).resolve().parents[2]


def hand_trace():
    """Window 0-100 ns, the chip busy 10-20 and 60-70: idle gaps 20-60
    (inside an ``enqueue``), 70-100 and 0-10."""
    chip = trace_reduce.Chip(
        busy_ns=20.0, modules=[[10, 20], [60, 70]],
        ops=[("%while.3 = f32[] while(%t), body=%body", 10, 20, ""),
             ("%fusion.7 = f32[5] fusion(%a), calls=%fused.7", 12, 16, ""),
             ("%fusion.8 = f32[5] fusion(%b), calls=%fused.8", 14, 18, ""),
             ("%copy.2 = f32[5] copy(%c)", 60, 70, "")])
    return trace_reduce.Trace(window=(0, 100), chips={0: chip},
                              spans=[("enqueue", 15, 65),
                                     ("readback", 70, 100)])


def test_gap_labelled_with_program_span():
    trace = hand_trace()
    spans = [("equilibrium.canon", 18, 30, {}),
             ("equilibrium.launch", 30, 58, {}),
             ("equilibrium.canon", 200, 210, {})]    # outside every gap
    assert program_trace.label_gaps(trace, spans) == [
        ["enqueue/equilibrium.launch", 40e-9], ["readback", 30e-9],
        ["none", 10e-9]]
    assert program_trace.label_gaps(trace, []) == trace.idle_gaps()


def test_fixture_without_program_spans_keeps_labels():
    trace = trace_reduce.read(str(TRACE))
    spans = program_trace.program_spans(str(TRACE))
    assert spans == []
    assert program_trace.label_gaps(trace, spans) == trace.idle_gaps()


def test_span_means_count_window_starts():
    spans = [("equilibrium.canon", 0, 2_000_000, {}),
             ("equilibrium.canon", 5_000_000, 9_000_000, {}),
             ("equilibrium.launch", 1, 1_000_001, {}),
             ("equilibrium.canon", 99_000_000, 99_500_000, {})]
    means = program_trace.span_means_ms(spans, (0, 50_000_000))
    assert means == {"equilibrium.canon": {"n": 2, "mean_ms": 3.0},
                     "equilibrium.launch": {"n": 1, "mean_ms": 1.0}}


HLO = """\
HloModule jit_f, is_scheduled=true

%fused.7 (p: f32[5]) -> f32[5] {
  %p = f32[5]{0} parameter(0)
  ROOT %cos.1 = f32[5]{0} cosine(%p), metadata={op_name="jit(f)/while/body/sic_power/cos"}
}

%fused.8 (p: f32[5]) -> f32[5] {
  %p.1 = f32[5]{0} parameter(0)
  ROOT %add.2 = f32[5]{0} add(%p.1, %p.1), metadata={op_name="jit(f)/while/body/add"}
}

ENTRY %main.9 (a: f32[5]) -> f32[5] {
  %a = f32[5]{0} parameter(0)
  %fusion.7 = f32[5]{0} fusion(%a), kind=kLoop, calls=%fused.7
  %fusion.8 = f32[5]{0} fusion(%a), kind=kLoop, calls=%fused.8, metadata={op_name="jit(f)/while/body/sic_power/exp"}
  %while.3 = f32[5]{0} while(%a), condition=%c, body=%b, metadata={op_name="jit(f)/while"}
  ROOT %copy.2 = f32[5]{0} copy(%while.3)
}
"""


def test_scope_map_fusion_takes_its_root_scope():
    scopes = program_trace.scope_map(HLO)
    assert scopes["fusion.7"] == "jit(f)/while/body/sic_power/cos"
    assert scopes["fusion.8"] == "jit(f)/while/body/sic_power/exp"  # own
    assert scopes["while.3"] == "jit(f)/while"
    assert scopes["copy.2"] == ""


def test_op_scope_attribution():
    trace = hand_trace()
    scopes = program_trace.scope_map(HLO)
    # fusion.7 (12-16) and fusion.8 (14-18) overlap: their union, 6 ns of
    # the 20 ns busy
    assert program_trace.scope_share(trace, scopes, "sic_power") == \
        pytest.approx(30.0)
    # the loop's own scope holds the ops of its body: the union is the loop
    assert program_trace.scope_share(trace, scopes, "jit(f)/while") == \
        pytest.approx(50.0)
    # a program without the scope reads None, not 0
    assert program_trace.scope_share(trace, scopes, "no_scope") is None
    assert program_trace.op_key("%copy-done.16 = f32[] copy-done(%x)") == \
        "copy-done.16"


def test_serve_stages_numbers():
    def res(batch, queue, inflight, ready, pack, readback):
        return types.SimpleNamespace(stages={
            "queue_s": queue, "pack_s": pack, "launch_s": 0.001,
            "inflight_s": inflight, "ready_wait_s": ready,
            "readback_s": readback, "batch": batch})
    rows = [res(0, 0.010, 0.030, 0.001, 0.002, 0.004),
            res(0, 0.020, 0.030, 0.001, 0.002, 0.004),
            res(1, 0.040, 0.045, 0.002, 0.004, 0.008), None,
            types.SimpleNamespace(stages=None)]
    got = program_trace.serve_stages(rows)
    assert got == pytest.approx({
        "serve_queue_p95_ms": 40.0, "serve_inflight_p95_ms": 47.0,
        "serve_pack_ms": 3.0, "serve_readback_ms": 6.0})


def test_serve_stages_none_without_stages():
    # a service whose results carry no stages (an older program)
    assert program_trace.serve_stages([types.SimpleNamespace(rid=0), None]) \
        is None
    assert program_trace.serve_stages([]) is None


def test_sic_power_reader_none_without_trace():
    spec = run.resolve("paper_mc_n5", ROOT)
    reader = run.load_module(spec["readers"]["sic_power_device_share"],
                             "reader_sic_power")
    assert reader.read(types.SimpleNamespace(trace=None, counters={})) is None


def test_sic_power_reader_reads_the_cells_program():
    """On a trace whose ops carry the names of the cell's compiled program
    (here compiled for the CPU), the share is the time of the ops under
    ``sic_power`` over the busy time."""
    spec = run.resolve("paper_mc_n5", ROOT)
    driver = run.load_module(spec["driver"], "mc_driver_scopes")
    scopes = program_trace.scope_map(
        driver.compiled_text(spec["config"], spec["traffic"]))
    inside = next(n for n, s in scopes.items() if "/sic_power/" in s)
    outside = next(n for n, s in scopes.items() if s and "sic_power" not in s)
    chip = trace_reduce.Chip(
        busy_ns=40.0, modules=[[0, 40]],
        ops=[(f"%{outside} = f32[] op()", 0, 40, ""),
             (f"%{inside} = f32[] op()", 10, 20, "")])
    trace = trace_reduce.Trace(window=(0, 40), chips={0: chip}, spans=[])
    reader = run.load_module(spec["readers"]["sic_power_device_share"],
                             "reader_sic_power_cell")
    assert reader.read(types.SimpleNamespace(trace=trace, counters={})) == \
        pytest.approx(25.0)
