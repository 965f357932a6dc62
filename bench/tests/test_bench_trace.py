"""The trace reduction, on a 0.3 s slice recorded from a traced run of
qwen3-4b-w8a8-pallas.chat-poisson on one TPU v5e (the end of one decode
chunk, the host's work between chunks and the start of the next), and on
small synthetic traces."""

import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parents[1] / "testdata"
KERNEL = r"^fused_nibble_matmul_pallas(\.\d+)?$"


@pytest.fixture(scope="module")
def recorded():
    return tr.from_events(json.loads(
        (DATA / "trace-qwen3-4b-decode.json").read_text()))


def test_recorded_window_busy_and_programs(recorded):
    t = recorded
    assert t.devices == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.3)
    assert t.busy_s == pytest.approx(0.290433979, rel=1e-9)
    secs, n = t.program("jit_chunk")
    assert n == 1 and secs == pytest.approx(0.151821004, rel=1e-9)
    assert t.program("jit_prefill_into_slot") == (0.0, 0)


def test_recorded_kernel_time(recorded):
    secs, calls = recorded.ops_s(KERNEL)
    assert calls == 538
    assert secs == pytest.approx(0.087110747, rel=1e-6)


def test_recorded_idle_gap_is_named_by_the_host(recorded):
    gaps = recorded.idle_gaps()
    assert len(gaps) == 1
    name, secs = gaps[0]
    assert name == "$array.py:631 _value"
    assert secs == pytest.approx(0.009565948, rel=1e-6)
    assert recorded.busy_s + secs == pytest.approx(recorded.window_s,
                                                   abs=2e-4)


def test_recorded_breakdown_counts_leaf_ops(recorded):
    b = recorded.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[:3] == ["fused_nibble_matmul_pallas", "fusion", "copy"]
    # a while op spans its body: it is not a leaf and never listed
    assert not any(n.startswith("while") for n in names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["device_ops"]) <= recorded.busy_s


def test_op_name_from_hlo_text():
    assert tr.op_name("%fused_nibble_matmul_pallas.48 = bf16[2048,2560]"
                      "{1,0} custom-call(s8[2048,2560] %x)") == \
        "fused_nibble_matmul_pallas.48"
    assert tr.op_name("copy-done") == "copy-done"


def _ev(kind, where, name, start, dur):
    return {"kind": kind, "where": where, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_synthetic_union_clip_and_gap_names():
    d = "/device:TPU:0"
    events = [
        _ev("host", "py", "bench.window", 1_000_000, 1_000_000),
        _ev("host", "py", "bench.step", 1_000_000, 900_000),
        _ev("host", "py", "sample", 1_400_000, 200_000),
        # nested ops: a while holding two kernels
        _ev("op", d, "while.3", 900_000, 400_000),
        _ev("op", d, "k.1", 950_000, 100_000),
        _ev("op", d, "k.2", 1_100_000, 100_000),
        _ev("op", d, "late.1", 1_700_000, 500_000),
        _ev("module", d, "jit_chunk(77)", 900_000, 400_000),
    ]
    t = tr.from_events(events)
    # busy: [1.0, 1.3) ms and [1.7, 2.0) ms, clipped to the window
    assert t.busy_s == pytest.approx(600e-6)
    assert t.program("jit_chunk") == (pytest.approx(300e-6), 1)
    assert t.ops_s(r"^k\.") == (pytest.approx(150e-6), 2)
    gaps = t.idle_gaps()
    assert gaps == [("sample", pytest.approx(400e-6))]
    b = t.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["late", "k"]


def test_busy_is_averaged_over_chips():
    events = [_ev("host", "py", "bench.window", 0, 1_000),
              _ev("op", "/device:TPU:0", "a", 0, 1_000),
              _ev("op", "/device:TPU:1", "a", 0, 500)]
    assert tr.from_events(events).busy_s == pytest.approx(750e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.from_events([_ev("op", "/device:TPU:0", "a", 0, 10)])


def test_op_result_type_and_rows():
    text = ("%fused_nibble_matmul_pallas.48 = bf16[2048,2560]{1,0:T(8,128)}"
            " custom-call(s8[2048,2560] %x)")
    assert tr.op_out(text) == "bf16[2048,2560]"
    assert tr.rows(tr.op_out(text)) == 2048
    assert tr.op_out("copy-done") == "" and tr.rows("") == 0


def _pad_share(prefill_rows, tokens):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "pad_share", DATA.parent / "metrics" / "prefill_pad_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    d = "/device:TPU:0"
    events = [_ev("host", "py", "bench.window", 0, 10_000),
              # a decode chunk: its kernel rows are not prefill rows
              _ev("module", d, "jit_chunk(5)", 100, 900),
              {**_ev("op", d, "fused_nibble_matmul_pallas.1", 200, 100),
               "out": "bf16[128,4096]"}]
    for i, m in enumerate(prefill_rows):
        start = 2_000 + 2_000 * i
        events += [_ev("module", d, "jit_prefill_into_slot(9)", start, 1_000),
                   {**_ev("op", d, "fused_nibble_matmul_pallas.2",
                          start + 100, 100), "out": f"bf16[{m},1024]"},
                   {**_ev("op", d, "fusion.3", start + 300, 100),
                    "out": "bf16[4096,4096]"}]
    ctx = {"trace": tr.from_events(events),
           "stats": {"prefill_tokens": tokens}}
    return mod.read(ctx)


def test_prefill_pad_share_reads_the_dispatched_rows():
    # two prefills padded to 2,048 rows for 3,072 real prompt tokens
    assert _pad_share([2048, 2048], 3072) == pytest.approx(25.0)
    # prefills cut to their prompts' lengths read no padding
    assert _pad_share([1024, 2048], 3072) == pytest.approx(0.0)
    assert _pad_share([], 3072) is None
