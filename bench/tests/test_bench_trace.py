"""The trace reduction on a constructed trace, against hand-computed
values."""
from bench.lib.trace import (gaps_of, op_name, reduce_trace, self_times,
                             union_length)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur, meta=""):
    return (plane, line, name, start, dur, meta)


def constructed():
    # window 1000..2000 ns; device ops (some overlapping, one nested, one
    # straddling the window start); host spans label the gaps
    return [
        ev(HOST, "python3", "bench.span", 1000, 1000),
        ev(HOST, "python3", "bench.serve_stream", 1000, 600),
        ev(HOST, "python3", "bench.arrival_wait", 1650, 300),
        ev(DEV, "XLA Ops", "fusion.1", 900, 200),           # clipped to 1000..1100
        ev(DEV, "XLA Ops", "while.3", 1200, 300),
        ev(DEV, "XLA Ops", "flash_decode_paged.7", 1250, 100),   # nested in while
        ev(DEV, "XLA Ops", "%staged_scatter.9 = bf16[8] custom-call(%x.1)", 1400, 200),
        ev(DEV, "XLA Ops", "%fusion.2 = f32[] fusion(%staged_scatter.9)", 1800, 50),
        ev(DEV, "Steps", "step", 1000, 1000),                # not an op line
    ]


def test_union_and_gaps():
    assert union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert gaps_of([(10, 20), (15, 30)], 0, 40) == [(0, 10), (30, 40)]


def test_self_times_subtract_nested():
    t = self_times([ev(DEV, "XLA Ops", "while.3", 0, 300),
                    ev(DEV, "XLA Ops", "k.1", 50, 100)])
    assert t == {"while": 200, "k": 100}


def test_reduce_hand_computed():
    r = reduce_trace(constructed(), kernels=("flash_decode_paged",
                                             "staged_scatter"))
    # busy: 1000-1100, 1200-1600 (while 1200-1500 + custom-call to 1600),
    # 1800-1850 => 100 + 400 + 50 = 550 ns of 1000
    assert r["window_s"] == 1000e-9
    assert abs(r["busy_s"] - 550e-9) < 1e-15
    assert abs(r["idle_share"] - 0.45) < 1e-12
    assert r["kernel_s"]["flash_decode_paged"] == 100e-9
    # by its own name; a fusion that reads the kernel's output is not it
    assert r["kernel_s"]["staged_scatter"] == 200e-9
    # gaps: 1100-1200 (serve_stream), 1600-1800 (midpoint 1700: arrival_wait
    # is the innermost open), 1850-2000 (arrival_wait ends 1950 < 1925? no:
    # midpoint 1925 lies in arrival_wait 1650-1950)
    assert r["gaps"] == [["bench.arrival_wait", 200e-9],
                         ["bench.arrival_wait", 150e-9],
                         ["bench.serve_stream", 100e-9]]
    ops = dict((n, t) for n, t in r["top_ops"])
    assert ops["while"] == 200e-9 and ops["staged_scatter"] == 200e-9
    assert ops["fusion"] == 150e-9 and ops["flash_decode_paged"] == 100e-9


def test_no_span_or_no_device_reads_nothing():
    assert reduce_trace([e for e in constructed() if e[2] != "bench.span"]) is None
    assert reduce_trace([e for e in constructed() if e[0] == HOST]) is None


def test_op_name_from_hlo_text():
    assert op_name("%flash_decode_paged.10 = bf16[4,32,1,64] custom-call(%a.1)") \
        == "flash_decode_paged"
    assert op_name("%fusion.361.remat_uncompressed = bf16[8] copy(%b)") \
        == "fusion.remat_uncompressed"
    assert op_name("while.3") == "while"
