"""The readers on made-up inputs: each takes its metric from counters,
``/healthz``, the driver or the trace, and returns nothing where there is
nothing to read."""

from benchmark.harness import coordinator
from benchmark.readers import driver_stat, healthz, prom_gauge, prom_ratio, trace_idle, trace_op

TEXT = """# HELP xaynet_messages_total x
xaynet_messages_total{phase="update",outcome="accepted"} 12
xaynet_messages_total{phase="update",outcome="rejected"} 1
xaynet_message_pipeline_seconds_sum{stage="decrypt_parse"} 0.5
xaynet_message_pipeline_seconds_count{stage="decrypt_parse"} 10
xaynet_message_pipeline_seconds_sum{stage="decrypt_parse_batch"} 0.25
xaynet_message_pipeline_seconds_count{stage="decrypt_parse_batch"} 5
xaynet_message_pipeline_seconds_sum{stage="total"} 3.0
xaynet_message_pipeline_seconds_bucket{stage="total",le="+Inf"} 10
xaynet_streaming_overlap_ratio 0.75
xaynet_bytes_staged_total{layout="packed"} 2400
"""


def test_parse_and_sum_with_label_patterns():
    samples = coordinator.parse_metrics(TEXT)
    assert coordinator.sample_sum(samples, "xaynet_messages_total",
                                  {"phase": "update", "outcome": "accepted"}) == 12
    assert coordinator.sample_sum(samples, "xaynet_message_pipeline_seconds_sum",
                                  {"stage": "decrypt_parse.*"}) == 0.75
    assert coordinator.sample_sum(samples, "xaynet_bytes_staged_total") == 2400
    assert coordinator.sample_sum(samples, "absent") == 0.0


def ctx():
    zero = [(n, l, 0.0) for n, l, _ in coordinator.parse_metrics(TEXT)]
    return {"metrics": {"open": zero, "close": coordinator.parse_metrics(TEXT)},
            "health": {"end": {"device": {"peak_bytes_in_use": [4_000, None, 8_000],
                                          "compile": {"seconds": 1.5}}}},
            "driver": {"lateness_p95_ms": 0.4, "offered_rate": None},
            "peak": {"hbm_bytes": 16_000, "hbm_bytes_per_s": 1e9},
            "cfg": {"batch_size": 2, "bytes_per_number": 6, "n_limbs": 2, "model_length": 1000},
            "trace": {"device_stand_in": False, "busy_s": 0.5, "window_s": 4.0,
                      "modules": {"jit_fold_packed_batch(1)": {"count": 4, "seconds": 0.004},
                                  "jit_unmask(2)": {"count": 1, "seconds": 1.0}}}}


def test_readers_read():
    c = ctx()
    stage = {"labels": {"stage": "decrypt_parse.*"}}
    mean_ms = prom_ratio.read(
        c, {"name": "xaynet_message_pipeline_seconds_sum", **stage},
        {"name": "xaynet_message_pipeline_seconds_count", **stage}, ["open", "close"], 1000.0)
    assert mean_ms == 50.0
    assert prom_gauge.read(c, "xaynet_streaming_overlap_ratio", "close") == 0.75
    assert healthz.read(c, ["device", "peak_bytes_in_use"], "end", 100.0, "hbm_bytes") == 50.0
    assert healthz.read(c, ["device", "compile", "seconds"], "end") == 1.5
    assert driver_stat.read(c, "lateness_p95_ms") == 0.4
    assert trace_idle.read(c) == 87.5
    assert trace_op.read(c, "fold") == 1.0  # ms per execution
    assert trace_op.read(c, "jit_", per="fold") == 251.0  # all programs' time, per fold
    # 2*6*1000 + 2*4*2*1000 = 28000 bytes at 1e9 B/s = 28 us; over 1 ms = 2.8 %
    assert abs(trace_op.read(c, "fold", roofline=True) - 2.8) < 1e-9


def test_readers_return_nothing_where_there_is_nothing_to_read():
    c = ctx()
    none = {"name": "absent"}
    assert prom_ratio.read(c, none, none, ["open", "close"]) is None
    assert prom_ratio.read(c, none, none, ["open", "end"]) is None
    assert prom_gauge.read(c, "absent", "close") is None
    assert healthz.read(c, ["device", "nothing"], "end") is None
    assert healthz.read(c, ["device"], "open") is None
    assert driver_stat.read(c, "offered_rate") is None
    assert trace_op.read(c, "no-such-kernel") is None
    c["trace"]["device_stand_in"] = True  # the CPU rehearsal: never a device number
    assert trace_idle.read(c) is None and trace_op.read(c, "fold") is None
    c["trace"] = None
    assert trace_idle.read(c) is None and trace_op.read(c, "fold") is None
