"""The program's own telemetry spans as the source of the host-side
per-layer metrics: a traced run of the CARAT cell on the CPU reports the
statics re-upload, the state pull, candidate scoring and stage 2, each
nested where the program runs it."""
import chipbench_cpu as cpu

CELL = "frontier_9408.carat_striped"
SPAN_METRICS = ("fleet_statics_ms", "fleet_sync_ms", "decide_score_ms",
                "stage2_ms")


def test_traced_run_reads_the_program_span_metrics():
    res = cpu.run_small(CELL, trace=True)
    assert res["correct"] is True, res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in SPAN_METRICS:
        assert m[name] > 0, name
        assert res["metrics"][name]["unit"] == "ms"
    # scoring runs inside decide, both per decision interval
    assert m["decide_score_ms"] < m["policy_decide_ms"]
