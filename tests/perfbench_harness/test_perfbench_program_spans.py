"""The program's spans beside a run (perfbench/program_spans.py): idle
gaps attributed to the innermost ``crdt.*`` span on a synthetic trace,
the recorded trace's reduction left as it was, the span line of an
untraced CPU run, and the readers of the program's spans."""

import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from perfbench import profile, program_spans
from perfbench.common import HERE, ROOT, load_module

TINY = os.path.join(HERE, "testdata", "tiny-v5e.xplane.pb")
MS = 1_000_000  # ns


def _line(name, spans):
    return NS(name=name, events=[
        NS(name=n, start_ns=a * MS, duration_ns=(b - a) * MS)
        for n, a, b in spans])


def _synthetic():
    """Window 0-100 ms; the chip busy at 10-20 and 60-70 ms, so idle
    gaps 0-10, 20-60 and 70-100 ms (middles 5, 40, 85)."""
    device = NS(name="/device:TPU:0", lines=[
        _line(profile.OPS_LINE, [("%fusion.1 = s32[8] fusion()", 10, 20),
                                 ("%fusion.2 = s32[8] fusion()", 60, 70)]),
    ])
    host = NS(name="/host:CPU", lines=[
        _line("harness", [("bench.window", 0, 100), ("bench.replay", 0, 90)]),
        _line("main", [("crdt.stage", 0, 15), ("crdt.gather", 18, 80),
                       ("crdt.converge.fetch", 30, 50)]),
        _line("worker", [("crdt.tick", 25, 55)]),
    ])
    return NS(planes=[device, host])


def test_idle_goes_to_the_innermost_program_span():
    out = program_spans.program_idle(_synthetic())
    by = out["idle_by_program"]
    # 5 ms: stage; 40 ms: fetch (nested in gather, later than the
    # other thread's tick); 85 ms: no program span, so the harness's
    assert by == pytest.approx({"crdt.stage": 0.010,
                                "crdt.converge.fetch": 0.040,
                                "bench.replay": 0.030})
    assert [k for k, _ in out["idle_gaps_program"]] == [
        "crdt.converge.fetch", "bench.replay", "crdt.stage"]
    cov = out["program_cover"]["bench.replay"]
    assert cov["time_s"] == pytest.approx(0.090)
    # stage 0-15, gather 18-80 (tick and fetch inside it)
    assert cov["time_covered"] == pytest.approx(77 / 90)
    assert cov["idle_s"] == pytest.approx(0.080)
    assert cov["idle_covered"] == pytest.approx(50 / 80)
    # the harness's own attribution of the same trace is unchanged
    assert profile.reduce(_synthetic())["idle_by_host"] == pytest.approx(
        {"bench.replay": 0.080})


def test_innermost_prefers_the_shorter_of_two_spans_that_start_together():
    spans = [(0, 10, "outer"), (0, 4, "inner"), (6, 8, "late")]
    got = program_spans._innermost(spans, [1, 5, 7, 11])
    assert [g[2] if g else None for g in got] == [
        "inner", "outer", "late", None]


def test_the_recorded_trace_reduces_as_before(tmp_path):
    """Every key ``profile.reduce_dir`` gives keeps its value; the tiny
    trace has no program span, so each gap falls back to the harness's
    span and the two attributions agree."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(TINY, d / "tiny.xplane.pb")
    base = profile.reduce_dir(str(tmp_path), "TPU v5 lite")
    got = program_spans.reduce_dir(str(tmp_path), "TPU v5 lite")
    for k, v in base.items():
        if k != "breakdown":
            assert json.dumps(got[k]) == json.dumps(v), k
    for k, v in base["breakdown"].items():
        assert json.dumps(got["breakdown"][k]) == json.dumps(v), k
    assert set(got) - set(base) == {"idle_by_program", "program_cover"}
    assert got["idle_by_program"] == pytest.approx(base["idle_by_host"])
    assert got["breakdown"]["idle_gaps_program"] == [
        [k, pytest.approx(v)] for k, v in base["breakdown"]["idle_gaps"]]
    assert profile.reduce_dir is program_spans._REDUCE_DIR


def test_untraced_run_says_its_program_spans(monkeypatch, capsys):
    from perfbench_tiny import tiny_cell

    run = program_spans.load_run()
    for k, v in list(os.environ.items()):
        if k.startswith("CRDT_TPU_"):
            monkeypatch.setenv(k, v)
    monkeypatch.setattr(run, "cell", tiny_cell)
    args = run.parse(["--workload", "textserver-type-small", "--seed",
                      str(2**31 + 13), "--seconds", "0.5", "--trace", "0"])
    out = program_spans.execute(run, args, require_tpu=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"], out["checks"]
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("program spans: ")]
    assert len(line) == 1
    spans = json.loads(line[0][len("program spans: "):])
    for name in ("tick", "tick.route", "tick.slo", "decode", "engine.flush"):
        assert spans[name]["count"] > 0, name
    t = spans["tick"]
    assert 0 <= t["self_ms"] <= t["total_ms"] and t["max_ms"] > 0
    assert run.guard_counters.__name__ == "guard_counters"


READERS = [
    ("route_ms.serve", "tick.route", "total_s", "ticks"),
    ("slo_ms.serve", "tick.slo", "total_s", "ticks"),
    ("tick_self_ms.serve", "tick", "self_s", "ticks"),
    ("decode_ms.serve", "decode", "total_s", "ticks"),
    ("flush_ms.serve", "engine.flush", "total_s", "ticks"),
    ("stage_ms.replay", "stage", "total_s", "replays"),
    ("gather_ms.replay", "gather", "total_s", "replays"),
    ("compact_ms.replay", "compact", "total_s", "replays"),
]


@pytest.mark.parametrize("metric,span,key,per", READERS)
def test_span_reader(metric, span, key, per):
    m = load_module("metrics", metric)
    s = {"count": 7, "total_s": 0.012, "self_s": 0.003, "max_s": 0.004}
    rd = {"run": {"ticks": 4, "replays": 4},
          "spans": {"spans": {span: s, "other": dict(s, total_s=9.0)}}}
    assert m.read(rd) == pytest.approx(s[key] * 1e3 / 4)
    # the parent program has no such span: nothing is reported
    assert m.read({"run": rd["run"], "spans": {"spans": {}}}) is None
    assert m.read({"run": rd["run"],
                   "spans": {"spans": {span: dict(s, count=0)}}}) is None
    assert m.read({"run": {}, "spans": rd["spans"]}) is None


def test_every_reader_is_declared():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in spec["per_layer"]}
    for metric, _, _, per in READERS:
        m = declared[metric]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        cell = ("textserver-type-small" if per == "ticks"
                else "fanin-1k-replay")
        assert m["workloads"] == [cell]
