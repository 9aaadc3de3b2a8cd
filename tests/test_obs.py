"""repro.obs: metrics/event streams, sync-count parity, restart survival.

The load-bearing assertions:

* the instrumented train loop performs EXACTLY the same number of
  ``jax.block_until_ready`` calls per run as the un-instrumented loop —
  the PR-7 one-sync-per-logical-batch invariant survives observability;
* the JSONL streams are append-only and a crash-torn final line (made with
  the same ``runtime.inject`` truncation the checkpoint injector uses)
  costs one record, never the read;
* events written across an in-process ``--auto-restart`` land in ONE
  stream with monotone step stamps and a process-monotone ``seq``.
"""
from __future__ import annotations

import gzip
import json
import logging
import os
import sys

import pytest

from repro.obs import (
    EVENT_KINDS,
    JsonlSink,
    MemorySink,
    NullSink,
    configure_run,
    emit_event,
    emit_metrics,
    events_active,
    read_jsonl,
    reset_sinks,
    set_sink,
    summarize_run,
)
from repro.obs import events as obs_events
from repro.obs.profile import ProfileWindow, parse_window
from repro.obs.report import render_text
from repro.obs.timeline import (
    STEP_ANNOTATION,
    annotated_steps,
    execution_spans,
    percentile,
    step_timeline,
    step_wall_times_ms,
    trace_files,
)
from repro.runtime.inject import InjectionPlan, tear_file

ARCH = ["--arch", "yi-6b", "--reduced", "--seq", "16", "--log-every", "4"]


def _mem_sinks():
    ev, mt = MemorySink(), MemorySink()
    set_sink("events", ev)
    set_sink("metrics", mt)
    return ev, mt


# -- sinks + stamping ------------------------------------------------------
def test_default_sink_is_inert_and_emits_are_free():
    reset_sinks()
    assert not events_active()
    emit_event("run_started", arch="x")  # no sink: must not raise
    emit_metrics({"kind": "train_step"})


def test_unknown_event_kind_raises_even_when_inert():
    reset_sinks()
    with pytest.raises(ValueError, match="unknown event kind"):
        emit_event("made_up_kind")


def test_reserved_stamp_fields_rejected():
    _mem_sinks()
    with pytest.raises(ValueError, match="collide"):
        emit_event("run_started", seq=16)


def test_stamping_run_id_rank_and_monotone_seq():
    ev, _ = _mem_sinks()
    obs_events.set_run_context("run-test")
    emit_event("run_started", arch="a")
    emit_event("run_finished", step=3, epsilon=1.0)
    a, b = ev.records
    assert a["kind"] == "run_started" and a["run_id"] == "run-test"
    assert a["rank"] == 0 and "t" in a
    assert b["step"] == 3 and b["seq"] > a["seq"]
    assert all(k in EVENT_KINDS for k in (a["kind"], b["kind"]))


def test_jsonl_sink_appends_and_survives_torn_tail(tmp_path):
    path = tmp_path / "events.jsonl"
    sink = JsonlSink(path)
    sink.emit({"kind": "a", "n": 1})
    sink.emit({"kind": "b", "n": 2})
    sink.close()
    # crash mid-write: the SAME truncation the torn@step checkpoint
    # injector applies — the final line becomes a prefix of a record
    tear_file(path)
    torn_lines = path.read_text().splitlines()
    assert len(torn_lines) >= 1
    with pytest.raises(json.JSONDecodeError):
        json.loads(torn_lines[-1])
    assert read_jsonl(path) == []  # both records damaged at 1/3 length
    # a restarted process APPENDS past the torn tail; the new record reads
    # back even though the torn prefix is still physically in the file
    sink2 = JsonlSink(path)
    sink2.emit({"kind": "c", "n": 3})
    sink2.close()
    got = read_jsonl(path)
    assert [r["kind"] for r in got] == ["c"]
    assert path.read_text().splitlines()[0] == torn_lines[0]  # append-only


def test_read_jsonl_missing_file_and_garbage_lines(tmp_path):
    assert read_jsonl(tmp_path / "nope.jsonl") == []
    p = tmp_path / "m.jsonl"
    p.write_text('{"ok": 1}\nnot json\n[1,2]\n{"ok": 2}\n')
    assert [r["ok"] for r in read_jsonl(p)] == [1, 2]


def test_configure_run_same_dir_keeps_stream_none_resets(tmp_path):
    rid = configure_run(tmp_path)
    assert rid and events_active()
    emit_event("run_started")
    # same dir (a --auto-restart attempt): sinks and run_id survive
    assert configure_run(tmp_path) == rid
    emit_event("run_finished")
    assert [r["kind"] for r in read_jsonl(tmp_path / "events.jsonl")] == [
        "run_started", "run_finished",
    ]
    assert configure_run(None) is None
    assert not events_active()


# -- emit points in the runtime --------------------------------------------
def test_watchdog_trip_emits_event():
    from repro.runtime.fault import StepWatchdog

    ev, _ = _mem_sinks()
    wd = StepWatchdog(trip_factor=3.0)
    wd.times.extend([0.01] * 10)
    wd.start_step()
    wd._t0 -= 1.0  # pretend the step took ~1s against a 10ms median
    wd.end_step(7)
    trips = [r for r in ev.records if r["kind"] == "watchdog_trip"]
    assert len(trips) == 1
    assert trips[0]["step"] == 7 and trips[0]["dt_s"] > trips[0]["median_s"]


def test_injection_emits_fault_event():
    ev, _ = _mem_sinks()
    InjectionPlan.from_spec("slow@1:0", env="").on_step(1)
    faults = [r for r in ev.records if r["kind"] == "fault_injected"]
    assert faults and faults[0]["spec"] == "slow@1:0"


def test_checkpoint_manager_emits_saved_and_restored(tmp_path):
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager

    ev, _ = _mem_sinks()
    mgr = CheckpointManager(str(tmp_path), save_every=1, async_save=False)
    mgr.save(1, {"w": np.ones((2,), np.float32)}, force=True)
    step, state = mgr.restore()
    assert step == 1 and state["w"].shape == (2,)
    kinds = [r["kind"] for r in ev.records]
    assert kinds.count("checkpoint_saved") == 1
    assert kinds.count("checkpoint_restored") == 1
    saved = next(r for r in ev.records if r["kind"] == "checkpoint_saved")
    assert saved["step"] == 1 and saved["path"].endswith("step_1.npz")


def test_queue_stats_and_shed_event():
    from repro.serving.queue import LatencyModel, Request, RequestQueue

    ev, _ = _mem_sinks()
    q = RequestQueue(LatencyModel())
    q.model.observe_prefill(10, 1.0)   # 100ms per prompt token
    q.model.observe_step(0.05)
    s = q.stats(free_slots=0, active_remaining=[4])
    assert s["queue_depth"] == 0 and s["shed_total"] == 0
    assert s["prefill_s_per_token"] == pytest.approx(0.1)
    assert s["step_s"] == pytest.approx(0.05)
    assert s["projected_wait_s"] == pytest.approx(4 * 0.05)
    # a 20-token prompt projects ~2s TTFT: a 100ms SLO must shed, and the
    # shed decision must land in the events stream with its projection
    admitted = q.offer(Request(rid=7, tokens=[1] * 20, slo_ttft_ms=100.0),
                       free_slots=1, active_remaining=[])
    assert not admitted
    shed = [r for r in ev.records if r["kind"] == "request_shed"]
    assert shed[0]["rid"] == 7
    assert shed[0]["projected_ttft_ms"] > shed[0]["slo_ttft_ms"]
    assert q.stats()["shed_total"] == 1


# -- train-loop integration ------------------------------------------------
def _count_syncs(monkeypatch, argv):
    """Run launch.train.main(argv) counting jax.block_until_ready calls."""
    import jax

    from repro.launch import train

    real = jax.block_until_ready
    calls = {"n": 0}

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    try:
        assert train.main(argv) == 0
    finally:
        monkeypatch.setattr(jax, "block_until_ready", real)
    return calls["n"]


def test_instrumentation_adds_zero_block_until_ready(tmp_path, monkeypatch):
    """The tentpole invariant: with the metrics stream ON, the accumulation
    loop performs exactly the same number of host syncs per run as with it
    OFF — one ``block_until_ready`` per logical batch, metrics riding it."""
    # --batch 4 --data-shards 2 on one process -> physical 2, accum 2:
    # the donated-accumulation path, no tuner needed
    base = ARCH + ["--steps", "3", "--batch", "4", "--data-shards", "2"]
    plain = _count_syncs(monkeypatch, list(base))
    obs_dir = tmp_path / "obs"
    instrumented = _count_syncs(
        monkeypatch, base + ["--obs-dir", str(obs_dir)]
    )
    assert plain == instrumented == 3  # one per logical batch, no extras
    train = [m for m in read_jsonl(obs_dir / "metrics.jsonl")
             if m["kind"] == "train_step"]
    assert [m["step"] for m in train] == [1, 2, 3]
    assert all(m["accumulation_steps"] == 2 for m in train)
    assert all(m["epsilon"] > 0 for m in train)
    assert all(m["norm_max"] >= m["norm_mean"] > 0 for m in train)


def test_events_survive_auto_restart_with_monotone_steps(tmp_path):
    from repro.launch.train import main

    d = tmp_path / "run"
    assert main(ARCH + [
        "--ckpt-dir", str(d), "--steps", "4", "--batch", "2",
        "--ckpt-every", "2", "--auto-restart", "2", "--fail-at-step", "2",
    ]) == 0
    events = read_jsonl(d / "events.jsonl")
    kinds = [e["kind"] for e in events]
    # one stream spans both attempts: the crash AND the recovery are visible
    assert kinds.count("run_started") == 2
    assert kinds.count("plan_adopted") == 2
    assert "fault_injected" in kinds
    assert "restart_attempt" in kinds
    assert "checkpoint_restored" in kinds
    assert kinds[-1] == "run_finished"
    # seq is process-monotone across the whole supervision loop
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # metric step stamps never go backwards: the restart resumed, not replayed
    steps = [m["step"] for m in read_jsonl(d / "metrics.jsonl")
             if m["kind"] == "train_step"]
    assert steps and steps == sorted(steps)
    restored = next(e for e in events if e["kind"] == "checkpoint_restored")
    assert all(s >= restored["step"] for s in steps[-2:])
    # every record of both attempts shares one run_id (same-dir reconfigure)
    assert len({e["run_id"] for e in events}) == 1


# -- profiler window + timeline --------------------------------------------
def test_parse_window():
    assert parse_window("3:5") == (3, 5)
    assert parse_window("4") == (4, 4)
    with pytest.raises(ValueError, match="N or N:M"):
        parse_window("a:b")
    with pytest.raises(ValueError, match="0 <= N <= M"):
        parse_window("5:3")


def test_profile_window_captures_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    ev, _ = _mem_sinks()
    win = ProfileWindow(0, 1, tmp_path / "profile")
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32))
    for step in range(3):
        win.before_step(step)
        jax.block_until_ready(f(x))
        win.after_step(step)
    assert win.done and not win.active
    kinds = [r["kind"] for r in ev.records]
    if "profile_started" not in kinds:
        pytest.skip("profiler unavailable on this backend")
    assert kinds.count("profile_stopped") == 1
    spans = execution_spans(tmp_path / "profile")
    assert spans, "trace written but no execution spans matched"
    assert step_wall_times_ms(tmp_path / "profile")


def test_timeline_groups_synthetic_trace(tmp_path):
    trace = {
        "traceEvents": [
            # step 0: two back-to-back executions (an accum microstep pair)
            {"ph": "X", "name": "PjRtCpuExecutable::Execute", "ts": 0,
             "dur": 100},
            {"ph": "X", "name": "PjRtCpuExecutable::Execute", "ts": 110,
             "dur": 100},
            # 5ms of host work, then step 1
            {"ph": "X", "name": "PjRtCpuExecutable::Execute", "ts": 5210,
             "dur": 300},
            # noise: a non-matching and a non-complete event
            {"ph": "X", "name": "HostLoopOverhead", "ts": 50, "dur": 10},
            {"ph": "B", "name": "PjRtCpuExecutable::Execute", "ts": 60},
        ]
    }
    d = tmp_path / "plugins" / "profile" / "2026"
    d.mkdir(parents=True)
    (d / "host.trace.json.gz").write_bytes(
        gzip.compress(json.dumps(trace).encode())
    )
    spans = execution_spans(tmp_path)
    assert [s["ts_us"] for s in spans] == [0, 110, 5210]
    times = step_wall_times_ms(tmp_path, group_us=1000.0)
    assert times == pytest.approx([0.21, 0.3])
    assert percentile(times, 0.5) == pytest.approx(0.21)
    assert percentile([], 0.5) == 0.0


def _write_trace(root, events):
    d = root / "plugins" / "profile" / "2026"
    d.mkdir(parents=True)
    (d / "host.trace.json.gz").write_bytes(
        gzip.compress(json.dumps({"traceEvents": events}).encode())
    )


def test_timeline_reads_the_programs_step_annotations(tmp_path):
    step = STEP_ANNOTATION
    _write_trace(tmp_path, [
        # steps out of order, one of them on two hosts
        {"ph": "X", "name": step, "ts": 5000, "dur": 900, "args": {"step_num": "8"}},
        {"ph": "X", "name": step, "ts": 0, "dur": 4000, "args": {"step_num": "7"}},
        {"ph": "X", "name": step, "ts": 100, "dur": 4200, "args": {"step_num": "7"}},
        {"ph": "X", "name": "PjRtCpuExecutable::Execute", "ts": 10, "dur": 100},
        {"ph": "X", "name": "PjRtCpuExecutable::Execute", "ts": 5010, "dur": 100},
        # no step number: not a step
        {"ph": "X", "name": step, "ts": 9000, "dur": 10},
    ])
    assert [a["step"] for a in annotated_steps(tmp_path)] == [7, 8]
    times, source = step_timeline(tmp_path)
    assert source == "annotations" and times == pytest.approx([4.3, 0.9])
    # an explicit pattern reads the execution spans instead
    times, source = step_timeline(tmp_path, pattern="PjRtCpuExecutable::Execute")
    assert source == "spans" and len(times) == 2


def test_profile_window_captures_step_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    win = ProfileWindow(1, 2, tmp_path / "profile")
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32))
    for step in range(4):
        win.before_step(step)
        with jax.profiler.StepTraceAnnotation(STEP_ANNOTATION, step_num=step):
            jax.block_until_ready(f(x))
        win.after_step(step)
    if not win.done or not trace_files(tmp_path / "profile"):
        pytest.skip("profiler unavailable on this backend")
    assert [a["step"] for a in annotated_steps(tmp_path / "profile")] == [1, 2]
    assert len(step_wall_times_ms(tmp_path / "profile")) == 2


# -- report + CLI ----------------------------------------------------------
def _fake_run_dir(tmp_path):
    configure_run(tmp_path, run_id="run-x")
    emit_event("run_started", arch="yi-6b")
    emit_event("plan_adopted", mode="mixed_ghost", policy="fixed",
               source="plan", physical_batch=2, accumulation_steps=2,
               branches={"f1": "ghost"}, kernels={"f1": {"fwd": "pallas"}})
    for i, (eps, dt) in enumerate([(0.1, 0.2), (0.2, 0.3), (0.3, 0.25)]):
        emit_metrics({"kind": "train_step", "loss": 1.0, "lr": 1e-3,
                      "clip_frac": 0.5, "epsilon": eps, "delta": 1e-5,
                      "step_s": dt, "examples_per_s": 4 / dt}, step=i + 1)
    emit_event("run_finished", step=3, epsilon=0.3, delta=1e-5)
    reset_sinks()
    return tmp_path


def test_summarize_run_and_render(tmp_path):
    d = _fake_run_dir(tmp_path)
    s = summarize_run(d)
    assert s["train_steps"] == 3
    assert s["epsilon_trajectory"] == [(1, 0.1), (2, 0.2), (3, 0.3)]
    assert s["final_epsilon"] == 0.3 and s["final_delta"] == 1e-5
    assert s["clip_frac_mean"] == pytest.approx(0.5)
    assert s["step_time_p50_s"] == pytest.approx(0.25)
    assert s["restarts"] == 0 and s["run_ids"] == ["run-x"]
    assert s["plan"]["branches"] == {"f1": "ghost"}
    text = render_text(s)
    assert "tap f1: branch=ghost kernels[fwd=pallas]" in text
    assert "epsilon: 0.1000 -> 0.3000" in text


def test_obs_cli_json_and_epsilon_gate(tmp_path, capsys):
    from repro.obs.__main__ import main as cli

    d = _fake_run_dir(tmp_path / "good")
    assert cli([str(d), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["final_epsilon"] == 0.3
    assert cli([str(d), "--require-epsilon"]) == 0
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli([str(empty), "--require-epsilon"]) == 1


def test_obs_cli_timeline_renders_profile(tmp_path, capsys):
    from repro.obs.__main__ import main as cli

    d = _fake_run_dir(tmp_path)
    prof = d / "profile" / "plugins" / "profile" / "x"
    prof.mkdir(parents=True)
    (prof / "h.trace.json").write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "XlaModule:main", "ts": 0, "dur": 500},
    ]}))
    assert cli([str(d), "--timeline"]) == 0
    assert "profiled steps: 1 span group" in capsys.readouterr().out


def test_obs_cli_timeline_renders_annotated_steps(tmp_path, capsys):
    from repro.obs.__main__ import main as cli

    d = _fake_run_dir(tmp_path)
    _write_trace(d / "profile", [
        {"ph": "X", "name": STEP_ANNOTATION, "ts": 1000 * k, "dur": 800,
         "args": {"step_num": str(k)}} for k in range(3)
    ])
    assert cli([str(d), "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "profiled steps: 3 dp.train_step annotation(s), p50 0.8ms" in out
    assert cli([str(d), "--timeline", "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["profile_step_source"] == "annotations"
    assert summary["profile_step_times_ms"] == [0.8, 0.8, 0.8]


# -- logging satellites ----------------------------------------------------
def test_log_level_reread_on_reconfigure(monkeypatch):
    from repro.utils.logging import get_logger, reconfigure

    monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
    logger = get_logger("obs-test-logger")
    assert logger.level == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
    reconfigure()  # module-level `log = get_logger(...)` bindings re-level
    assert logger.level == logging.WARNING
    # and a fresh get_logger call also re-reads the env on its own
    assert get_logger("obs-test-logger").level == logging.WARNING


def test_log_records_carry_rank_prefix_when_distributed(monkeypatch):
    import jax

    from repro.utils.logging import _rank_prefix, get_logger

    assert _rank_prefix() == ""  # single process: no prefix noise
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    assert _rank_prefix() == "p1 "
    logger = get_logger("obs-rank-test")
    record = logging.LogRecord("obs-rank-test", logging.INFO, __file__, 1,
                               "msg", (), None)
    for f in logger.handlers[0].filters:
        f.filter(record)
    assert record.rank == "p1 "
    assert "p1 " in logging.Formatter(
        "%(levelname).1s %(rank)s%(name)s] %(message)s"
    ).format(record)


def test_rank_prefix_needs_no_jax_import(monkeypatch):
    from repro.utils.logging import _rank_prefix

    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert _rank_prefix() == ""


# -- epsilon budget alarm ---------------------------------------------------
def test_epsilon_alarm_fires_once_and_is_latched():
    from repro.core.engine import PrivacyEngine

    ev, _ = _mem_sinks()
    try:
        engine = PrivacyEngine(
            loss_with_ctx=lambda p, b, c: None,
            batch_size=10,
            sample_size=100,
            max_grad_norm=1.0,
            steps=20,
            target_epsilon=2.0,
        )
        assert not engine.check_epsilon_alarm(0.5, step=0)  # nothing spent yet
        fired = []
        for i in range(engine.steps):
            engine.record_step()
            fired.append(engine.check_epsilon_alarm(0.5, step=i + 1))
        # the sigma bisection lands end-of-run spend at ~target, so the 50%
        # alarm crosses strictly inside the run — and the latch keeps the
        # event one-shot even though we check after every step
        assert sum(fired) == 1
        assert fired.index(True) < engine.steps - 1
        crossed = [r for r in ev.records if r["kind"] == "epsilon_budget_crossed"]
        assert len(crossed) == 1
        rec = crossed[0]
        assert rec["step"] == fired.index(True) + 1
        assert rec["fraction"] == 0.5
        assert rec["target_epsilon"] == 2.0
        assert rec["epsilon"] >= 0.5 * rec["target_epsilon"]
        assert rec["delta"] == engine.target_delta
    finally:
        reset_sinks()


def test_epsilon_alarm_disabled_paths():
    from repro.core.engine import PrivacyEngine

    ev, _ = _mem_sinks()
    try:
        engine = PrivacyEngine(
            loss_with_ctx=lambda p, b, c: None,
            batch_size=10,
            sample_size=100,
            max_grad_norm=1.0,
            steps=5,
            noise_multiplier=0.4,  # no target_epsilon: alarm is a no-op
        )
        engine.record_step(5)
        assert not engine.check_epsilon_alarm(0.5)
        engine.target_epsilon = 0.01  # would fire, but frac<=0 disables
        assert not engine.check_epsilon_alarm(0.0)
        assert ev.records == []
    finally:
        reset_sinks()
