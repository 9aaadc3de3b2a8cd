"""One run of one training cell: set-up, the measured window, the readings.

Set-up builds the cell's compiled loop (the configuration's ``Trainer``),
makes its state from the seed and drives it through ``FIRST_STEPS`` steps,
keeping what the comparison needs; the same object then runs the window.
The window starts and ends at step boundaries; each step is: make the
batch, dispatch the step, wait for it, account for it, each inside a
``chipbench.*`` span.  After the window the peak memory is read, the state
is freed, and the plain reference follows the first steps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import tempfile
import time
from typing import Any, Optional

import jax
import numpy as np

from chipbench import bench, compare, peaks, refops
from chipbench import trace as trace_mod

FIRST_STEPS = 3


def span(name: str):
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def one_step(trainer, state, step: int):
    with span("make_batch"):
        batch = trainer.batch(step)
    with span("dispatch"):
        state, out = trainer.step(state, batch)
    with span("sync"):
        jax.block_until_ready((state, out))
    with span("record_step"):
        trainer.record_step()
    return state, out


def first_steps(trainer, seed: int) -> tuple[Any, dict]:
    """Make the state from the seed and take the first steps through the
    window's own calls, reading what the comparison needs on the way."""
    state = trainer.init_state(seed)
    outs, grad, clipped_sum = [], None, None
    for i in range(FIRST_STEPS):
        state, out = one_step(trainer, state, i)
        outs.append(jax.device_get(out))
        if i == 0:
            grad = refops.to_numpy(trainer.first_grad_norms(state))
            clipped_sum = refops.to_numpy(trainer.first_sum_norms(state))
    change = refops.to_numpy(trainer.change_norms(state))
    readings = {
        "names": trainer.leaf_names,
        "losses": np.array([float(o["loss"]) for o in outs]),
        "norms": np.array([[float(o["norm_mean"]), float(o["norm_max"])] for o in outs]),
        "grad": grad, "sum": clipped_sum, "change": change,
    }
    if "per_sample_norms" in outs[0]:
        readings["sample_norms"] = refops.to_numpy(outs[0]["per_sample_norms"])
    return state, readings


@dataclasses.dataclass
class Window:
    steps: int
    samples: int
    step_s: list[float]
    window_s: float


def run_window(trainer, state, seconds: float, max_steps: Optional[int] = None):
    times, n = [], 0
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        state, _ = one_step(trainer, state, FIRST_STEPS + n)
        te = time.perf_counter()
        times.append(te - ts)
        n += 1
        if te - t0 >= seconds or (max_steps is not None and n >= max_steps):
            break
    return state, Window(steps=n, samples=n * trainer.samples_per_step, step_s=times,
                         window_s=te - t0)


def end_to_end(window: Window, setup_s: float, peak_bytes: int) -> dict[str, float]:
    return {
        "samples_per_s": window.samples / window.window_s,
        "step_s.p95": float(np.percentile(window.step_s, 95)),
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader may read."""
    trace: trace_mod.Trace
    window: Window
    cfg: dict
    wl: dict
    config: Any  # the configuration's module
    trainer: Any
    peaks: peaks.Peaks
    chips: int


def free(tree) -> None:
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peak_bytes(chips: int) -> int:
    """Peak device memory of the fullest chip: the allocator's buffers
    (``peak_bytes_in_use``) plus what the runtime reserved for the compiled
    programs' temporaries (``peak_bytes_reserved``, on a TPU)."""
    def one(d):
        stats = d.memory_stats()
        return int(stats["peak_bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0))
    return max(one(d) for d in jax.devices()[:chips])


def run_cell(cell: str, seed: int, seconds: float, traced: bool, t_start: float,
             *, variant: str = "program") -> dict:
    """Run one cell and return its result line (``checks`` last)."""
    bm = bench.benchmark()
    wl = bench.workload(cell)
    cfg = bench.config(wl["config"])
    mod = bench.config_module(wl["config"])
    e2e_entries, layer_entries = bench.cell_metrics(bm, cell)
    entry = next(w for w in bm["workloads"] if w["name"] == cell)
    chips = entry["chips"]

    trainer = mod.Trainer(cfg, wl, variant)
    state, prog = first_steps(trainer, seed)
    setup_s = time.perf_counter() - t_start

    trace = None
    with contextlib.ExitStack() as stack:
        if traced:
            tdir = stack.enter_context(tempfile.TemporaryDirectory(prefix="chipbench-trace-"))
            jax.profiler.start_trace(tdir)
            try:
                state, window = run_window(trainer, state, seconds, wl["trace_steps"])
            finally:
                jax.profiler.stop_trace()
            trace = trace_mod.load(trace_mod.find_xplane(tdir))
        else:
            state, window = run_window(trainer, state, seconds)
    peak = peak_bytes(chips)
    free(state)

    ref = mod.reference(cfg, wl, seed, FIRST_STEPS)
    numbers, where = compare.training_numbers(prog, ref)
    checked = compare.checks(numbers, wl.get("limits", {}))
    print(f"chipbench: worst leaves {where}", file=sys.stderr)

    metrics = {}
    if traced:
        ctx = MetricContext(trace=trace, window=window, cfg=cfg, wl=wl, config=mod,
                            trainer=trainer, peaks=peaks.peaks_for(jax.devices()[0].device_kind),
                            chips=chips)
        for m in layer_entries:
            value = bench.metric_module(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(window, setup_s, peak)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e_entries}

    device = device_info(chips) | {"memory_peak_bytes": peak}
    result = {
        "correct": compare.passed(checked),
        "attempted": window.steps,
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.longest_gaps(10)}
    result["checks"] = checked
    return result


def report_checks(checked: dict) -> None:
    """The compared numbers beside their limits, as the last lines of stderr."""
    for name, c in checked.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program: only the first run of a cell compiles."""
    jax.config.update("jax_compilation_cache_dir", str(bench.CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
