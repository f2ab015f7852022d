"""Chip benchmark of the repository: one cell of ``BENCHMARK.json`` per run.

    python chipbench/run.py --workload fednl-w8a.topk3000 --seed 7 \
        --seconds 10 --trace 0

A cell names a configuration (``chipbench/configs/<config>.json``) and a
traffic mix (``chipbench/traffic/<traffic>.json``); the mix names the
driver (``chipbench/drivers/<driver>.py``) that builds the program's
timed path from them. The run builds inputs and weights on the device
from ``--seed``, compiles and warms up every program the window drives
(set-up), then calls the program for ``--seconds`` seconds, checks what
the timed path produced against the plain reference, and prints one JSON
line. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window and reports its per-layer metrics, each read by its
own reader ``chipbench/metrics/<metric>.py``.

The run refuses to report anything without a TPU, without as many chips
as the cell asks for, or on a device missing from ``chipbench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

IN_FLIGHT = 2  # program calls queued on the device at once


class Refused(RuntimeError):
    """The run cannot report a result (no chip, unknown device, bad cell)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by its path (file names may hold
    dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str) -> dict:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = dict(cells[workload])
    cell["config_data"] = load_json(BENCH / "configs" / f"{cell['config']}.json")
    cell["traffic_data"] = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = load_json(BENCH / "limits" / f"{workload}.json")
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if workload in m.get("workloads", [workload])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if workload in m.get("workloads", [workload])]
    return cell


def make_driver(cell: dict, seed: int, **kw):
    name = cell["traffic_data"]["driver"]
    mod = load_module(BENCH / "drivers" / f"{name}.py", f"chipbench_driver_{name}")
    return mod.Driver(cell["config_data"], cell["traffic_data"],
                      cell["limits"], seed, **kw)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else a fixed directory inside the checkout. Every program is
    cached, however fast it compiled, so that a warm run compiles
    nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices_for(chips: int, require_tpu: bool = True):
    """The cell's devices, and the peaks of their kind. Refuses a run
    without a TPU, with too few chips, or on a kind the table lacks."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devs)}")
    table = load_json(BENCH / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if require_tpu and kind not in table:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json")
    return devs[:chips], table.get(kind)


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run_window(driver, seconds: float, annotate=None):
    """Call the program until ``seconds`` have passed, with at most
    ``IN_FLIGHT`` calls queued, then wait for the last. Returns the
    number of calls and the window's length in seconds."""
    import jax

    span = annotate or (lambda *a, **k: contextlib.nullcontext())
    pending = collections.deque()
    calls = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        with span("bench.dispatch", call=calls):
            pending.append(driver.call())
        calls += 1
        if len(pending) > IN_FLIGHT:
            with span("bench.wait"):
                jax.block_until_ready(pending.popleft())
        if time.perf_counter() >= deadline:
            break
    with span("bench.final_sync"):
        jax.block_until_ready(list(pending))
    return calls, time.perf_counter() - t0


def read_per_layer(cell, driver, reduced, calls, window_s, devs, peaks):
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    ctx = dict(trace=reduced, counts=driver.layer_counts(calls),
               calls=calls, window_s=window_s, units=calls * driver.units_per_call,
               flops_per_unit=driver.flops_per_unit, chips=len(devs), peaks=peaks)
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, driver_kw=None) -> dict:
    """One run of one cell (as ``find_cell`` gives it); returns the
    result line as a dict."""
    import jax

    devs, peaks = devices_for(int(cell["chips"]), require_tpu)
    use_compile_cache()
    driver = make_driver(cell, seed, devices=devs, **(driver_kw or {}))
    driver.setup()
    setup_s = time.perf_counter() - T_START

    tdir = None
    if trace:
        from chipbench import trace as tr

        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        with jax.profiler.trace(tdir):
            with jax.profiler.TraceAnnotation("bench.window"):
                calls, window_s = run_window(driver, seconds,
                                             jax.profiler.TraceAnnotation)
    else:
        calls, window_s = run_window(driver, seconds)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}

    result = {"attempted": calls}
    if trace:
        reduced = tr.reduce_dir(tdir, driver.kernel_tags(), len(devs))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        metrics = read_per_layer(cell, driver, reduced, calls, window_s, devs,
                                 peaks)
        result["breakdown"] = reduced.breakdown()
    else:
        units = calls * driver.units_per_call
        metrics = {}
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = driver.end_to_end(m["name"], units, window_s)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = driver.check()
    correct = all(c["ok"] for c in checks.values())
    result.update(correct=correct, failed=0 if correct else calls,
                  metrics=metrics, device=device)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    result["numbers"] = getattr(driver, "numbers",
                                {k: c["value"] for k, c in checks.items()})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(find_cell(args.workload), args.seed,
                          args.seconds, bool(args.trace))
    except Refused as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    line = {k: result[k] for k in order if k in result}
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
