#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), traffic mix (``bench/traffic/<mix>.json``),
driver (``bench/drivers/<driver>.py``, named by the configuration),
reference (``bench/reference/<reference>.py``) and per-layer metric
readers (``bench/metrics/<metric>.py``) are files found by name, so a new
cell, mix or metric is new files and entries, not an edit.

Set-up (JAX start, inputs and weights from the seed, compile or cache
load, warm-up) runs from process start to the window.  The window calls
the cell back to back, each call ending in ``block_until_ready``, for
``--seconds``; a mix may keep calls dispatched ahead (``dispatch_ahead``).  With ``--trace 1`` the window runs under the profiler
and the per-layer metrics are read from the trace; otherwise the
end-to-end metrics are printed.  Then the program's state is released
and every answer of the window is compared with the plain reference.
Without an accelerator, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell needs."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def resolve(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` and everything it names, read from ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    return dict(cell=cell, config=cfg, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]


def check_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"bench: cell needs {chips} TPU chip(s), JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")


def window(cell, seconds: float):
    """Call back to back until ``seconds`` have passed; returns the calls'
    outputs and the time from window start to the last completion.

    A cell whose ``ahead`` is above 0 keeps that many calls dispatched
    beyond the one it waits for, so that the chip stays fed while the
    host stands still.  Once the time is up nothing more is sent, every
    call sent is waited for, and the clock is read after that wait."""
    import jax
    ahead = getattr(cell, "ahead", 0)
    outs, sent = [], collections.deque()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            while (len(sent) <= ahead
                   and time.perf_counter() - t0 < seconds):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    sent.append(cell.dispatch())
            if not sent:
                break
            with jax.profiler.TraceAnnotation("bench.call"):
                outs.append(cell.wait(sent.popleft()))
    return outs, time.perf_counter() - t0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_chip: bool = True,
             workers: int = min(12, (os.cpu_count() or 2) - 1),
             t_start: float = _T_START) -> dict:
    """Set up, measure, check; returns the result dictionary."""
    spec = resolve(name, root)
    cfg, traffic = spec["config"], spec["traffic"]
    import jax
    if require_chip:
        check_chip(spec["cell"]["chips"])
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    # cache every program, the small ones too, so that a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = importlib.import_module(f"bench.drivers.{cfg['driver']}")

    with jax.profiler.TraceAnnotation("bench.setup"):
        cell = driver.Cell(cfg, traffic, seed, trace)
    setup_s = time.perf_counter() - t_start

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    try:
        outs, elapsed = window(
            cell, min(seconds, traffic.get("trace_seconds", seconds))
            if trace else seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()

    dev = jax.devices()[0]
    n_dev = spec["cell"]["chips"]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in jax.devices()[:n_dev]]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()), memory_peak_bytes=max(mem))
    units = cell.units(outs)
    metrics, breakdown = {}, None
    if trace:
        from bench import reduce
        xplane = reduce.find_xplane(tdir)
        red = reduce.reduce_trace(xplane)
        print(f"trace: {os.path.getsize(xplane)} bytes, {red['n_ops']} "
              "device ops in the window", file=sys.stderr, flush=True)
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = red["breakdown"]
        ctx = dict(units=units, busy_s=red["busy_s"],
                   window_s=red["window_s"], scope_s=red["scope_s"],
                   config=cfg, traffic=traffic, device_kind=dev.device_kind,
                   peaks=lambda: peaks(dev.device_kind, root), cell=cell)
        for m in spec["per_layer"]:
            v = reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        e2e = dict(cell.end_to_end(outs, elapsed), setup_s=setup_s)
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = dict(value=e2e[m["name"]],
                                          unit=m["unit"])

    cell.release()
    readings = cell.check(outs, workers=workers)
    checks = {k: dict(value=readings[k], limit=v)
              for k, v in cfg["limits"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = dict(correct=correct, attempted=int(units["attempted"]),
                  failed=cell.failed(readings), metrics=metrics,
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    for k, c in res["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    # the check lines stay the last of standard error: what the runtime
    # logs while the interpreter shuts down goes nowhere
    os.dup2(os.open(os.devnull, os.O_WRONLY), 2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
