"""One cell, one run: load the cell's files by name, build the gateway,
drive its traffic, measure, check every verdict against the plain
reference, and print the result line.

What belongs to one configuration, traffic mix, metric or kernel sits
in a file of its own, found by name:

  BENCHMARK.json                  cells, metrics, bounds
  bench/workloads/<cell>.json     configuration, loop, traffic params,
                                  limits of the compared numbers
  bench/configs/<config>.json     the deployment's settings
  bench/traffic/<loop>.py         the load generator of a loop kind
  bench/signals/<kind>.py         the configuration's signal model
  bench/reference/<name>.py       the plain reference and comparison
  bench/metrics/<metric>.py       a per-layer metric's reader (a name
                                  "a.b" is read by "a.py")
  bench/bytes/<kernel>.py         a kernel's useful-byte model
  bench/peaks.json                peaks, keyed by device_kind
"""
from __future__ import annotations

import argparse
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload entry of BENCHMARK.json with its files.  `staged`
    also looks in bench/staged.json: cells whose files are here but
    whose chip measurements, and so whose bounds, are not yet (only
    the CPU rehearsal runs them)."""

    def __init__(self, name: str, root: Path = ROOT, staged: bool = False):
        bm = load_json(root / "BENCHMARK.json")
        if staged:
            more = load_json(root / "bench" / "staged.json")
            bm = {k: bm[k] + more.get(k, []) for k in
                  ("configs", "workloads", "end_to_end", "per_layer")}
            bm["end_to_end"] = list({m["name"]: m for m in
                                     bm["end_to_end"]}.values())
        entries = [w for w in bm["workloads"] if w["name"] == name]
        if not entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = entries[0]
        self.chips = int(self.entry["chips"])
        self.wl = load_json(root / "bench" / "workloads" / f"{name}.json")
        cfg_file = [c["file"] for c in bm["configs"]
                    if c["name"] == self.entry["config"]][0]
        self.cfg = load_json(root / cfg_file)
        self.end_to_end = [m for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])]
        moves = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bm["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def peak_of(device_kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"bench/peaks.json; add its published peaks")
    return peaks[device_kind]


def reader_path(metric: str) -> Path:
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.is_file() else \
        BENCH / "metrics" / f"{metric.split('.')[0]}.py"


class Stretch:
    """The traced stretch of a `--trace 1` run: where it starts and
    ends on the host clock and the ticks inside it.  The device is
    idle at both ends (`Gateway.quiesce`), so the trace holds exactly
    the calls dispatched in those ticks."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.plan = None
        self.t0 = self.t1 = None
        self.ticks = (0, 0)
        self.dir = None


class Ctx:
    """What a traffic loop gets: the configuration, its parameters, the
    gateway, the signal and reference modules, and the clock."""

    clock = staticmethod(time.perf_counter)
    sleep = staticmethod(time.sleep)

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 gateway, stretch):
        self.cfg = cell.cfg
        self.params = cell.wl["params"]
        self.seconds = seconds
        self.rng = np.random.default_rng(seed % (1 << 64))
        self.gateway = gateway
        self.reference = load_module(
            BENCH / "reference" / f"{cell.cfg['reference']}.py")
        self._signal = load_module(
            BENCH / "signals" / f"{cell.cfg['signal']['kind']}.py")
        self.work_by_tick = {}
        self.window = None
        self.stretch = stretch

    def log(self, msg: str) -> None:
        """A progress line on standard error, with the run's clock."""
        print(f"[bench {self.clock() - self.t_start:8.3f}s] {msg}",
              file=sys.stderr, flush=True)

    def signal(self, **shape):
        return self._signal.make(self.cfg["signal"], self.rng, **shape)

    def tick_hook(self, now) -> None:
        """Start and stop the profiler around the traced stretch, in
        the middle of the window."""
        st = self.stretch
        if st is None or st.t1 is not None:
            return
        gw = self.gateway
        if st.plan is None:
            w0, w1 = self.window
            st.plan = (w0 + w1 - st.seconds) / 2
        if st.t0 is None and now is not None and now >= st.plan:
            import jax
            gw.quiesce()
            st.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(st.dir, profiler_options=opts)
            st.t0 = self.clock()
            st.ticks = (gw.sched.tick_no, None)
            gw.spans.recording = True
        elif st.t0 is not None and (now is None
                                    or now >= st.t0 + st.seconds):
            import jax
            gw.spans.recording = False
            gw.quiesce()
            st.t1 = self.clock()
            st.ticks = (st.ticks[0], gw.sched.tick_no)
            jax.profiler.stop_trace()

    def in_stretch(self, t: np.ndarray) -> np.ndarray:
        """Mask of host-clock times inside the traced stretch (inside
        the window when nothing was traced)."""
        st = self.stretch
        a, b = ((st.t0, st.t1) if st is not None and st.t1 is not None
                else self.window)
        return (t >= a) & (t < b)


class Layer:
    """What a per-layer metric's reader gets: the traced stretch's host
    spans (the harness's: name, start, end; the program's: name, start,
    end, arguments), its ticks, the work its calls retired, the
    generator's lag, and the reduced device trace."""

    def __init__(self, cell: Cell, gw, stretch: Stretch, rec: dict,
                 work: dict, trace: dict, device_kind: str):
        a, b = stretch.t0, stretch.t1
        self.cfg = cell.cfg
        self.spans = list(gw.spans.spans)
        self.program_spans = [s for s in gw.program_spans(args=True)
                              if a <= s[1] < b]
        t_a, t_b = stretch.ticks
        self.ticks = t_b - t_a
        self.work = [0, 0]
        for tick, (samples, slots) in work.items():
            if t_a < tick <= t_b:
                self.work[0] += samples
                self.work[1] += slots
        self.gen_lag_s = rec.get("gen_lag_s", np.zeros(0))
        self.trace = trace
        self.device_kind = device_kind

    def program_ms_per_tick(self, name: str):
        d = [e - s for n, s, e, _ in self.program_spans if n == name]
        return 1e3 * sum(d) / self.ticks if d and self.ticks else None

    def roofline(self, kernel: str):
        if self.trace is None or kernel != self.cfg["kernel"]:
            return None
        model = load_module(BENCH / "bytes" / f"{kernel}.py")
        kernel_s = sum(v for k, v in self.trace["by_op"].items()
                       if k.startswith(model.TRACE_OP))
        if kernel_s <= 0 or not self.work[0]:
            return None
        useful = model.useful_bytes(self.cfg, *self.work)
        least = useful / peak_of(self.device_kind)["hbm_bytes_per_s"]
        return 100.0 * least / kernel_s


def _xplane(dirname: str) -> str:
    found = sorted(glob.glob(os.path.join(
        dirname, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no trace in {dirname}")
    return found[-1]


def use_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless JAX_COMPILATION_CACHE_DIR names one."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False,
             interpret=None, keep_trace: str = "") -> dict:
    """Drive one run of the cell and return its result object."""
    import jax
    from bench.lib.gateway import Gateway
    dev = jax.devices()[0]
    stretch = (Stretch(float(cell.wl["trace_seconds"])) if trace
               else None)
    gw = Gateway(cell.cfg, trace=trace, interpret=interpret)
    ctx = Ctx(cell, seed, seconds, gw, stretch)
    ctx.t_start = t_start
    loop = load_module(BENCH / "traffic" / f"{cell.wl['loop']}.py")
    rec = loop.drive(ctx)
    setup_s = ctx.window[0] - t_start
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    out = {"attempted": rec["attempted"], "failed": rec["failed"]}
    if trace:
        from bench.lib import xplane
        if stretch.t1 is None:
            raise RuntimeError("the window closed before the traced "
                               "stretch began")
        path = _xplane(stretch.dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep_trace, f"{cell.name}-{seed}.xplane.pb"))
        spans = gw.spans.spans + gw.program_spans()
        red = xplane.reduce(xplane.load(path), stretch.t0, stretch.t1,
                            spans, n_devices=cell.chips)
        shutil.rmtree(stretch.dir, ignore_errors=True)
        layer = Layer(cell, gw, stretch, rec, ctx.work_by_tick, red,
                      dev.device_kind)
        metrics = {}
        for m in cell.per_layer:
            value = load_module(reader_path(m["name"])).read(layer)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {
            "device_ops": xplane.top(red["by_op"]),
            "idle_gaps": xplane.top(red["idle_by_span"])}
    else:
        values = dict(rec["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    # the reference runs once the window has closed, the device's peak
    # has been read and the program's state is freed
    ref = ctx.reference
    del gw, ctx
    gc.collect()
    limits = cell.wl["limits"]
    t_ref = time.perf_counter()
    numbers, missing = rec["check"](ref, limits)
    numbers["missing"] = (missing, 0)
    correct = all(v <= lim for v, lim in numbers.values())
    result = {"correct": bool(correct), **out, "metrics": metrics,
              "device": device}
    if "breakdown" in out:
        result["breakdown"] = result.pop("breakdown")
    if control:
        # the control's readings beside the program's, for the limits
        ctl, _ = rec["control"](ref, limits)
        result["control"] = {k: {"value": v, "limit": lim}
                             for k, (v, lim) in ctl.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    result["_reference_s"] = time.perf_counter() - t_ref
    return result


def emit(result: dict) -> None:
    ref_s = result.pop("_reference_s", None)
    if ref_s is not None:
        print(f"reference and comparison took {ref_s:.3f} s",
              file=sys.stderr)
    for name, c in result.get("control", {}).items():
        print(f"control {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the lower-precision control in "
                         "the program's place (for setting limits)")
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler trace into this directory")
    ap.add_argument("--staged", type=int, choices=(0, 1), default=0,
                    help="also find the cell in bench/staged.json (to "
                         "measure a cell before it enters BENCHMARK.json)")
    args = ap.parse_args(argv)
    cell = Cell(args.workload, staged=bool(args.staged))
    import jax
    use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU: JAX's first device is {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, control=bool(args.control),
                      keep_trace=args.keep_trace)
    if not all(math.isfinite(m["value"]) for m in
               result["metrics"].values()):
        print("bench: a metric is not finite", file=sys.stderr)
    emit(result)
    return 0
