#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in ONE process (a chip belongs to one process at a time) on the machine
it is started on. Finds the cell's configuration, traffic mix and per-layer
metrics by name (``harness/spec.py``), builds everything from ``--seed``,
warms up, measures for ``--seconds`` and checks the outputs against the
plain reference. The LAST stdout line is the result object; everything else
it has to say goes to earlier lines. With no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result. ``--rehearsal`` is
for the CPU tests only: tiny sizes, ``platform: cpu``, never a chip number.
"""

from __future__ import annotations

import time

_T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _process_age_s() -> float:
    """Seconds since this process started, from the kernel's own record
    (10 ms ticks); 0 where /proc is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS_START = _T_IMPORT - _process_age_s()


class Env:
    """What a driver needs from the run: sizes, the window's edges, spans."""

    CHOICE_KEYS = ("accel_pipeline", "accel_batcher", "accel_resident",
                   "accel_min_window", "accel_pallas", "accel_mesh")

    def __init__(self, args):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearsal = bool(args.rehearsal)
        self.trace = bool(args.trace)
        self.keep_trace = args.keep_trace
        self.setup_s = None
        self.trace_dir = None
        self.traced_s = 0.0
        self._t_trace = 0.0
        self._tracing = False
        self._null = contextlib.nullcontext()

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    def sized(self, d: dict) -> dict:
        """The file as it is run: in a rehearsal its ``rehearsal`` group
        overrides the sizes, in a chip run nothing does."""
        out = {k: v for k, v in d.items() if k != "rehearsal"}
        if self.rehearsal:
            out.update(d.get("rehearsal", {}))
        return out

    def scale_gate(self, node, conf: dict) -> None:
        """Rehearsal only: host XLA's flush gate (256) is never crossed by
        a 4-validator window, so it is scaled down ON THE OBJECT, as
        ``chip_smoke.py --cpu-rehearsal`` does. Never in a chip run."""
        if self.rehearsal and "accel_min_window" in conf:
            node.core.hg.accel.min_window = int(conf["accel_min_window"])

    def span(self, name: str):
        if not self._tracing:
            return self._null
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def window_open(self) -> None:
        if self.trace:
            import jax

            self.trace_dir = tempfile.mkdtemp(prefix="babble_bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the harness's spans, not frames
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
            self._t_trace = time.monotonic()
        self.setup_s = time.monotonic() - T_PROCESS_START

    def window_close(self) -> None:
        if self._tracing:
            import jax

            self.traced_s = time.monotonic() - self._t_trace
            self._tracing = False
            jax.profiler.stop_trace()


def _device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(jax) -> int:
    peak = 0
    for d in jax.devices():
        try:
            st = d.memory_stats() or {}
        except Exception:  # a backend that reports none
            st = {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def _drivers() -> dict:
    from benchmark.harness import ingest, ring

    return {"live-ring": ring.run, "core-ingest": ingest.run}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU tests only: tiny sizes, prints platform cpu")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this file")
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from benchmark.harness import layer, spec
    from benchmark.harness import trace as trace_mod

    try:
        bench = spec.load_benchmark(args.root)
        cell = spec.resolve_cell(bench, args.workload, args.root)
    except spec.SpecError as err:
        print(f"benchmark: {err}", file=sys.stderr)
        return 2
    kind = cell.config.get("driver")
    drivers = _drivers()
    if kind not in drivers:
        print(f"benchmark: config {cell.config_name!r} names driver "
              f"{kind!r}; known: {sorted(drivers)}", file=sys.stderr)
        return 2

    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the explicit pin
    import jax

    device = _device_info(jax)
    want = "cpu" if args.rehearsal else "tpu"
    if device["platform"] != want or device["count"] < cell.chips:
        print(f"benchmark: cell {cell.name!r} needs {cell.chips} {want} "
              f"device(s); jax found {device}", file=sys.stderr)
        return 3
    try:
        from babble_tpu import native_crypto
        from babble_tpu.ops.device import ensure_device
    except ImportError as err:
        print(f"benchmark: the program is not in this checkout: {err}",
              file=sys.stderr)
        return 5

    env = Env(args)
    env.log(f"cell {cell.name}: config {cell.config_name} "
            f"({kind}), traffic {cell.traffic_name}, seed {env.seed}, "
            f"{env.seconds:g}s, trace {int(env.trace)}")
    env.log(f"device {json.dumps(device)}; jax {jax.__version__}; "
            f"os.cpu_count() {os.cpu_count()}")

    ensure_device()  # places the compile cache where the program keeps it
    if not native_crypto.available():
        print("benchmark: the native crypto library could not be built "
              "from native/secp256k1.cc", file=sys.stderr)
        return 4

    try:
        result = drivers[kind](cell, env)
    except Exception:
        traceback.print_exc()
        return 1

    summary = None
    if env.trace_dir is not None:
        try:
            path = trace_mod.find_xplane(env.trace_dir)
            if path is not None:
                env.log(f"trace {os.path.getsize(path)} bytes, "
                        f"{env.traced_s:.2f}s traced")
                if env.keep_trace:
                    os.makedirs(os.path.dirname(env.keep_trace) or ".",
                                exist_ok=True)
                    shutil.copyfile(path, env.keep_trace)
                summary = trace_mod.reduce_file(path, env.traced_s)
        finally:
            shutil.rmtree(env.trace_dir, ignore_errors=True)

    for note in result["notes"]:
        env.log("check: " + note)
    env.log("choices the code made on this device: "
            + json.dumps(result.get("chosen", {})))
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    correct = bool(result["correct"])
    if env.trace:
        ctx = {"counters": result["counters"], "samples": result["samples"],
               "trace": summary, "cell": cell, "device_kind": device["kind"],
               "log": env.log}
        for m in cell.per_layer:
            value = layer.evaluate(cell.definitions[m["name"]], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        values = dict(result["end_to_end"], setup_s=env.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": units[m["name"]]}
            else:
                correct = False
                env.log(f"check: end-to-end metric {m['name']} could not "
                        "be computed in this run")
    device["memory_peak_bytes"] = _memory_peak(jax)
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if env.trace:
        device["window_s"] = env.traced_s
        device["busy_s"] = summary.busy_s if summary is not None else 0.0
        if summary is not None:
            line["breakdown"] = summary.breakdown()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
